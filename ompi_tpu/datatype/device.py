"""On-device datatype packing: the descriptor program as ONE XLA
gather.

The north-star item SURVEY §2.9.1 calls "datatype packing done
on-device": a committed datatype's run descriptors (engine.py) are
compiled once into an element-index vector, and packing a
device-resident buffer becomes ``buf[idx]`` — a single XLA gather the
compiler fuses into the collective that consumes it (reference
counterpart: the convertor pack loop feeding coll buffers,
opal/datatype/opal_convertor.h:131-137, which walks descriptors
element-wise on the host CPU).  Unpack is the mirrored scatter.

Eligibility: every run must use the same primitive dtype as the
buffer, with displacements/strides that are whole elements —
exactly the shapes MPI vector/indexed/subarray types of one base
type produce.  Mixed-type structs fall back to the host convertor
(they would need byte-level gathers that defeat XLA vectorization).

One exception to "same dtype as the buffer": MPI_DOUBLE may travel
as its bit pattern, a uint64 buffer (runtime/x64.py: a TPU v5e holds
no binary64, it does hold 64 bits).  MAX and MIN of such a buffer are
integer compares on an order key, exact for every binary64 value.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from .engine import Datatype

_BITS = np.dtype(np.uint64)
_SIGN = 1 << 63
#: the reductions a bit-pattern carrier serves on the device
BITS_OPS = frozenset(("MPI_MAX", "MPI_MIN"))


def label(datatype: Datatype) -> str:
    """What a message or a span calls the datatype: its name, else its
    combiner (VECTOR, INDEXED, ...)."""
    return datatype.name or str(datatype.envelope[0])


def order_key(b):
    """binary64 bit patterns (uint64, traced) -> uint64 keys whose
    unsigned order is IEEE's total order of the doubles: a negative
    value has all its bits turned, another its sign bit set.  -0 sorts
    below +0 and a NaN beyond the infinity of its sign."""
    import jax.numpy as jnp
    u = jnp.uint64
    return b ^ ((u(0) - (b >> u(63))) | u(_SIGN))


def from_order_key(k):
    """The inverse of ``order_key``."""
    import jax.numpy as jnp
    u = jnp.uint64
    return k ^ (((k >> u(63)) - u(1)) | u(_SIGN))


class Typed:
    """A committed datatype and a count, resolved once: the index
    vector whose gather is the packed stream (``idx``), the one
    primitive type of its runs (``dtype``), and what the device
    providers of a typed ``*_arr`` collective need of them: ``pack``
    (the gather, inside the provider's jit), ``sig`` (what keys their
    programs: base type, carrier and a digest of ``idx``, so equal
    layouts share one executable whatever datatype object described
    them).  ``bits``: the buffer holds MPI_DOUBLE as uint64 bit
    patterns; ``pack`` then hands the reduction order keys and
    ``unkey`` turns its results back."""

    __slots__ = ("datatype", "count", "dtype", "idx", "elems", "span",
                 "bits", "sig", "_twin")

    def __init__(self, datatype: Datatype, count: int, dtype: np.dtype,
                 idx: np.ndarray) -> None:
        import hashlib

        self.datatype = datatype
        self.count = count
        self.dtype = dtype
        self.idx = idx
        self.elems = int(idx.size)          # packed stream, in elements
        self.span = int(idx.max()) + 1 if idx.size else 0
        self.bits = False
        self.sig = ("typed", dtype.str, self.elems,
                    hashlib.blake2b(np.ascontiguousarray(idx).tobytes(),
                                    digest_size=16).hexdigest())
        self._twin = None

    # a provider's cache key holds the Typed itself: equal layouts are
    # one key
    def __hash__(self) -> int:
        return hash(self.sig)

    def __eq__(self, other) -> bool:
        return isinstance(other, Typed) and self.sig == other.sig

    def carried(self, dtype) -> Optional["Typed"]:
        """The Typed for a buffer of ``dtype``: this one when it is the
        base type, its bit-pattern twin for a uint64 buffer under
        MPI_DOUBLE, None for any other (the host convertor's)."""
        dtype = np.dtype(dtype)
        if dtype == self.dtype:
            return self
        if dtype != _BITS or self.dtype != np.float64:
            return None
        if self._twin is None:
            t = Typed.__new__(Typed)
            for k in ("datatype", "count", "dtype", "idx", "elems", "span"):
                setattr(t, k, getattr(self, k))
            t.bits, t.sig, t._twin = True, self.sig + ("bits",), self
            self._twin = t
        return self._twin

    def pack(self, arr):
        import jax.numpy as jnp
        out = jnp.take(arr.reshape(-1), jnp.asarray(self.idx), axis=0)
        return order_key(out) if self.bits else out

    def unkey(self, out):
        """What the reduction of packed streams returned (an array or
        a tuple of them), as the caller gets it."""
        if not self.bits:
            return out
        if isinstance(out, tuple):
            return tuple(from_order_key(o) for o in out)
        return from_order_key(out)

    # the host's view, for the integrity plane: the operand a deposit
    # stands for and the values an output holds, in the base type
    def operand(self, deposit) -> np.ndarray:
        return np.asarray(deposit).reshape(-1)[self.idx].view(self.dtype)

    def answer(self, out) -> np.ndarray:
        return np.asarray(out).view(self.dtype)


@functools.lru_cache(maxsize=64)
def resolve(datatype: Datatype, count: int) -> Optional[Typed]:
    """The ``Typed`` of ``count`` elements of ``datatype``, or None
    when the datatype is not device-packable.  The one cache: index
    construction is host-side and O(n) (16 MiB of indices for the 2 Mi
    elements of a large vector, hence the bound), the device gather is
    the per-call cost."""
    runs = datatype.runs_for_count(count)
    if not runs:
        return None
    item = runs[0].dtype.itemsize
    chunks = []
    for r in runs:
        if r.dtype != runs[0].dtype:
            return None  # mixed primitive types: host convertor
        if r.disp % item or r.stride % item:
            return None  # sub-element displacement: host convertor
        base = r.disp // item
        stride = r.stride // item
        # (nblocks, count) element grid -> flat packed order
        grid = (base
                + stride * np.arange(r.nblocks, dtype=np.int64)[:, None]
                + np.arange(r.count, dtype=np.int64)[None, :])
        chunks.append(grid.reshape(-1))
    idx = np.concatenate(chunks) if len(chunks) > 1 else chunks[0]
    if (idx < 0).any():
        return None  # negative displacement: host convertor owns it
    return Typed(datatype, count, runs[0].dtype, idx)


def element_indices(datatype: Datatype, count: int) -> Optional[np.ndarray]:
    """Element indices (into a flat element-typed buffer view) whose
    gather equals the datatype's packed stream for ``count`` elements,
    or None when the datatype is not device-packable."""
    t = resolve(datatype, count)
    return None if t is None else t.idx


def device_pack(datatype: Datatype, count: int, arr):
    """Pack a device-resident array through the datatype: one XLA
    gather (jittable; fuses into downstream collectives).  ``arr`` is
    the flat element-typed buffer the datatype addresses."""
    t = resolve(datatype, count)
    if t is None:
        raise ValueError(
            f"datatype {datatype.name or datatype.id} is not "
            f"device-packable (mixed types or sub-element layout)")
    if t.dtype != np.dtype(arr.dtype):
        raise ValueError(
            f"buffer dtype {arr.dtype} does not match datatype base "
            f"{t.dtype}")
    return t.pack(arr)


def device_unpack(datatype: Datatype, count: int, packed, out):
    """Scatter a packed stream back through the datatype into ``out``
    (a flat element-typed device array); returns the updated array
    (functional, XLA scatter)."""
    idx = element_indices(datatype, count)
    if idx is None:
        raise ValueError("datatype is not device-packable")
    import jax.numpy as jnp

    return out.reshape(-1).at[jnp.asarray(idx)].set(packed)


def is_device_packable(datatype: Datatype, count: int) -> bool:
    return resolve(datatype, count) is not None


# ---------------------------------------------------------------------------
# typed device collectives: comm.<op>_arr(x, op, datatype, count)
# ---------------------------------------------------------------------------

def typed_count(datatype: Datatype, count: Optional[int], x) -> int:
    """``count`` of a typed call; None means as many elements of the
    type as the buffer holds."""
    if count is not None:
        return int(count)
    nbytes = int(x.size) * np.dtype(x.dtype).itemsize
    return max(1, nbytes // max(1, datatype.extent))


def typed_operand(datatype: Datatype, count: int, x) -> Optional[Typed]:
    """The one eligibility rule of every provider: a ``Typed`` when the
    packed stream of ``count`` elements of ``datatype`` can be gathered
    from ``x`` on the device (committed, device-packable, base type
    equal to the buffer's, or MPI_DOUBLE carried as uint64 bit
    patterns), else None (the host convertor serves it).  Depends only
    on the datatype, the count and the buffer's dtype and size, which
    MPI requires to match across ranks, so every member reaches the
    same verdict."""
    if not datatype.committed:
        from ompi_tpu import errhandler as _eh
        raise _eh.MPIException(
            _eh.ERR_TYPE, f"datatype {label(datatype)} is not "
            "committed (MPI_ERR_TYPE)")
    t = resolve(datatype, count)
    if t is not None:
        t = t.carried(x.dtype)
    if t is None:
        return None
    size = int(x.size)
    if size < t.span:
        raise IndexError(
            f"buffer of {size} elements is shorter than the {t.span} "
            f"that {count} x {label(datatype)} address")
    return t


# ---------------------------------------------------------------------------
# segment packing for fused collectives (coll/fusion): N small payloads
# ride one flattened buffer; the offset table is host-side static so the
# pack/unpack slices bake into the fused executable
# ---------------------------------------------------------------------------

def segment_offsets(shapes):
    """Offset table for a flat concatenation of arrays with the given
    shapes: (offsets, lengths, total_elements).  Host-side and static —
    fused-collective bodies slice with these as compile-time constants
    (0-d shapes contribute one element)."""
    offs, lens = [], []
    total = 0
    for sh in shapes:
        n = 1
        for d in sh:
            n *= int(d)
        offs.append(total)
        lens.append(n)
        total += n
    return tuple(offs), tuple(lens), total


def pack_segments(arrays):
    """Flatten + concatenate payloads into one fused buffer.  Must be
    called INSIDE a jitted body: eager reshapes/concats each cost a
    device dispatch of their own, which is exactly the constant
    fusion exists to amortize."""
    import jax.numpy as jnp

    return jnp.concatenate([a.reshape(-1) for a in arrays])


def unpack_segments(flat, shapes):
    """Mirror of pack_segments: slice the fused buffer back into the
    original shapes (static slices; fuses into the surrounding jit)."""
    offs, lens, _ = segment_offsets(shapes)
    return [flat[o:o + n].reshape(sh)
            for o, n, sh in zip(offs, lens, shapes)]
