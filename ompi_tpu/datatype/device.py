"""On-device datatype packing: the descriptor program as static
slices of the buffer, or as ONE XLA gather where the layout has no
such form.

The north-star item SURVEY §2.9.1 calls "datatype packing done
on-device": a committed datatype's run descriptors (engine.py) are
merged once into the layout they describe, a few regular runs
``(base, nblocks, blocklen, stride)`` in elements, and packing a
device-resident buffer lowers by that layout inside the collective
that consumes it (reference counterpart: the convertor pack loop
feeding coll buffers, opal/datatype/opal_convertor.h:131-137, which
walks descriptors element-wise on the host CPU):

* a contiguous run is a static slice, the array itself when that is
  all of it;
* equal blocks a stride apart are the leading columns of a (blocks,
  stride) view, with the last block apart: the buffer may end at the
  datatype's span, not at a whole stride.  Where the stride is a few
  elements (one component of interleaved fields) the view is of rows
  of 128 strides, transposed, so that the stride lies along rows;
* anything else (many runs, an ``indexed`` with irregular
  displacements) is ``buf[idx]``, a per-element gather: 7 to 25 ns an
  element on a v5e (PERF.md section 5), where a slice runs near the
  memory's speed.

No index vector exists for a sliced layout until the host's view asks
for one (``Typed.idx``).  Unpack is the mirrored scatter.

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
#: the reductions that round in a float type: the bits of such a fold
#: depend on how it is associated, so it is never taken in another shape
_ROUNDING_OPS = frozenset(("MPI_SUM", "MPI_PROD"))
#: R: the most a datatype may address, in packed streams, and still
#: have its P buffers folded whole before ONE pack (``Typed.
#: folds_first``).  Folding first reads ``span / elems`` times the bytes
#: to save P - 1 packs; set from the chip (PERF.md section 5 has a
#: reading on each side)
FOLD_FIRST_SPAN = 8


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


def _merge(runs):
    """Runs ``(base, nblocks, blocklen, stride)`` in elements, in
    packed order, merged into the fewest regular runs a left-to-right
    pass finds: adjacent blocks join, equal blocks equally far apart
    fold into one strided run.  However a datatype was built (a
    ``vector``, an ``hvector`` in whole elements, a ``subarray`` row
    block, an ``indexed`` with even displacements, ``count`` of a type
    whose extent continues its stride), one regular layout comes out
    as the same single run; a lone block has ``stride == blocklen``."""
    out = []
    for base, n, l, s in runs:
        if n == 1 or s == l:            # one block, or blocks that touch
            n, l = 1, n * l
            s = l
        if out:
            b0, n0, l0, s0 = out[-1]
            if n0 == 1 and n == 1 and base == b0 + l0:
                out[-1] = (b0, 1, l0 + l, l0 + l)
                continue
            step = base - b0 if n0 == 1 else s0
            if l == l0 and step > l and base == b0 + n0 * step \
                    and (n == 1 or s == step):
                out[-1] = (b0, n0 + n, l, step)
                continue
        out.append((base, n, l, s))
    return tuple(out)


#: a vector register's lanes.  A TPU lays a 2-D view out in tiles of
#: 128 lanes, so a (blocks, stride) view of a stride below that is
#: padded to 128 a row (stride 5: 25 times the bytes, and minutes of
#: compile; measured, PERF.md section 5).
_LANES = 128
#: the most runs packed as a concatenation of slices
_MAX_SLICED_RUNS = 4


def _rows(flat, base, n, l, s):
    """``n`` blocks as the leading ``l`` columns of a (blocks, stride)
    view, with the last block apart where the buffer ends at the
    datatype's span and not at a whole stride.  For rows at least
    ``_LANES`` long, or at most ``_LANES`` of them."""
    from jax import lax
    last = base + (n - 1) * s
    if flat.shape[0] >= last + s:
        return lax.slice(flat, (base,), (last + s,)) \
            .reshape(n, s)[:, :l].reshape(-1)
    tail = lax.slice(flat, (last,), (last + l,))
    if n == 1:
        return tail
    head = lax.slice(flat, (base,), (last,)).reshape(n - 1, s)[:, :l]
    return lax.concatenate([head.reshape(-1), tail], 0)


def _slice_run(flat, base, n, l, s):
    """One regular run of ``flat`` (1-D, traced) with static bounds."""
    from jax import lax
    if n == 1:
        if base == 0 and l == flat.shape[0]:
            return flat
        return lax.slice(flat, (base,), (base + l,))
    # rows of _LANES strides each that the run holds whole
    w = _LANES * s
    rows = ((n - 1) * s + l) // w
    if s >= _LANES or not rows:
        return _rows(flat, base, n, l, s)
    # many blocks a few elements apart: those rows transposed, so that
    # the stride lies along rows, where a slice is address arithmetic
    # and no view has a minor dimension of a few elements; the blocks
    # past the last whole row are a row view
    head = lax.slice(flat, (base,), (base + rows * w,)).reshape(rows, w).T
    head = head.reshape(_LANES, s, rows)[:, :l, :].reshape(_LANES * l, rows)
    head = head.T.reshape(-1)
    done = rows * _LANES
    if done == n:
        return head
    return lax.concatenate(
        [head, _rows(flat, base + rows * w, n - done, l, s)], 0)


class Typed:
    """A committed datatype and a count, resolved once: the regular
    runs its packed stream is made of (``layout``: ``(base, nblocks,
    blocklen, stride)`` in elements, in packed order), the one
    primitive type of its runs (``dtype``), and what the device
    providers of a typed ``*_arr`` collective need of them: ``pack``
    (inside the provider's jit; its ``stream`` is static slices where
    ``sliced``, else the gather of ``idx``), ``sig`` (what keys their
    programs: base type, carrier and the layout itself, or a digest of
    ``idx`` where the layout is gathered, so equal layouts share one
    executable whatever datatype object described them).  ``bits``:
    the buffer holds MPI_DOUBLE as uint64 bit patterns; ``pack`` then
    hands the reduction order keys and ``unkey`` turns its results
    back."""

    __slots__ = ("datatype", "count", "dtype", "layout", "sliced", "elems",
                 "span", "bits", "sig", "_idx", "_twin")

    def __init__(self, datatype: Datatype, count: int, dtype: np.dtype,
                 layout: tuple) -> None:
        import hashlib

        self.datatype = datatype
        self.count = count
        self.dtype = dtype
        self.layout = layout
        #: a few runs, each one block or equal blocks a constant
        #: positive gap apart: read with slices, no index vector
        self.sliced = len(layout) <= _MAX_SLICED_RUNS \
            and all(n == 1 or l < s for _, n, l, s in layout)
        # packed stream, in elements; the buffer it is read from
        self.elems = sum(n * l for _, n, l, _ in layout)
        self.span = max(b + (n - 1) * max(s, 0) + l for b, n, l, s in layout)
        self.bits = False
        self._idx = None
        self.sig = ("typed", dtype.str, self.elems) + (
            layout if self.sliced else
            (hashlib.blake2b(np.ascontiguousarray(self.idx).tobytes(),
                             digest_size=16).hexdigest(),))
        self._twin = None

    # a provider's cache key holds the Typed itself: equal layouts are
    # one key
    def __hash__(self) -> int:
        return hash(self.sig)

    def __eq__(self, other) -> bool:
        return isinstance(other, Typed) and self.sig == other.sig

    @property
    def idx(self) -> np.ndarray:
        """The element-index vector whose gather is the packed stream:
        what a gathered layout packs with, and the host's view of any
        (``operand``, ``element_indices``, ``device_unpack``).  Built
        when first asked for: O(n) on the host, 8 bytes an element."""
        if self._idx is None:
            chunks = [(b + s * np.arange(n, dtype=np.int64)[:, None]
                       + np.arange(l, dtype=np.int64)[None, :]).reshape(-1)
                      for b, n, l, s in self.layout]
            self._idx = np.concatenate(chunks) if len(chunks) > 1 \
                else chunks[0]
        return self._idx

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
            for k in ("datatype", "count", "dtype", "layout", "sliced",
                      "elems", "span", "_idx"):
                setattr(t, k, getattr(self, k))
            t.bits, t.sig, t._twin = True, self.sig + ("bits",), self
            self._twin = t
        return self._twin

    def stream(self, arr):
        """The packed stream of ``arr``, as the buffer holds it."""
        import jax.numpy as jnp
        flat = arr.reshape(-1)
        if not self.sliced:
            return jnp.take(flat, jnp.asarray(self.idx), axis=0)
        parts = [_slice_run(flat, *run) for run in self.layout]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def keyed(self, arr):
        """What the reduction compares: order keys of a bit-pattern
        carrier, any other buffer as it is."""
        return order_key(arr) if self.bits else arr

    def pack(self, arr):
        return self.keyed(self.stream(arr))

    def folds_first(self, opname: str) -> bool:
        """Whether a stacked reduction of P buffers folds them whole,
        gaps included, and packs the one result, instead of packing P
        times and folding the streams.  A pack selects elements and
        these folds are elementwise, so the answers are the same values
        wherever the fold does not round: MAX, MIN, the bitwise and
        logical operations on any type, SUM and PROD on integers (a
        float SUM keeps the association it has).  Not for one block,
        whose pack is a plain slice and costs nothing; not where the
        datatype skips so much that folding the gaps costs more than
        the packs saved (``FOLD_FIRST_SPAN``).  Read from the call
        alone, so every rank reaches the same verdict."""
        return (opname not in _ROUNDING_OPS or self.dtype.kind in "iub") \
            and (len(self.layout) > 1 or self.layout[0][1] > 1) \
            and self.span <= FOLD_FIRST_SPAN * self.elems

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
    when the datatype is not device-packable.  The one cache: merging
    the run descriptors is host-side and O(runs); a gathered layout's
    index vector is O(n) besides (8 bytes an element, hence the
    bound), a sliced layout has none until the host's view asks."""
    runs = datatype.runs_for_count(count)
    if not runs:
        return None
    item = runs[0].dtype.itemsize
    elems = []
    for r in runs:
        if r.dtype != runs[0].dtype:
            return None  # mixed primitive types: host convertor
        if r.disp % item or r.stride % item:
            return None  # sub-element displacement: host convertor
        base, stride = r.disp // item, r.stride // item
        if min(base, base + (r.nblocks - 1) * stride) < 0:
            return None  # negative displacement: host convertor owns it
        if r.nblocks and r.count:
            elems.append((base, r.nblocks, r.count, stride))
    if not elems:
        return None
    return Typed(datatype, count, runs[0].dtype, _merge(elems))


def element_indices(datatype: Datatype, count: int) -> Optional[np.ndarray]:
    """Element indices (into a flat element-typed buffer view) whose
    gather equals the datatype's packed stream for ``count`` elements,
    or None when the datatype is not device-packable."""
    t = resolve(datatype, count)
    return None if t is None else t.idx


def device_pack(datatype: Datatype, count: int, arr):
    """Pack a device-resident array through the datatype: static
    slices of a regular layout, else one XLA gather (jittable; fuses
    into downstream collectives).  ``arr`` is the flat element-typed
    buffer the datatype addresses, or MPI_DOUBLE's uint64 carrier."""
    t = resolve(datatype, count)
    if t is None:
        raise ValueError(
            f"datatype {datatype.name or datatype.id} is not "
            f"device-packable (mixed types or sub-element layout)")
    c = t.carried(arr.dtype)
    if c is None:
        raise ValueError(
            f"buffer dtype {arr.dtype} does not match datatype base "
            f"{t.dtype}")
    return c.stream(arr)


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
    packed stream of ``count`` elements of ``datatype`` can be read out
    of ``x`` on the device (committed, device-packable, base type
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
