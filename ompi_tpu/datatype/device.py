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
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .engine import Datatype

_idx_cache: dict = {}
_dtype_cache: dict = {}


def element_indices(datatype: Datatype, count: int) -> Optional[np.ndarray]:
    """Element indices (into a flat element-typed buffer view) whose
    gather equals the datatype's packed stream for ``count`` elements,
    or None when the datatype is not device-packable.  Cached per
    (datatype id, count) — index construction is host-side and O(n),
    the device gather is the per-call cost."""
    key = (datatype.id, count)
    hit = _idx_cache.get(key)
    if hit is not None:
        return hit
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
    _idx_cache[key] = idx
    _dtype_cache[key] = runs[0].dtype
    return idx


def device_pack(datatype: Datatype, count: int, arr):
    """Pack a device-resident array through the datatype: one XLA
    gather (jittable; fuses into downstream collectives).  ``arr`` is
    the flat element-typed buffer the datatype addresses."""
    import jax.numpy as jnp

    idx = element_indices(datatype, count)
    if idx is None:
        raise ValueError(
            f"datatype {datatype.name or datatype.id} is not "
            f"device-packable (mixed types or sub-element layout)")
    base = _dtype_cache[(datatype.id, count)]
    if base != np.dtype(arr.dtype):
        raise ValueError(
            f"buffer dtype {arr.dtype} does not match datatype base "
            f"{base}")
    return jnp.take(arr.reshape(-1), jnp.asarray(idx), axis=0)


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
    return element_indices(datatype, count) is not None


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
