"""Ragged exchange on device arrays: ``comm.alltoallv_arr``.

MPI_Alltoallv on a functional array API.  The counts and displacements
are host integers, as MPI's are; the receive buffer's size is a static
``capacity`` (MPI's receive buffer is the user's to size).  What a
repartition by key calls every step (a bucket sort's key exchange, a
shuffle, a join, expert-parallel token dispatch): the split is decided
at run time and differs on every call.

So the counts are OPERANDS of the device program, never part of its
key.  ``body`` builds the one program coll/hbm runs for P ranks on one
chip: P x P block copies whose offsets and lengths come from an int32
operand.  A copy of a dynamic length is a loop over chunks of a static
length (``dynamic_slice`` fused into an in-place
``dynamic_update_slice``), the last chunk laid back so that it ends
where the copy ends, with the partial tiles at a block's two ends and
every short block merged through a small window under a mask: nothing
is read or written beyond a count.  The result buffers are never
initialised: what lies outside the received blocks is not part of the
result (MPI leaves it untouched).

PERF.md section 5 has the chip's readings of this body and of the ones
it was chosen over.
"""
from __future__ import annotations

import numpy as np

from ompi_tpu import errhandler as _eh
from ompi_tpu.mca.params import registry

pv_device_ops = registry.register_pvar(
    "coll", "alltoallv", "device_ops",
    help="alltoallv_arr rank-calls served by the device program "
         "(coll/hbm's ompi_alltoallv); once a rank-call")
pv_elems = registry.register_pvar(
    "coll", "alltoallv", "elems",
    help="Elements the device-served alltoallv_arr rank-calls were asked "
         "to send: the sum of their scounts, not of any padded bound")

#: the longest chunk of a block copy, in elements (PERF.md section 5)
CHUNK = 1 << 19
#: elements of a 4-byte type in one tile of a 1-D array on the chip: a
#: chunk whose DESTINATION offset is known to be a multiple of it is
#: copied in one pass (``body``)
TILE = 1024
#: element sizes the device program moves (8-byte ones as the carrier
#: runtime/x64 states; the entry has refused what jax would narrow)
ITEMSIZES = (2, 4, 8)
_ROWS = ("scounts", "sdispls", "rcounts", "rdispls")


class Deposit:
    """What a rank brings to the meeting: its send buffer and its own
    view of the exchange (``meta``: int64 rows scounts, sdispls,
    rcounts, rdispls), with the length it wants back."""

    __slots__ = ("x", "meta", "capacity", "nbytes")

    def __init__(self, x, meta: np.ndarray, capacity: int) -> None:
        self.x = x
        self.meta = meta
        self.capacity = capacity
        # what the offload accounting reports as moved: the bytes sent
        self.nbytes = int(meta[0].sum()) * np.dtype(x.dtype).itemsize

    def flipped(self, flip) -> "Deposit":
        """The deposit a corrupting chip would have made
        (obs/integrity.flip_value): the same counts, ``flip`` of the
        array."""
        return Deposit(flip(self.x), self.meta, self.capacity)


def arguments(size: int, length: int, scounts, rcounts, sdispls, rdispls,
              capacity) -> np.ndarray:
    """MPI's argument contract, checked on the caller's own side: the
    (4, size) int64 ``meta`` of a call.  Displacements default to the
    exclusive prefix sums (packed blocks in rank order)."""
    if capacity is None:
        raise _eh.MPIException(
            _eh.ERR_ARG, "alltoallv_arr: capacity (the static length of "
            "the result, MPI's receive buffer) must be given "
            "(MPI_ERR_ARG)")
    meta = np.empty((4, size), np.int64)
    for row, (given, of) in enumerate(
            ((scounts, None), (sdispls, 0), (rcounts, None), (rdispls, 2))):
        if given is None and of is not None:
            meta[row, 0] = 0
            np.cumsum(meta[of, :-1], out=meta[row, 1:])
            continue
        v = np.asarray(given)
        if v.shape != (size,) or v.dtype.kind not in "iu":
            raise _eh.MPIException(
                _eh.ERR_COUNT, f"alltoallv_arr: {_ROWS[row]} must be "
                f"{size} integers, one a rank (MPI_ERR_COUNT)")
        meta[row] = v
    if meta.min() < 0:
        raise _eh.MPIException(
            _eh.ERR_COUNT, "alltoallv_arr: a negative count or "
            "displacement (MPI_ERR_COUNT)")
    if (meta[1] + meta[0]).max() > length:
        raise _eh.MPIException(
            _eh.ERR_BUFFER, f"alltoallv_arr: a send block ends at "
            f"{int((meta[1] + meta[0]).max())}, past the {length} "
            "elements of the send buffer (MPI_ERR_BUFFER)")
    if (meta[3] + meta[2]).max() > capacity:
        raise _eh.MPIException(
            _eh.ERR_TRUNCATE, f"alltoallv_arr: a receive block ends at "
            f"{int((meta[3] + meta[2]).max())}, past the capacity of "
            f"{capacity} elements (MPI_ERR_TRUNCATE)")
    return meta


def operand(deposits) -> np.ndarray:
    """The program's int32 operand from the P deposits, indexed
    ``[what, source, destination]``: how many elements, from where in
    the source's buffer, to where in the destination's result.  Checks
    what only the meeting can: that what rank i states it sends to j is
    what j states it receives from i."""
    metas = np.stack([d.meta for d in deposits])         # (P, 4, P)
    counts = metas[:, 0, :]
    if not np.array_equal(counts, metas[:, 2, :].T):
        i, j = np.argwhere(counts != metas[:, 2, :].T)[0]
        raise _eh.MPIException(
            _eh.ERR_COUNT, f"alltoallv_arr: rank {i} sends {counts[i, j]} "
            f"elements to rank {j}, which expects {metas[j, 2, i]} "
            "(MPI_ERR_COUNT)")
    if max(max(d.x.shape[0], d.capacity) for d in deposits) >= 1 << 31:
        raise _eh.MPIException(
            _eh.ERR_COUNT, "alltoallv_arr: a buffer of 2**31 elements or "
            "more (MPI_ERR_COUNT)")
    return np.stack([counts, metas[:, 1, :], metas[:, 3, :].T]).astype(
        np.int32)


def chunk_of(length: int, capacity: int, size: int, chunk: int,
             tile: int) -> int:
    """The static chunk of one source-destination pair, a whole number
    of tiles: half of what a balanced split would send it, as a power
    of two, at most ``chunk``, at least one tile, no longer than either
    buffer; 0 where a buffer holds no whole tile."""
    half = 1 << (max(1, length // (2 * size)) - 1).bit_length()
    fits = min(length, capacity) // tile * tile
    return min(fits, max(tile, min(chunk, half) // tile * tile))


def _merge(x, o, c, s, r, k: int):
    """``o`` with ``x[s:s + c]`` at ``[r, r + c)``, for ``c`` <= ``k``:
    one window of ``k`` elements on each side, clamped into its buffer,
    the source's rolled into place and merged under a mask.  ``c`` 0
    changes nothing."""
    import jax.numpy as jnp
    from jax import lax

    ws = jnp.clip(s, 0, x.shape[0] - k)
    wr = jnp.clip(r, 0, o.shape[0] - k)
    at = r - wr
    w = jnp.roll(lax.dynamic_slice(x, (ws,), (k,)), at - (s - ws))
    idx = lax.iota(jnp.int32, k)
    return lax.dynamic_update_slice(
        o, jnp.where((idx >= at) & (idx < at + c), w,
                     lax.dynamic_slice(o, (wr,), (k,))), (wr,))


def body(capacities):
    """``ompi_alltoallv(meta, *xs) -> P results``: the program of one
    ragged exchange among P ranks of one chip.  ``capacities`` (a
    result's length, a rank) and the lengths of ``xs`` are static;
    every count and offset is read from ``meta`` (``operand``).

    A block x[s:s + c] -> out[r:r + c] is copied in three parts: a
    *middle* that starts at the first tile boundary of the DESTINATION
    and is a whole number of tiles, and a *head* and a *tail* of less
    than a tile each.  The middle is a loop of ``dynamic_slice`` /
    ``dynamic_update_slice`` over chunks of a static length k (whole
    tiles), the last chunk laid back so that it ends where the middle
    ends.  Its destination offset is masked to a multiple of the tile
    in unsigned arithmetic, which changes no value and lets the
    compiler see the alignment: it then fuses the two into ONE pass
    over the data (an unaligned read, an aligned write in place);
    without it they are two passes through a temporary (PERF.md section
    5).  Head and tail are merged through windows of two tiles.  A block
    whose middle is shorter than a chunk is merged whole through one
    window of k + 2 tiles, under one conditional a result.  Nothing is
    read or written beyond a count, so no order of the displacements
    is required."""
    import jax.numpy as jnp
    from jax import lax

    def ompi_alltoallv(meta, *xs):
        size, dtype = len(xs), xs[0].dtype
        # the constants are read when the program is traced
        a = TILE * max(1, 4 // dtype.itemsize)
        aligned = jnp.uint32(~(a - 1) & 0xFFFFFFFF)
        outs = []
        for j, cap in enumerate(capacities):
            out = lax.empty((cap,), dtype)
            pairs = []
            for i, x in enumerate(xs):
                if min(x.shape[0], cap) == 0:
                    continue
                k = chunk_of(x.shape[0], cap, size, CHUNK, a)
                c, s, r = meta[0, i, j], meta[1, i, j], meta[2, i, j]
                head = jnp.minimum(-r % a, c)
                mid = (c - head) // a * a
                big = (mid >= k) & (k > 0)
                pairs.append((x, c, s, r, head, mid, big, k))
                if not k:
                    continue

                def copy(t, o, x=x, s=s + head, q=(r + head) // a, mid=mid,
                         k=k):
                    # in tiles; the last chunk ends where the middle does
                    off = jnp.minimum(t * (k // a), (mid - k) // a)
                    return lax.dynamic_update_slice(
                        o, lax.dynamic_slice(x, (s + off * a,), (k,)),
                        (((q + off) * a).astype(jnp.uint32) & aligned,))

                out = lax.fori_loop(
                    0, jnp.where(big, (mid + (k - 1)) // k, 0), copy, out)
            small = jnp.bool_(False)
            for x, c, s, r, head, mid, big, k in pairs:
                small = small | (~big & (c > 0))
                edge = min(2 * a, x.shape[0], cap)
                out = _merge(x, out, jnp.where(big, head, 0), s, r, edge)
                out = _merge(x, out, jnp.where(big, c - head - mid, 0),
                             s + head + mid, r + head + mid, edge)

            def short_blocks(o, pairs=pairs, cap=cap):
                for x, c, s, r, head, mid, big, k in pairs:
                    o = _merge(x, o, jnp.where(big, 0, c), s, r,
                               min(k + 2 * a, x.shape[0], cap))
                return o

            if pairs:
                out = lax.cond(small, short_blocks, lambda o: o, out)
            outs.append(out)
        return tuple(outs)

    return ompi_alltoallv


def alltoallv_arr(comm, entry, x, scounts, rcounts, sdispls, rdispls,
                  capacity):
    """``comm.alltoallv_arr``: check the arguments, refuse an element
    this device would not hold whole (runtime/x64), hand the shim the
    elements sent and the capacity for the call's ``coll`` span, and
    call the winning provider's entry with the call's ``meta``."""
    if not hasattr(x, "dtype"):
        x = np.asarray(x)
    if x.ndim != 1:
        raise _eh.MPIException(
            _eh.ERR_BUFFER, "alltoallv_arr: the send buffer is a 1-D "
            f"array of elements, not of shape {tuple(x.shape)} "
            "(MPI_ERR_BUFFER)")
    if x.dtype.itemsize >= 8 and comm.state.device is not None:
        from ompi_tpu.runtime import x64
        x64.check(x.dtype, "alltoallv_arr")
    meta = arguments(comm.size, x.shape[0], scounts, rcounts, sdispls,
                     rdispls, capacity)
    capacity = int(capacity)
    tr = comm.state.tracer
    if tr is None:
        return entry(comm, x, meta, capacity)
    tr.coll_args = {"elems": int(meta[0].sum()), "capacity": capacity}
    try:
        return entry(comm, x, meta, capacity)
    finally:
        tr.coll_args = None     # a span sampled out took nothing
