"""Ragged exchange on device arrays: ``comm.alltoallv_arr``.

MPI_Alltoallv on a functional array API.  The counts and displacements
are host integers, as MPI's are; the receive buffer's size is a static
``capacity`` (MPI's receive buffer is the user's to size).  What a
repartition by key calls every step (a bucket sort's key exchange, a
shuffle, a join, expert-parallel token dispatch and combine): the split
is decided at run time and differs on every call.

The send buffer may have rows: an array of any rank >= 1 whose leading
dimension is what the counts count, as MPI counts in a contiguous row
datatype (an expert-parallel token is a row of ``hidden`` features).
Counts, displacements and ``capacity`` are in rows; the result is
``(capacity, *x.shape[1:])``.  Every rank's rows have one shape and one
element type (MPI's matching type signatures).

So the counts are OPERANDS of the device program, never part of its
key, on either provider:

* coll/hbm (P ranks on one chip): ``body``, the ``ompi_alltoallv``
  program, P x P block copies over the flat view of every buffer
  (counts x elements a row) whose offsets and lengths come from an
  int32 operand.  A copy of a dynamic length is a loop over chunks of
  a static length (``dynamic_slice`` fused into an in-place
  ``dynamic_update_slice``), the last chunk laid back so that it ends
  where the copy ends, with the partial tiles at a block's two ends and
  every short block merged through a small window under a mask:
  nothing is read or written beyond a count.
* coll/tpu (one rank a chip of a mesh): ``mesh_program``, the
  ``ompi_alltoallv_mesh`` program, one ``lax.ragged_all_to_all`` over
  ICI whose offsets and sizes are every rank's row of an int32 operand
  (``mesh_operand``).  XLA:CPU cannot lower that collective
  (``NO_LOWERING``), so a CPU mesh is served through the host.

The result buffers are never initialised: what lies outside the
received blocks is not part of the result (MPI leaves it untouched).

PERF.md section 5 has the chip's readings of these bodies and of the
ones they were chosen over.
"""
from __future__ import annotations

import functools
import struct
from itertools import accumulate
from operator import add

import numpy as np

from ompi_tpu import errhandler as _eh
from ompi_tpu.mca.params import registry
from ompi_tpu.runtime import x64 as _x64

pv_device_ops = registry.register_pvar(
    "coll", "alltoallv", "device_ops",
    help="alltoallv_arr rank-calls served by a device program "
         "(coll/hbm's ompi_alltoallv on one chip, coll/tpu's "
         "ompi_alltoallv_mesh over a mesh); once a rank-call")
pv_elems = registry.register_pvar(
    "coll", "alltoallv", "elems",
    help="Elements the device-served alltoallv_arr rank-calls were asked "
         "to send: the sum of their scounts times the elements of a row, "
         "not of any padded bound")
pv_bytes = registry.register_pvar(
    "coll", "alltoallv", "bytes",
    help="Bytes the device-served alltoallv_arr rank-calls were asked to "
         "send: the sum of their scounts times the bytes of a row, not "
         "of any padded bound")

#: the longest chunk of a block copy, in elements (PERF.md section 5)
CHUNK = 1 << 19
#: elements of a 4-byte type in one tile of a 1-D array on the chip: a
#: chunk whose DESTINATION offset is known to be a multiple of it is
#: copied in one pass (``body``)
TILE = 1024
#: element sizes the device programs move (8-byte ones as the carrier
#: runtime/x64 states; the entry has refused what jax would narrow)
ITEMSIZES = (2, 4, 8)
#: platforms whose compiler cannot lower ``ragged-all-to-all``: a mesh
#: of their devices is served through the host
NO_LOWERING = ("cpu",)
#: the narrowest row the mesh program moves, in bytes: one tile of 128
#: 32-bit lanes.  The chip's ``ragged-all-to-all`` lays every row out on
#: whole tiles, so a narrower row costs up to 128 times its bytes (a
#: 1-D buffer of 16 Mi keys would not fit a chip)
MESH_ROW_BYTES = 512
_ROWS = ("scounts", "sdispls", "rcounts", "rdispls")
#: ``meta[SENT]``: the rows a rank-call sends, summed once
SENT = 4
_SEQUENCES = (list, tuple)


def row_elems(x) -> int:
    """Elements of one row of ``x`` (1 for a 1-D buffer)."""
    n = 1
    for d in x.shape[1:]:
        n *= d
    return n


class Deposit:
    """What a rank brings to the meeting: its send buffer and its own
    view of the exchange (``meta``, ``arguments``'s), with the length
    it wants back."""

    __slots__ = ("x", "meta", "capacity", "nbytes")

    def __init__(self, x, meta: tuple, capacity: int) -> None:
        self.x = x
        self.meta = meta
        self.capacity = capacity
        # what the offload accounting reports as moved: the bytes sent
        self.nbytes = meta[SENT] * row_elems(x) * x.dtype.itemsize

    def flipped(self, flip) -> "Deposit":
        """The deposit a corrupting chip would have made
        (obs/integrity.flip_value): the same counts, ``flip`` of the
        array."""
        return Deposit(flip(self.x), self.meta, self.capacity)


def _row(given, size: int, row: int) -> tuple:
    """``given`` as ``size`` Python ints: a list or tuple of ints as it
    is, anything numpy reads as integers through ``tolist``; else
    MPI_ERR_COUNT."""
    if type(given) is np.ndarray:
        v = given
    elif type(given) in _SEQUENCES and len(given) == size \
            and all(type(v) is int for v in given):
        return tuple(given)
    else:
        v = np.asarray(given)
    if v.shape != (size,) or v.dtype.kind not in "iu":
        raise _eh.MPIException(
            _eh.ERR_COUNT, f"alltoallv_arr: {_ROWS[row]} must be "
            f"{size} integers, one a rank (MPI_ERR_COUNT)")
    return tuple(v.tolist())


def arguments(size: int, length: int, scounts, rcounts, sdispls, rdispls,
              capacity) -> tuple:
    """MPI's argument contract, checked on the caller's own side: the
    ``meta`` of a call, ``(scounts, sdispls, rcounts, rdispls, sent)``,
    four rows of ``size`` Python ints and the sum of ``scounts``.
    Displacements default to the exclusive prefix sums (packed blocks
    in rank order)."""
    if capacity is None:
        raise _eh.MPIException(
            _eh.ERR_ARG, "alltoallv_arr: capacity (the static length of "
            "the result, MPI's receive buffer) must be given "
            "(MPI_ERR_ARG)")
    sc = _row(scounts, size, 0)
    sd = (0, *accumulate(sc[:-1])) if sdispls is None \
        else _row(sdispls, size, 1)
    rc = _row(rcounts, size, 2)
    rd = (0, *accumulate(rc[:-1])) if rdispls is None \
        else _row(rdispls, size, 3)
    if min(min(sc), min(sd), min(rc), min(rd)) < 0:
        raise _eh.MPIException(
            _eh.ERR_COUNT, "alltoallv_arr: a negative count or "
            "displacement (MPI_ERR_COUNT)")
    end = max(map(add, sd, sc))
    if end > length:
        raise _eh.MPIException(
            _eh.ERR_BUFFER, f"alltoallv_arr: a send block ends at "
            f"{end}, past the {length} elements of the send buffer "
            "(MPI_ERR_BUFFER)")
    end = max(map(add, rd, rc))
    if end > capacity:
        raise _eh.MPIException(
            _eh.ERR_TRUNCATE, f"alltoallv_arr: a receive block ends at "
            f"{end}, past the capacity of {capacity} elements "
            "(MPI_ERR_TRUNCATE)")
    return sc, sd, rc, rd, sum(sc)


def _checked(deposits, longest: int) -> list:
    """The deposits' ``meta``s, after what only the meeting can check:
    that every rank's rows have one shape and one element type (MPI's
    matching type signatures), that what rank i states it sends to j is
    what j states it receives from i, and that ``longest`` (of the
    deposits' lengths and capacities, in elements) fits an int32."""
    x0 = deposits[0].x
    for i, d in enumerate(deposits):
        if d.x.shape[1:] != x0.shape[1:] or d.x.dtype != x0.dtype:
            raise _eh.MPIException(
                _eh.ERR_TYPE, f"alltoallv_arr: rank {i} sends rows of "
                f"{tuple(d.x.shape[1:])} {d.x.dtype}, rank 0 of "
                f"{tuple(x0.shape[1:])} {x0.dtype} (MPI_ERR_TYPE)")
    metas = [d.meta for d in deposits]
    sends = [m[0] for m in metas]
    expects = list(zip(*[m[2] for m in metas]))     # [i][j]: j's of i
    if sends != expects:
        i, j = next((i, j) for i, (s, e) in enumerate(zip(sends, expects))
                    for j in range(len(s)) if s[j] != e[j])
        raise _eh.MPIException(
            _eh.ERR_COUNT, f"alltoallv_arr: rank {i} sends {sends[i][j]} "
            f"elements to rank {j}, which expects {expects[i][j]} "
            "(MPI_ERR_COUNT)")
    if longest >= 1 << 31:
        raise _eh.MPIException(
            _eh.ERR_COUNT, "alltoallv_arr: a buffer of 2**31 elements or "
            "more (MPI_ERR_COUNT)")
    return metas


def operand(deposits, longest: int) -> np.ndarray:
    """coll/hbm's int32 operand from the P deposits, indexed ``[what,
    source, destination]``: how many rows, from where in the source's
    buffer, to where in the destination's result (``_checked`` first)."""
    metas = _checked(deposits, longest)
    flat = []                   # row-major [what, source, destination]
    for m in metas:
        flat += m[0]
    for m in metas:
        flat += m[1]
    for column in zip(*[m[3] for m in metas]):
        flat += column
    P = len(metas)
    return np.frombuffer(_int32s(3 * P * P).pack(*flat),
                         np.int32).reshape(3, P, P)


def mesh_operand(deposits, longest: int) -> np.ndarray:
    """coll/tpu's int32 operand from the P deposits, ``(P, 4, P)``: rank
    i's row is what ``lax.ragged_all_to_all`` asks of it, in rows: where
    in its buffer each block starts, how long it is, where in the
    receiver's result it goes (rank j's ``rdispls[i]``, which only the
    meeting knows), and how many rows come from each rank
    (``_checked`` first)."""
    metas = _checked(deposits, longest)
    flat = []
    for m, lands in zip(metas, zip(*[m[3] for m in metas])):
        flat += m[1]
        flat += m[0]
        flat += lands
        flat += m[2]
    P = len(metas)
    return np.frombuffer(_int32s(4 * P * P).pack(*flat),
                         np.int32).reshape(P, 4, P)


@functools.lru_cache(maxsize=None)
def _int32s(n: int) -> struct.Struct:
    """The packer of ``n`` native int32s (the format parsed once)."""
    return struct.Struct(f"={n}i")


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
    result's length, a rank) and the shapes of ``xs`` are static;
    every count and offset is read from ``meta`` (``operand``).  Rows
    are moved on the flat view of every buffer: the operand times the
    elements of a row, the results shaped back to rows.

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

    def flat(meta, xs, caps):
        size, dtype = len(xs), xs[0].dtype
        # the constants are read when the program is traced
        a = TILE * max(1, 4 // dtype.itemsize)
        aligned = jnp.uint32(~(a - 1) & 0xFFFFFFFF)
        outs = []
        for j, cap in enumerate(caps):
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

    def ompi_alltoallv(meta, *xs):
        row = xs[0].shape[1:]
        if not row:
            return flat(meta, xs, capacities)
        w = row_elems(xs[0])
        outs = flat(meta * w, [x.reshape(-1) for x in xs],
                    [c * w for c in capacities])
        return tuple(o.reshape((c, *row)) for o, c in zip(outs, capacities))

    return ompi_alltoallv


def mesh_program(mesh, capacity: int, sharding):
    """``ompi_alltoallv_mesh(meta, x)``: the ONE program of a ragged
    exchange among the P ranks of ``mesh``, one a chip: every rank's
    buffer is its shard of ``x`` and its row of ``meta``
    (``mesh_operand``) its offsets and sizes, both on ``sharding``; the
    result is a ``(capacity, *row)`` shard a rank.  An 8-byte element
    travels as two 32-bit words (the compiler does not split a 64-bit
    ragged-all-to-all), and a row as the ``(pack, words / pack)`` block
    the chip's collective lays out on whole tiles, ``pack`` the 16-bit
    words a 32-bit lane holds: one relayout copy in, one out."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def ompi_alltoallv_mesh(meta, x):
        m = meta[0]
        n, dtype = x.shape[0], x.dtype
        if dtype.itemsize == 8:
            x = lax.bitcast_convert_type(x, jnp.uint32)
        words = row_elems(x)
        pack = 2 if x.dtype.itemsize == 2 and words % 2 == 0 else 1
        block = (pack, words // pack)
        out = _exchange(x.reshape((n, *block)),
                        lax.empty((capacity, *block), x.dtype),
                        m[0], m[1], m[2], m[3])
        out = out.reshape((capacity, *x.shape[1:]))
        return out if out.dtype == dtype \
            else lax.bitcast_convert_type(out, dtype)

    return jax.jit(jax.shard_map(ompi_alltoallv_mesh, mesh=mesh,
                                 in_specs=(P("r"), P("r")),
                                 out_specs=P("r"), check_vma=False),
                   in_shardings=(sharding, sharding),
                   out_shardings=sharding)


def _exchange(x, out, offsets, sizes, lands, takes):
    """The collective of ``ompi_alltoallv_mesh``, over the mesh axis."""
    from jax import lax
    return lax.ragged_all_to_all(x, out, offsets, sizes, lands, takes,
                                 axis_name="r")


def through_host(deposits, devices, staged) -> list:
    """The meeting's answer where the deposits cannot be the shards of
    one mesh program (MPI lets every rank size its own send buffer and
    capacity): each block copied through host memory, each result put
    back on its rank's device; ``staged`` counts the rank-calls."""
    metas = _checked(deposits, 0)
    xs = [np.asarray(d.x) for d in deposits]
    outs = []
    for j, d in enumerate(deposits):
        out = np.zeros((d.capacity, *xs[j].shape[1:]), xs[j].dtype)
        for i, (sc, sd, _rc, _rd, _n) in enumerate(metas):
            r = metas[j][3][i]
            out[r:r + sc[j]] = xs[i][sd[j]:sd[j] + sc[j]]
        outs.append(_x64.put(out, devices[j], "alltoallv_arr"))
    staged.add(len(deposits))
    return outs


def alltoallv_arr(comm, entry, x, scounts, rcounts, sdispls, rdispls,
                  capacity):
    """``comm.alltoallv_arr``: check the arguments, refuse an element
    this device would not hold whole (runtime/x64), hand the shim the
    rows and elements sent, the bytes of a row and the capacity for the
    call's ``coll`` span, and call the winning provider's entry with the
    call's ``meta``."""
    dtype = getattr(x, "dtype", None)
    if dtype is None:
        x = np.asarray(x)
        dtype = x.dtype
    shape = x.shape
    if not shape:
        raise _eh.MPIException(
            _eh.ERR_BUFFER, "alltoallv_arr: the send buffer is an array "
            "of rows, not a scalar (MPI_ERR_BUFFER)")
    if dtype.itemsize >= 8 and comm.state.device is not None:
        _x64.check(dtype, "alltoallv_arr")
    meta = arguments(comm.size, shape[0], scounts, rcounts, sdispls,
                     rdispls, capacity)
    capacity = int(capacity)
    tr = comm.state.tracer
    if tr is None:
        return entry(comm, x, meta, capacity)
    w = row_elems(x)
    tr.coll_args = {"rows": meta[SENT], "elems": meta[SENT] * w,
                    "row_bytes": w * dtype.itemsize, "capacity": capacity}
    try:
        return entry(comm, x, meta, capacity)
    finally:
        tr.coll_args = None     # a span sampled out took nothing
