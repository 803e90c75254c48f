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
  ICI whose offsets and sizes are every rank's row of an int32 operand.
  XLA:CPU cannot lower that collective (``NO_LOWERING``), so a CPU mesh
  is served through the host.  The program has two bodies, and the
  buffer's own layout on the chip chooses (``slab_rows``):

  - the *slab body*, for a row-major buffer (a 2-D buffer of 2- or
    4-byte elements whose row is a whole number of 128-element lanes,
    laid out on ``(t, 128)`` tiles, ``t`` dividing its rows and the
    capacity): the buffer viewed as slabs of ``t`` rows is the same
    bytes, so the collective moves every slab a block touches, as it
    lies, into a staging buffer, and ONE pass on arrival, a Pallas
    kernel, moves each block's rows to its ``rdispls``
    (``slab_operand``, ``arrival``);
  - the *row body*, for any other buffer: each row travels as its own
    tile-shaped block (``mesh_operand``), so the compiler relays the
    whole buffer out to that shape and back.  The expert-parallel
    dispatch's 1,864-word rows take it: the chip lays a buffer whose
    row is not a whole number of lanes out column-major, 128 rows to a
    tile column, and moving one of its rows is a transpose whatever
    the body.

  The operand crosses from the host ONCE a call, onto the first rank's
  chip: the program's operand is ``(P * P, k, P)`` on the mesh, the
  first chip's shard the meeting's matrix and every other chip's a
  placeholder of zeros made once with the program and never written,
  and the program's first step is an all-reduce of the P shards over
  ICI (``mesh_program``), so every chip holds the matrix and takes its
  own row.  A numpy operand would be sharded by the jit onto every chip
  at every launch: P host-to-device transfers a call, each dear on the
  host.  coll/hbm's one chip has no other chip to spread to and takes
  its numpy operand as it is.

The result buffers are never initialised: what lies outside the
received blocks is not part of the result (MPI leaves it untouched).

PERF.md section 5 has the chip's readings of these bodies and of the
ones they were chosen over.
"""
from __future__ import annotations

import functools
import math
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
pv_slab_ops = registry.register_pvar(
    "coll", "alltoallv", "slab_ops",
    help="alltoallv_arr rank-calls that coll/tpu's ompi_alltoallv_mesh "
         "served with its slab body (a row-major buffer moved as whole "
         "tile rows, one pass on arrival; coll/ragged.slab_rows); "
         "once a rank-call, with coll_alltoallv_device_ops")
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
pv_operand_puts = registry.register_pvar(
    "coll", "alltoallv", "operand_puts",
    help="Host-to-device transfers of the count operand of coll/tpu's "
         "ompi_alltoallv_mesh: one a meeting, onto the first rank's "
         "chip (the program spreads it to the others over ICI); none "
         "for a meeting served through the host")

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


def layout_of(x):
    """The layout ``x`` holds on its device (None where it states none)."""
    return getattr(getattr(x, "format", None), "layout", None)


def slab_rows(layout, shape: tuple, itemsize: int, capacity: int) -> int:
    """THE rule of the mesh program's slab body: the rows ``t`` of one
    slab where a buffer of ``shape`` laid out as ``layout`` is
    row-major on ``(t, 128)`` tiles (minor-to-major ``{1,0}``, which the
    chip gives a row of whole 128-element lanes), of 2- or 4-byte
    elements, ``t`` dividing its rows and ``capacity``: viewed as
    ``(rows / t, t, row)`` it is then the same bytes, and so is the
    staging buffer viewed as rows.  0 for any other buffer, which the
    row body serves (an 8-byte element, a row that is not a whole
    number of lanes, which the chip lays out column-major, a row of
    more than one dimension, a layout with no tiles)."""
    tiling = getattr(layout, "tiling", None)
    if len(shape) != 2 or itemsize not in (2, 4) or shape[1] % 128 \
            or not tiling or tuple(layout.major_to_minor) != (0, 1):
        return 0
    t, lanes = tiling[0]
    if lanes != 128 or t < 1 or shape[0] % t or capacity % t:
        return 0
    return t


def _window(itemsize: int) -> int:
    """Rows a window of ``arrival`` is aligned to: one tile of the chip's
    memory, 8 rows of 32-bit words (a 2-byte element packs two rows a
    word)."""
    return 32 // itemsize


def staging_slabs(capacity: int, t: int, size: int, itemsize: int) -> int:
    """Slabs of ``t`` rows in the slab body's staging buffer, rounded to
    whole windows of ``arrival``: room for what ``size`` senders' blocks
    touch when they fill the capacity (a block of c rows touches at most
    (c + 2t - 2) / t slabs), and for one chunk of ``arrival`` and its
    window."""
    a = _window(itemsize)
    rows = max(capacity + 2 * size * (t - 1), -(-capacity // a) * a + a)
    whole = math.lcm(t, a)
    return -(-rows // whole) * whole // t


def slab_operand(deposits, longest: int, t: int):
    """coll/tpu's int32 operand for the slab body, ``(P, 7, P)``, from
    the P deposits (``_checked`` first): rank i's row is, in slabs of
    ``t`` rows, where in its buffer the slabs of each block start, how
    many the block touches, where they land in each receiver's staging
    buffer (after the slabs of every lower sender) and how many slabs
    it receives from each rank; then, in rows and ordered by where they
    land in its result, each received block's first row in the staging
    buffer, its ``rdispls`` and its count (``arrival``'s ``meta``).
    None where two of a rank's receive blocks overlap, which MPI
    forbids and ``arrival`` does not serve: the meeting then serves the
    call through the host."""
    metas = _checked(deposits, longest)
    P = len(metas)
    starts, slabs = [], []
    for sc, sd, _rc, _rd, _n in metas:
        starts.append([d // t for d in sd])
        slabs.append([-(-(d + c) // t) - d // t if c else 0
                      for c, d in zip(sc, sd)])
    lands = [[0] * P for _ in range(P)]            # [sender][receiver]
    for j in range(P):
        at = 0
        for i in range(P):
            lands[i][j] = at
            at += slabs[i][j]
    flat = []
    for j, m in enumerate(metas):
        rc, rd = m[2], m[3]
        order = sorted(range(P), key=rd.__getitem__)
        end = 0
        for i in order:
            if rc[i]:
                if rd[i] < end:
                    return None
                end = rd[i] + rc[i]
        flat += starts[j]
        flat += slabs[j]
        flat += lands[j]
        flat += [slabs[i][j] for i in range(P)]
        flat += [t * lands[i][j] + metas[i][1][j] % t for i in order]
        flat += [rd[i] for i in order]
        flat += [rc[i] for i in order]
    return np.frombuffer(_int32s(7 * P * P).pack(*flat),
                         np.int32).reshape(P, 7, P)


#: the bits of a 32-bit word each of its rows holds, by rows a word
_HALVES = {1: (0xFFFFFFFF,), 2: (0xFFFF, 0xFFFF0000)}
#: output rows a step of ``arrival`` writes, and the most bytes of the
#: window it reads (PERF.md section 5)
ARRIVAL_ROWS = 128
ARRIVAL_WINDOW_BYTES = 2 << 20


def _items(meta, rows: int, capacity: int, k: int, a: int):
    """``arrival``'s work list, ``(5, G)`` int32, one column a step: the
    aligned first row of the step's window in the staging buffer, the
    shift that puts it in place, the chunk of ``k`` result rows it
    writes, and the rows ``[lo, hi)`` of that chunk its block covers.
    Steps go in result order, a block's chunks in turn; steps past the
    last repeat it with nothing to cover."""
    import jax.numpy as jnp

    P = meta.shape[1]
    first, rd, rc = meta[0], meta[1], meta[2]
    c0 = rd // k
    cnt = jnp.where(rc > 0, (rd + rc - 1) // k - c0 + 1, 0)
    ends = jnp.cumsum(cnt)
    total = ends[-1]
    g = jnp.arange(-(-capacity // k) + 2 * P, dtype=jnp.int32)
    at = jnp.minimum(g, jnp.maximum(total - 1, 0))
    b = jnp.minimum(jnp.sum(at[:, None] >= ends[None, :], axis=1), P - 1)
    # a rank that receives nothing still has steps: they write no row,
    # and a chunk inside the result
    chunk = jnp.minimum(c0[b] + at - (ends[b] - cnt[b]),
                        -(-capacity // k) - 1)
    src = chunk * k - rd[b] + first[b]
    base = jnp.clip(src // a, 0, (rows - k - a) // a) * a
    live = g < total
    lo = jnp.where(live, jnp.maximum(rd[b], chunk * k), 0)
    hi = jnp.where(live, jnp.minimum(rd[b] + rc[b], chunk * k + k), 0)
    return jnp.stack([base, src - base, chunk, lo, hi]).astype(jnp.int32)


def arrival(staged, meta, capacity: int, interpret: bool = False):
    """The slab body's one pass on arrival: the ``(capacity, row)``
    result from the staging buffer ``staged``, each received block's
    rows moved to its ``rdispls``.  ``meta`` is ``(3, P)`` int32, a
    block a column in result order (``slab_operand``): its first row in
    ``staged``, its ``rdispls``, its count.  Rows outside every block
    are not part of the result.

    A Pallas kernel whose steps (``_items``, made on the device from
    the P columns: nothing capacity-long crosses from the host) each
    write ``ARRIVAL_ROWS`` result rows of one block: the pipeline reads
    a window of the staging buffer aligned to the chip's tile, the
    kernel rolls it into place in 32-bit words (two rows of a 2-byte
    element share a word, so an odd shift joins the halves of
    neighbouring words) and stores it, under a mask where the block
    covers part of the chunk.  Bits only: no float value is converted
    or computed on.  ``interpret`` runs it on a CPU (the tests)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    given = staged.dtype
    if interpret and jnp.issubdtype(given, jnp.floating):
        # XLA:CPU may quiet a NaN it moves as a float; the chip does not
        staged = lax.bitcast_convert_type(
            staged, jnp.dtype(f"uint{8 * given.itemsize}"))
    rows, width = staged.shape
    dtype = staged.dtype
    pack = 4 // dtype.itemsize
    a = _window(dtype.itemsize)
    k = min(ARRIVAL_ROWS, -(-capacity // a) * a)
    lanes = width // 128
    wb = 128 * max(d for d in range(1, lanes + 1) if lanes % d == 0 and (
        d == 1 or (k + a) * d * 128 * dtype.itemsize <= ARRIVAL_WINDOW_BYTES))
    words = (k + a) // pack
    items = _items(meta, rows, capacity, k, a)

    def window(lb, g, it):
        return (pl.multiple_of(it[0, g], a), pl.multiple_of(lb * wb, 128))

    def chunk(lb, g, it):
        return (it[2, g], lb)

    def step(it, src, out):
        g = pl.program_id(1)
        shift, lo, hi = it[1, g], it[3, g], it[4, g]
        at = it[2, g] * k
        w = pltpu.bitcast(src[...], jnp.uint32)
        if pack == 1:
            v = pltpu.roll(w, (words - shift % words) % words, 0)[:k]
        else:
            half = shift // 2
            w0 = pltpu.roll(w, (words - half % words) % words, 0)
            w1 = pltpu.roll(w0, words - 1, 0)
            v = jnp.where(shift % 2 == 1, (w0 >> 16) | (w1 << 16),
                          w0)[:k // 2]

        @pl.when((lo == at) & (hi == at + k))
        def _():
            out[...] = pltpu.bitcast(v, dtype)

        @pl.when((hi > lo) & ((lo != at) | (hi != at + k)))
        def _():
            # the row of each word's first half; its h-th half holds row + h
            row = pack * lax.broadcasted_iota(jnp.int32, v.shape, 0) + at
            keep = jnp.zeros(v.shape, jnp.uint32)
            for h, bits in enumerate(_HALVES[pack]):
                keep = keep | jnp.where((row + h >= lo) & (row + h < hi),
                                        jnp.uint32(bits), jnp.uint32(0))
            old = pltpu.bitcast(out[...], jnp.uint32)
            out[...] = pltpu.bitcast((v & keep) | (old & ~keep), dtype)

    out = pl.pallas_call(
        step, out_shape=jax.ShapeDtypeStruct((capacity, width), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(width // wb, items.shape[1]),
            in_specs=[pl.BlockSpec((pl.Element(k + a), pl.Element(wb)),
                                   window)],
            out_specs=pl.BlockSpec((k, wb), chunk)),
        # two windows, two result blocks and the 32-bit copies of a
        # window pass the default scoped limit
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        interpret=interpret, name="ompi_alltoallv_arrival",
    )(items, staged)
    return out if out.dtype == given else lax.bitcast_convert_type(out, given)


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


def mesh_program(mesh, capacity: int, sharding, slab: int = 0):
    """``ompi_alltoallv_mesh(meta, x)``: the ONE program of a ragged
    exchange among the P ranks of ``mesh``, one a chip: every rank's
    buffer is its shard of ``x`` and its row of the operand its offsets
    and sizes; the result is a ``(capacity, *row)`` shard a rank.  The
    program only moves bits.

    ``meta`` is ``(P * P, k, P)`` on ``sharding``, as ``x`` is: the
    first chip's shard is the ``(P, k, P)`` operand, put there by the
    host, and every other chip's is zeros (``spare_operand``).  The
    program's first step is ONE all-reduce of those shards over ICI, so
    every chip holds the operand without a transfer from the host of
    its own, and takes its row.

    With ``slab`` (``slab_rows``'s t) it is the slab body, ``meta`` the
    ``slab_operand``: the buffer viewed as slabs of t rows (no copy on
    a row-major buffer), ONE collective of whole slabs into a staging
    buffer of ``staging_slabs``, ONE pass that moves each block's rows
    into place (``arrival``).  A block may carry up to 2(t - 1) rows
    beyond its count, which land in staging and never reach the
    result.

    Without, it is the row body, ``meta`` the ``mesh_operand``: an
    8-byte element travels as two 32-bit words (the compiler does not
    split a 64-bit ragged-all-to-all), and a row as the ``(pack, words /
    pack)`` block the chip's collective lays out on whole tiles,
    ``pack`` the 16-bit words a 32-bit lane holds: one relayout copy
    of the whole buffer in, one out."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    interpret = mesh.devices.flat[0].platform != "tpu"

    def slab_body(m, x):
        n, width = x.shape
        room = staging_slabs(capacity, slab, mesh.devices.size,
                             x.dtype.itemsize)
        staged = _exchange(x.reshape((n // slab, slab, width)),
                           lax.empty((room, slab, width), x.dtype),
                           m[0], m[1], m[2], m[3])
        return arrival(staged.reshape((room * slab, width)), m[4:7],
                       capacity, interpret)

    def ompi_alltoallv_mesh(meta, x):
        m = lax.psum(meta, "r")[lax.axis_index("r")]
        if slab:
            return slab_body(m, x)
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


def put_operand(meta: np.ndarray, device):
    """The one host-to-device transfer of a mesh meeting: the int32
    operand onto ``device``, the first rank's chip, by its client's own
    call (``jax.device_put`` of the same array cost 297 us of host time
    against this call's 130 on a four-chip v5e host: PERF.md section
    5)."""
    return device.client.buffer_from_pyval(meta, device)


def spare_operand(devices, k: int) -> list:
    """The placeholders of ``mesh_program``'s operand, made once with
    the program: a ``(P, k, P)`` int32 shard of zeros on every chip of
    ``devices`` but the first.  The program reads them and adds nothing
    with them; nothing writes or donates them."""
    P = len(devices)
    zeros = np.zeros((P, k, P), np.int32)
    return [_x64.put(zeros, d, "alltoallv_arr") for d in devices[1:]]


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
