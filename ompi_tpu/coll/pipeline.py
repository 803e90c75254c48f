"""coll/pipeline: the large-message tier of the device collective
engine — segmented, pipelined, topology-aware algorithms.

The fused fast path (docs/DESIGN.md §8) owns the small-message regime:
ONE assembled shard_map per collective, dispatch constant amortized by
batching.  Large messages invert the trade — the payload dominates and
the operation is worth an algorithm chosen for its size.  This module
is the re-design of the reference's segmented algorithms (ref:
coll_tuned_decision_fixed.c:72 segmented ring above 1 MiB;
coll_base_allreduce.c:343 ring reduce-scatter + allgather;
Rabenseifner's decomposition) on the rendezvous machinery:

* **segring** — chunked ``ppermute`` ring allreduce: inside one
  compiled kernel per segment, P-1 reduce-scatter steps (each rank
  accumulates one stripe per hop) then P-1 allgather steps.  Per-chunk
  accumulation is a rank-ordered left fold computed by exactly ONE
  rank and circulated verbatim, so every rank's output is byte
  identical by construction.
* **segrd** — per-segment recursive doubling (power-of-two comms):
  log2(P) exchange rounds; both operand orders are computed and
  selected by rank parity (the MPICH operand-order discipline), so
  all ranks evaluate the identical expression tree.
* **ring bcast / pairwise alltoall** — segmented data movement on the
  same machinery (bit-exact by construction), over the mesh only: on
  one chip (coll/hbm) there is no wire to overlap with, and both stay
  one rendezvous and one kernel or handoff at every size.

**One program, one rendezvous** is what serves every operation routed
here by default (coll/plan.py, DESIGN.md §22): the allreduce
algorithms, and since PR 29 the mesh bcast and alltoall too.  The
per-segment bodies below run only with ``coll_plan_enable=0``.

**Pipelining** (the per-segment path): segments run through the
asynchronous rendezvous (``device.meet_begin``/``meet_finish``), a
rank depositing segment k and then slicing segment k+1 while the
dispatcher thread launches segment k, depth bounded by
``coll_pipeline_depth``.  On the chip the stages did not overlap the
wire: a 1 MiB slice costs a rank 1.1 to 2.9 ms of host time for 25 us
of wire, so the device idled 93 to 98% (PERF.md §6, PR 28 and PR 29).

**Segment-size discipline**: every segment of every message is padded
to ONE fixed per-host segment shape (op identity elements; sliced off
at unpack), so the CompiledLRU holds exactly one executable per
(algorithm, mesh, segment shape, dtype, op) — segment-size variants
cannot blow the bounded cache no matter how many distinct message
sizes a workload sweeps.

**Hierarchy** (``coll_hier_enable``): multi-slice meshes stop
serializing through one link — intra-slice XLA ``psum`` (the device
tier), inter-slice reduction by the slice leaders over the tcp/OOB
host path, then an intra-slice device bcast.  Slice membership comes
from ``topo.slice_groups`` (device slice_index / modex node_id, or
``coll_hier_slice_size`` for explicit shaping).

Selection rides the measured-rules machinery: ``tuned.device_algorithm``
consults ``calibrate`` (per-host segment size, small/segmented and
hierarchical crossovers, refreshed by ``bench.py --probe-pipeline``)
and the decision is cached per communicator — the per-comm module
binding discipline of the reference's comm_select.
"""

from __future__ import annotations

import functools
from collections import deque
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from ompi_tpu import trace as _trace
from ompi_tpu.mca.params import registry
from ompi_tpu.obs import integrity as _ig

# interned span names for the per-kind dispatch spans (args: cid,
# payload bytes, interned algorithm tag, the operation's sequence)
_PIPE_NAME = {
    kind: _trace.intern_name(f"pipeline_{kind}",
                             ("cid", "nbytes", "alg$", "op"))
    for kind in ("allreduce", "bcast", "alltoall")
}

# phase-profiler aliases (docs/DESIGN.md §18): host pack (segment
# slicing) and unpack (trim + concat) sub-op phases
_CAT_PHASE = _trace.CAT_PHASE
_NAME_PH_PACK = _trace.NAME_PH_PACK
_NAME_PH_UNPACK = _trace.NAME_PH_UNPACK
_L_ENTRY = _trace.L_ENTRY
_L_EXIT = _trace.L_EXIT
_L_PACK = _trace.L_PACK
_L_UNPACK = _trace.L_UNPACK

_seg_size_var = registry.register(
    "coll", "seg", "size", 1 << 20, int,
    help="Segment size (bytes) for the segmented/pipelined large-"
         "message device algorithms (ref: "
         "coll_tuned_decision_fixed.c:72).  Rounded up so ring "
         "stripes stay equal; coll_tuned_use_measured_rules replaces "
         "this with the calibrated per-host segment size")
_depth_var = registry.register(
    "coll", "pipeline", "depth", 2, int,
    help="Outstanding segments in the pipelined rendezvous: host "
         "packing of segment k+1 overlaps device dispatch of segment "
         "k up to this depth.  1 = fully synchronous")
_enable_var = registry.register(
    "coll", "pipeline", "enable", True, bool,
    help="Enable the segmented/pipelined large-message device tier "
         "(messages below coll_pipeline_min_bytes keep the fused "
         "single-dispatch path either way)")
_min_bytes_var = registry.register(
    "coll", "pipeline", "min_bytes", 4 << 20, int,
    help="Static fused-vs-segmented crossover: messages at least this "
         "large take the segmented pipeline.  "
         "coll_tuned_use_measured_rules replaces it with the "
         "calibrated per-host crossover")
_rd_max_var = registry.register(
    "coll", "pipeline", "rd_max_bytes", 8 << 20, int,
    help="Upper bound of the per-segment recursive-doubling window "
         "(power-of-two comms): above it the ring's lower bytes-on-"
         "the-wire wins (2(P-1)/P x n vs log2(P) x n)")
_hier_var = registry.register(
    "coll", "hier", "enable", False, bool,
    help="Enable the hierarchical allreduce tier: intra-slice XLA "
         "psum + inter-slice reduction over the tcp/OOB host path + "
         "intra-slice bcast.  Needs >= 2 slices (topo.slice_groups)")
_hier_slice_var = registry.register(
    "coll", "hier", "slice_size", 0, int,
    help="Force hierarchical slices of this many consecutive ranks "
         "(0 = auto: group by device slice_index, else modex node)")
_hier_min_var = registry.register(
    "coll", "hier", "min_bytes", 1 << 20, int,
    help="Static minimum payload for the hierarchical tier (the "
         "leader hop adds host-path latency that small messages "
         "cannot amortize)")

pv_segments = registry.register_pvar(
    "coll", "pipeline", "segments",
    help="Segments dispatched through the pipelined rendezvous")
pv_inflight = registry.register_pvar(
    "coll", "pipeline", "inflight",
    help="Segments outstanding on the calling rank, the new one "
         "included, summed at every segment begun: over "
         "coll_pipeline_segments it is the mean depth the pipeline "
         "reached (1.0 = nothing overlapped)")
pv_ops = registry.register_pvar(
    "coll", "pipeline", "ops",
    help="Collectives routed to the segmented large-message tier")
pv_hier = registry.register_pvar(
    "coll", "hier", "ops",
    help="Collectives routed to the hierarchical tier")

#: returned by maybe_device_coll when the large-message tier does not
#: apply and the caller should keep its fused single-dispatch path
UNHANDLED = object()

# ops with a pairwise accumulation step (segring/segrd); every XLA-
# lowerable reducer and gather-fold op has one
_BINOPS = {
    "MPI_SUM": "add", "MPI_MAX": "maximum", "MPI_MIN": "minimum",
    "MPI_PROD": "multiply", "MPI_BAND": "bitwise_and",
    "MPI_BOR": "bitwise_or", "MPI_BXOR": "bitwise_xor",
    "MPI_LAND": None, "MPI_LOR": None, "MPI_LXOR": None,
}


def _binop(opname: str) -> Callable:
    import jax.numpy as jnp
    name = _BINOPS[opname]
    if name is not None:
        return getattr(jnp, name)
    # logical ops: normalize to 0/1 in the input dtype at every step
    if opname == "MPI_LAND":
        return lambda a, b: ((a != 0) & (b != 0)).astype(a.dtype)
    if opname == "MPI_LOR":
        return lambda a, b: ((a != 0) | (b != 0)).astype(a.dtype)
    return lambda a, b: ((a != 0) ^ (b != 0)).astype(a.dtype)


def _pad_value(opname: Optional[str], dtype) -> Any:
    """Identity element of the op — tail segments are padded with it
    so EVERY segment hits one compiled shape and the padding cannot
    perturb real elements."""
    dt = np.dtype(dtype)
    if opname in ("MPI_MAX",):
        return dt.type(np.iinfo(dt).min) if dt.kind in "iu" \
            else dt.type(-np.inf)
    if opname in ("MPI_MIN",):
        return dt.type(np.iinfo(dt).max) if dt.kind in "iu" \
            else dt.type(np.inf)
    if opname in ("MPI_PROD", "MPI_LAND"):
        return dt.type(1)
    if opname == "MPI_BAND":
        return dt.type(~dt.type(0)) if dt.kind in "iu" else dt.type(1)
    # SUM, OR/XOR families, and data-movement kinds (bcast/alltoall)
    return dt.type(0)


# ---------------------------------------------------------------------------
# per-segment compiled kernels (one executable per (alg, mesh, segment
# shape, dtype, op) in the shared CompiledLRU)
# ---------------------------------------------------------------------------

def _seg_kernel(kind: str, mesh, seg_elems: int, dtype, extra) -> Callable:
    from ompi_tpu.coll import device
    dev_key = tuple(d.id for d in mesh.devices.reshape(-1))
    key = (kind, dev_key, (seg_elems,), np.dtype(dtype).str, extra)
    return device.compile_cache.get(
        key, lambda: _build_seg_kernel(kind, mesh, seg_elems, dtype, extra))


def _build_seg_kernel(kind: str, mesh, seg_elems: int, dtype,
                      extra) -> Callable:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    size = mesh.devices.size
    ring = [(j, (j + 1) % size) for j in range(size)]

    if kind == "segring":
        # Rabenseifner on a ring: P-1 reduce-scatter hops (rank i ends
        # holding the fully reduced stripe (i+1)%P), then P-1 allgather
        # hops writing each circulating stripe into place.  Chunk c's
        # fold is the rank-ordered left fold starting at rank c,
        # computed once and circulated verbatim — all ranks byte equal.
        opname = extra
        binop = _binop(opname)
        assert seg_elems % size == 0
        m = seg_elems // size

        def body(x):
            i = lax.axis_index("r")
            stripes = x.reshape(size, m)

            def stripe(idx):
                return lax.dynamic_slice_in_dim(stripes, idx, 1, 0)[0]

            acc = stripe(i)
            for t in range(size - 1):
                acc = lax.ppermute(acc, "r", perm=ring)
                acc = binop(acc, stripe((i - t - 1) % size))
            out = jnp.zeros_like(stripes)
            out = lax.dynamic_update_slice_in_dim(
                out, acc[None], (i + 1) % size, 0)
            cur = acc
            for t in range(size - 1):
                cur = lax.ppermute(cur, "r", perm=ring)
                out = lax.dynamic_update_slice_in_dim(
                    out, cur[None], (i - t) % size, 0)
            return out.reshape(-1)

        in_specs, out_specs = P("r"), P(None)
    elif kind == "segrd":
        # recursive doubling (power-of-two comms): both operand orders
        # are computed and rank parity selects — every rank evaluates
        # the identical balanced expression tree, so cross-rank
        # byte-identity holds even for order-sensitive float folds
        opname = extra
        binop = _binop(opname)

        def body(x):
            i = lax.axis_index("r")
            acc = x
            s = 1
            while s < size:
                perm = [(j, j ^ s) for j in range(size)]
                other = lax.ppermute(acc, "r", perm=perm)
                low = (i & s) == 0
                acc = jnp.where(low, binop(acc, other), binop(other, acc))
                s <<= 1
            return acc

        in_specs, out_specs = P("r"), P(None)
    elif kind == "segbcast":
        # neighbor-only ring circulation: the payload hops rank to
        # rank; each rank latches the copy arriving at hop
        # (rank - root) % P.  Bit-exact (pure data movement).
        root = extra

        def body(x):
            i = lax.axis_index("r")
            dist = (i - root) % size
            cur = x
            acc = x
            for t in range(1, size):
                cur = lax.ppermute(cur, "r", perm=ring)
                acc = jnp.where(dist == t, cur, acc)
            return acc

        in_specs, out_specs = P("r"), P(None)
    elif kind == "sega2a":
        # pairwise exchange (ref: coll_base_alltoall.c pairwise): at
        # step t every rank sends its block (i+t)%P via a shift-t
        # permutation and files the received block under its source row
        assert seg_elems % size == 0
        m = seg_elems // size

        def body(x):
            i = lax.axis_index("r")
            blocks = x.reshape(size, m)

            def block(idx):
                return lax.dynamic_slice_in_dim(blocks, idx, 1, 0)[0]

            out = jnp.zeros_like(blocks)
            out = lax.dynamic_update_slice_in_dim(out, block(i)[None], i, 0)
            for t in range(1, size):
                shifted = [(j, (j + t) % size) for j in range(size)]
                recv = lax.ppermute(block((i + t) % size), "r",
                                    perm=shifted)
                out = lax.dynamic_update_slice_in_dim(
                    out, recv[None], (i - t) % size, 0)
            return out.reshape(-1)

        in_specs, out_specs = P("r"), P("r")
    else:
        raise KeyError(kind)

    # a stable program name: the profiler's device plane shows
    # jit_ompi_<kind>(<fingerprint>), so a trace reduction can tell the
    # exchange from the pack and unpack programs around it
    body.__name__ = body.__qualname__ = "ompi_" + kind
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


# ---------------------------------------------------------------------------
# the pipelined executor
# ---------------------------------------------------------------------------

def segment_elems(comm, itemsize: int) -> int:
    """Per-host segment size in elements, rounded UP to a multiple of
    the comm size so ring stripes and alltoall blocks stay equal."""
    from ompi_tpu.coll import calibrate
    seg_bytes = calibrate.segment_bytes(comm.size, _seg_size_var.value)
    elems = max(comm.size, seg_bytes // max(1, itemsize))
    rem = elems % comm.size
    return elems + (comm.size - rem) if rem else elems


def _pull_segment(it, ph):
    """Pack stage: pull one (value, fn) job from the segment
    generator.  The slice+pad work happens inside next(), so the
    interval around it IS the host-pack phase: banked in the ``pack``
    accumulator on every op the phase profiler sees, recorded as
    ph_pack on a kept one (the operation's ctx says which, the same
    for every segment and on every member).  Hot (once per segment,
    per rank): audited by hotpath_audit.  The exhausted-iterator probe
    banks one ~0 interval."""
    if ph is None:
        return next(it, None)
    tr = ph[0]
    t0 = tr.lap()
    job = next(it, None)
    t1 = tr.lap_to(_L_PACK, _L_ENTRY)
    if ph[4]:
        tr.end_at(t0, t1, _NAME_PH_PACK, _CAT_PHASE, ph[1], ph[2], ph[3])
    return job


def _run_pipelined(module, comm, jobs, ck=None) -> List[Any]:
    """Drive (value, fn) segment jobs through the async rendezvous
    with bounded depth.  Every begun handle is finished even on error
    — peers park on the generation's refcounted results.  ``ck`` is
    the integrity-plane spec shared by every segment (each segment
    takes its own sampling decision at the meet gate)."""
    from ompi_tpu.coll import device
    depth = max(1, _depth_var.value)
    check = module._abort_check(comm)
    tr = comm.state.tracer
    ph = None
    if tr is not None and tr.phase:
        # ONE ctx, one keep-or-skip decision, for all the operation's
        # segments: on the communicator's sequence number, so the same
        # on every member
        ph = (tr, comm.cid, comm._coll_seq, 0,
              tr.keep(_CAT_PHASE, comm._coll_seq))
    it = iter(jobs)
    handles: deque = deque()
    outs: List[Any] = []
    try:
        while True:
            job = _pull_segment(it, ph)
            if job is None:
                break
            value, fn = job
            handles.append(device.meet_begin(comm, value, fn, check,
                                             ck, ph))
            pv_segments.add(1)
            pv_inflight.add(len(handles))
            if len(handles) > depth:
                outs.append(device.meet_finish(comm, handles.popleft(),
                                               check))
        while handles:
            outs.append(device.meet_finish(comm, handles.popleft(), check))
    except BaseException:
        while handles:  # drain: results are refcounted per generation
            try:
                device.meet_finish(comm, handles.popleft(), check)
            except BaseException:  # noqa: BLE001 — already failing
                pass
        raise
    return outs


def _flat_segments(flat, n: int, seg: int, pad):
    """Slice ``flat`` into fixed-size segments, padding the tail with
    the op identity — the pack stage (host-side slicing of segment k+1
    overlaps device dispatch of segment k through the async meet)."""
    import jax.numpy as jnp
    for lo in range(0, n, seg):
        piece = flat[lo:lo + seg]
        if piece.shape[0] < seg:
            piece = jnp.concatenate(
                [piece, jnp.full((seg - piece.shape[0],), pad,
                                 piece.dtype)])
        yield piece


def _concat_trim(outs: List[Any], n: int, seg: int):
    import jax.numpy as jnp
    tail = n - (len(outs) - 1) * seg
    if tail != seg:
        outs = outs[:-1] + [outs[-1][:tail]]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def _unpack_end(tr, comm, t0: int, nbytes: int = 0) -> None:
    """The end of an unpack stage that started at ``t0`` (Tracer.lap):
    banked in the ``unpack`` accumulator, recorded as ph_unpack on a
    kept op.  Only reached with the phase profiler armed."""
    t1 = tr.lap_to(_L_UNPACK, _L_EXIT)
    seq = comm._coll_seq
    if tr.kept(_CAT_PHASE, seq):
        tr.end_at(t0, t1, _NAME_PH_UNPACK, _CAT_PHASE, comm.cid, seq,
                  nbytes)


def _unpack_trim(comm, outs: List[Any], n: int, seg: int):
    """Unpack stage: trim the padded tail and concatenate, banked and
    recorded as the unpack phase when the phase profiler is armed."""
    tr = comm.state.tracer
    if tr is None or not tr.phase:
        return _concat_trim(outs, n, seg)
    t0 = tr.lap()
    out = _concat_trim(outs, n, seg)
    _unpack_end(tr, comm, t0)
    return out


_plan_mod = None


def _plan():
    """Lazy plan-compiler import (coll/plan imports this module)."""
    global _plan_mod
    if _plan_mod is None:
        from ompi_tpu.coll import plan as _plan_mod_imp
        _plan_mod = _plan_mod_imp
    return _plan_mod


# -- mesh (coll/tpu) algorithms ---------------------------------------------

def _mesh_seg_reduce(module, comm, x, op, alg: str):
    """Segmented allreduce over the mesh: the compiled-plan path (one
    jitted whole-schedule program, one rendezvous — DESIGN.md §22)
    when enabled, else segring/segrd kernels pipelined per segment."""
    pl = _plan()
    if pl.enabled():
        return pl.mesh_reduce(module, comm, x, op, alg)
    import jax.numpy as jnp
    from ompi_tpu.coll import device
    mesh = comm.mesh()
    shape = x.shape
    flat = jnp.asarray(x).reshape(-1)
    n = flat.shape[0]
    dtype = flat.dtype
    seg = segment_elems(comm, dtype.itemsize)
    opname = op.name
    size = comm.size
    kind = "segring" if alg == "segring" else "segrd"

    def fn(shards):
        g = device._assemble(mesh, shards)
        jfn = _seg_kernel(kind, mesh, seg, dtype, opname)
        return device._scatter_out(jfn(g), mesh, size)

    if comm.state.tracer is not None:
        # the phase profiler's twin (device._phase_fn); its launch
        # interval holds the kernel's resolution (a cache hit) too
        fn.traced = functools.partial(
            device._mesh_exec, mesh, size,
            lambda g: _seg_kernel(kind, mesh, seg, dtype, opname)(g),
            None)

    pad = _pad_value(opname, dtype)
    ck = _ig.spec("allreduce", opname, flat) if _ig.on else None
    outs = _run_pipelined(module, comm,
                          ((p, fn) for p in _flat_segments(flat, n, seg,
                                                           pad)), ck)
    return _unpack_trim(comm, outs, n, seg).reshape(shape)


def _mesh_seg_bcast(module, comm, x, root: int):
    """Large mesh bcast: the compiled-plan path (one whole-payload
    program, one rendezvous) when enabled, else the ring-circulation
    kernel pipelined per segment."""
    pl = _plan()
    if pl.enabled():
        return pl.mesh_move(module, comm, x, "segbcast", root)
    import jax.numpy as jnp
    from ompi_tpu.coll import device
    mesh = comm.mesh()
    shape = x.shape
    flat = jnp.asarray(x).reshape(-1)
    n = flat.shape[0]
    dtype = flat.dtype
    seg = segment_elems(comm, dtype.itemsize)
    size = comm.size

    def fn(shards):
        g = device._assemble(mesh, shards)
        jfn = _seg_kernel("segbcast", mesh, seg, dtype, root)
        return device._scatter_out(jfn(g), mesh, size)

    if comm.state.tracer is not None:
        # the phase profiler's twin (device._phase_fn); its launch
        # interval holds the kernel's resolution (a cache hit) too
        fn.traced = functools.partial(
            device._mesh_exec, mesh, size,
            lambda g: _seg_kernel("segbcast", mesh, seg, dtype, root)(g),
            None)

    ck = _ig.spec("bcast", "", flat, root) if _ig.on else None
    outs = _run_pipelined(module, comm,
                          ((p, fn) for p in _flat_segments(flat, n, seg,
                                                           dtype.type(0))),
                          ck)
    return _unpack_trim(comm, outs, n, seg).reshape(shape)


def _mesh_seg_alltoall(module, comm, x):
    """Large mesh alltoall: the compiled-plan path (one whole-payload
    program, one rendezvous) when enabled, else the segmented pairwise
    exchange: segment k covers columns [k*m, (k+1)*m) of EVERY
    destination block, so each segment is a (P, m) exchange hitting
    one compiled shape."""
    pl = _plan()
    if pl.enabled():
        return pl.mesh_move(module, comm, x, "sega2a")
    import jax.numpy as jnp
    from ompi_tpu.coll import device
    mesh = comm.mesh()
    size = comm.size
    shape = x.shape
    rows = jnp.asarray(x).reshape(size, -1)  # row p = block for rank p
    cols = rows.shape[1]
    seg = segment_elems(comm, rows.dtype.itemsize)
    m = max(1, seg // size)
    seg = m * size
    dtype = rows.dtype

    def fn(shards):
        g = device._assemble(mesh, shards)
        jfn = _seg_kernel("sega2a", mesh, seg, dtype, None)
        return device._scatter_out(jfn(g), mesh, size)

    if comm.state.tracer is not None:
        # the phase profiler's twin (device._phase_fn); its launch
        # interval holds the kernel's resolution (a cache hit) too
        fn.traced = functools.partial(
            device._mesh_exec, mesh, size,
            lambda g: _seg_kernel("sega2a", mesh, seg, dtype, None)(g),
            None)

    def jobs():
        for lo in range(0, cols, m):
            sub = rows[:, lo:lo + m]
            if sub.shape[1] < m:
                sub = jnp.concatenate(
                    [sub, jnp.zeros((size, m - sub.shape[1]), dtype)],
                    axis=1)
            yield sub.reshape(-1), fn

    ck = _ig.spec("alltoall", "", rows) if _ig.on else None
    outs = _run_pipelined(module, comm, jobs(), ck)
    tr = comm.state.tracer
    t0 = tr.lap() if tr is not None and tr.phase else 0
    pieces = [o.reshape(size, m) for o in outs]
    tail = cols - (len(pieces) - 1) * m
    if tail != m:
        pieces = pieces[:-1] + [pieces[-1][:, :tail]]
    full = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces,
                                                              axis=1)
    full = full.reshape(shape)
    if t0:
        _unpack_end(tr, comm, t0)
    return full


# -- hbm (intra-chip) segmentation ------------------------------------------

def _hbm_seg_reduce(module, comm, x, op):
    """Segmented intra-chip allreduce: the compiled-plan path (one
    stacked whole-payload kernel, one rendezvous) when enabled, else
    per-segment stacked kernels (elementwise over the rank axis —
    bit-exact vs the monolithic stacked reduce at ANY dtype),
    pipelined through the async meet."""
    pl = _plan()
    if pl.enabled():
        return pl.hbm_reduce(module, comm, x, op)
    import jax.numpy as jnp
    x = module._deposit(comm, x)
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    dtype = flat.dtype
    seg = segment_elems(comm, dtype.itemsize)
    size = comm.size
    opname = op.name
    jbody, out_map = module._stacked("allreduce", opname, size, (seg,),
                                     dtype)

    def fn(shards):
        return out_map(jbody(*shards), size)

    if comm.state.tracer is not None:
        # the phase profiler's twin (device._phase_fn)
        from ompi_tpu.coll import device
        fn.traced = functools.partial(device._stacked_exec, jbody,
                                      out_map, size)

    pad = _pad_value(opname, dtype)
    ck = _ig.spec("allreduce", opname, flat) if _ig.on else None
    outs = _run_pipelined(module, comm,
                          ((p, fn) for p in _flat_segments(flat, n, seg,
                                                           pad)), ck)
    return _unpack_trim(comm, outs, n, seg).reshape(shape)


# ---------------------------------------------------------------------------
# hierarchical tier
# ---------------------------------------------------------------------------

def hier_eligible(comm) -> bool:
    """Comm-consistent: slice grouping depends only on modex/device
    data every member shares.  Cached — consulted per large message."""
    cached = comm.__dict__.get("_hier_eligible")
    if cached is not None:
        return cached
    ok = False
    if _hier_var.value and comm.size >= 4 and comm.mesh() is not None:
        from ompi_tpu.topo import topo as topomod
        groups = topomod.slice_groups(comm, _hier_slice_var.value)
        # need >= 2 slices of >= 2 ranks each: a 1-rank slice would
        # make the intra tier a no-op and the leader hop pure overhead
        ok = len(groups) >= 2 and all(len(g) >= 2 for g in groups)
    comm.__dict__["_hier_eligible"] = ok
    return ok


def _hier_plan(comm) -> Tuple[Any, Optional[Any]]:
    """(intra_slice_comm, leader_comm_or_None) — built collectively at
    first use (the pick is comm-consistent, so every member arrives
    together) and cached; ULFM shrink/respawn epochs invalidate it
    with the other per-comm plans (_COMM_CACHE_KEYS)."""
    plan = comm.__dict__.get("_hier_plan")
    if plan is None:
        from ompi_tpu.comm.communicator import UNDEFINED
        from ompi_tpu.obs import health as _health
        from ompi_tpu.topo import topo as topomod
        groups = topomod.slice_groups(comm, _hier_slice_var.value)
        mine = next(i for i, g in enumerate(groups) if comm.rank in g)
        # gray-failure reroute (DESIGN.md §24): a rank resident on a
        # degraded host biases its OWN split key past every healthy
        # rank's, so the slice leader (intra.rank 0 = smallest key)
        # lands on a healthy host whenever the slice has one.  The
        # split outcome is computed from the GATHERED keys, so even
        # if members read the mask at slightly different moments the
        # result stays collectively consistent — only the ordering
        # can differ between plans built at different times, never
        # membership, and the plan is built (and cached) once,
        # collectively, right here.
        node = getattr(getattr(comm.state, "rte", None), "node_id", 0)
        key = comm.rank + (comm.size
                           if _health.node_degraded(node) else 0)
        intra = comm.split(mine, key=key)
        lead = comm.split(0 if intra.rank == 0 else UNDEFINED,
                          key=key)
        plan = (intra, lead)
        comm.__dict__["_hier_plan"] = plan
    return plan


def _hier_allreduce(module, comm, x, op):
    """Reduce inside each slice on-device, combine slice results over
    the leaders' tcp/OOB host path, fan the total back out on-device.
    The inter-slice hop moves ONE slice-reduced payload per slice
    instead of serializing the whole comm through one link."""
    from ompi_tpu.coll import device
    intra, lead = _hier_plan(comm)
    y = intra.allreduce_arr(x, op)
    if lead is not None:
        # leaders reduce across slices over the host/OOB path (the
        # reference's inter-node tier; tcp btl between processes)
        y = device._host_arr_fallback().allreduce_arr(lead, y, op)
    pv_hier.add(1)
    return intra.bcast_arr(y, 0)


# ---------------------------------------------------------------------------
# the entry consulted by coll/device (TpuCollModule / HbmCollModule)
# ---------------------------------------------------------------------------

def maybe_device_coll(module, comm, kind: str, x, op=None, root=None):
    """Route one *_arr call to the large-message tier, or return
    ``UNHANDLED`` (the caller keeps its fused single-dispatch path).
    Must be comm-consistent: the pick depends only on knobs, the
    process-wide calibration profile, comm properties and the
    MPI-matched payload size."""
    if not _enable_var.value:
        return UNHANDLED
    nbytes = int(getattr(x, "nbytes", 0) or 0)
    if nbytes <= 0 or comm.size < 2:
        return UNHANDLED
    from ompi_tpu.coll import tuned
    alg = tuned.device_algorithm(comm, kind, nbytes,
                                 op.name if op is not None else None)
    if alg is None:
        return UNHANDLED
    tr = comm.state.tracer
    t0 = tr.start() if tr is not None and tr.keep(
        _trace.CAT_COLL_DISPATCH, comm._coll_seq) else 0
    if module.name == "hbm":
        # coll/hbm consults the tier for allreduce alone: with no wire
        # to overlap, its alltoall is one stacked kernel and its bcast
        # one shared-HBM handoff at every size
        out = _hbm_seg_reduce(module, comm, x, op)
    elif alg == "hier":
        out = _hier_allreduce(module, comm, x, op)
    elif kind == "allreduce":
        out = _mesh_seg_reduce(module, comm, x, op, alg)
    elif kind == "bcast":
        out = _mesh_seg_bcast(module, comm, x, root)
    elif kind == "alltoall":
        out = _mesh_seg_alltoall(module, comm, x)
    else:
        return UNHANDLED
    pv_ops.add(1)
    if t0:
        tr.end(t0, _PIPE_NAME[kind], _trace.CAT_COLL_DISPATCH,
               comm.cid, nbytes, _trace.intern_name(alg), comm._coll_seq)
    return out
