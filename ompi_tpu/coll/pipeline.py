"""coll/pipeline: the router of the large-message tier of the device
collective engine, and its hierarchical allreduce.

The fused fast path (docs/DESIGN.md §8) owns the small-message regime:
ONE assembled shard_map per collective, dispatch constant amortized by
batching.  From ``coll_pipeline_min_bytes`` up the payload dominates
and the operation is worth an algorithm chosen for its size.
``maybe_device_coll`` is the one entry ``coll/device`` consults: it
asks ``tuned.device_algorithm`` (knobs, the calibration profile, comm
properties and the MPI-matched payload size, so every member picks the
same) and hands the operation to a compiled plan of ``coll/plan.py`` —
``mesh_reduce`` (segring / segrd), ``mesh_move`` (segbcast / sega2a),
``hbm_reduce`` on one chip — or keeps the caller's single-dispatch
path (``UNHANDLED``).  Every plan is one program behind one rendezvous
(DESIGN.md §12); no program body lives here.

**Hierarchy** (``coll_hier_enable``): multi-slice meshes stop
serializing through one link — intra-slice XLA ``psum`` (the device
tier), inter-slice reduction by the slice leaders over the tcp/OOB
host path, then an intra-slice device bcast.  Slice membership comes
from ``topo.slice_groups`` (device slice_index / modex node_id, or
``coll_hier_slice_size`` for explicit shaping).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

from ompi_tpu import trace as _trace
from ompi_tpu.coll import plan as _plan
from ompi_tpu.mca.params import registry

# interned span names for the per-kind dispatch spans (args: cid,
# payload bytes, interned algorithm tag, the operation's sequence)
_PIPE_NAME = {
    kind: _trace.intern_name(f"pipeline_{kind}",
                             ("cid", "nbytes", "alg$", "op"))
    for kind in ("allreduce", "bcast", "alltoall")
}

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

pv_ops = registry.register_pvar(
    "coll", "pipeline", "ops",
    help="Collectives routed to the segmented large-message tier")
pv_hier = registry.register_pvar(
    "coll", "hier", "ops",
    help="Collectives routed to the hierarchical tier")

#: returned by maybe_device_coll when the large-message tier does not
#: apply and the caller should keep its fused single-dispatch path
UNHANDLED = object()


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
        out = _plan.hbm_reduce(module, comm, x, op)
    elif alg == "hier":
        out = _hier_allreduce(module, comm, x, op)
    elif kind == "allreduce":
        out = _plan.mesh_reduce(module, comm, x, op, alg)
    elif kind == "bcast":
        out = _plan.mesh_move(module, comm, x, "segbcast", root)
    elif kind == "alltoall":
        out = _plan.mesh_move(module, comm, x, "sega2a")
    else:
        return UNHANDLED
    pv_ops.add(1)
    if t0:
        tr.end(t0, _PIPE_NAME[kind], _trace.CAT_COLL_DISPATCH,
               comm.cid, nbytes, _trace.intern_name(alg), comm._coll_seq)
    return out
