"""coll/plan: compiled collective plans — ONE jitted whole-payload
program and ONE rendezvous per large-message collective.

Every operation that ``pipeline.maybe_device_coll`` routes to the
large-message tier runs here.  For each (alg, mesh, segment geometry,
dtype, op) the plan compiler builds ONE jitted program covering the
whole payload — the full reduce-scatter + allgather ring (segring) or
the recursive-doubling exchange (segrd) as a single shard_map with
buffer donation, the stacked one-chip kernel (hbm), or a data mover
(segbcast / sega2a) — and binds it into a ``Plan`` holding the
prebuilt sharding, the meet-fn closure and the pad identity.
Executing a plan is pure data motion:

    pack (identity-pad to the plan's fixed shape, zero-copy staging
    bypass where the runtime aliases aligned host buffers)
      -> ONE ``device.meet`` (the ULFM abort check rides the meet, so
         fault handling sits at the plan boundary)
      -> unpack (trim) + pvar/trace accounting.

**Segment-size discipline**: a payload is padded (op identity
elements; sliced off at unpack) to a whole number of fixed per-host
segments (``segment_elems``: ``coll_seg_size``, or the calibrated
size), so the compiled programs are keyed by segment COUNT, never by
message size, and a sweep of message sizes cannot blow the bounded
cache.  Sub-segment payloads quantize the plan shape to the next pow2
(multiple of comm size).  ``coll_pipeline_segments`` advances by the
segment count of every planned allreduce.

Keying and lifetime:

* jitted executables live in the process-wide ``device.compile_cache``
  under ``("plan_<alg>", dev_key, geometry, dtype, op, donate)`` —
  dev_key is a top-level element, so ``drop_mesh`` on device loss and
  shrink epochs evicts exactly the stale-mesh programs.
* resolved ``Plan`` objects live per comm in ``comm._coll_plans``
  (bounded LRU, ``coll_plan_cache_max``), purged by ULFM's
  ``_COMM_CACHE_KEYS`` at shrink/respawn epochs and by
  ``SELECTION_CACHE_KEYS`` when an autotune fold moves the calibrated
  segment size out from under the plan geometry.

Reduce lowering: with ``coll_plan_native_reduce`` (default), plans
for SUM/MAX/MIN lower to the runtime's native cross-replica reduction
(psum/pmax/pmin) — the same backend-pragmatic discipline as the fused
path's bcast-as-masked-psum — because a compiler-scheduled fused
reduction beats a hop-explicit schedule wherever the runtime provides
one.  Other ops, and all ops with the knob off, keep the faithful
batched schedule, which real multi-slice topologies may prefer:

* **segring** — chunked ``ppermute`` ring allreduce: P-1
  reduce-scatter steps (each rank accumulates one stripe per hop) then
  P-1 allgather steps.  Per-chunk accumulation is a rank-ordered left
  fold computed by exactly ONE rank and circulated verbatim, so every
  rank's output is byte identical by construction.
* **segrd** — recursive doubling (power-of-two comms): log2(P)
  exchange rounds; both operand orders are computed and selected by
  rank parity (the MPICH operand-order discipline), so all ranks
  evaluate the identical expression tree.

Data movement: a mesh bcast or alltoall that ``tuned.device_algorithm``
routes to the tier (``segbcast`` / ``sega2a``) is planned the same way
(``mesh_move``): one program named for its algorithm, every slice
inside the jit, one rendezvous.  Bit-exact for every bit pattern (no
arithmetic touches the payload), so not the fused path's masked psum.

DESIGN.md §12.
"""

from __future__ import annotations

from collections import OrderedDict
import functools
import time
from typing import Any, Callable, Optional

import numpy as np

from ompi_tpu import obs as _obs
from ompi_tpu import trace as _trace
from ompi_tpu.coll import device as _dev
from ompi_tpu.obs import integrity as _ig
from ompi_tpu.mca.params import registry
from ompi_tpu.runtime import staging as _staging

_CAT_SEG = _trace.CAT_COLL_SEGMENT
_CAT_PHASE = _trace.CAT_PHASE
_NAME_PLAN = _trace.NAME_PLAN_EXEC
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

_cache_max_var = registry.register(
    "coll", "plan", "cache_max", 32, int,
    help="Per-communicator bound on resolved Plan objects (LRU). "
         "Jitted executables are bounded separately by the "
         "process-wide compile cache (coll_device_cache_max)")

_native_var = registry.register(
    "coll", "plan", "native_reduce", True, bool,
    help="Lower plan reduce phases for SUM/MAX/MIN to the runtime's "
         "native cross-replica reduction (psum/pmax/pmin); 0 keeps "
         "the hop-explicit batched ring / recursive-doubling "
         "schedule for every op")

pv_segments = registry.register_pvar(
    "coll", "pipeline", "segments",
    help="Segments covered by planned allreduces (a plan's segment "
         "count, added once per operation)")
pv_builds = _obs.scoped_pvar(
    "coll", "plan", "builds",
    help="collective plans resolved (per rank): a Plan object built "
         "and cached on the comm — steady state should be ~0")
pv_hits = _obs.scoped_pvar(
    "coll", "plan", "hits",
    help="collective ops served by an already-resolved plan")
pv_exec_us = _obs.scoped_pvar(
    "coll", "plan", "exec_us",
    help="cumulative wall microseconds inside plan execution "
         "(pack + rendezvous + unpack)")

#: ops with a native cross-replica lowering in the runtime
_NATIVE_OPS = frozenset(("MPI_SUM", "MPI_MAX", "MPI_MIN"))

#: interned alg ids for the plan_exec span
_ALG_ID = {
    "segring": _trace.intern_name("segring"),
    "segrd": _trace.intern_name("segrd"),
    "hbm": _trace.intern_name("hbm"),
    "segbcast": _trace.intern_name("segbcast"),
    "sega2a": _trace.intern_name("sega2a"),
}


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
    """Identity element of the op — a ragged payload is padded with it
    so every size hits a compiled shape keyed by segment count and the
    padding cannot perturb real elements."""
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


def segment_elems(comm, itemsize: int) -> int:
    """Per-host segment size in elements, rounded UP to a multiple of
    the comm size so ring stripes and alltoall blocks stay equal."""
    from ompi_tpu.coll import calibrate
    seg_bytes = calibrate.segment_bytes(comm.size, _seg_size_var.value)
    elems = max(comm.size, seg_bytes // max(1, itemsize))
    rem = elems % comm.size
    return elems + (comm.size - rem) if rem else elems


def _plan_segments(comm, n: int, seg: int):
    """(nsegs, seg_elems) for an n-element payload.  Payloads below
    one calibrated segment quantize to the next pow2 (rounded to a
    comm-size multiple) so a 64 KiB message is not identity-padded to
    a 1 MiB program; at or above, the calibrated segment is the unit.
    Either way the key set stays log-bounded in payload size."""
    size = comm.size
    if n < seg:
        s = 1
        while s < n:
            s <<= 1
        rem = s % size
        if rem:
            s += size - rem
        return 1, min(s, seg)
    return -(-n // seg), seg


class Plan:
    """One resolved collective plan: the prebound meet-fn (prebuilt
    sharding + jitted whole-schedule program + scatter), the pad
    identity, this rank's deposit device and the interned ids the
    executor stamps into spans.  Everything per-op-variable is an
    ``execute`` argument; everything else was decided at build."""

    __slots__ = ("alg", "alg_id", "nsegs", "seg", "total", "itemsize",
                 "np_dtype", "pad_val", "fn", "meet", "device", "ck")

    def __init__(self, alg: str, nsegs: int, seg: int, np_dtype,
                 pad_val, fn, meet, device, ck=None) -> None:
        self.alg = alg
        self.alg_id = _ALG_ID[alg]
        self.nsegs = nsegs
        self.seg = seg
        self.total = nsegs * seg
        self.itemsize = np_dtype.itemsize
        self.np_dtype = np_dtype
        self.pad_val = pad_val
        self.fn = fn
        self.meet = meet
        self.device = device
        # integrity spec, built unconditionally (plans outlive
        # arm/disarm); execute() re-gates on the live arm flag
        self.ck = ck

    def execute(self, module, comm, flat, n: int):
        """The whole steady-state op.  Hot (once per large-message
        collective): audited by hotpath_audit — pack/unpack and all
        key/closure work live off this path."""
        tr = comm.state.tracer
        t0 = 0
        if tr is not None:
            # Tracer.keep, inlined: on the sequence number, the same
            # on every member; sampled out is no call, no clock read
            op = comm._coll_seq
            if not tr._plo <= op < tr._phi:
                tr._restep(op)
            if op % tr._period[_CAT_SEG]:
                tr._skipped[_CAT_SEG] += 1
            else:
                t0 = tr.start()
        ns0 = time.perf_counter_ns()
        value = flat
        if n != self.total:
            value = _pack(comm, flat, n, self)
        out = self.meet(comm, value, self.fn, module._abort_check(comm),
                        self.ck if _ig.on else None)
        if n != self.total:
            out = _unpack(comm, out, n, self)
        pv_exec_us.add((time.perf_counter_ns() - ns0) // 1000,
                       _obs.current_band())
        if t0:
            tr.end(t0, _NAME_PLAN, _CAT_SEG,
                   comm.cid, n * self.itemsize, self.alg_id,
                   comm._coll_seq)
        return out


def _pack(comm, flat, n: int, plan: Plan):
    """Identity-pad ``flat`` (n,) to the plan's fixed (total,) shape.
    On a zero-copy runtime this is ONE memcpy into a fresh aligned
    host buffer that device_put then aliases — no device program, and
    fresh per op because the padded array may still back an unforced
    program when the next op starts (unlike osc's lock-serialized
    mirror reuse).  Copying runtimes compose on device."""
    tr = comm.state.tracer
    t0 = tr.lap() if tr is not None and tr.phase else 0
    if _staging.runtime_zero_copy():
        import jax
        buf = _staging.aligned_empty(plan.total * plan.itemsize)
        view = buf.view(plan.np_dtype)
        np.copyto(view[:n], np.asarray(flat))
        view[n:] = plan.pad_val
        value = jax.device_put(view, plan.device)
    else:
        import jax.numpy as jnp
        value = jnp.concatenate(
            [jnp.asarray(flat),
             jnp.full((plan.total - n,), plan.pad_val, plan.np_dtype)])
    if t0:
        _pack_end(tr, comm, t0, n * plan.itemsize)
    return value


def _pack_end(tr, comm, t0: int, nbytes: int) -> None:
    """The end of a pack stage that started at ``t0`` (Tracer.lap):
    banked in the ``pack`` accumulator, recorded as ph_pack on a kept
    op.  Only reached with the phase profiler armed."""
    t1 = tr.lap_to(_L_PACK, _L_ENTRY)
    seq = comm._coll_seq
    if tr.kept(_CAT_PHASE, seq):
        tr.end_at(t0, t1, _NAME_PH_PACK, _CAT_PHASE, comm.cid, seq, nbytes)


def _unpack_end(tr, comm, t0: int, nbytes: int) -> None:
    """The end of an unpack stage that started at ``t0`` (Tracer.lap):
    banked in the ``unpack`` accumulator, recorded as ph_unpack on a
    kept op.  Only reached with the phase profiler armed."""
    t1 = tr.lap_to(_L_UNPACK, _L_EXIT)
    seq = comm._coll_seq
    if tr.kept(_CAT_PHASE, seq):
        tr.end_at(t0, t1, _NAME_PH_UNPACK, _CAT_PHASE, comm.cid, seq,
                  nbytes)


def _unpack(comm, out, n: int, plan: Plan):
    tr = comm.state.tracer
    t0 = tr.lap() if tr is not None and tr.phase else 0
    res = out[:n]
    if t0:
        _unpack_end(tr, comm, t0, n * plan.itemsize)
    return res


def _pack_rows(comm, flat, n: int, plan: Plan):
    """``_pack`` for an alltoall: ``flat`` is comm.size destination
    blocks of n // size elements, and each block is zero-padded at its
    own end to the plan's total // size (a pad at the vector's end
    would shift every block but the first)."""
    tr = comm.state.tracer
    t0 = tr.lap() if tr is not None and tr.phase else 0
    import jax.numpy as jnp
    size = comm.size
    rows = jnp.asarray(flat).reshape(size, n // size)
    value = jnp.pad(
        rows, ((0, 0), (0, (plan.total - n) // size))).reshape(-1)
    if t0:
        _pack_end(tr, comm, t0, n * plan.itemsize)
    return value


def _unpack_rows(comm, out, n: int, plan: Plan):
    """``_unpack`` for an alltoall: trim each source block's pad."""
    tr = comm.state.tracer
    t0 = tr.lap() if tr is not None and tr.phase else 0
    size = comm.size
    res = out.reshape(size, plan.total // size)[:, :n // size].reshape(-1)
    if t0:
        _unpack_end(tr, comm, t0, n * plan.itemsize)
    return res


def _plans_of(comm) -> OrderedDict:
    plans = comm.__dict__.get("_coll_plans")
    if plans is None:
        plans = comm.__dict__["_coll_plans"] = OrderedDict()
    return plans


def _resolve(comm, pkey, builder) -> Plan:
    """Per-comm Plan LRU: hit moves to the back, build trims to
    coll_plan_cache_max.  comm objects are rank-local, so this needs
    no lock; the expensive XLA compile below it is deduped by the
    process-wide compile cache."""
    plans = _plans_of(comm)
    plan = plans.get(pkey)
    band = _obs.current_band()
    if plan is not None:
        plans.move_to_end(pkey)
        pv_hits.add(1, band)
        return plan
    plan = builder()
    plans[pkey] = plan
    cap = max(1, int(_cache_max_var.value))
    while len(plans) > cap:
        plans.popitem(last=False)
    pv_builds.add(1, band)
    return plan


# -- mesh plans -------------------------------------------------------------

def _compile_mesh(alg: str, mesh, size: int, nsegs: int, seg: int,
                  np_dtype, opname: str, native: bool, donate: bool):
    """The ONE jitted program covering the whole multi-segment
    schedule: global (size*nsegs*seg,) in P("r"), replicated out."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    binop = _binop(opname)
    if native:
        if opname == "MPI_SUM":
            body = lambda x: lax.psum(x, "r")  # noqa: E731
        elif opname == "MPI_MAX":
            body = lambda x: lax.pmax(x, "r")  # noqa: E731
        else:
            body = lambda x: lax.pmin(x, "r")  # noqa: E731
    elif alg == "segring":
        # the full reduce-scatter + allgather ring, batched over the
        # leading nsegs axis
        ring = [(j, (j + 1) % size) for j in range(size)]
        m = seg // size

        def body(x):
            i = lax.axis_index("r")
            stripes = x.reshape(nsegs, size, m)

            def stripe(idx):
                return lax.dynamic_slice_in_dim(
                    stripes, idx, 1, axis=1)[:, 0]

            acc = stripe(i)
            for t in range(size - 1):
                acc = lax.ppermute(acc, "r", perm=ring)
                acc = binop(acc, stripe((i - t - 1) % size))
            # rank i now owns fully-reduced stripe (i+1) % size
            out = jnp.zeros((nsegs, size, m), x.dtype)
            out = lax.dynamic_update_slice_in_dim(
                out, acc[:, None], (i + 1) % size, axis=1)
            cur = acc
            for t in range(size - 1):
                cur = lax.ppermute(cur, "r", perm=ring)
                out = lax.dynamic_update_slice_in_dim(
                    out, cur[:, None], (i - t) % size, axis=1)
            return out.reshape(nsegs * seg)
    else:
        # recursive doubling over the whole padded vector — the
        # schedule is elementwise, so batching over segments is free
        def body(x):
            i = lax.axis_index("r")
            acc = x
            s = 1
            while s < size:
                perm = [(j, j ^ s) for j in range(size)]
                other = lax.ppermute(acc, "r", perm=perm)
                low = (i & s) == 0
                acc = jnp.where(low, binop(acc, other),
                                binop(other, acc))
                s <<= 1
            return acc

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("r"),
                       out_specs=P(None), check_vma=False)
    if donate:
        return jax.jit(fn, donate_argnums=(0,))
    return jax.jit(fn)


def _build_mesh_plan(comm, alg: str, nsegs: int, seg: int, np_dtype,
                     opname: str, donate: bool) -> Plan:
    mesh = comm.mesh()
    size = comm.size
    devs = list(mesh.devices.reshape(-1))
    dev_key = tuple(d.id for d in devs)
    native = bool(_native_var.value) and opname in _NATIVE_OPS
    # native programs are alg-independent — one compile serves both
    # segring and segrd picks for the same geometry
    if native:
        ckey = ("plan_native", dev_key, (nsegs * seg,), np_dtype.str,
                opname, donate)
    else:
        ckey = ("plan_" + alg, dev_key, (nsegs, seg), np_dtype.str,
                opname, donate)
    jfn = _dev.compile_cache.get(
        ckey, lambda: _compile_mesh(alg, mesh, size, nsegs, seg,
                                    np_dtype, opname, native, donate))
    return Plan(alg, nsegs, seg, np_dtype,
                _pad_value(opname, np_dtype),
                _mesh_meet_fn(mesh, size, jfn), _dev.meet,
                devs[comm.rank],
                _ig.spec_static("allreduce", opname,
                                np.empty(0, np_dtype)))


def _mesh_meet_fn(mesh, size: int, jfn):
    """The meeting's computation of a mesh plan: assemble the global
    array on the prebuilt sharding, call the compiled program, hand
    each rank the part on its own device."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, P("r"))

    def fn(shards, _m=mesh, _sh=sharding, _j=jfn, _n=size):
        g = _dev._assemble(_m, shards, _sh)
        return _dev._scatter_out(_j(g), _m, _n)

    # the phase profiler's twin (device._phase_fn), built once with
    # the plan; untraced, the body above is what runs
    fn.traced = functools.partial(_dev._mesh_exec, mesh, size, jfn, sharding)
    return fn


def mesh_reduce(module, comm, x, op, alg: str):
    """Plan-path segmented allreduce over the mesh: resolve (or reuse)
    the plan for this payload's geometry, then one pack / one
    rendezvous / one unpack."""
    import jax.numpy as jnp

    # 1-D payloads (the common case) flow through UNTOUCHED: a
    # same-shape jnp reshape is a fresh dispatch whose result lands
    # uncommitted on the default device, and _assemble would then
    # re-place 7 of 8 shards with a device_put on EVERY op
    if getattr(x, "ndim", None) == 1:
        shape, flat = None, x
    else:
        shape = x.shape
        flat = jnp.asarray(x).reshape(-1)
    n = int(flat.shape[0])
    np_dtype = np.dtype(flat.dtype)
    nsegs, seg = _plan_segments(
        comm, n, segment_elems(comm, np_dtype.itemsize))
    # donation is only sound when the pack stage owns the padded
    # buffer; exact-fit payloads flow the caller's array straight in.
    # Never while the integrity plane is armed: after a mismatch it
    # re-reads every deposited operand
    donate = nsegs * seg != n and not _ig.on
    pkey = ("mesh", alg, nsegs, seg, np_dtype.str, op.name, donate)
    plan = _resolve(
        comm, pkey,
        lambda: _build_mesh_plan(comm, alg, nsegs, seg, np_dtype,
                                 op.name, donate))
    pv_segments.add(nsegs)
    out = plan.execute(module, comm, flat, n)
    return out if shape is None else out.reshape(shape)


# -- mesh data-movement plans (bcast, alltoall) -----------------------------

def _compile_mesh_move(alg: str, mesh, size: int, total: int, root):
    """The ONE jitted program of a large mesh bcast or alltoall: a
    rank's (total,) in P("r").  Named for the algorithm whose schedule
    it fuses (the profiler's device plane shows jit_ompi_<alg>, which a
    trace reduction matches).  The payload stays 1-D throughout: a
    (size, m) view of a tiled 1-D array is a relayout, which compiles
    to a copy loop over the whole payload.
    Nothing is donated: the integrity plane re-reads a deposited
    operand after a mismatch."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    if alg == "segbcast":
        # scatter then allgather: stripe j of the root's payload goes
        # to rank j over one one-pair permute (the others receive
        # zeros and keep what they had: a select, never an add, so
        # -0.0 and NaN payloads arrive as sent), then one tiled
        # all-gather.  Each chip receives about `total` over the wire,
        # the least a bcast needs
        m = total // size

        def body(x):
            i = lax.axis_index("r")

            def stripe(j):
                return lax.slice_in_dim(x, j * m, (j + 1) * m)

            mine = stripe(root)
            for j in range(size):
                if j != root:
                    got = lax.ppermute(stripe(j), "r", perm=[(root, j)])
                    mine = jnp.where(i == j, got, mine)
            return lax.all_gather(mine, "r", tiled=True)

        out_specs = P(None)
    else:
        # block j of every rank to rank j, filed by source: the
        # runtime's own all-to-all, which the path under the crossover
        # already trusts
        def body(x):
            return lax.all_to_all(x, "r", split_axis=0, concat_axis=0,
                                  tiled=True)

        out_specs = P("r")

    body.__name__ = body.__qualname__ = "ompi_" + alg
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("r"),
                                 out_specs=out_specs, check_vma=False))


def _build_move_plan(comm, alg: str, nsegs: int, seg: int, np_dtype,
                     root) -> Plan:
    mesh = comm.mesh()
    size = comm.size
    devs = list(mesh.devices.reshape(-1))
    dev_key = tuple(d.id for d in devs)
    ckey = ("plan_" + alg, dev_key, (nsegs * seg,), np_dtype.str, root)
    jfn = _dev.compile_cache.get(
        ckey, lambda: _compile_mesh_move(alg, mesh, size, nsegs * seg,
                                         root))
    kind = "bcast" if alg == "segbcast" else "alltoall"
    return Plan(alg, nsegs, seg, np_dtype, np_dtype.type(0),
                _mesh_meet_fn(mesh, size, jfn), _dev.meet,
                devs[comm.rank],
                _ig.spec_static(kind, "", np.empty(0, np_dtype),
                                root or 0))


def mesh_move(module, comm, x, alg: str, root=None):
    """Plan-path mesh bcast (``segbcast``, from ``root``) or alltoall
    (``sega2a``): one program over the whole payload behind one
    rendezvous.  A size that is not a whole number of segments is
    zero-padded to the plan's shape (an alltoall's blocks each at
    their own end) and trimmed after, so the compiled programs stay
    keyed by segment count, never by message size."""
    import jax.numpy as jnp

    if getattr(x, "ndim", None) == 1:
        shape, flat = None, x  # no same-shape reshape dispatch
    else:
        shape = x.shape
        flat = jnp.asarray(x).reshape(-1)
    n = int(flat.shape[0])
    np_dtype = np.dtype(flat.dtype)
    nsegs, seg = _plan_segments(
        comm, n, segment_elems(comm, np_dtype.itemsize))
    pkey = ("mesh", alg, nsegs, seg, np_dtype.str, root)
    plan = _resolve(
        comm, pkey,
        lambda: _build_move_plan(comm, alg, nsegs, seg, np_dtype, root))
    if alg == "sega2a" and n != plan.total:
        out = _unpack_rows(
            comm, plan.execute(module, comm,
                               _pack_rows(comm, flat, n, plan), plan.total),
            n, plan)
    else:
        out = plan.execute(module, comm, flat, n)
    return out if shape is None else out.reshape(shape)


# -- hbm (intra-chip) plans -------------------------------------------------

def _build_hbm_plan(module, comm, nsegs: int, seg: int, np_dtype,
                    opname: str, device_hint) -> Plan:
    size = comm.size
    jbody, out_map = module._stacked("allreduce", opname, size,
                                     (nsegs * seg,), np_dtype)

    def fn(shards, _j=jbody, _o=out_map, _n=size):
        return _o(_j(*shards), _n)

    fn.traced = functools.partial(_dev._stacked_exec, jbody, out_map, size)

    return Plan("hbm", nsegs, seg, np_dtype,
                _pad_value(opname, np_dtype), fn, _dev.meet,
                device_hint,
                _ig.spec_static("allreduce", opname,
                                np.empty(0, np_dtype)))


def hbm_reduce(module, comm, x, op):
    """Plan-path intra-chip allreduce: the stacked whole-payload
    kernel behind exactly one rendezvous."""
    x = module._deposit(comm, x)
    if getattr(x, "ndim", None) == 1:
        shape, flat = None, x  # no same-shape reshape dispatch
    else:
        shape = x.shape
        flat = x.reshape(-1)
    n = int(flat.shape[0])
    np_dtype = np.dtype(flat.dtype)
    nsegs, seg = _plan_segments(
        comm, n, segment_elems(comm, np_dtype.itemsize))
    pkey = ("hbm", nsegs, seg, np_dtype.str, op.name)
    dev = getattr(x, "device", None)
    plan = _resolve(
        comm, pkey,
        lambda: _build_hbm_plan(module, comm, nsegs, seg, np_dtype,
                                op.name, dev))
    pv_segments.add(nsegs)
    out = plan.execute(module, comm, flat, n)
    return out if shape is None else out.reshape(shape)
