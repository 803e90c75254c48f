"""coll/plan: compiled collective plans — ONE jitted whole-payload
program and ONE rendezvous per large-message collective.

Every operation that ``pipeline.maybe_device_coll`` routes to the
large-message tier runs here.  For each (alg, mesh, length, dtype, op)
the plan compiler builds ONE jitted program covering the whole
payload — the full reduce-scatter + allgather ring (segring) or the
recursive-doubling exchange (segrd) as a single shard_map, the
stacked one-chip kernel (hbm), or a data mover (segbcast / sega2a) —
and binds it into a ``Plan`` holding the prebuilt sharding and the
meet-fn closure.  Executing a plan is ONE ``device.meet`` (the ULFM
abort check rides the meet, so fault handling sits at the plan
boundary) plus pvar/trace accounting: no pack, no unpack, no dispatch
beside the one program.

**A plan runs at the payload's own length.**  None of these programs
needs a segment geometry: the one-chip kernel, the native
psum / pmax / pmin and recursive doubling are elementwise over the
whole vector, an alltoall's blocks are equal by MPI's definition, and
the stripe schedules (the hop-explicit ring, segbcast) take one
segment of ``n`` with stripes of ``n // size``.  So the plan key and
the compile key carry the length, as ``HbmCollModule._run``'s key
carries the shape of everything under the tier's crossover, and a new
length compiles a new program (0.6 to 1.1 s on a v5e, PERF.md): the
per-comm plan LRU (``coll_plan_cache_max``) and the process-wide
compile cache (``coll_device_cache_max``) bound what is HELD, nothing
bounds the keys a sweep of sizes can ask for.  Jobs whose sizes come
from a model repeat a handful.  ``coll_pipeline_segments`` still
advances by the segment count of every planned allreduce
(``_plan_segments``), a count and no longer a shape.

**What is left of pad and trim**: a stripe schedule whose payload
does not divide by the comm size (segring with
``coll_plan_native_reduce`` 0 or a non-native op, segbcast) is padded
to the next multiple of ``comm.size`` with the op's identity by ONE
jitted program (``ompi_plan_pad``) and trimmed by one
(``ompi_plan_trim``), around the plan's execute.  ``coll_plan_padded``
counts those calls; the layer account books them as pack / unpack.
The same code runs on every backend.

Keying and lifetime:

* jitted executables live in the process-wide ``device.compile_cache``
  under ``("plan_<alg>", dev_key, (total,), dtype, op, donate)`` —
  dev_key is a top-level element, so ``drop_mesh`` on device loss and
  shrink epochs evicts exactly the stale-mesh programs.
* resolved ``Plan`` objects live per comm in ``comm._coll_plans``
  (bounded LRU, ``coll_plan_cache_max``), purged by ULFM's
  ``_COMM_CACHE_KEYS`` at shrink/respawn epochs and by
  ``SELECTION_CACHE_KEYS`` when an autotune fold moves the calibrated
  segment size (a plan holds its segment count).

Reduce lowering: with ``coll_plan_native_reduce`` (default), plans
for SUM/MAX/MIN lower to the runtime's native cross-replica reduction
(psum/pmax/pmin) — the same backend-pragmatic discipline as the fused
path's bcast-as-masked-psum — because a compiler-scheduled fused
reduction beats a hop-explicit schedule wherever the runtime provides
one.  Other ops, and all ops with the knob off, keep the faithful
schedule, which real multi-slice topologies may prefer:

* **segring** — ``ppermute`` ring allreduce: P-1 reduce-scatter steps
  (each rank accumulates one stripe per hop) then P-1 allgather
  steps.  Per-stripe accumulation is a rank-ordered left fold computed
  by exactly ONE rank and circulated verbatim, so every rank's output
  is byte identical by construction.
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
import warnings

import numpy as np

from ompi_tpu import obs as _obs
from ompi_tpu import trace as _trace
from ompi_tpu.coll import device as _dev
from ompi_tpu.obs import integrity as _ig
from ompi_tpu.mca.params import registry

# a padded plan donates the buffer its pad program made; donation is
# a no-op on the CPU backend, and the warning would fire once a
# compiled program in every tier-1 run
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

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
    help="Segment size (bytes) of the large-message device tier (ref: "
         "coll_tuned_decision_fixed.c:72): what coll_pipeline_segments "
         "counts a planned allreduce in, and osc/device's default "
         "segment.  No program has its shape: a plan runs at the "
         "payload's own length.  coll_tuned_use_measured_rules "
         "replaces this with the calibrated per-host segment size")

_cache_max_var = registry.register(
    "coll", "plan", "cache_max", 32, int,
    help="Per-communicator bound on resolved Plan objects (LRU). "
         "Jitted executables are bounded separately by the "
         "process-wide compile cache (coll_device_cache_max)")

_native_var = registry.register(
    "coll", "plan", "native_reduce", True, bool,
    help="Lower plan reduce phases for SUM/MAX/MIN to the runtime's "
         "native cross-replica reduction (psum/pmax/pmin); 0 keeps "
         "the hop-explicit ring / recursive-doubling schedule for "
         "every op")

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
         "(the rendezvous and the one program)")
pv_padded = registry.register_pvar(
    "coll", "plan", "padded",
    help="Planned collectives that padded and trimmed their payload: "
         "a stripe schedule (hop-explicit segring, segbcast) over a "
         "length that does not divide by the comm size.  Every other "
         "plan runs at the payload's own length")

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
    """Identity element of the op: what ``ompi_plan_pad`` appends to a
    payload that a stripe schedule cannot split evenly, so that the
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
    # SUM, OR/XOR families, and data-movement kinds (bcast)
    return dt.type(0)


def segment_elems(comm, itemsize: int) -> int:
    """Per-host segment size in elements, rounded UP to a multiple of
    the comm size."""
    from ompi_tpu.coll import calibrate
    seg_bytes = calibrate.segment_bytes(comm.size, _seg_size_var.value)
    elems = max(comm.size, seg_bytes // max(1, itemsize))
    rem = elems % comm.size
    return elems + (comm.size - rem) if rem else elems


def _plan_segments(n: int, seg: int) -> int:
    """How many ``seg``-element segments an n-element payload covers:
    what ``coll_pipeline_segments`` advances by for a planned
    allreduce.  A count only: no program has a segment's shape (a plan
    runs at the payload's own length), so this bounds no key set."""
    return max(1, -(-n // seg))


def _stripe_total(n: int, size: int) -> int:
    """The length a stripe schedule runs at: ``n`` where it divides by
    the comm size, else the next multiple (padded and trimmed)."""
    return -(-n // size) * size


class Plan:
    """One resolved collective plan: the prebound meet-fn (prebuilt
    sharding + jitted whole-schedule program + scatter) and the
    interned ids the executor stamps into spans.  The program runs at
    the payload's own length, but for a stripe schedule over a length
    that does not divide by the comm size, whose plan also holds the
    two jitted programs that pad to the next multiple and trim back
    (``pad`` / ``trim``, else None).  Everything per-op-variable is an
    ``execute`` argument; everything else was decided at build."""

    __slots__ = ("alg", "alg_id", "nsegs", "itemsize", "fn", "meet",
                 "ck", "pad", "trim")

    def __init__(self, alg: str, nsegs: int, np_dtype, fn, meet,
                 ck=None, pad=None, trim=None) -> None:
        self.alg = alg
        self.alg_id = _ALG_ID[alg]
        self.nsegs = nsegs
        self.itemsize = np_dtype.itemsize
        self.fn = fn
        self.meet = meet
        # integrity spec, built unconditionally (plans outlive
        # arm/disarm); execute() re-gates on the live arm flag
        self.ck = ck
        self.pad = pad
        self.trim = trim

    def execute(self, module, comm, flat, n: int):
        """The whole steady-state op: ``flat`` is what the program
        takes, ``n`` the payload's elements (the span's bytes).  Hot
        (once per large-message collective): audited by hotpath_audit
        — all key/closure work lives off this path."""
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
        out = self.meet(comm, flat, self.fn, module._abort_check(comm),
                        self.ck if _ig.on else None)
        pv_exec_us.add((time.perf_counter_ns() - ns0) // 1000,
                       _obs.current_band())
        if t0:
            tr.end(t0, _NAME_PLAN, _CAT_SEG,
                   comm.cid, n * self.itemsize, self.alg_id,
                   comm._coll_seq)
        return out


def _compile_pad_trim(n: int, total: int, np_dtype, opname):
    """The two programs of a padded plan, each ONE jitted function
    with a name the device trace shows (``jit_ompi_plan_pad``,
    ``jit_ompi_plan_trim``; never a lambda: the benchmark's hbm
    allreduce cells count every ``jit__lambda`` as the kernel)."""
    import jax
    from jax import lax

    pad_val = _pad_value(opname, np_dtype)

    def ompi_plan_pad(x):
        return lax.pad(x, pad_val, ((0, total - n, 0),))

    def ompi_plan_trim(out):
        return lax.slice(out, (0,), (n,))

    return jax.jit(ompi_plan_pad), jax.jit(ompi_plan_trim)


def _pad_trim(n: int, total: int, np_dtype, opname):
    """(pad, trim) for a plan over ``total`` elements serving payloads
    of ``n``: (None, None) at the payload's own length."""
    if total == n:
        return None, None
    return _dev.compile_cache.get(
        ("plan_pad_trim", n, total, np_dtype.str, opname),
        lambda: _compile_pad_trim(n, total, np_dtype, opname))


def _execute(plan: Plan, module, comm, flat, n: int):
    """``plan.execute``, and around it, for the payloads that still
    pad, one jitted pad of the rank's own deposit before the
    rendezvous and one jitted trim of its own result after.  Booked as
    pack / unpack where the phase profiler is armed."""
    if plan.pad is None:
        return plan.execute(module, comm, flat, n)
    pv_padded.add(1)
    tr = comm.state.tracer
    if tr is not None and not tr.phase:
        tr = None
    nbytes = n * plan.itemsize
    t0 = tr.lap() if tr is not None else 0
    value = plan.pad(module._deposit(comm, flat))
    if t0:
        _pack_end(tr, comm, t0, nbytes)
    out = plan.execute(module, comm, value, n)
    t0 = tr.lap() if tr is not None else 0
    out = plan.trim(out)
    if t0:
        _unpack_end(tr, comm, t0, nbytes)
    return out


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

def _compile_mesh(alg: str, mesh, size: int, total: int, opname: str,
                  native: bool, donate: bool):
    """The ONE jitted program covering the whole schedule: global
    (size*total,) in P("r"), replicated out."""
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
        # the full reduce-scatter + allgather ring over one segment of
        # `total`: size stripes of m, sliced out of the 1-D payload
        ring = [(j, (j + 1) % size) for j in range(size)]
        m = total // size

        def body(x):
            i = lax.axis_index("r")

            def stripe(idx):
                return lax.dynamic_slice_in_dim(x, idx * m, m)

            acc = stripe(i)
            for t in range(size - 1):
                acc = lax.ppermute(acc, "r", perm=ring)
                acc = binop(acc, stripe((i - t - 1) % size))
            # rank i now owns fully-reduced stripe (i+1) % size
            out = lax.dynamic_update_slice_in_dim(
                jnp.zeros((total,), x.dtype), acc,
                ((i + 1) % size) * m, axis=0)
            cur = acc
            for t in range(size - 1):
                cur = lax.ppermute(cur, "r", perm=ring)
                out = lax.dynamic_update_slice_in_dim(
                    out, cur, ((i - t) % size) * m, axis=0)
            return out
    else:
        # recursive doubling over the whole vector: the schedule is
        # elementwise, so any length serves
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


def _build_mesh_plan(comm, alg: str, n: int, total: int, np_dtype,
                     opname: str, native: bool, donate: bool) -> Plan:
    mesh = comm.mesh()
    size = comm.size
    dev_key = tuple(d.id for d in mesh.devices.reshape(-1))
    # native programs are alg-independent — one compile serves both
    # segring and segrd picks for the same length
    ckey = ("plan_native" if native else "plan_" + alg, dev_key,
            (total,), np_dtype.str, opname, donate)
    jfn = _dev.compile_cache.get(
        ckey, lambda: _compile_mesh(alg, mesh, size, total, opname,
                                    native, donate))
    nsegs = _plan_segments(n, segment_elems(comm, np_dtype.itemsize))
    return Plan(alg, nsegs, np_dtype,
                _mesh_meet_fn(mesh, size, jfn), _dev.meet,
                _ig.spec_static("allreduce", opname,
                                np.empty(0, np_dtype)),
                *_pad_trim(n, total, np_dtype, opname))


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


def _flat(module, comm, x):
    """(shape or None, the payload as 1-D).  1-D payloads (the common
    case) flow through UNTOUCHED: a same-shape reshape is a fresh
    dispatch whose result lands uncommitted on the default device, and
    _assemble would then re-place 7 of 8 shards with a device_put on
    EVERY op."""
    if getattr(x, "ndim", None) == 1:
        return None, x
    return x.shape, module._deposit(comm, x).reshape(-1)


def mesh_reduce(module, comm, x, op, alg: str):
    """Plan-path allreduce over the mesh: resolve (or reuse) the plan
    for this payload's length, then one rendezvous and one program.
    The native lowering and recursive doubling take any length; the
    hop-explicit ring takes one that divides by the comm size and
    pads the others (``_execute``).

    Nothing is donated at the payload's own length: the caller's array
    goes straight in, and it is the caller's.  Donation is sound only
    where the pad program made the buffer the plan consumes, and never
    while the integrity plane is armed: after a mismatch it re-reads
    every deposited operand."""
    shape, flat = _flat(module, comm, x)
    n = int(flat.shape[0])
    np_dtype = np.dtype(flat.dtype)
    native = bool(_native_var.value) and op.name in _NATIVE_OPS
    total = n if native or alg != "segring" \
        else _stripe_total(n, comm.size)
    donate = total != n and not _ig.on
    pkey = ("mesh", alg, n, np_dtype.str, op.name, donate)
    plan = _resolve(
        comm, pkey,
        lambda: _build_mesh_plan(comm, alg, n, total, np_dtype, op.name,
                                 native, donate))
    pv_segments.add(plan.nsegs)
    out = _execute(plan, module, comm, flat, n)
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


def _build_move_plan(comm, alg: str, n: int, total: int, np_dtype,
                     root) -> Plan:
    mesh = comm.mesh()
    size = comm.size
    dev_key = tuple(d.id for d in mesh.devices.reshape(-1))
    ckey = ("plan_" + alg, dev_key, (total,), np_dtype.str, root)
    jfn = _dev.compile_cache.get(
        ckey, lambda: _compile_mesh_move(alg, mesh, size, total, root))
    kind = "bcast" if alg == "segbcast" else "alltoall"
    nsegs = _plan_segments(n, segment_elems(comm, np_dtype.itemsize))
    return Plan(alg, nsegs, np_dtype,
                _mesh_meet_fn(mesh, size, jfn), _dev.meet,
                _ig.spec_static(kind, "", np.empty(0, np_dtype),
                                root or 0),
                *_pad_trim(n, total, np_dtype, None))


def mesh_move(module, comm, x, alg: str, root=None):
    """Plan-path mesh bcast (``segbcast``, from ``root``) or alltoall
    (``sega2a``): one program over the whole payload, at its own
    length, behind one rendezvous.  An alltoall's blocks are equal, so
    its length always divides by the comm size; a bcast whose length
    does not is zero-padded to the next multiple and trimmed
    (``_execute``).  A new length compiles a new program, as
    under the tier's crossover: the plan LRU and the compile cache
    bound what is held, not what a sweep of sizes can ask for."""
    shape, flat = _flat(module, comm, x)
    n = int(flat.shape[0])
    np_dtype = np.dtype(flat.dtype)
    total = _stripe_total(n, comm.size)
    pkey = ("mesh", alg, n, np_dtype.str, root)
    plan = _resolve(
        comm, pkey,
        lambda: _build_move_plan(comm, alg, n, total, np_dtype, root))
    out = _execute(plan, module, comm, flat, n)
    return out if shape is None else out.reshape(shape)


# -- hbm (intra-chip) plans -------------------------------------------------

def _build_hbm_plan(module, comm, n: int, np_dtype, opname: str) -> Plan:
    size = comm.size
    jbody, out_map = module._stacked("allreduce", opname, size, (n,),
                                     np_dtype)

    def fn(shards, _j=jbody, _o=out_map, _n=size):
        return _o(_j(*shards), _n)

    fn.traced = functools.partial(_dev._stacked_exec, jbody, out_map, size)

    nsegs = _plan_segments(n, segment_elems(comm, np_dtype.itemsize))
    return Plan("hbm", nsegs, np_dtype, fn, _dev.meet,
                _ig.spec_static("allreduce", opname,
                                np.empty(0, np_dtype)))


def hbm_reduce(module, comm, x, op):
    """Plan-path intra-chip allreduce: the stacked whole-payload
    kernel (an elementwise fold of the deposits, any length) behind
    exactly one rendezvous."""
    shape, flat = _flat(module, comm, module._deposit(comm, x))
    n = int(flat.shape[0])
    np_dtype = np.dtype(flat.dtype)
    pkey = ("hbm", n, np_dtype.str, op.name)
    plan = _resolve(
        comm, pkey,
        lambda: _build_hbm_plan(module, comm, n, np_dtype, op.name))
    pv_segments.add(plan.nsegs)
    out = plan.execute(module, comm, flat, n)
    return out if shape is None else out.reshape(shape)
