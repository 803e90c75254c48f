"""Device collectives: coll/tpu (XLA collectives on the mesh) and
coll/hbm (intra-chip stacked collectives).

This is the north-star component (BASELINE.json): MPI blocking
collectives on TPU-resident buffers lowered to XLA collectives —
psum / psum_scatter / all_gather / all_to_all / ppermute — on the
communicator's device mesh, with reduction ops mapped to XLA
computations.  It replaces the reference's entire §3.4 pyramid
(tuned decision → ring send/recv loops → op function table,
ref: coll_tuned_decision_fixed.c:44-86 + coll_base_allreduce.c:343 +
op_base_functions.c) with ONE compiled HLO collective over ICI.

Execution model: MPI ranks on a TPU host are threads of one process,
each owning a device (see docs/DESIGN.md).  A device collective is a
**rendezvous**: every member thread deposits its shard; the last
arriver zero-copy assembles the global jax.Array
(make_array_from_single_device_arrays), runs the cached jitted
shard_map collective, and hands each member its output shard.  The
assembled op IS the communicator-wide collective — XLA sees the full
mesh and schedules ICI transfers itself.

coll/hbm is the co-located analog of the reference's coll/sm
(ref: ompi/mca/coll/sm/coll_sm_module.c:102,167 — ranks on one node
collect in a shared segment): ranks sharing ONE chip reduce through
HBM with a single fused kernel, no ICI at all.

Ineligible calls (host buffers, unsupported ops, pair dtypes) fall
back to the p2p module stack — the same per-communicator, per-function
fallback discipline as the reference's comm_select.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ompi_tpu import obs as _obs
from ompi_tpu import trace as _trace
from ompi_tpu.obs import integrity as _ig
from ompi_tpu.coll.framework import CollComponent, CollModule, coll_framework
from ompi_tpu.pml.monitoring import count_offload
from ompi_tpu.coll import ragged as _ragged
from ompi_tpu.coll.tuned import TunedModule
from ompi_tpu.datatype import device as _dtdev
from ompi_tpu.mca.params import registry
from ompi_tpu.op.op import MAX, MIN, PREDEFINED, PROD, SUM, Op, jax_binary
from ompi_tpu.runtime import x64 as _x64

# trace ids as module constants: meet() runs once per device
# collective and must not pay module-attribute lookups for them
_CAT_DISP = _trace.CAT_COLL_DISPATCH
_NAME_MEET = _trace.NAME_MEET
_CAT_PHASE = _trace.CAT_PHASE
_NAME_PH_RDV = _trace.NAME_PH_RDV
_NAME_PH_DISPATCH = _trace.NAME_PH_DISPATCH
_NAME_PH_ENTRY = _trace.NAME_PH_ENTRY
_NAME_PH_ASSEMBLE = _trace.NAME_PH_ASSEMBLE
_NAME_PH_LAUNCH = _trace.NAME_PH_LAUNCH
_NAME_PH_SCATTER = _trace.NAME_PH_SCATTER
_HIST_RDV = _trace.HIST_RDV_WAIT
_L_ENTRY = _trace.L_ENTRY
_L_RDV_SLOT = _trace.L_RDV_SLOT
_L_RDV_SKEW = _trace.L_RDV_SKEW
_L_RDV_SERVE = _trace.L_RDV_SERVE
_L_RDV_WAKE = _trace.L_RDV_WAKE
_L_EXIT = _trace.L_EXIT
_L_ASSEMBLE = _trace.L_ASSEMBLE
_L_LAUNCH = _trace.L_LAUNCH
_L_SCATTER = _trace.L_SCATTER
_L_RENDEZVOUS = _trace.L_RENDEZVOUS
_now = time.perf_counter_ns

_prio_tpu = registry.register(
    "coll", "tpu", "priority", 80, int,
    help="Selection priority of the XLA-mesh collective component")
_prio_hbm = registry.register(
    "coll", "hbm", "priority", 70, int,
    help="Selection priority of the intra-chip collective component")
_rv_poll_var = registry.register(
    "coll", "device", "rendezvous_poll", 0.25, float,
    help="Rendezvous wait poll interval in seconds (bounds abort "
         "latency for device collectives)")
_rv_timeout_var = registry.register(
    "coll", "device", "rendezvous_timeout", 300.0, float,
    help="Seconds a device-collective rendezvous may stall before "
         "raising (dead/diverged peer diagnosis)")
_pv_doorbells = registry.register_pvar(
    "coll", "device", "rdv_doorbells",
    help="Progress doorbells rung by rendezvous publishers: one for "
         "each waiter that had left the condvar for its idle selector")
_pv_parks = registry.register_pvar(
    "coll", "device", "rdv_parks",
    help="Waits in which a rendezvous waiter left the condvar (its "
         "2 ms there ran out) to sweep its progress engine and park")
_pv_typed_dev = registry.register_pvar(
    "coll", "typed", "device_ops",
    help="Typed *_arr collectives (a datatype argument) served on the "
         "device with the pack inside the collective's own program; "
         "once a rank-call")
_pv_typed_sliced = registry.register_pvar(
    "coll", "typed", "sliced_packs",
    help="Typed *_arr collectives served on the device whose datatype "
         "packs as static slices of the buffer, no gather "
         "(datatype/device.Typed.sliced); once a rank-call, so "
         "coll_typed_device_ops less this is the calls still gathering")
_pv_typed_folded = registry.register_pvar(
    "coll", "typed", "folded_first",
    help="Typed *_arr reductions served on the device that folded the "
         "ranks' whole buffers and packed the one result, not each "
         "rank's buffer (datatype/device.Typed.folds_first: a fold that "
         "rounds nowhere, a layout that skips little, coll/hbm); once a "
         "rank-call, so coll_typed_device_ops less this is the calls "
         "still packing once a rank")
_pv_typed_host = registry.register_pvar(
    "coll", "typed", "host_packs",
    help="Typed *_arr collectives whose datatype the host convertor "
         "packed (not device-packable, or the call was not eligible "
         "for a device provider); once a rank-call")
_cache_max_var = registry.register(
    "coll", "device", "cache_max", 256, int,
    help="Bound on the compiled-collective LRU cache (distinct "
         "(kind, mesh, shape, dtype, fusion-signature) executables "
         "kept hot).  Shape-churn workloads evict least-recently-used "
         "entries instead of growing without bound; hit/miss/eviction "
         "counters are exported as MPI_T pvars "
         "(coll_device_cache_{hits,misses,evictions,size})")
_reduce_as_allreduce_var = registry.register(
    "coll", "device", "reduce_as_allreduce", True, bool,
    help="Lower reduce_arr as an on-device allreduce (SPMD computes "
         "everywhere; XLA schedules the same AllReduce for "
         "CollectiveReduce, so this costs 2(n-1)/n x a true reduce's "
         "bandwidth but keeps the result device-resident).  False "
         "routes reduce_arr to the host-staged true reduce — the "
         "tuned-decision seam VERDICT r1 asked for.")

# ops with a native XLA cross-replica lowering
_XLA_REDUCERS = {"MPI_SUM", "MPI_MAX", "MPI_MIN"}
# commutative+associative ops lowered as all_gather + on-device fold
_GATHER_FOLD = {"MPI_PROD", "MPI_LAND", "MPI_BAND", "MPI_LOR",
                "MPI_BOR", "MPI_LXOR", "MPI_BXOR"}

# dispatch kind -> integrity-plane spec kind (DESIGN.md §25)
_CK_KINDS = {"allreduce": "allreduce", "reduce_scatter": "redscat",
             "allgather": "gather", "alltoall": "alltoall"}


def _is_jax_array(x) -> bool:
    import jax
    return isinstance(x, jax.Array)


def _dtype_of(x) -> np.dtype:
    """dtype without materializing device arrays on the host —
    np.asarray on a jax.Array is a full device-to-host transfer."""
    dt = getattr(x, "dtype", None)
    return np.dtype(dt) if dt is not None else np.asarray(x).dtype


def _ndim_of(x) -> int:
    nd = getattr(x, "ndim", None)
    return nd if nd is not None else np.asarray(x).ndim


def _shape_of(x):
    sh = getattr(x, "shape", None)
    return sh if sh is not None else np.asarray(x).shape


def _fold_fn(opname: str):
    import jax.numpy as jnp
    return {
        "MPI_PROD": lambda s: jnp.prod(s, axis=0),
        "MPI_LAND": lambda s: jnp.all(s != 0, axis=0).astype(s.dtype),
        "MPI_BAND": lambda s: functools.reduce(jnp.bitwise_and, s),
        "MPI_LOR": lambda s: jnp.any(s != 0, axis=0).astype(s.dtype),
        "MPI_BOR": lambda s: functools.reduce(jnp.bitwise_or, s),
        "MPI_LXOR": lambda s: ((s != 0).sum(axis=0) % 2).astype(s.dtype),
        "MPI_BXOR": lambda s: functools.reduce(jnp.bitwise_xor, s),
    }[opname]


def track_state(state) -> None:
    """First device-collective touch by a rank: register its finalize
    hook so pending fused batches flush before the finalize fence."""
    if state.__dict__.get("_device_coll_tracked"):
        return
    state._device_coll_tracked = True
    state.progress.register_finalize_hook(
        functools.partial(_finalize_state, state))


def _finalize_state(state) -> None:
    # flush pending fused batches: every member rank's hook runs
    # before its finalize fence, so the flush rendezvous still meets
    from ompi_tpu.coll import fusion
    fusion.flush_state(state)
    state._device_coll_tracked = False


def _coll_delay_injector(state):
    """Deterministic ft_inject 'delay' faults at the rendezvous choke
    point: seed-driven random stalls before a rank deposits, so chaos
    runs exercise straggler arrival orders and fusion flush timing
    (cached per rank-state; False = framework disarmed)."""
    inj = state.__dict__.get("_coll_delay_inj")
    if inj is None:
        from ompi_tpu import ft_inject
        inj = ft_inject.coll_injector(state.rank) or False
        state._coll_delay_inj = inj
    return inj


def _coll_sever_injector(state):
    """ft_inject 'rdv_sever' (the hang-doctor chaos class): a one-shot
    deterministic wedge — the victim rank stops short of depositing at
    its Nth rendezvous, stranding every peer in _wait_for until the
    session is poisoned (cached per rank-state; False = disarmed)."""
    inj = state.__dict__.get("_coll_sever_inj")
    if inj is None:
        from ompi_tpu import ft_inject
        inj = ft_inject.rdv_sever_injector(
            state.rank, getattr(state, "size", None)) or False
        state._coll_sever_inj = inj
    return inj


def _coll_slow_injector(state):
    """ft_inject 'host_slow' (the GRAY failure, DESIGN.md §24): every
    rank resident on ft_inject_victim_host stalls a deterministic
    delay_ms*(factor-1) before each deposit — the whole host crawls
    while its heartbeats keep flowing, which is exactly the shape the
    health plane must catch (cached per rank-state; False =
    disarmed or this rank lives elsewhere)."""
    inj = state.__dict__.get("_coll_slow_inj")
    if inj is None:
        from ompi_tpu import ft_inject
        node = getattr(getattr(state, "rte", None), "node_id", 0)
        inj = ft_inject.host_slow_injector(node) or False
        state._coll_slow_inj = inj
    return inj


def _coll_sdc_injector(state):
    """ft_inject 'device_sdc' (the SILENT failure, DESIGN.md §25):
    the victim rank's chip bit-flips its collective operand at the
    armed op count — after the integrity gate digested it, exactly
    the divergence the bisection round attributes.  On an unsampled
    op the flip lands on the raw operand and propagates silently:
    the honest semantics of 1-in-N detection (cached per rank-state;
    False = disarmed or this rank is not the victim)."""
    inj = state.__dict__.get("_coll_sdc_inj")
    if inj is None:
        from ompi_tpu import ft_inject
        inj = ft_inject.sdc_injector(
            state.rank, getattr(state, "size", None)) or False
        state._coll_sdc_inj = inj
    return inj


def _sever_hold(abort_check) -> None:
    """The wedge itself: hold THIS rank before it deposits, in small
    abort-checked sleeps, so the hang doctor finds a live stall (peers
    parked at the rendezvous, this rank absent) and the session poison
    still unwinds everything cleanly — abort_check raises once the
    pool declares the job dead.  Bounded by the rendezvous stall
    timeout so a doctor-less run errors instead of hanging forever."""
    deadline = time.monotonic() + _rv_timeout_var.value
    while True:
        if abort_check:
            abort_check()
        if time.monotonic() > deadline:
            raise RuntimeError(
                "ft_inject rdv_sever: hold outlived the rendezvous "
                "stall timeout with no abort")
        time.sleep(0.02)


# -- phase profiler helpers (docs/DESIGN.md §18) ----------------------------
# A "ph ctx" is the tuple (tracer, cid, seq, nbytes, kept) a traced op
# builds ONCE, only when tracer.phase is armed (the zero-cost-when-off
# gate everywhere else is a single attribute check).  ``seq`` is the
# communicator's collective sequence number, the key of the
# operation's coll span.  EVERY operation carries a ctx then: the
# layer accumulators (trace.LAYERS) bank every boundary of every
# operation.  ``kept`` (Tracer.keep on the sequence number, so the
# same on every member) says whether the operation also writes its
# phase spans, all of them or none, so one op's decomposition is
# always coherent and whole on every rank.  Nothing here waits for the
# device: a traced operation differs from an untraced one by clock
# reads and ring stores.

def _phase_fn(fn, shards, ph):
    """Run a meeting's computation.  Untraced (``ph`` None) that is
    ``fn(shards)`` and nothing else.  With a ctx, a computation that
    brought a traced twin (``fn.traced``: the compiled plans and the
    one-chip kernels attach one where they are built) runs that
    instead, and its steps bank against the triggering rank's tracer;
    a kept op records ph_dispatch around it.  The publisher never
    waits for the device."""
    if ph is None:
        return fn(shards)
    t0 = _now()
    tfn = getattr(fn, "traced", None)
    res = fn(shards) if tfn is None else tfn(shards, ph)
    if ph[4]:
        ph[0].end_at(t0, _now(), _NAME_PH_DISPATCH, _CAT_PHASE,
                     ph[1], ph[2], ph[3])
    return res


def _mesh_exec(mesh, size: int, jfn, sharding, shards: List, ph) -> List:
    """The traced twin of a compiled mesh plan's computation: assemble
    the global array, call the compiled collective, split the output
    per rank; each step banks its accumulator against the publisher's
    ctx and records its span on a kept op."""
    lns = ph[0]._lns
    t0 = _now()
    g = _assemble(mesh, shards, sharding)
    t1 = _now()
    out = jfn(g)
    t2 = _now()
    parts = _scatter_out(out, mesh, size)
    t3 = _now()
    lns[_L_ASSEMBLE] += t1 - t0
    lns[_L_LAUNCH] += t2 - t1
    lns[_L_SCATTER] += t3 - t2
    if ph[4]:
        end_at = ph[0].end_at
        end_at(t0, t1, _NAME_PH_ASSEMBLE, _CAT_PHASE, ph[1], ph[2], ph[3])
        end_at(t1, t2, _NAME_PH_LAUNCH, _CAT_PHASE, ph[1], ph[2], ph[3])
        end_at(t2, t3, _NAME_PH_SCATTER, _CAT_PHASE, ph[1], ph[2], ph[3])
    return parts


def _stacked_exec(jbody, out_map, n: int, shards: List, ph) -> List:
    """The traced twin of a one-chip meeting's computation: the stacked
    kernel, then the per-rank split of its result (``out(r, n)``),
    with the same accounting as _mesh_exec (nothing to assemble: the
    shards are the kernel's arguments)."""
    lns = ph[0]._lns
    t0 = _now()
    r = jbody(*shards)
    t1 = _now()
    parts = out_map(r, n)
    t2 = _now()
    lns[_L_LAUNCH] += t1 - t0
    lns[_L_SCATTER] += t2 - t1
    if ph[4]:
        # the last arriver, under the meeting's lock: one store call
        ph[0].end_at2(t0, t1, _NAME_PH_LAUNCH, _CAT_PHASE,
                      t1, t2, _NAME_PH_SCATTER, _CAT_PHASE,
                      ph[1], ph[2], ph[3])
    return parts


def _per_rank(r, n: int) -> List:
    """``out(r, n)`` of a stacked kernel whose result is already one
    value a rank."""
    return list(r)


class Rendezvous:
    """Per-communicator meeting point for device collectives.

    Generation-tracked so a fast rank may enter collective g+1 while
    stragglers of generation g are still reading their outputs (MPI
    permits ranks to leave a collective at different times)."""

    _SENTINEL = object()  # a deposited value may legitimately be None

    def __init__(self, size: int) -> None:
        self.size = size
        self.cv = threading.Condition()
        self.slots: List[Any] = [self._SENTINEL] * size
        self.count = 0
        self.gen = 0
        self.results: Dict[int, List[Any]] = {}
        self.errors: Dict[int, BaseException] = {}
        self.readers: Dict[int, int] = {}
        # the Progress engines of waiters that may be parked in their
        # idle selector, where notify_all does not reach (_wait_for)
        self._away: set = set()
        # per-generation stamps of the phase profiler (perf_counter_ns;
        # set only by a traced publisher, dropped with the results):
        # when the meeting became full, when its results were published
        self.t_full: Dict[int, int] = {}
        self.t_rel: Dict[int, int] = {}

    def _wait_for(self, cond, what: str, abort_check, progress) -> None:
        """Wait (cv held on entry and exit) until cond() holds.  Polls
        at ``coll_device_rendezvous_poll`` (abort flags are checked
        each tick, bounding abort latency) and fails after
        ``coll_device_rendezvous_timeout`` of no progress — a stuck
        peer must become a diagnosable error, not a silent hang.

        A waiter sits on the condvar first, where the publisher's
        ``notify_all`` alone wakes it: in the common meeting (all peers
        arrive within a couple of ms) it runs no progress sweep, which
        costs 10-50x a condvar wake.  Once a 2 ms wait there has run
        out it leaves the condvar for good and keeps its rank's
        ``progress`` engine turning (the opal_progress-in-every-
        blocking-call discipline, ref: opal/runtime/opal_progress.c:186:
        passive-target RMA — osc lock grants, fetch_and_op application,
        the sharedfp file pointer — targets THIS rank while it sits in
        a collective): sweep outside the lock, then park in the
        progress idle selector, which frag arrival (inproc send →
        wakeup) rings.

        ``notify_all`` does not reach the selector, so a waiter that
        may park there says so in ``_away`` under the lock before it
        drops it; the publisher rings exactly those, after the lock is
        released (``begin``).  A wait that timed out re-takes the lock,
        perhaps behind the publisher, who then saw no flag: the waiter
        looks at cond() once more before it leaves."""
        import time

        poll = _rv_poll_var.value
        stall = _rv_timeout_var.value

        def tick(t_start: float) -> None:
            if abort_check:
                abort_check()
            if time.monotonic() - t_start > stall:
                raise RuntimeError(
                    f"device-collective rendezvous stalled >{stall}s "
                    f"({what}; peers dead or diverged? tune "
                    f"coll_device_rendezvous_timeout)")

        t0 = time.monotonic()
        if progress is None:
            while not cond():
                if not self.cv.wait(timeout=poll):
                    tick(t0)
            return
        park = min(poll, 0.05)
        on_cv = True
        left = 0
        while not cond():
            if on_cv:
                if self.cv.wait(timeout=0.002) or cond():
                    # notified; or the look-once-more
                    continue
                on_cv = False
                left = 1
            selects = progress.has_idle_fds
            if selects:
                self._away.add(progress)
            # progress outside the cv: handlers may send replies
            # (osc acks) and must never run under the meeting lock
            self.cv.release()
            try:
                if left:
                    _pv_parks.add(1)
                    left = 0
                events = progress.progress()
                if events == 0 and selects:
                    # park in the idle selector: woken by frag
                    # arrival AND by the publisher's ring
                    progress.idle_wait(park)
            finally:
                self.cv.acquire()
                if selects:
                    self._away.discard(progress)
            if events == 0 and not selects:
                # no kernel-wakeable fds: park on the condvar (a
                # GIL-holding spin here is measured strictly worse
                # on shared cores) with a short timeout so the pml
                # still gets swept every few ms
                self.cv.wait(timeout=0.002)
            tick(t0)

    def begin(self, rank: int, value: Any,
              fn: Callable[[List[Any]], List[Any]],
              abort_check: Optional[Callable[[], None]] = None,
              progress: Any = None,
              ph: Optional[tuple] = None) -> int:
        """Deposit `value` for the next generation; the last arriver
        runs fn(slots) -> outputs, inline.  Returns the generation
        token to collect with ``finish``.  Slots recycle as soon as
        the meeting is full, so a fast rank may deposit for generation
        g+1 while stragglers still read the results of g."""
        ta = td = 0
        ring = ()
        if ph is not None:
            # layer account (trace.LAYERS; inline: this runs on every
            # operation of every rank): the interval before the
            # rendezvous (entry, or exit after a collect) ends, the
            # wait for the slot and the meeting's lock starts
            tr = ph[0]
            lns = tr._lns
            c = tr._t_cur
            ta = _now()
            if c:
                lns[tr._cur_k] += ta - c
        with self.cv:
            # wait until my slot from the previous generation is consumed
            self._wait_for(lambda: self.slots[rank] is self._SENTINEL,
                           "previous generation unconsumed",
                           abort_check, progress)
            if ta:
                # one clock read under the meeting's lock; the banking
                # and the spans wait until it is released (every
                # microsecond held here is one the next member waits)
                td = _now()
            gen = self.gen
            self.slots[rank] = value
            self.count += 1
            if self.count == self.size:
                shards = list(self.slots)
                self.count = 0
                self.slots = [self._SENTINEL] * self.size
                self.gen += 1
                if td:
                    self.t_full[gen] = td
                # the last arriver computes inline, under the cv
                try:
                    self.results[gen] = _phase_fn(fn, shards, ph)
                except BaseException as e:  # noqa: BLE001
                    self.errors[gen] = e
                    self.results[gen] = [None] * self.size
                self.readers[gen] = self.size
                if td:
                    # published: what follows (notify, doorbells,
                    # the lock and the GIL changing hands) is the
                    # hand-off, every member's rdv_wake
                    self.t_rel[gen] = _now()
                self.cv.notify_all()
                if self._away:
                    ring = list(self._away)
        if ring:
            # the doorbell write is a system call that gives the GIL
            # away: made under the lock it woke waiters only to block
            # on that lock
            for prog in ring:
                prog.wakeup()
            _pv_doorbells.add(len(ring))
        if td:
            if c:
                # deposited at td: the wait for the slot, one more
                # rendezvous; the rank's skew starts at td
                lns[_L_RDV_SLOT] += td - ta
                lns[_L_RENDEZVOUS] += 1
                tr._t_cur = td
                tr._cur_k = _L_ENTRY
            if ph[4]:
                # kept: ph_entry (the last boundary before, shim entry
                # or the end of a pack, to the rendezvous) and the
                # slot-side ph_rdv_wait, which also feeds the
                # straggler-skew histogram
                if c:
                    tr.end_at2(c, ta, _NAME_PH_ENTRY, _CAT_PHASE,
                               ta, td, _NAME_PH_RDV, _CAT_PHASE,
                               ph[1], ph[2], ph[3], _HIST_RDV)
                else:
                    tr.end_at(ta, td, _NAME_PH_RDV, _CAT_PHASE,
                              ph[1], ph[2], ph[3], 0, 0, _HIST_RDV)
        return gen

    def finish(self, rank: int, gen: int,
               abort_check: Optional[Callable[[], None]] = None,
               progress: Any = None,
               ph: Optional[tuple] = None) -> Any:
        """Collect this rank's output of generation ``gen`` (a token
        from ``begin``).  Each member must finish every generation it
        begins, exactly once — results are refcounted away after the
        last reader."""
        tf = _now() if ph is not None and ph[4] else 0
        with self.cv:
            self._wait_for(lambda: gen in self.results,
                           f"waiting for peers (gen {gen})",
                           abort_check, progress)
            if ph is not None:
                # running again: the reading and the generation's two
                # stamps under the lock, the arithmetic after it
                now = _now()
                t_full = self.t_full.get(gen, 0)
                t_rel = self.t_rel.get(gen, 0)
            err = self.errors.get(gen)
            out = self.results[gen][rank]
            self.readers[gen] -= 1
            if self.readers[gen] == 0:
                del self.results[gen], self.readers[gen]
                self.errors.pop(gen, None)
                self.t_full.pop(gen, None)
                self.t_rel.pop(gen, None)
        if ph is not None:
            tr = ph[0]
            c = tr._t_cur
            if c:
                # the time since the last boundary (the deposit, or
                # what the rank did since) splits at the generation's
                # two stamps, clamped into it: until the meeting was
                # full (skew), until the results were published
                # (serve), until running again (wake)
                if t_full < c:
                    t_full = c
                elif t_full > now:
                    t_full = now
                if t_rel < t_full:
                    t_rel = t_full
                elif t_rel > now:
                    t_rel = now
                lns = tr._lns
                lns[_L_RDV_SKEW] += t_full - c
                lns[_L_RDV_SERVE] += t_rel - t_full
                lns[_L_RDV_WAKE] += now - t_rel
                tr._t_cur = now
                tr._cur_k = _L_EXIT
            if tf:
                # kept: the collect-side ph_rdv_wait, from the entry of
                # finish to running again, with how the wait splits in
                # its two free columns (skew_ns until the meeting was
                # full, wake_ns from the publish to now); it feeds the
                # straggler-skew histogram (rdv_wait IS the cross-rank
                # skew signal)
                sk = (t_full if t_full < now else now) - tf
                tr.end_at(tf, now, _NAME_PH_RDV, _CAT_PHASE,
                          ph[1], ph[2], ph[3], sk if sk > 0 else 0,
                          now - (t_rel if t_rel > tf else tf), _HIST_RDV)
        if err is not None:
            raise RuntimeError(
                f"device collective failed on a peer: {err}") from err
        return out

    def run(self, rank: int, value: Any, fn: Callable[[List[Any]], List[Any]],
            abort_check: Optional[Callable[[], None]] = None,
            progress: Any = None, ph: Optional[tuple] = None) -> Any:
        """Deposit `value`; last arriver runs fn(slots) -> outputs;
        block until this rank's output is ready (begin + finish)."""
        gen = self.begin(rank, value, fn, abort_check, progress, ph=ph)
        return self.finish(rank, gen, abort_check, progress, ph=ph)

    def snapshot(self) -> dict:
        """Doctor-facing state capture (DESIGN.md §23): which ranks
        have deposited for the current generation and which are
        absent.  Cold path (fires on a watchdog stall); tries the
        meeting lock briefly and falls back to a lock-free read —
        under the GIL a stale list read is safe, and a wedged meeting
        is by definition not changing."""
        got = self.cv.acquire(timeout=0.2)
        try:
            arrived = [r for r in range(self.size)
                       if self.slots[r] is not self._SENTINEL]
            return {
                "size": self.size,
                "gen": self.gen,
                "count": self.count,
                "arrived": arrived,
                "absent": [r for r in range(self.size)
                           if self.slots[r] is self._SENTINEL],
                "pending_gens": sorted(self.results.keys()),
            }
        finally:
            if got:
                self.cv.release()


def meet(comm, value, fn, abort_check, ck=None, account=True) -> Any:
    """The one rendezvous entry point for offloaded collectives:
    reports the bypassed traffic to pml/monitoring (the offload fast
    paths must not blind the observability story), then runs the
    meeting with this rank's progress engine kept turning.  ``ck`` is
    the integrity-plane check spec (DESIGN.md §25): non-None only when
    the plane is armed and the op is algebraically checkable — the
    sampled gate may then wrap (value, fn) in a digest-carrying pair.
    The spec depends only on (kind, op, dtype), so every rank passes
    the same ck and the comm-consistent sampling invariant holds.
    ``account=False`` (coll/sm's host-buffer collectives, which borrow
    this meeting point) keeps the operation out of the phase
    profiler's layer account: that account is of device collectives,
    and a job's own barriers around a measured region must not add to
    it."""
    rv = _get_rendezvous(comm)
    track_state(comm.state)
    inj = _coll_delay_injector(comm.state)
    if inj:
        d = inj.maybe_delay()
        if d:
            time.sleep(d)
    sl = _coll_slow_injector(comm.state)
    if sl:
        time.sleep(sl.delay_s())
    sv = _coll_sever_injector(comm.state)
    if sv and sv.should_sever():
        _sever_hold(abort_check)
    nbytes = int(getattr(value, "nbytes", 0) or 0)
    count_offload(comm, nbytes)
    if ck is not None:
        value, fn = _ig.gate(comm, value, fn, ck)
    sj = _coll_sdc_injector(comm.state)
    if sj and sj.should_flip():
        value = _ig.flip_value(value)
    tr = comm.state.tracer
    if tr is None:
        return rv.run(comm.rank, value, fn, abort_check,
                      progress=comm.state.progress)
    # dispatch span: entry->rendezvous-release of the device fast path
    # (cat coll_dispatch feeds the dispatch-latency histogram).  ``seq``
    # (one per rendezvous) keys the span; ``op``, the communicator's
    # collective sequence, decides keep-or-skip for the span and for
    # the operation's phases, the same on every member
    seq = comm._dev_seq
    comm._dev_seq = seq + 1
    op = comm._coll_seq
    # Tracer.keep, inlined (the sampled-out steady state makes no call
    # and reads no clock)
    if not tr._plo <= op < tr._phi:
        tr._restep(op)
    per = tr._period
    if op % per[_CAT_DISP]:
        tr._skipped[_CAT_DISP] += 1
        t0 = 0
    else:
        t0 = _now()
    # phase ctx (docs/DESIGN.md §18): one tuple per op ONLY when the
    # profiler is armed; off, a single attribute check
    ph = None
    if tr.phase:
        if op % per[_CAT_PHASE]:
            tr._skipped[_CAT_PHASE] += 1
            ph = (tr, comm.cid, op, nbytes, False)
        else:
            ph = (tr, comm.cid, op, nbytes, True)
        if not account:
            tr._t_cur = 0   # no open cursor: its boundaries bank nothing
    out = rv.run(comm.rank, value, fn, abort_check,
                 progress=comm.state.progress, ph=ph)
    if t0:
        tr.end(t0, _NAME_MEET, _CAT_DISP, comm.cid, seq, nbytes, op)
    return out


def _get_rendezvous(comm) -> Rendezvous:
    # per-comm fast path: the (cid, group)-keyed lookup below costs a
    # lock + tuple build per collective, measurable at the 4-byte floor
    rv = comm.__dict__.get("_device_rv")
    if rv is not None:
        return rv
    world = comm.state.rte.world
    # disjoint communicators may share a cid (uniqueness is
    # per-process), so the group is part of the key
    key = ("coll_rv", comm.cid, tuple(comm.group))
    with world.shared_lock:
        rv = world.shared.get(key)
        if rv is None:
            rv = Rendezvous(comm.size)
            world.shared[key] = rv
    comm.__dict__["_device_rv"] = rv
    return rv


# ---------------------------------------------------------------------------
# compiled-collective cache: (kind, mesh_key, shape, dtype, extra) -> fn,
# fused entries keyed additionally on their fusion signature.  Bounded
# LRU (the per-(op, dtype, shape, comm) caching from SURVEY.md §7.6 —
# but shape-churn workloads must evict, not grow without bound).
# ---------------------------------------------------------------------------


class CompiledLRU:
    """Bounded compiled-executable cache with MPI_T observability.

    ``builds`` is the compile trace counter tests assert against (a
    cache hit must skip recompilation — asserted by count, never by
    timing).  Builders run OUTSIDE the lock: an XLA compile must not
    stall every other collective's cache hit; two racing builders of
    one key both compile and the last write wins — identical
    executables, same as the old dict."""

    def __init__(self) -> None:
        self._d: "OrderedDict[Tuple, Callable]" = OrderedDict()
        # who compiled each entry (ompi_tpu/obs cid band): the serving
        # control plane enforces a per-session cache share, and a
        # preempted/destroyed session's executables are dropped by band
        self._bands: Dict[Tuple, int] = {}
        self._lock = threading.Lock()
        self.builds = 0
        # session-banded (ompi_tpu/obs): a resident pool shares one
        # compile cache, so per-tenant hit counts are the difference
        # between "warm for me" and "warm because of my neighbor"
        self.pv_hits = _obs.scoped_pvar(
            "coll", "device", "cache_hits",
            help="Compiled-collective cache hits")
        self.pv_misses = registry.register_pvar(
            "coll", "device", "cache_misses",
            help="Compiled-collective cache misses (each one is a "
                 "full XLA compile)")
        self.pv_evictions = registry.register_pvar(
            "coll", "device", "cache_evictions",
            help="Compiled-collective LRU evictions "
                 "(coll_device_cache_max bound enforced)")
        self.pv_band_evictions = registry.register_pvar(
            "coll", "device", "cache_band_evictions",
            help="Own-band LRU evictions forced by the per-session "
                 "cache share quota (dvm_quota_cache_share_pct)")
        registry.register_pvar(
            "coll", "device", "cache_size", var_class="level",
            getter=lambda: len(self._d),
            help="Compiled-collective cache entries currently held")

    def __len__(self) -> int:
        return len(self._d)

    def clear(self) -> None:
        with self._lock:
            self._d.clear()
            self._bands.clear()

    def count_band(self, band: int) -> int:
        """Entries currently attributed to `band` (compile-time
        current_band of the inserting thread)."""
        with self._lock:
            n = 0
            for b in self._bands.values():
                if b == band:
                    n += 1
            return n

    def drop_band(self, band: int) -> int:
        """Drop every executable compiled under session band `band`.
        The DVM calls this when a session is destroyed or preempted:
        its cid band may be reused by a later tenant, and share
        accounting must not charge the newcomer for a ghost's
        compiles.  Returns how many entries were dropped."""
        with self._lock:
            stale = [k for k, b in self._bands.items() if b == band]
            for k in stale:
                self._d.pop(k, None)
                del self._bands[k]
            return len(stale)

    def drop_mesh(self, dev_key: Tuple) -> int:
        """Drop every executable compiled against `dev_key` (a tuple
        of device ids — the mesh identity every _mesh_collective and
        fused key embeds as a top-level element).  Comm.shrink calls
        this: the survivor mesh re-keys on its own device list, so
        entries for the dead shape would squat in the bounded cache
        until evicted.  Returns how many entries were dropped."""
        with self._lock:
            stale = [k for k in self._d if dev_key in k]
            for k in stale:
                del self._d[k]
                self._bands.pop(k, None)
            return len(stale)

    def drop_device(self, dev_id: int) -> int:
        """Drop every executable whose mesh includes device ``dev_id``
        (any top-level dev_key tuple containing it).  The respawn
        rejoin calls this for each replaced rank's device: the
        replacement re-binds the same world rank but possibly a
        different physical device, and an executable compiled against
        a mesh naming the old device must never be served against the
        rebuilt one.  Returns how many entries were dropped."""
        with self._lock:
            stale = [k for k in self._d
                     if any(isinstance(p, tuple) and dev_id in p
                            for p in k)]
            for k in stale:
                del self._d[k]
                self._bands.pop(k, None)
            return len(stale)

    def get(self, key: Tuple, builder: Callable[[], Callable]) -> Callable:
        with self._lock:
            fn = self._d.get(key)
            if fn is not None:
                self._d.move_to_end(key)
                self.pv_hits.add(1, _obs.current_band())
                return fn
        self.pv_misses.add(1)
        self.builds += 1
        tr = _trace.current_tracer()
        if tr is None:
            fn = builder()
        else:
            t0 = tr.start()
            fn = builder()
            tr.end(t0, _trace.NAME_XLA_COMPILE, _trace.CAT_COMPILE,
                   _trace.intern_name(str(key[0])))
        band = _obs.current_band()
        with self._lock:
            self._d[key] = fn
            self._d.move_to_end(key)
            self._bands[key] = band
            cap = max(1, _cache_max_var.value)
            # per-session cache share (serving control plane): a tenant
            # over its share evicts ITS OWN oldest entries, never a
            # neighbor's — churn degrades the offender, not the pool.
            # Band 0 is unbanded (no session) and exempt.
            share = registry.get("dvm_quota_cache_share_pct", 0)
            if band and share and 0 < share < 100:
                band_cap = max(1, cap * share // 100)
                mine = [k for k in self._d if self._bands.get(k) == band]
                if len(mine) > band_cap:
                    for k in mine[:len(mine) - band_cap]:
                        self._d.pop(k, None)
                        del self._bands[k]
                        self.pv_band_evictions.add(1)
            while len(self._d) > cap:
                k, _ = self._d.popitem(last=False)
                self._bands.pop(k, None)
                self.pv_evictions.add(1)
        return fn


compile_cache = CompiledLRU()


# serving-plane HBM quota hook (ompi_tpu/serve/quota): lazy-bound so
# coll never imports the serve package unless a pool armed a quota —
# and a plain mpirun world pays one None check per deposit, nothing
# else.  serve.quota.install() points this at the real charge
# function.
_hbm_charge_hook: Optional[Callable[[int], None]] = None


def _charge_hbm(nbytes: int) -> None:
    hook = _hbm_charge_hook
    if hook is not None:
        hook(nbytes)


def _mesh_collective(kind: str, mesh, shape, dtype, extra=None,
                     typed=None) -> Callable:
    # keyed by device ids, NOT mesh identity: every rank builds its own
    # (equal) Mesh object, and whichever thread is last-arriver must hit
    # the same compiled executable (a miss costs a full XLA compile)
    dev_key = tuple(d.id for d in mesh.devices.reshape(-1))
    key = (kind, dev_key, tuple(shape), np.dtype(dtype).str, extra)
    if typed is not None:
        key += (typed,)
    return compile_cache.get(
        key, lambda: _build_mesh_collective(kind, mesh, shape, dtype, extra,
                                            typed))


def _build_mesh_collective(kind: str, mesh, shape, dtype,
                           extra=None, typed=None) -> Callable:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    size = mesh.devices.size

    if kind == "allreduce":
        opname = extra
        if opname in _XLA_REDUCERS:
            red = {"MPI_SUM": lax.psum, "MPI_MAX": lax.pmax,
                   "MPI_MIN": lax.pmin}[opname]
            body = lambda x: red(x, "r")  # noqa: E731
        else:
            fold = _fold_fn(opname)
            body = lambda x: fold(  # noqa: E731
                lax.all_gather(x, "r", tiled=False))
        in_specs, out_specs = P("r"), P(None)
    elif kind == "reduce_scatter":
        opname = extra or "MPI_SUM"
        if opname == "MPI_SUM":
            body = lambda x: lax.psum_scatter(x, "r", tiled=True)  # noqa: E731
        else:
            # non-SUM ops have no XLA ReduceScatter lowering: gather
            # the shards, fold on-device, keep this rank's stripe
            if opname == "MPI_MAX":
                fold = lambda g: jnp.max(g, axis=0)  # noqa: E731
            elif opname == "MPI_MIN":
                fold = lambda g: jnp.min(g, axis=0)  # noqa: E731
            else:
                fold = _fold_fn(opname)

            def body(x):
                g = lax.all_gather(x, "r", tiled=False)
                r = fold(g)
                i = lax.axis_index("r")
                m = r.shape[0] // size
                return lax.dynamic_slice_in_dim(r, i * m, m, axis=0)

        in_specs, out_specs = P("r"), P("r")
    elif kind == "allgather":
        body = lambda x: lax.all_gather(x, "r", tiled=True)  # noqa: E731
        in_specs, out_specs = P("r"), P(None)
    elif kind == "alltoall":
        body = lambda x: lax.all_to_all(  # noqa: E731
            x, "r", split_axis=0, concat_axis=0, tiled=True)
        in_specs, out_specs = P("r"), P("r")
    elif kind == "bcast":
        root = extra

        def body(x):  # bcast as masked psum (one AllReduce over ICI)
            mask = (lax.axis_index("r") == root)
            return lax.psum(jnp.where(mask, x, jnp.zeros_like(x)), "r")

        in_specs, out_specs = P("r"), P(None)
    elif kind == "ppermute":
        perm = extra

        def body(x):
            return lax.ppermute(x, "r", perm=list(perm))

        in_specs, out_specs = P("r"), P("r")
    else:
        raise KeyError(kind)

    if typed is not None:
        # a typed call: every rank's shard is the buffer its datatype
        # addresses, packed here, inside the one program
        untyped = body

        def body(x):
            return typed.unkey(untyped(typed.pack(x)))

        body.__name__ = body.__qualname__ = "ompi_typed_" + kind

    # check_vma off: collective bodies are intentionally rank-divergent
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _assemble(mesh, shards: List, sharding=None):
    """Zero-copy global array from per-rank single-device shards.
    Shards already on rank i's mesh device are used in place; stray
    shards (created on the default device) are moved first.  Callers
    that run per-op (the plan executor) pass a prebuilt ``sharding``
    — constructing NamedSharding fresh costs ~1/5 of a whole small
    collective on the CPU runtime."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    devs = list(mesh.devices.reshape(-1))
    placed = []
    for i, s in enumerate(shards):
        if getattr(s, "device", None) == devs[i]:
            placed.append(s)
        else:
            _charge_hbm(int(getattr(s, "nbytes", 0)))
            placed.append(_x64.put(s, devs[i],
                                   "coll/tpu device collective"))
    n = placed[0].shape[0]
    global_shape = (n * len(placed),) + tuple(placed[0].shape[1:])
    if sharding is None:
        sharding = NamedSharding(mesh, P("r"))
    return jax.make_array_from_single_device_arrays(
        global_shape, sharding, placed)


def _scatter_out(out, mesh, size: int) -> List:
    """Split a collective output back into per-rank arrays, indexed by
    comm rank (mesh device order == comm rank order)."""
    dev_order = {d.id: i for i, d in enumerate(mesh.devices.reshape(-1))}
    parts: List[Any] = [None] * size
    if len(out.addressable_shards) == size:
        for sh in out.addressable_shards:
            parts[dev_order[sh.device.id]] = sh.data
        return parts
    # replicated output: every rank reads the same array
    return [out] * size


_pipeline_mod = None


def _pipeline():
    """Lazy large-message tier (coll/pipeline) — resolved once; the
    4-byte-floor hot path must not pay an import-machinery dict walk
    per collective."""
    global _pipeline_mod
    if _pipeline_mod is None:
        from ompi_tpu.coll import pipeline as _p
        _pipeline_mod = _p
    return _pipeline_mod


def typed_arr(comm, entry, x, op: Op, datatype, count):
    """``comm.<op>_arr(x, op, datatype, count)``: resolve the count,
    refuse a buffer whose 8-byte elements this device would not hold
    (jax narrows them, or its float64 is not binary64: runtime/x64),
    hand the shim the datatype for the call's ``coll`` span (its name,
    the count and the packed bytes) and call the winning provider's
    entry with the two arguments.  Untyped calls never come here."""
    if not hasattr(x, "dtype"):
        x = np.asarray(x)
    dt = x.dtype
    if dt.itemsize < 8 and datatype.runs:
        # a buffer narrower than the datatype's base is what jax made
        # of a double with x64 off
        dt = datatype.runs[0].dtype
    if dt.itemsize >= 8 and comm.state.device is not None:
        _x64.check(dt, "typed *_arr collective")
    count = _dtdev.typed_count(datatype, count, x)
    tr = comm.state.tracer
    if tr is None:
        return entry(comm, x, op, datatype, count)
    tr.coll_args = {"datatype": _dtdev.label(datatype), "count": count,
                    "packed_bytes": count * datatype.size}
    try:
        return entry(comm, x, op, datatype, count)
    finally:
        tr.coll_args = None     # a span sampled out took nothing


def _count_typed(t, folded: bool = False) -> None:
    """One typed rank-call served on the device with ``t``; ``folded``:
    by a program that folds first and packs once."""
    _pv_typed_dev.add(1)
    if t.sliced:
        _pv_typed_sliced.add(1)
    if folded:
        _pv_typed_folded.add(1)


def _typed_on_device(mod, comm, kind: str, x, op: Op, datatype, count):
    """The ``Typed`` a device provider serves a typed call with, or
    None when the call goes to the host fallback.  One rule for
    coll/tpu and coll/hbm: the provider's own eligibility, a reduction
    with an on-device lowering, datatype/device.typed_operand, for a
    reduce_scatter a packed stream that splits evenly, and for
    MPI_DOUBLE carried as bit patterns a reduction that compares."""
    if not mod._eligible(comm, x) or (
            op.name not in _XLA_REDUCERS and op.name not in _GATHER_FOLD):
        return None
    t = _dtdev.typed_operand(datatype, count, x)
    if t is None or (kind == "reduce_scatter" and t.elems % comm.size) \
            or (t.bits and op.name not in _dtdev.BITS_OPS):
        # MPI_DOUBLE as bit patterns: a reduction that is arithmetic
        # is the host's, in binary64
        return None
    return t


def _measured_host_wins(comm, kind: str, nbytes: int) -> bool:
    """Measured-crossover reroute (--mca coll_tuned_use_measured_rules):
    below the calibrated device-vs-host crossover the host seg path
    wins — the size-independent dispatch constant dominates the device
    path there.  Comm-consistent: the profile is process-wide and
    nbytes is MPI-matched across ranks, so every member reroutes (or
    not) together."""
    from ompi_tpu.coll import calibrate
    if not calibrate.use_measured_rules():
        return False
    return 0 < nbytes < calibrate.crossover_bytes(kind, comm.size)


class TpuCollModule(CollModule):
    """XLA-mesh collectives for comms whose ranks own distinct devices."""

    name = "tpu"

    def __init__(self, fallback: "HostArrModule") -> None:
        self.fallback = fallback
        self.pvar_offload = registry.register_pvar(
            "coll", "tpu", "offloaded_collectives",
            help="Number of collectives executed as XLA mesh ops")

    # -- helpers ---------------------------------------------------------
    def _eligible(self, comm, *arrays) -> bool:
        """Must be comm-consistent: every member reaches the same
        verdict, else some ranks enter the rendezvous while others take
        the p2p fallback — a silent deadlock.  Depends only on comm
        properties and dtype/op/shape, which MPI requires to match
        across ranks; local buffer residency does NOT matter (stray
        host buffers are moved in _assemble)."""
        if comm.size == 1:
            return False
        if comm.mesh() is None:
            return False
        return all(_dtype_of(a).fields is None for a in arrays)

    @staticmethod
    def _norm(x):
        """Normalize scalars/0-d arrays to rank-1 for sharding."""
        if getattr(x, "ndim", None) == 0:
            return x.reshape(1), True
        return x, False

    def _abort_check(self, comm):
        cached = comm.__dict__.get("_device_abort_check")
        if cached is not None:
            return cached
        world = getattr(comm.state.rte, "world", None)
        ulfm = comm.state.ulfm  # None when mpi_ft_ulfm is off

        def check():
            if world is not None and world.aborted and \
                    world.aborted[0] != comm.state.rank:
                raise RuntimeError(
                    f"peer rank {world.aborted[0]} aborted during "
                    "device collective")
            if ulfm is not None and ulfm.active:
                # a peer died while we were parked in the rendezvous:
                # surface ERR_PROC_FAILED/ERR_REVOKED out of the wait
                # instead of spinning until the meet timeout
                ulfm.poll()
                ulfm.check_comm(comm)
        comm.__dict__["_device_abort_check"] = check
        return check

    @staticmethod
    def _deposit(comm, x):
        """Ensure the deposited value lives on the rank's device: a
        host buffer is moved by its own rank, which is where an 8-byte
        element that would not arrive whole is refused (runtime/x64)."""
        if _is_jax_array(x):
            return x
        arr = np.asarray(x)
        _charge_hbm(arr.nbytes)
        return _x64.put(arr, comm.state.device, "device collective")

    def _run(self, comm, value, fn, ck=None):
        return self._meet(comm, self._deposit(comm, value), fn, ck)

    def _meet(self, comm, value, fn, ck=None):
        out = meet(comm, value, fn, self._abort_check(comm), ck)
        self.pvar_offload.add(1)
        return out

    # -- device-array collectives (the *_arr vtable surface) -------------
    def _typed(self, comm, kind: str, x, op: Op, datatype, count):
        """A typed allreduce / reduce_scatter: each rank's shard is
        the buffer its datatype addresses and the pack is inside the
        mesh program (_build_mesh_collective), one rendezvous and one
        program a call.  Never planned: the large-message tier's plans
        are keyed by a contiguous payload."""
        t = _typed_on_device(self, comm, kind, x, op, datatype, count)
        if t is None:
            return self.fallback.typed(comm, kind, x, op, datatype, count)
        mesh = comm.mesh()
        opname = op.name

        def fn(shards):
            g = _assemble(mesh, shards)
            jfn = _mesh_collective(kind, mesh, g.shape, g.dtype, opname, t)
            return _scatter_out(jfn(g), mesh, comm.size)

        ck = _ig.spec_typed(_CK_KINDS[kind], opname, t) if _ig.on else None
        out = self._run(comm, x.reshape(-1), fn, ck)
        _count_typed(t)
        return out

    def allreduce_arr(self, comm, x, op: Op, datatype=None, count=None):
        if datatype is not None:
            return self._typed(comm, "allreduce", x, op, datatype, count)
        if not self._eligible(comm, x) or (
                op.name not in _XLA_REDUCERS
                and op.name not in _GATHER_FOLD) \
                or _measured_host_wins(comm, "allreduce",
                                       int(getattr(x, "nbytes", 0) or 0)):
            return self.fallback.allreduce_arr(comm, x, op)
        pl = _pipeline()
        out = pl.maybe_device_coll(self, comm, "allreduce", x, op=op)
        if out is not pl.UNHANDLED:
            self.pvar_offload.add(1)
            return out
        mesh = comm.mesh()
        x, was_scalar = self._norm(x)

        def fn(shards):
            g = _assemble(mesh, shards)
            jfn = _mesh_collective("allreduce", mesh, g.shape, g.dtype,
                                   op.name)
            return _scatter_out(jfn(g), mesh, comm.size)

        ck = _ig.spec("allreduce", op.name, x) if _ig.on else None
        out = self._run(comm, x, fn, ck)
        return out.reshape(()) if was_scalar else out

    def reduce_scatter_block_arr(self, comm, x, op: Op, datatype=None,
                                 count=None):
        if datatype is not None:
            return self._typed(comm, "reduce_scatter", x, op, datatype,
                               count)
        if not self._eligible(comm, x) or (
                op.name not in _XLA_REDUCERS
                and op.name not in _GATHER_FOLD) \
                or _ndim_of(x) == 0 \
                or x.shape[0] % comm.size != 0:
            return self.fallback.reduce_scatter_block_arr(comm, x, op)
        mesh = comm.mesh()
        opname = op.name

        def fn(shards):
            g = _assemble(mesh, shards)
            jfn = _mesh_collective("reduce_scatter", mesh, g.shape,
                                   g.dtype, opname)
            return _scatter_out(jfn(g), mesh, comm.size)

        ck = _ig.spec("redscat", opname, x) if _ig.on else None
        return self._run(comm, x, fn, ck)

    def allgather_arr(self, comm, x):
        if not self._eligible(comm, x):
            return self.fallback.allgather_arr(comm, x)
        mesh = comm.mesh()
        x, _ = self._norm(x)

        def fn(shards):
            g = _assemble(mesh, shards)
            jfn = _mesh_collective("allgather", mesh, g.shape, g.dtype)
            return _scatter_out(jfn(g), mesh, comm.size)

        ck = _ig.spec("gather", "", x) if _ig.on else None
        return self._run(comm, x, fn, ck)

    def alltoall_arr(self, comm, x):
        if not self._eligible(comm, x) or _ndim_of(x) == 0 \
                or x.shape[0] % comm.size != 0 \
                or _measured_host_wins(comm, "alltoall",
                                       int(getattr(x, "nbytes", 0) or 0)):
            return self.fallback.alltoall_arr(comm, x)
        pl = _pipeline()
        out = pl.maybe_device_coll(self, comm, "alltoall", x)
        if out is not pl.UNHANDLED:
            self.pvar_offload.add(1)
            return out
        mesh = comm.mesh()

        def fn(shards):
            g = _assemble(mesh, shards)
            jfn = _mesh_collective("alltoall", mesh, g.shape, g.dtype)
            return _scatter_out(jfn(g), mesh, comm.size)

        ck = _ig.spec("alltoall", "", x) if _ig.on else None
        return self._run(comm, x, fn, ck)

    def _ragged_eligible(self, comm, x) -> bool:
        """THE rule of what the mesh serves of ``alltoallv_arr``: every
        rank on its own chip of the comm's mesh, a platform whose
        compiler lowers ``ragged-all-to-all`` (XLA:CPU does not),
        elements of 2, 4 or 8 bytes and rows of at least
        ``ragged.MESH_ROW_BYTES``, all of which MPI makes the same on
        every rank.  Counts, displacements, lengths and capacities are
        not asked: the meeting sees them all (``_ragged_mesh``)."""
        if x.dtype.itemsize not in _ragged.ITEMSIZES \
                or _ragged.row_elems(x) * x.dtype.itemsize \
                < _ragged.MESH_ROW_BYTES \
                or not self._eligible(comm, x):
            return False
        return comm.mesh().devices.flat[0].platform \
            not in _ragged.NO_LOWERING

    def _ragged_mesh(self, comm) -> Callable:
        """The meeting's computation of a mesh ``alltoallv_arr``: ONE
        ``ompi_alltoallv_mesh`` program whose executable is resolved at
        the meeting from the deposits' shape and capacity and kept by
        them alone (no count is in any key: a new count matrix builds
        nothing), and whose counts are its operand.  A mesh program's
        shards are of one shape: deposits that differ in length or
        capacity are served through the host (``ragged.through_host``),
        counted there.  Which of the program's two bodies serves the key
        is read once, when it is built, from the first deposit's own
        layout (``ragged.slab_rows``: the slab body for a row-major
        buffer, the row body for any other).  The counts cross to the
        device once a meeting: ONE put of the operand onto the first
        rank's chip, beside the zeros the key's program was built with
        on every other chip (``ragged.spare_operand``), and the program
        spreads it over ICI.  The device counters move here, where the
        path is known, for every rank-call of the meeting."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, size = comm.mesh(), comm.size
        sharding = NamedSharding(mesh, P("r"))
        devs = list(mesh.devices.reshape(-1))
        dev_key = tuple(d.id for d in devs)
        staged = self.fallback.pvar_staged

        def uniform(deposits) -> bool:
            x, cap = deposits[0].x, deposits[0].capacity
            return all(d.x.shape == x.shape and d.capacity == cap
                       for d in deposits)

        def build(x, cap):
            t = _ragged.slab_rows(_ragged.layout_of(x), x.shape,
                                  x.dtype.itemsize, cap)
            return (_ragged.mesh_program(mesh, cap, sharding, t), t,
                    _ragged.spare_operand(devs, 7 if t else 4))

        def launch(deposits, g):
            x, cap = deposits[0].x, deposits[0].capacity
            jfn, t, spare = compile_cache.get(
                ("alltoallv_mesh", dev_key, x.shape, x.dtype.str, cap),
                lambda: build(x, cap))
            w = _ragged.row_elems(x)
            longest = max(x.shape[0], cap) * w
            if not t:
                meta = _ragged.mesh_operand(deposits, longest)
            else:
                meta = _ragged.slab_operand(deposits, longest, t)
                if meta is None:
                    # receive blocks that overlap: the host's answer,
                    # handed back as the program's would be
                    return _assemble(mesh, _ragged.through_host(
                        deposits, devs, staged), sharding)
            meta = jax.make_array_from_single_device_arrays(
                (size * size, *meta.shape[1:]), sharding,
                [_ragged.put_operand(meta, devs[0]), *spare])
            _ragged.pv_operand_puts.add(1)
            out = jfn(meta, g)
            rows = sum(d.meta[_ragged.SENT] for d in deposits)
            _ragged.pv_device_ops.add(size)
            if t:
                _ragged.pv_slab_ops.add(size)
            _ragged.pv_elems.add(rows * w)
            _ragged.pv_bytes.add(rows * w * x.dtype.itemsize)
            return out

        def fn(deposits):
            if not uniform(deposits):
                return _ragged.through_host(deposits, devs, staged)
            g = _assemble(mesh, [d.x for d in deposits], sharding)
            return _scatter_out(launch(deposits, g), mesh, size)

        def traced(deposits, ph):
            # the phase profiler's twin (_phase_fn): assemble, launch
            # and scatter banked as a compiled mesh plan's are
            if not uniform(deposits):
                return _ragged.through_host(deposits, devs, staged)
            return _mesh_exec(mesh, size, functools.partial(launch, deposits),
                              sharding, [d.x for d in deposits], ph)

        fn.traced = traced
        return fn

    def alltoallv_arr(self, comm, x, meta, capacity: int):
        """One rendezvous and one ``ompi_alltoallv_mesh`` program a
        call over ICI; every rank's counts and offsets reach it as an
        int32 operand, put on the first rank's chip and spread by the
        program (coll/ragged.py)."""
        if not self._ragged_eligible(comm, x):
            return self.fallback.alltoallv_arr(comm, x, meta, capacity)
        fn = comm.__dict__.get("_tpu_ragged")
        if fn is None:
            fn = comm.__dict__["_tpu_ragged"] = self._ragged_mesh(comm)
        return self._meet(
            comm, _ragged.Deposit(self._deposit(comm, x), meta, capacity),
            fn)

    def bcast_arr(self, comm, x, root: int):
        if not self._eligible(comm, x) \
                or _measured_host_wins(comm, "bcast",
                                       int(getattr(x, "nbytes", 0) or 0)):
            return self.fallback.bcast_arr(comm, x, root)
        pl = _pipeline()
        out = pl.maybe_device_coll(self, comm, "bcast", x, root=root)
        if out is not pl.UNHANDLED:
            self.pvar_offload.add(1)
            return out
        mesh = comm.mesh()
        x, was_scalar = self._norm(x)

        def fn(shards):
            g = _assemble(mesh, shards)
            jfn = _mesh_collective("bcast", mesh, g.shape, g.dtype, root)
            return _scatter_out(jfn(g), mesh, comm.size)

        ck = _ig.spec("bcast", "", x, root) if _ig.on else None
        out = self._run(comm, x, fn, ck)
        return out.reshape(()) if was_scalar else out

    def reduce_arr(self, comm, x, op: Op, root: int):
        # SPMD style: compute everywhere, deliver at root — a tuned
        # decision (coll_device_reduce_as_allreduce); see the var's
        # help for the bandwidth trade-off
        if not _reduce_as_allreduce_var.value:
            return self.fallback.reduce_arr(comm, x, op, root)
        out = self.allreduce_arr(comm, x, op)
        return out if comm.rank == root else None

    def ppermute_arr(self, comm, x, perm):
        """Neighbor shift — the ring-attention / pipeline primitive
        (SURVEY.md §2.8: mesh-axis neighbor ppermute)."""
        if not self._eligible(comm, x):
            return self.fallback.ppermute_arr(comm, x, perm)
        mesh = comm.mesh()
        x, _ = self._norm(x)
        perm_t = tuple(sorted((int(a), int(b)) for a, b in perm))

        def fn(shards):
            g = _assemble(mesh, shards)
            jfn = _mesh_collective("ppermute", mesh, g.shape, g.dtype,
                                   perm_t)
            return _scatter_out(jfn(g), mesh, comm.size)

        return self._run(comm, x, fn)


class HbmCollModule(CollModule):
    """Intra-chip collectives: every member rank shares one device, so
    the collective is a single fused on-chip kernel through HBM
    (coll/sm analog — the 'node' is the chip)."""

    name = "hbm"

    def __init__(self, fallback: "HostArrModule") -> None:
        self.fallback = fallback
        self.pvar_offload = registry.register_pvar(
            "coll", "hbm", "offloaded_collectives",
            help="Number of collectives executed as stacked on-chip "
                 "kernels through HBM")

    def _eligible(self, comm, *arrays) -> bool:
        # comm-consistent only (see TpuCollModule._eligible).  The
        # device-layout half (all members on ONE chip) never changes
        # for a comm, so it is computed once; per call only the dtype
        # check remains (4-byte-floor hot path).
        one_dev = comm.__dict__.get("_hbm_one_device")
        if one_dev is None:
            if comm.size == 1:
                one_dev = False
            else:
                devs = set()
                one_dev = True
                for g in comm.group:
                    st = comm._peer_state(g)
                    if st is None or st.device is None:
                        one_dev = False
                        break
                    devs.add(st.device.id)
                one_dev = one_dev and len(devs) == 1
            comm.__dict__["_hbm_one_device"] = one_dev
        return one_dev and all(
            _dtype_of(a).fields is None for a in arrays)

    _abort_check = TpuCollModule._abort_check
    _norm = staticmethod(TpuCollModule._norm)

    _deposit = staticmethod(TpuCollModule._deposit)

    def _stacked(self, kind: str, opname: str, nshards: int, shape, dtype,
                 extra=None) -> Callable:
        # process-global LRU (shared with the mesh path, "hbm"-prefixed
        # keys): every rank has its own module instance, but the
        # last-arriver thread rotates — a per-instance cache would
        # recompile once per distinct executing thread
        key = ("hbm", kind, opname, nshards, tuple(shape),
               np.dtype(dtype).str, extra)
        return compile_cache.get(
            key, lambda: self._build_stacked(kind, opname, extra))

    @staticmethod
    def _build_stacked(kind: str, opname: str, typed=None) -> Callable:
        """``typed`` (datatype/device.Typed, the ``extra`` of a typed
        call) packs inside the kernel: each rank's deposit in front of
        the same arithmetic, or, where the fold rounds nowhere and the
        datatype skips little (``Typed.folds_first``), the one fold of
        the whole deposits.  An ``alltoallv``'s extra is the ranks'
        capacities (coll/ragged.body): static, like the lengths of its
        arguments; its counts are an operand."""
        import jax
        import jax.numpy as jnp

        if kind == "alltoallv":
            return (jax.jit(_ragged.body(typed)), _per_rank)

        # Per-rank output splitting happens INSIDE the jitted body
        # (tuple outputs): one dispatch per collective instead of one
        # plus a host-side slice per rank.  `out(r, n)` maps the jit
        # result to the n per-rank values without any further device
        # ops.
        if kind == "allreduce":
            if opname == "MPI_SUM":
                body = lambda *s: jnp.sum(jnp.stack(s), axis=0)  # noqa: E731
            elif opname == "MPI_MAX":
                body = lambda *s: jnp.max(jnp.stack(s), axis=0)  # noqa: E731
            elif opname == "MPI_MIN":
                body = lambda *s: jnp.min(jnp.stack(s), axis=0)  # noqa: E731
            else:
                fold = _fold_fn(opname)
                body = lambda *s: fold(jnp.stack(s))  # noqa: E731
            out = lambda r, n: [r] * n  # noqa: E731
        elif kind == "reduce_scatter":
            if opname == "MPI_SUM":
                red = lambda stk: jnp.sum(stk, axis=0)  # noqa: E731
            elif opname == "MPI_MAX":
                red = lambda stk: jnp.max(stk, axis=0)  # noqa: E731
            elif opname == "MPI_MIN":
                red = lambda stk: jnp.min(stk, axis=0)  # noqa: E731
            else:
                red = _fold_fn(opname)

            def body(*s):
                r = red(jnp.stack(s))
                m = r.shape[0] // len(s)
                return tuple(
                    jax.lax.dynamic_slice_in_dim(r, i * m, m, axis=0)
                    for i in range(len(s)))

            out = _per_rank
        elif kind == "allgather":
            body = lambda *s: jnp.concatenate(s, axis=0)  # noqa: E731
            out = lambda r, n: [r] * n  # noqa: E731
        elif kind == "alltoall":
            # rank i's output is block i of every input, in rank order.
            # Written as that, XLA moves each byte once (one fusion an
            # input, no temporary); as stack + swapaxes it compiles to
            # update loops through a stacked copy, 9x the time at 32 MiB
            # a rank on a v5e
            def body(*s):
                n = len(s)
                m = s[0].shape[0] // n
                return tuple(
                    jnp.concatenate([x[i * m:(i + 1) * m] for x in s])
                    for i in range(n))

            out = _per_rank
        else:
            raise KeyError(kind)

        if typed is not None:
            if typed.folds_first(opname):
                # pack(fold) and fold(pack) select the same elements of
                # an elementwise fold: P - 1 packs fewer.  The fold is a
                # chain over the ranks in rank order, one pass and no
                # stacked copy of P whole buffers
                pair = jax_binary(PREDEFINED[opname])
                span = typed.span

                def body(*s):
                    # a deposit may be longer than the span, and each
                    # its own length
                    keys = [typed.keyed(a if a.shape[0] == span
                                        else jax.lax.slice(a, (0,), (span,)))
                            for a in s]
                    r = typed.stream(functools.reduce(pair, keys))
                    if kind == "reduce_scatter":
                        m = r.shape[0] // len(s)
                        r = tuple(jax.lax.slice(r, (i * m,), ((i + 1) * m,))
                                  for i in range(len(s)))
                    return typed.unkey(r)
            else:
                untyped = body

                def body(*s):
                    return typed.unkey(untyped(*[typed.pack(a) for a in s]))

            # a stable program name for the device trace
            body.__name__ = body.__qualname__ = "ompi_typed_" + kind

        return (jax.jit(body), out)

    def _ragged_program(self, dtype) -> tuple:
        """The ``(program, split)`` of an alltoallv's plan.  Which
        executable serves a meeting depends on every rank's send length
        and capacity, so it is resolved AT the meeting, from the
        deposits, and kept by those lengths alone: no count is in any
        key, and a new count matrix builds nothing."""
        progs: Dict[Tuple, Callable] = {}

        def program(*deposits):
            xs = [d.x for d in deposits]
            key = (*[x.shape for x in xs], *[d.capacity for d in deposits])
            jbody = progs.get(key)
            if jbody is None:
                P = len(xs)
                jbody = progs[key] = self._stacked(
                    "alltoallv", "", P, key[:P], dtype, key[P:])[0]
            longest = max(max(x.shape[0] for x in xs),
                          max(d.capacity for d in deposits))
            return jbody(_ragged.operand(
                deposits, longest * _ragged.row_elems(xs[0])), *xs)

        return program, _per_rank

    def _run(self, comm, kind, opname, x, extra=None, meta=None):
        """``meta`` (an alltoallv's counts and displacements, with
        ``extra`` its capacity) travels to the meeting beside the
        array: the deposit is the pair."""
        x = self._deposit(comm, x)
        # pre-resolved plan: the (kind, op, shape, dtype) -> closure
        # resolution is cached on the comm so the per-call cost is one
        # dict hit, not key construction + jit-cache lookup + closure
        # rebuild (VERDICT r2 #3)
        plans = comm.__dict__.get("_hbm_plans")
        if plans is None:
            plans = comm.__dict__["_hbm_plans"] = {}
        pkey = (kind, opname, x.shape, x.dtype, extra)
        fn = plans.get(pkey)
        if fn is None:
            if meta is None:
                jbody, out = self._stacked(kind, opname, comm.size,
                                           x.shape, x.dtype, extra)
            else:
                jbody, out = self._ragged_program(x.dtype)
            size = comm.size

            def fn(shards, _j=jbody, _o=out, _n=size):
                return _o(_j(*shards), _n)

            # the phase profiler's twin (_phase_fn), built once with
            # the plan: the untraced body above is what runs otherwise
            fn.traced = functools.partial(_stacked_exec, jbody, out, size)
            plans[pkey] = fn
        if meta is not None:
            # not sampled by the integrity plane (DESIGN.md section 25):
            # the equal-block conservation spec would sum what no count
            # names, on both sides
            return self._meet(comm, _ragged.Deposit(x, meta, extra), fn)
        ck = None
        if _ig.on:
            # a typed call's deposit is not the operand the kernel
            # reduces: its spec digests the packed stream (extra.operand)
            ck = _ig.spec(_CK_KINDS.get(kind, kind), opname, x) \
                if extra is None else _ig.spec_typed(
                    _CK_KINDS.get(kind, kind), opname, extra)
        return self._meet(comm, x, fn, ck)

    def _typed(self, comm, kind: str, x, op: Op, datatype, count):
        """A typed allreduce / reduce_scatter: the stacked kernel with
        the pack inside it (``extra`` carries the Typed into the plan
        key, the cache key and the builder), one rendezvous and one
        program a call."""
        t = _typed_on_device(self, comm, kind, x, op, datatype, count)
        if t is None:
            return self.fallback.typed(comm, kind, x, op, datatype, count)
        out = self._run(comm, kind, op.name, x.reshape(-1), t)
        _count_typed(t, t.folds_first(op.name))
        return out

    def _meet(self, comm, x, fn, ck=None):
        out = meet(comm, x, fn, self._abort_check(comm), ck)
        self.pvar_offload.add(1)
        return out

    def allreduce_arr(self, comm, x, op: Op, datatype=None, count=None):
        if datatype is not None:
            return self._typed(comm, "allreduce", x, op, datatype, count)
        if not self._eligible(comm, x) or (
                op.name not in _XLA_REDUCERS and op.name not in _GATHER_FOLD):
            return self.fallback.allreduce_arr(comm, x, op)
        pl = _pipeline()
        out = pl.maybe_device_coll(self, comm, "allreduce", x, op=op)
        if out is not pl.UNHANDLED:
            self.pvar_offload.add(1)
            return out
        x, was_scalar = self._norm(x)
        out = self._run(comm, "allreduce", op.name, x)
        return out.reshape(()) if was_scalar else out

    def reduce_scatter_block_arr(self, comm, x, op: Op, datatype=None,
                                 count=None):
        if datatype is not None:
            return self._typed(comm, "reduce_scatter", x, op, datatype,
                               count)
        # every stacked-foldable op, not just SUM: BASELINE config 5
        # is MPI_MAX — a SUM-only guard silently host-staged it at
        # ~100 ms/op through the d2h fallback (r5 finding)
        if not self._eligible(comm, x) or (
                op.name not in _XLA_REDUCERS
                and op.name not in _GATHER_FOLD) \
                or _ndim_of(x) == 0 \
                or x.shape[0] % comm.size != 0:
            return self.fallback.reduce_scatter_block_arr(comm, x, op)
        return self._run(comm, "reduce_scatter", op.name, x)

    def allgather_arr(self, comm, x):
        if not self._eligible(comm, x):
            return self.fallback.allgather_arr(comm, x)
        return self._run(comm, "allgather", "", x)

    def alltoall_arr(self, comm, x):
        if not self._eligible(comm, x) or _ndim_of(x) == 0 \
                or x.shape[0] % comm.size != 0:
            return self.fallback.alltoall_arr(comm, x)
        # never segmented, whatever the size: on one device there is no
        # wire for host packing to overlap with, and the whole-payload
        # kernel splits per rank inside the jit
        return self._run(comm, "alltoall", "", x)

    def _ragged_eligible(self, comm, x) -> bool:
        """THE rule of what the chip serves of ``alltoallv_arr``:
        every rank on this one chip and elements of 2, 4 or 8 bytes
        (8 as the carrier runtime/x64 states; the entry refused what
        jax would narrow), both of which MPI makes the same on every
        rank.  Nothing a rank alone knows is asked: counts and
        displacements (packed or with gaps, in any order, zero) are the
        program's operands, and what they must satisfy (inside the
        buffers, what i sends j is what j expects of i) is an error
        where it does not hold, not a fallback."""
        return x.dtype.itemsize in _ragged.ITEMSIZES \
            and self._eligible(comm, x)

    def alltoallv_arr(self, comm, x, meta, capacity: int):
        """One rendezvous and one ``ompi_alltoallv`` program a call;
        the counts of all ranks reach it as an int32 operand.  Rows
        travel on the flat view (coll/ragged.body)."""
        if not self._ragged_eligible(comm, x):
            return self.fallback.alltoallv_arr(comm, x, meta, capacity)
        out = self._run(comm, "alltoallv", "", x, capacity, meta)
        elems = meta[_ragged.SENT] * _ragged.row_elems(x)
        _ragged.pv_device_ops.add(1)
        _ragged.pv_elems.add(elems)
        _ragged.pv_bytes.add(elems * x.dtype.itemsize)
        return out

    def bcast_arr(self, comm, x, root: int):
        if not self._eligible(comm, x):
            return self.fallback.bcast_arr(comm, x, root)

        x = self._deposit(comm, x)

        def fn(shards):
            return [shards[root]] * comm.size

        ck = _ig.spec("bcast", "", x, root) if _ig.on else None
        return self._meet(comm, x, fn, ck)

    def reduce_arr(self, comm, x, op: Op, root: int):
        if not _reduce_as_allreduce_var.value:
            return self.fallback.reduce_arr(comm, x, op, root)
        out = self.allreduce_arr(comm, x, op)
        return out if comm.rank == root else None

    def ppermute_arr(self, comm, x, perm):
        if not self._eligible(comm, x):
            return self.fallback.ppermute_arr(comm, x, perm)
        x = self._deposit(comm, x)
        pmap = {int(a): int(b) for a, b in perm}

        def fn(shards):
            import jax.numpy as jnp
            outs = [None] * comm.size
            for src, dst in pmap.items():
                outs[dst] = shards[src]
            z = None
            for i in range(comm.size):
                if outs[i] is None:
                    if z is None:
                        z = jnp.zeros_like(shards[0])
                    outs[i] = z
            return outs

        return self._meet(comm, x, fn)


class HostArrModule(CollModule):
    """Always-eligible *_arr fallback: stage device arrays through the
    host and run the p2p collective stack (the 'coll/cuda staging
    wrapper' analog, ref: ompi/mca/coll/cuda)."""

    name = "arr_host"

    def __init__(self) -> None:
        self.p2p = TunedModule()
        from ompi_tpu.datatype import engine as dtmod
        self._dt = dtmod
        # the engagement check's other half: a device module that
        # finds a call ineligible lands here without a word, so this
        # is the only place the staging shows
        self.pvar_staged = registry.register_pvar(
            "coll", "arr_host", "staged_collectives",
            help="Number of *_arr collectives staged through host "
                 "memory and run on the p2p stack")

    def _np(self, x) -> np.ndarray:
        """Device-to-host staging: every *_arr entry point below reads
        its input through here exactly once, so this is the count."""
        self.pvar_staged.add(1)
        return np.asarray(x)

    def _back(self, comm, arr: np.ndarray):
        """The result's way back to the rank's device; an 8-byte
        element that would not arrive whole raises (runtime/x64)."""
        dev = comm.state.device
        if dev is not None:
            return _x64.put(arr, dev, "host-staged *_arr collective")
        return arr

    def _dtype_of(self, arr):
        return self._dt.from_numpy_dtype(arr.dtype)

    def typed(self, comm, kind: str, x, op: Op, datatype, count):
        """A typed allreduce / reduce_scatter no device provider
        serves: the host convertor packs ``count`` elements of
        ``datatype`` out of the staged buffer, and the untyped
        host-staged call reduces the packed stream in the type's one
        primitive element type."""
        from ompi_tpu import errhandler as _eh
        from ompi_tpu.datatype import convertor
        runs = datatype.runs_for_count(count)
        if not runs or any(r.dtype != runs[0].dtype for r in runs):
            raise _eh.MPIException(
                _eh.ERR_TYPE, f"a typed {kind}_arr reduces one element "
                f"type; {_dtdev.label(datatype)} mixes them "
                "(MPI_ERR_TYPE)")
        carrier = _dtype_of(x)
        packed = np.frombuffer(
            convertor.pack(datatype, count, np.ascontiguousarray(x)),
            dtype=runs[0].dtype)
        _pv_typed_host.add(1)
        # MPI_DOUBLE that came as uint64 bit patterns is reduced here in
        # binary64 and goes back as it came
        return self._back(
            comm, self._reduced(comm, kind, packed, op).view(carrier))

    def _reduced(self, comm, kind: str, x, op: Op) -> np.ndarray:
        """The host-staged allreduce / reduce_scatter of a flat stream,
        still on the host."""
        a = self._np(x).reshape(-1)
        if kind == "allreduce":
            r = np.empty_like(a)
            self.p2p.allreduce(comm, a, r, a.size, self._dtype_of(a), op)
        else:
            n = a.size // comm.size
            r = np.empty(n, dtype=a.dtype)
            self.p2p.reduce_scatter_block(comm, a, r, n, self._dtype_of(a),
                                          op)
        return r

    def allreduce_arr(self, comm, x, op: Op, datatype=None, count=None):
        if datatype is not None:
            return self.typed(comm, "allreduce", x, op, datatype, count)
        return self._back(comm, self._reduced(
            comm, "allreduce", x, op).reshape(_shape_of(x)))

    def bcast_arr(self, comm, x, root: int):
        a = self._np(x).reshape(-1).copy()
        self.p2p.bcast(comm, a, a.size, self._dtype_of(a), root)
        return self._back(comm, a.reshape(_shape_of(x)))

    def reduce_arr(self, comm, x, op: Op, root: int):
        a = self._np(x).reshape(-1)
        r = np.empty_like(a) if comm.rank == root else None
        self.p2p.reduce(comm, a, r, a.size, self._dtype_of(a), op, root)
        return self._back(comm, r.reshape(_shape_of(x))) \
            if comm.rank == root else None

    def allgather_arr(self, comm, x):
        shp = _shape_of(x)
        a = self._np(x).reshape(-1)
        r = np.empty(a.size * comm.size, dtype=a.dtype)
        self.p2p.allgather(comm, a, a.size, self._dtype_of(a), r, a.size,
                           self._dtype_of(a))
        out_shape = (comm.size,) if not shp else \
            (comm.size * shp[0],) + tuple(shp[1:])
        return self._back(comm, r.reshape(out_shape))

    def alltoall_arr(self, comm, x):
        shp = _shape_of(x)
        a = self._np(x).reshape(-1)
        n = a.size // comm.size
        r = np.empty_like(a)
        self.p2p.alltoall(comm, a, n, self._dtype_of(a), r, n,
                          self._dtype_of(a))
        return self._back(comm, r.reshape(shp))

    def alltoallv_arr(self, comm, x, meta, capacity: int):
        """Stage, run the p2p stack's alltoallv, put back.  The
        elements travel as unsigned integers of their width (data
        movement: no element type the host's datatype engine lacks),
        a row as that many of them; what no block covers comes back as
        zeros."""
        a = np.ascontiguousarray(self._np(x))
        dt, row = a.dtype, a.shape[1:]
        w = _ragged.row_elems(a)
        a = a.reshape(-1).view(np.dtype(f"u{dt.itemsize}"))
        r = np.zeros(capacity * w, a.dtype)
        sc, sd, rc, rd = ([c * w for c in v] for v in meta[:_ragged.SENT])
        mpi_dt = self._dtype_of(a)
        self.p2p.alltoallv(comm, a, sc, sd, mpi_dt, r, rc, rd, mpi_dt)
        return self._back(comm, r.view(dt).reshape((capacity, *row)))

    def reduce_scatter_block_arr(self, comm, x, op: Op, datatype=None,
                                 count=None):
        if datatype is not None:
            return self.typed(comm, "reduce_scatter", x, op, datatype,
                              count)
        shp = _shape_of(x)
        r = self._reduced(comm, "reduce_scatter", x, op)
        out_shape = (shp[0] // comm.size,) + tuple(shp[1:]) if shp \
            else r.shape
        return self._back(comm, r.reshape(out_shape))

    def ppermute_arr(self, comm, x, perm):
        from ompi_tpu.coll.base import _irecv_into, _isend
        a = np.ascontiguousarray(self._np(x))
        out = np.zeros_like(a)
        reqs = []
        for src, dst in perm:
            if int(dst) == comm.rank:
                reqs.append(_irecv_into(comm, out.reshape(-1), int(src),
                                        -115))
        for src, dst in perm:
            if int(src) == comm.rank:
                reqs.append(_isend(comm, a.reshape(-1), int(dst), -115))
        for q in reqs:
            q.wait()
        return self._back(comm, out)


class TpuComponent(CollComponent):
    name = "tpu"

    @property
    def priority(self):
        return _prio_tpu.value

    def comm_query(self, comm):
        if comm.mesh() is None:
            return None
        return (self.priority, TpuCollModule(_host_arr_fallback()))


class HbmComponent(CollComponent):
    name = "hbm"

    @property
    def priority(self):
        return _prio_hbm.value

    def comm_query(self, comm):
        devs = set()
        for g in comm.group:
            st = comm._peer_state(g)
            if st is None or st.device is None:
                return None
            devs.add(st.device.id)
        if len(devs) != 1 or comm.size == 1:
            return None
        return (self.priority, HbmCollModule(_host_arr_fallback()))


class ArrHostComponent(CollComponent):
    name = "arr_host"
    priority = 5

    def comm_query(self, comm):
        return (self.priority, HostArrModule())


_host_fallback_singleton: Optional[HostArrModule] = None


def _host_arr_fallback() -> HostArrModule:
    """Process-wide host-staged *_arr fallback shared by every device
    module (stateless beyond its decision hooks)."""
    global _host_fallback_singleton
    if _host_fallback_singleton is None:
        _host_fallback_singleton = HostArrModule()
    return _host_fallback_singleton


coll_framework.add_component(TpuComponent())
coll_framework.add_component(HbmComponent())
coll_framework.add_component(ArrHostComponent())
