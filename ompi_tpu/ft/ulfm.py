"""ULFM-style rank-failure mitigation: detect -> ERR_PROC_FAILED ->
revoke / shrink / agree.

Re-design of the ULFM prototype's run-through-stabilization surface
(ref: the MPI-4 FT proposal's MPIX_Comm_revoke/shrink/agree +
failure_ack, ompi/communicator/ft and the errmgr framework):

* **detect** — a permanently dead rank (ft_inject ``rank_kill``, a
  killed tpud child, tcp reconnect exhaustion, or OOB heartbeat
  silence) becomes a per-rank failure *record* carried job-wide:
  thread-rank worlds deliver it directly to every survivor's
  ``UlfmState``; process-rank jobs append ``ulfm:note:<n>`` records to
  the KV store, consumed by a per-rank watcher thread (the ft.py
  epoch-watcher pattern).  Each ingested failure bumps a monotonic
  local failure epoch.
* **report** — pending and future p2p/collective operations naming a
  failed peer complete with ``ERR_PROC_FAILED`` through
  ``errhandler.dispatch`` instead of hanging: ``pml/ob1`` grows a
  ``ulfm_sweep`` that drains parked requests, and the coll shim /
  device rendezvous abort-check consult ``check_comm`` on entry.
* **mitigate** — ``Comm.revoke()`` poisons a communicator job-wide
  (in-flight ops drain with ``ERR_REVOKED``); ``Comm.agree(flag)``
  runs a fault-tolerant agreement whose decision is published
  put-once, so every survivor returns the SAME flag no matter when
  the killer strikes; ``Comm.shrink()`` returns a survivor
  communicator, rebuilding the device mesh and dropping the
  CompiledLRU entries keyed on the old mesh shape.
* **observe** — detect/revoke/shrink/agree emit trace instants and
  ``ulfm_*`` pvars.

Agreement/shrink run over a *store*, not over p2p: the control plane
must stay usable on a communicator whose data plane is already
revoked or holed.  Thread-rank worlds use the world-shared dict;
process ranks use KV put-once (incr-claim) records.

Documented simplifications vs the reference: an ANY_SOURCE receive
with unacknowledged failures completes with
``ERR_PROC_FAILED_PENDING`` (the reference leaves it pending until
``MPIX_Comm_failure_ack``); rendezvous deposits of a dead generation
are simply abandoned (the shrunk comm gets a fresh rendezvous keyed
on its new cid).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, List, Optional, Set, Tuple

from ompi_tpu import errhandler as _eh
from ompi_tpu import obs as _obs
from ompi_tpu import trace as _trace
from ompi_tpu.mca.params import registry

_enable_var = registry.register(
    "mpi", "ft", "ulfm", True, bool,
    help="Attach the ULFM failure-mitigation layer at MPI_Init "
         "(detect dead ranks, raise MPI_ERR_PROC_FAILED, enable "
         "Comm.revoke/agree/shrink).  Off: permanent failures hang "
         "or abort, the pre-ULFM behavior")
_agree_timeout_var = registry.register(
    "mpi", "ft", "ulfm_agree_timeout", 60.0, float,
    help="Deadline (s) for the agree/shrink decision loop; expiry "
         "raises MPI_ERR_OTHER (survivors unreachable, not dead)")

_pv_failures = registry.register_pvar(
    "ulfm", "", "failures_detected",
    help="Rank failures ingested by this rank's ULFM state")
_pv_revokes = registry.register_pvar(
    "ulfm", "", "revokes",
    help="Communicator revocations ingested by this rank")
_pv_agreements = registry.register_pvar(
    "ulfm", "", "agreements",
    help="Fault-tolerant agreements completed by this rank")
_pv_shrink_us = registry.register_pvar(
    "ulfm", "", "shrink_rebuild_us", var_class="highwatermark",
    help="Slowest Comm.shrink on this rank: survivor agreement + "
         "communicator/mesh rebuild + compile-cache invalidation (us)")


class RankKilled(SystemExit):
    """Injected permanent rank death (ft_inject ``rank_kill``).

    A SystemExit subclass on purpose: it must behave exactly like the
    process dying — ``Communicator._guard`` and ``errhandler.dispatch``
    both re-raise SystemExit untouched, so no error handler can absorb
    the kill."""


# -- per-rank state ---------------------------------------------------------


class UlfmState:
    """One per rank: the failure/revocation view plus the plumbing
    that turns delivered records into drained requests.

    ``active`` flips True on the first delivered record and never
    flips back — the hot-path cost while healthy is one attribute
    fetch and one falsy check (the trace-layer zero-cost contract)."""

    def __init__(self, state) -> None:
        self.state = state
        self.lock = threading.Lock()
        self.failed: Set[int] = set()          # global ranks
        self.acked: Set[int] = set()           # failure_ack'd ranks
        # revoked communicators as (cid, group-tuple): disjoint comms
        # of different processes may share a cid, the group keeps a
        # revoke from poisoning an unrelated communicator
        self.revoked: Set[Tuple[int, Tuple[int, ...]]] = set()
        self.epoch = 0                         # monotonic failure epoch
        self.active = False
        self._dirty = False
        self._seen: Set[tuple] = set()
        self._pending: List[tuple] = []
        # test seam: called at named agreement phases so kill-at-every-
        # phase tests are deterministic instead of timer-raced
        self._agree_test_hook = None

    # -- record delivery (any thread) -----------------------------------
    def deliver(self, rec: tuple) -> None:
        with self.lock:
            if rec in self._seen:
                return
            self._seen.add(rec)
            self._pending.append(rec)
            self._dirty = True
            self.active = True
        self.state.progress.wakeup()

    # -- ingestion (the rank's own thread, via poll) --------------------
    def poll(self) -> int:
        if not self._dirty:
            return 0
        with self.lock:
            pending, self._pending = self._pending, []
            self._dirty = False
        n = 0
        for rec in pending:
            n += self._ingest(rec)
        return n

    def _ingest(self, rec: tuple) -> int:
        if rec[0] == "fail":
            grank = int(rec[1])
            if grank == self.state.rank:
                # a respawned replacement replays the KV note stream
                # and meets its predecessor's death note: its own rank
                # is alive by construction
                return 0
            if (len(rec) > 2 and int(rec[2]) <=
                    getattr(self.state, "respawn_epoch", 0)):
                # epoch-tagged note from a failure the respawn
                # protocol already recovered: ingesting it would
                # re-mark a revived rank dead forever
                return 0
            if grank in self.failed:
                return 0
            self.failed.add(grank)
            self.epoch += 1
            _pv_failures.add(1)
            rte = self.state.rte
            if getattr(rte, "kv", None) is not None:
                # EnvRTE/HybridRTE fences shrink their KV quorum by
                # this set (dead ranks never arrive at a fence)
                rte.ulfm_failed = set(self.failed)
            _trace.instant_state(self.state, "ulfm_detect", "ft",
                                 failed=grank, epoch=self.epoch)
            _obs.record_event(_obs.EV_ULFM_DETECT, grank, self.epoch,
                              rank=self.state.rank)
        elif rec[0] == "revoke":
            key = (int(rec[1]), tuple(rec[2]))
            if key in self.revoked:
                return 0
            self.revoked.add(key)
            _pv_revokes.add(1)
            _trace.instant_state(self.state, "ulfm_revoke", "ft",
                                 cid=key[0])
            _obs.record_event(_obs.EV_ULFM_REVOKE, key[0],
                              rank=self.state.rank)
        else:
            return 0
        self._sweep_pml()
        return 1

    def unfail(self, grank: int) -> None:
        """Respawn rejoin (ft/respawn): ``grank`` has been replaced in
        place — stop treating it as dead.  The delivery dedup for its
        old failure records is cleared too, so a LATER kill of the same
        world rank is detected again (``active`` stays True: the
        entry-check cost is already paid and a re-kill must drain
        instantly)."""
        with self.lock:
            self.failed.discard(grank)
            self.acked.discard(grank)
            self._seen = {
                r for r in self._seen
                if not (r[0] == "fail" and int(r[1]) == grank)}
            self._pending = [
                r for r in self._pending
                if not (r[0] == "fail" and int(r[1]) == grank)]
        rte = self.state.rte
        if getattr(rte, "kv", None) is not None:
            rte.ulfm_failed = set(self.failed)

    def _sweep_pml(self) -> None:
        # reaches PmlOb1 through any monitoring/vprotocol wrapper
        # (both delegate unknown attributes to the wrapped pml)
        sweep = getattr(self.state.pml, "ulfm_sweep", None)
        if sweep is not None:
            sweep(self.failed, self.revoked)

    def _progress_cb(self) -> int:
        return self.poll()

    # -- entry checks (raise, callers route through dispatch) -----------
    def check_comm(self, comm) -> None:
        """Collective-entry check: a revoked comm raises ERR_REVOKED,
        a comm with a failed member raises ERR_PROC_FAILED."""
        if (comm.cid, tuple(comm.group)) in self.revoked:
            raise _eh.MPIException(
                _eh.ERR_REVOKED,
                f"MPI_ERR_REVOKED: communicator {comm.name or comm.cid} "
                f"was revoked")
        dead = self.failed.intersection(comm.group)
        if dead:
            raise _eh.MPIException(
                _eh.ERR_PROC_FAILED,
                f"MPI_ERR_PROC_FAILED: rank(s) "
                f"{sorted(dead)} of {comm.name or comm.cid} failed")

    def check_peer(self, comm, peer: int) -> None:
        """P2P-entry check for an op naming comm-rank ``peer``."""
        if (comm.cid, tuple(comm.group)) in self.revoked:
            raise _eh.MPIException(
                _eh.ERR_REVOKED,
                f"MPI_ERR_REVOKED: communicator {comm.name or comm.cid} "
                f"was revoked")
        if peer >= 0:
            if comm.group[peer] in self.failed:
                raise _eh.MPIException(
                    _eh.ERR_PROC_FAILED,
                    f"MPI_ERR_PROC_FAILED: peer rank {peer} failed")
        else:  # ANY_SOURCE with unacknowledged failures
            pending = (self.failed.intersection(comm.group)
                       - self.acked)
            if pending:
                raise _eh.MPIException(
                    _eh.ERR_PROC_FAILED_PENDING,
                    f"MPI_ERR_PROC_FAILED_PENDING: unacknowledged "
                    f"failed rank(s) {sorted(pending)}")


def attach(state) -> Optional[UlfmState]:
    """Install a UlfmState on ``state`` (before pml selection, so the
    pml can cache the reference) and hook the progress engine."""
    if not _enable_var.value:
        state.ulfm = None
        return None
    u = UlfmState(state)
    state.ulfm = u
    state.progress.register(u._progress_cb)
    return u


# -- failure/revoke publication ---------------------------------------------


def publish_world_failure(world, grank: int) -> None:
    """Thread-rank delivery: mark the rank failed on the world, break
    the fence barrier (survivors fall through to the ULFM fence), and
    deliver the record to every live rank's UlfmState."""
    publish_world_failures(world, (grank,))


def publish_world_failures(world, granks) -> None:
    """Atomic failure-DOMAIN delivery: mark EVERY rank in ``granks``
    failed before any waiter wakes, so a whole-host death surfaces as
    one consistent failure set — survivors of a host kill observe all
    N resident ranks dead at once, never N racing single-rank
    detections with fences recounting quorum between them."""
    fresh = []
    for grank in granks:
        if grank not in world.ulfm_failed:
            fresh.append(int(grank))
        world.ulfm_failed.add(grank)
    if fresh:
        try:
            world.barrier.abort()
        except Exception:  # noqa: BLE001 — barrier may be mid-reset
            pass
    cv = getattr(world, "_uf_cv", None)
    if cv is not None:
        with cv:           # release anyone parked in a ULFM fence
            cv.notify_all()
    for st in list(world.states):  # indexed by rank; remote = None
        u = getattr(st, "ulfm", None)
        if u is not None:
            for grank in granks:
                u.deliver(("fail", int(grank)))


def publish_failure(state, grank: int) -> None:
    """Propagate a suspected-permanent rank failure job-wide: direct
    delivery in thread-rank worlds, a ``ulfm:note:<n>`` KV record for
    process-rank jobs (each rank's watcher thread consumes it)."""
    world = getattr(state.rte, "world", None)
    if world is not None and hasattr(world, "ulfm_failed"):
        publish_world_failure(world, grank)
    kv = getattr(state.rte, "kv", None)
    if kv is not None:
        try:
            n = kv.incr("ulfm:nseq")
            kv.put(f"ulfm:note:{n}", ["fail", int(grank)])
        except (ConnectionError, OSError, RuntimeError):
            pass  # control plane gone: local delivery still drains us
    u = getattr(state, "ulfm", None)
    if u is not None:
        u.deliver(("fail", int(grank)))


def publish_revoke(comm) -> None:
    """MPIX_Comm_revoke: poison ``comm`` job-wide.  Not collective —
    any member may revoke; the notice reaches every rank through the
    same channels failure records ride."""
    state = comm.state
    rec = ("revoke", int(comm.cid), tuple(comm.group))
    world = getattr(state.rte, "world", None)
    if world is not None and hasattr(world, "states"):
        for st in list(world.states):
            u = getattr(st, "ulfm", None)
            if u is not None:
                u.deliver(rec)
    kv = getattr(state.rte, "kv", None)
    if kv is not None:
        try:
            n = kv.incr("ulfm:nseq")
            kv.put(f"ulfm:note:{n}",
                   ["revoke", int(comm.cid), list(comm.group)])
        except (ConnectionError, OSError, RuntimeError):
            pass
    u = getattr(state, "ulfm", None)
    if u is not None:
        u.deliver(rec)
        u.poll()  # the revoker's own parked ops drain immediately


# -- KV watcher (process ranks; the ft.start_watcher pattern) ---------------


def start_watcher(state) -> None:
    """Consume ``ulfm:note:<n>`` records from the KV store on a daemon
    thread with its own KVClient (the shared client is single-threaded
    by contract)."""
    addr = os.environ.get("TPUMPI_KV_ADDR")
    if not addr or getattr(state, "ulfm", None) is None:
        return

    def run() -> None:
        from ompi_tpu.runtime.kvstore import KVClient
        try:
            kv = KVClient(addr)
        except (OSError, RuntimeError):
            return
        n = 0
        while True:
            try:
                rec = kv.get(f"ulfm:note:{n}", timeout=3600.0)
            except (RuntimeError, OSError, TimeoutError):
                if getattr(state, "finalized", False):
                    return
                continue
            n += 1
            u = getattr(state, "ulfm", None)
            if u is None or getattr(state, "finalized", False):
                return
            if rec and rec[0] == "fail":
                # respawn-mode notes carry the recovery epoch the
                # failure opens; _ingest drops stale epochs so note
                # replay after a rejoin cannot re-kill a revived rank
                if len(rec) > 2:
                    u.deliver(("fail", int(rec[1]), int(rec[2])))
                else:
                    u.deliver(("fail", int(rec[1])))
            elif rec and rec[0] == "revoke":
                u.deliver(("revoke", int(rec[1]), tuple(rec[2])))

    threading.Thread(target=run, daemon=True,
                     name=f"ulfm-watcher-{state.rank}").start()


# -- injected kills ---------------------------------------------------------


def arm_rank_kill(state, after_s: float) -> None:
    """ft_inject ``rank_kill``: after ``after_s`` the victim's next
    progress sweep raises RankKilled — out of whatever wait it is
    parked in (the WaitSync spin runs progress, so armed interrupts
    escape blocking calls)."""

    def fire() -> None:
        if getattr(state, "finalized", False):
            return
        _trace.instant_state(state, "ft_inject", "ft",
                             cls="rank_kill", rank=state.rank)
        _obs.record_event(_obs.EV_FT_INJECT, _obs.intern("rank_kill"),
                          _obs.intern("rank"), rank=state.rank)
        # this incarnation can never finalize: let process-wide
        # last-rank accounting (coll.device) stop waiting for it
        state.ulfm_dead = True
        state.progress.interrupt = RankKilled(
            f"ft_inject rank_kill: rank {state.rank}")
        state.progress.wakeup()

    t = threading.Timer(max(0.0, after_s), fire)
    t.daemon = True
    t.start()


def kill_now(state):
    """Deterministic in-line kill for tests/benchmarks: the calling
    rank dies HERE (no timer race)."""
    state.ulfm_dead = True
    raise RankKilled(f"rank {state.rank} killed (ulfm.kill_now)")


# -- the agreement/shrink store ---------------------------------------------


class _InprocStore:
    """Thread-rank backend: the world-shared dict under its lock."""

    def __init__(self, state) -> None:
        self.world = state.rte.world

    def put_once(self, key: tuple, value: Any) -> bool:
        with self.world.shared_lock:
            if key in self.world.shared:
                return False
            self.world.shared[key] = value
            return True

    def try_get(self, key: tuple) -> Any:
        with self.world.shared_lock:
            return self.world.shared.get(key)

    def next_cid(self) -> int:
        # shrink cids live far above next_cid_local's counting range
        with self.world.shared_lock:
            n = self.world.shared.get(("ulfm", "cid"), 4096)
            self.world.shared[("ulfm", "cid")] = n + 1
            return n


class _KvStore:
    """Process-rank backend: KV put-once via incr-claim (the first
    caller's pre-increment is 0 — it owns the write)."""

    def __init__(self, state) -> None:
        self.kv = state.rte.kv

    @staticmethod
    def _k(key: tuple) -> str:
        return "ulfm:" + ":".join(str(p) for p in key)

    def put_once(self, key: tuple, value: Any) -> bool:
        return self.kv.put_once(self._k(key), value)

    def try_get(self, key: tuple) -> Any:
        try:
            return self.kv.get(self._k(key), timeout=0.05)
        except (TimeoutError, RuntimeError):
            return None

    def next_cid(self) -> int:
        return 4096 + self.kv.incr("ulfm:cid")


def _store(state):
    if getattr(state.rte, "kv", None) is not None:
        return _KvStore(state)
    return _InprocStore(state)


def _require(comm) -> UlfmState:
    u = getattr(comm.state, "ulfm", None)
    if u is None:
        raise RuntimeError(
            "ULFM is disabled (--mca mpi_ft_ulfm 0): "
            "revoke/agree/shrink unavailable")
    return u


def _tick(comm) -> None:
    """One decision-loop beat: run progress (armed interrupts — e.g. a
    rank_kill landing mid-agreement — fire here) and yield."""
    comm.state.progress.progress()
    time.sleep(0.0005)


# -- MPIX_Comm_agree --------------------------------------------------------


def agree(comm, flag) -> bool:
    """Fault-tolerant agreement: returns the AND of the contributed
    flags, identical on every survivor regardless of when members die.

    Two-phase over the store: (1) every member publishes its
    contribution put-once; (2) the lowest-ranked *live* member gathers
    the contributions of everyone not known-failed and publishes the
    decision put-once.  A leader dying mid-gather just promotes the
    next survivor; because the decision is put-once, a late write from
    a zombie leader cannot split the outcome."""
    u = _require(comm)
    store = _store(comm.state)
    seq = comm.__dict__.get("_ulfm_agree_seq", 0)
    comm.__dict__["_ulfm_agree_seq"] = seq + 1
    base = ("agree", comm.cid, tuple(comm.group), seq)
    hook = u._agree_test_hook
    u.poll()
    if hook is not None:
        hook("pre_contrib")
    store.put_once(base + ("c", comm.rank), bool(flag))
    if hook is not None:
        hook("post_contrib")
    deadline = time.monotonic() + max(1.0, _agree_timeout_var.value)
    while True:
        d = store.try_get(base + ("d",))
        if d is not None:
            if hook is not None:
                hook("post_decision")
            _pv_agreements.add(1)
            _trace.instant_state(comm.state, "ulfm_agree", "ft",
                                 cid=comm.cid, seq=seq,
                                 flag=bool(d["flag"]))
            _obs.record_event(_obs.EV_ULFM_AGREE, comm.cid, seq,
                              int(bool(d["flag"])),
                              rank=comm.state.rank)
            return bool(d["flag"])
        u.poll()
        live = [r for r in range(comm.size)
                if comm.group[r] not in u.failed]
        if live and live[0] == comm.rank:
            vals: List[bool] = []
            complete = True
            for r in range(comm.size):
                v = store.try_get(base + ("c", r))
                if v is not None:
                    vals.append(bool(v))
                elif comm.group[r] not in u.failed:
                    complete = False
                    break
            if complete:
                if hook is not None:
                    hook("pre_decision")
                store.put_once(base + ("d",), {"flag": all(vals)})
                continue
        if time.monotonic() > deadline:
            raise _eh.MPIException(
                _eh.ERR_OTHER,
                f"ulfm agree timed out on {comm.name or comm.cid}")
        _tick(comm)


# -- MPIX_Comm_shrink -------------------------------------------------------

# per-comm cached plans/verdicts that key on the OLD group/mesh (the
# ft.recover invalidation list + the device/fusion fast-path caches)
_COMM_CACHE_KEYS = (
    "_seg_eligible", "_coll_seg", "_seg_ar_plan", "_hbm_one_device",
    "_hbm_plans", "_device_rv", "_device_abort_check",
    "_oversub_verdict", "_mesh_none", "_mesh", "_fusion_engine",
    "_dev_seq",
    # large-message tier (coll/pipeline + topo): routing thresholds,
    # hierarchy plans and the cart device mesh all key on the old
    # group/mesh — segment state must not leak across shrink/respawn
    # epochs
    "_pipeline_pick", "_hier_eligible", "_hier_plan",
    "_cart_device_mesh",
    # compiled collective plans (DESIGN.md §12): Plan objects hold the
    # old mesh, its sharding and a jitted executable bound to the old
    # device set — stale-mesh executables must never survive an epoch
    "_coll_plans",
    # osc framework: the per-window component verdict keys on the old
    # mesh (device eligibility), so a shrunk comm must re-decide
    "_osc_pick",
)

# the subset safe to purge while a comm stays LIVE: pure routing
# thresholds whose recompute is rank-local (coll/autotune re-resolves
# them online when the calibrate profile moves).  _hier_plan and the
# rendezvous caches are NOT here — their rebuild is collective
# (subcomm construction) and may only happen at epoch boundaries.
# _coll_plans qualifies: a Plan rebuild is rank-local (the jitted
# executable comes out of the process-wide CompiledLRU) and keys on
# calibrated segment size, which is exactly what an autotune fold moves
SELECTION_CACHE_KEYS = ("_pipeline_pick", "_osc_pick", "_coll_plans")


def purge_comm_caches(comm, keys=_COMM_CACHE_KEYS) -> None:
    """Drop per-comm cached plans/verdicts.  The full key list is the
    shrink/respawn epoch boundary; callers on a live comm must pass
    SELECTION_CACHE_KEYS (see above)."""
    for k in keys:
        comm.__dict__.pop(k, None)


def _invalidate(comm) -> None:
    """Drop everything keyed on the dying comm's group/mesh: cached
    per-comm plans, the device rendezvous, and the CompiledLRU entries
    compiled against the old mesh shape (a shrunk world re-keys on the
    survivor device list — stale executables would never be hit again
    but would squat in the bounded cache)."""
    mesh = comm.__dict__.get("_mesh")
    if mesh is not None:
        try:
            from ompi_tpu.coll import device
            dev_key = tuple(d.id for d in mesh.devices.reshape(-1))
            device.compile_cache.drop_mesh(dev_key)
        except Exception:  # noqa: BLE001 — cache hygiene, never fatal
            pass
    purge_comm_caches(comm)
    world = getattr(comm.state.rte, "world", None)
    if world is not None and hasattr(world, "shared"):
        group = tuple(comm.group)
        with world.shared_lock:
            world.shared.pop(("coll_rv", comm.cid, group), None)
            # device-osc shard tables of windows on the dying comm:
            # the shards belong to the old mesh/group and must not be
            # resurrected by a cid reuse after recovery
            dead = [k for k in world.shared
                    if isinstance(k, tuple) and k and
                    k[0] == "osc_devwin" and k[1] == comm.cid and
                    k[2] == group]
            for k in dead:
                world.shared.pop(k, None)


# -- store hygiene ----------------------------------------------------------

# first elements of world.shared tuple keys owned by the ULFM/respawn
# control plane (the KV spellings all live under the "ulfm:" prefix)
_STORE_KEY_HEADS = ("agree", "shrink", "respawn", "ulfm")


def purge_tickets(state) -> None:
    """Epoch-rollover hygiene: drop consumed agreement/shrink tickets
    (contributions, decisions, and their put-once claim counters).
    Failure notes are deliberately kept — a late-starting watcher
    replays the note stream from n=0 and relies on the epoch filter,
    not on deletion, to skip recovered failures."""
    world = getattr(state.rte, "world", None)
    if world is not None and hasattr(world, "shared"):
        with world.shared_lock:
            for k in [k for k in world.shared
                      if isinstance(k, tuple) and k
                      and k[0] in ("agree", "shrink")]:
                del world.shared[k]
    kv = getattr(state.rte, "kv", None)
    if kv is not None:
        try:
            kv.purge("ulfm:agree:")
            kv.purge("ulfm:shrink:")
        except (ConnectionError, OSError, RuntimeError):
            pass


def purge_store(state) -> None:
    """Finalize hygiene (stale-note satellite): remove every ULFM
    record this job wrote — failure notes, the note sequence counter,
    agreement/shrink/respawn tickets and their claim counters — so a
    looped world (pytest re-entry, warm launcher pools) starts with a
    clean failure plane instead of replaying last run's deaths."""
    world = getattr(state.rte, "world", None)
    if world is not None and hasattr(world, "shared"):
        with world.shared_lock:
            for k in [k for k in world.shared
                      if isinstance(k, tuple) and k
                      and k[0] in _STORE_KEY_HEADS]:
                del world.shared[k]
    kv = getattr(state.rte, "kv", None)
    if kv is not None:
        try:
            kv.purge("ulfm:")
        except (ConnectionError, OSError, RuntimeError):
            pass


def shrink(comm, name: str = ""):
    """MPIX_Comm_shrink: agree on the failed set, build the survivor
    communicator (fresh cid from the store so every member lands on
    the same one), and invalidate what the old mesh shape cached."""
    u = _require(comm)
    store = _store(comm.state)
    t0 = time.perf_counter()
    u.poll()
    seq = comm.__dict__.get("_ulfm_shrink_seq", 0)
    comm.__dict__["_ulfm_shrink_seq"] = seq + 1
    base = ("shrink", comm.cid, tuple(comm.group), seq)
    store.put_once(base + ("c", comm.rank),
                   sorted(u.failed.intersection(comm.group)))
    deadline = time.monotonic() + max(1.0, _agree_timeout_var.value)
    while True:
        d = store.try_get(base + ("d",))
        if d is not None:
            break
        u.poll()
        live = [r for r in range(comm.size)
                if comm.group[r] not in u.failed]
        if live and live[0] == comm.rank:
            union: Set[int] = set(u.failed.intersection(comm.group))
            complete = True
            for r in range(comm.size):
                v = store.try_get(base + ("c", r))
                if v is not None:
                    union.update(int(x) for x in v)
                elif comm.group[r] not in u.failed:
                    complete = False
                    break
            if complete:
                store.put_once(base + ("d",), {
                    "failed": sorted(union), "cid": store.next_cid()})
                continue
        if time.monotonic() > deadline:
            raise _eh.MPIException(
                _eh.ERR_OTHER,
                f"ulfm shrink timed out on {comm.name or comm.cid}")
        _tick(comm)
    decided = set(int(x) for x in d["failed"])
    survivors = [g for g in comm.group if g not in decided]
    # adopt the decided view: a member that learned of a failure only
    # through the decision must treat that rank as failed from now on
    for g in decided:
        u.deliver(("fail", int(g)))
    u.poll()
    from ompi_tpu.comm.communicator import Communicator, Group
    new = Communicator(comm.state, int(d["cid"]), Group(survivors),
                       name=name or f"{comm.name or 'comm'}-shrink")
    new.errhandler = comm.errhandler
    _invalidate(comm)
    dur_us = int((time.perf_counter() - t0) * 1e6)
    _pv_shrink_us.update_max(dur_us)
    _trace.instant_state(comm.state, "ulfm_shrink", "ft",
                         cid=comm.cid, new_cid=new.cid,
                         survivors=len(survivors), us=dur_us)
    _obs.record_event(_obs.EV_ULFM_SHRINK, comm.cid, new.cid,
                      len(survivors), dur_us, rank=comm.state.rank)
    return new
