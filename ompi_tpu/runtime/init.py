"""MPI bring-up / teardown for one rank.

Mirrors the ompi_mpi_init sequence (ref: ompi/runtime/ompi_mpi_init.c:
rte init → frameworks open → pml select → modex fence → add_procs →
comm_world/self → coll select → final fence) and ompi_mpi_finalize.c's
reverse teardown.
"""

from __future__ import annotations

from typing import Optional

from ompi_tpu.btl import base as btl_base
from ompi_tpu.btl import inproc as _btl_inproc  # noqa: F401 (registers)
from ompi_tpu.btl import self_btl as _btl_self  # noqa: F401
from ompi_tpu.btl import shm as _btl_shm  # noqa: F401
from ompi_tpu.btl import tcp as _btl_tcp  # noqa: F401
from ompi_tpu.comm.communicator import (SESSION_CID_STRIDE, Communicator,
                                        Group)
from ompi_tpu.pml import ob1 as _pml_ob1
from ompi_tpu.pml import monitoring as _pml_monitoring
from .state import ProcState, clear_current, set_current


def mpi_init(state: ProcState, device=None) -> ProcState:
    import os

    set_current(state)
    state.device = device
    # span tracer attach (ompi_tpu/trace) BEFORE pml/coll selection so
    # every layer constructed below can cache state.tracer (None when
    # trace_enable is off — the whole hot-path cost)
    from ompi_tpu import trace as _trace
    _trace.attach(state)
    # online autotune attach rides DIRECTLY on the trace attach (it
    # force-attaches a tracer when trace_enable is off) so the pml/
    # coll layers below still cache a non-None state.tracer
    from ompi_tpu.coll import autotune as _autotune
    _autotune.attach(state)
    # debugger attach support (MPIR analog, ref: ompi/debuggers):
    # SIGUSR1 dumps every thread's stack to stderr so
    # ompi_tpu.tools.attach --stacks can show where a hung job is
    # stuck; binding (rtc/hwloc analog) applies TPUMPI_BIND
    try:
        import faulthandler
        import signal as _signal
        faulthandler.register(_signal.SIGUSR1, all_threads=True,
                              chain=True)
        # crash backtraces (SIGSEGV/SIGFPE/SIGABRT -> all-thread
        # dumps): the opal/mca/backtrace analog for native-code
        # faults in jax/XLA/our C++ ring
        faulthandler.enable(all_threads=True)
    except (ImportError, AttributeError, ValueError, OSError):
        pass  # non-main thread or unsupported platform
    from ompi_tpu.runtime import pstat as _pstat
    _pstat.register_pvars(state.rank)
    # telemetry plane: percentile gauges + flight recorder (idempotent
    # across looped worlds), and the scrape tick when enabled
    from ompi_tpu import obs as _obs
    _obs.attach(state)
    from ompi_tpu.runtime import topology as _topo
    _world = getattr(state.rte, "world", None)
    if _world is not None:
        # thread-rank: sched_setaffinity(0) binds the calling THREAD.
        # The binding index is the rank's position within its NODE
        # (TPUMPI_NODE_RANK_BASE), not within its shell — two shells
        # on one node must not overlap their core assignments
        node_base = int(os.environ.get(
            "TPUMPI_NODE_RANK_BASE",
            str(getattr(_world, "rank_base", 0))))
        _local_rank = state.rank - node_base
    else:
        # process-rank: the launcher exports the rank's index WITHIN
        # its node (never the global rank — that would misbind every
        # node after the first)
        _local_rank = int(os.environ.get("TPUMPI_LOCAL_RANK", "0"))
    try:
        _topo.apply_binding(_local_rank)
    except (ValueError, OSError):
        pass
    # refine the oversubscription hint with the true local-rank count:
    # thread-rank worlds (inproc/hybrid) know it exactly; process-ranks
    # read the launcher's TPUMPI_LOCAL_SIZE (ref: the reference
    # auto-enables yield_when_idle when ranks exceed cores)
    world = getattr(state.rte, "world", None)
    nlocal = getattr(world, "nlocal", None) or (
        world.size if world is not None
        else int(os.environ.get("TPUMPI_LOCAL_SIZE", "1")))
    state.progress.oversubscribed = nlocal > (os.cpu_count() or 1)
    # ULFM failure-mitigation state BEFORE pml selection so the pml
    # can cache state.ulfm (None when mpi_ft_ulfm is off — the same
    # one-is-None-check contract as the tracer)
    from ompi_tpu.ft import ulfm as _ulfm
    _ulfm.attach(state)
    # 1. select the single pml engine (ref: ompi_mpi_init.c:640),
    # optionally interposed by pml/monitoring
    comp, pml_cls = _pml_ob1.pml_framework.select_one(state)
    from ompi_tpu.pml import vprotocol as _pml_vprotocol
    state.pml = _pml_vprotocol.maybe_wrap(
        _pml_monitoring.maybe_wrap(pml_cls(state), state), state)
    # live recovery: a restarted rank joins at a bumped epoch
    # (runtime/ft.py); post-recovery cross-process traffic rides tcp
    # only — the shm rings of a pre-failure epoch cannot be made
    # stale-byte-safe, so shm stays out of an epoch>0 world
    state.ft_epoch = int(os.environ.get("TPUMPI_FT_EPOCH", "0"))
    # self-healing respawn (ft/respawn): a replacement PROCESS carries
    # TPUMPI_RESPAWN=1 and the epoch its failure opened — it must run
    # the rejoin protocol before doing real work, and it must never
    # re-arm the fault that killed its predecessor.  Thread-world
    # replacements get these attrs set by the driver before mpi_init
    # (threads share the environment, so the env flag is a
    # process-rank signal only).
    if (not state.respawn_joining and os.environ.get("TPUMPI_RESPAWN")
            and getattr(state.rte, "kv", None) is not None):
        state.respawn_joining = True
        state.respawn_epoch = max(0, state.ft_epoch - 1)
    # 2. btl modules + endpoint wiring (modex happens inside init).
    # At a recovery epoch the shm COMPONENT is skipped outright — a
    # constructed-then-dropped module would have created rings,
    # registered callbacks and forced poll_mode for a transport the
    # epoch never uses
    modules = []
    for c in btl_base.btl_framework.components():
        if state.ft_epoch and getattr(c, "name", "") == "shm":
            continue
        modules += c.init_modules(state)
    state.btls = modules
    # publish our state for inproc peers + our device assignment for
    # the job (VERDICT r1 #2: device ids ride the modex so launchers /
    # future cross-host device planes can see the chip map), then
    # fence (modex sync #1, ref: ompi_mpi_init.c:654-661)
    world = getattr(state.rte, "world", None)
    if world is not None:
        world.states[state.rank] = state
    if device is not None:
        state.rte.modex_put("device_id", int(device.id))
    # node + cores ride the modex so collective algorithm selection
    # can be COMM-CONSISTENT about oversubscription (every member of
    # a comm must pick the same algorithm; local env hints diverge —
    # e.g. a dpm-spawned singleton vs its 8-rank parent)
    state.rte.modex_put("node_id", getattr(state.rte, "node_id", 0))
    state.rte.modex_put("cores", os.cpu_count() or 1)
    if state.ft_epoch and os.environ.get("FT_DEBUG"):
        import sys as _sys
        print(f"[ft-init r{state.rank}] entering fence 1 "
              f"(epoch {state.ft_epoch})", file=_sys.stderr, flush=True)
    state.rte.fence()
    if state.ft_epoch and os.environ.get("FT_DEBUG"):
        import sys as _sys
        print(f"[ft-init r{state.rank}] fence 1 passed",
              file=_sys.stderr, flush=True)
    endpoints = btl_base.wire_endpoints(state, modules)
    state.pml.add_procs(endpoints)
    # 3. predefined communicators: world cid 0, self cid 1.  The world
    # group is this JOB's rank block — a spawned job's world starts at
    # its universe base (dpm, ref: ompi/dpm)
    wbase = getattr(state.rte, "world_base", 0)
    wsize = getattr(state.rte, "world_size", state.size)
    # DVM-resident sessions carry a session cid band: the predefined
    # comms live at the band base, so even cid 0/1 are session-unique
    # across the pool (next_cid floors derived comms into the same
    # band; SESSION_CID_STRIDE keeps the session dimension disjoint
    # from respawn-epoch banding).  Ordinary jobs have band 0 — world
    # cid 0, self cid 1.
    band = state.cid_band * SESSION_CID_STRIDE
    state.comm_world = Communicator(state, band,
                                    Group(range(wbase, wbase + wsize)),
                                    name="MPI_COMM_WORLD")
    from ompi_tpu import attrs as _attrs
    _attrs.init_world_attrs(state.comm_world)
    state.comm_self = Communicator(state, band + 1, Group([state.rank]),
                                   name="MPI_COMM_SELF")
    # wire the predefined communicators' error handler EXPLICITLY
    # (mpi_errhandler_world_default; derived comms keep inheriting
    # from their parent) — the dispatch fallback for handler-less
    # objects resolves through comm_world, so this is the one place
    # the job default is installed
    from ompi_tpu import errhandler as _eh
    state.comm_world.errhandler = _eh.world_default()
    state.comm_self.errhandler = state.comm_world.errhandler
    # 4. collective module stacks are installed by Communicator
    # construction itself (coll_base_comm_select analog)
    # 5. final fence before returning (sync #2, ref: :833-838)
    state.rte.fence()
    state.initialized = True
    if os.environ.get("TPUMPI_FT_RECOVER"):
        # the launcher runs the recover errmgr policy: watch for
        # recovery epochs so a daemon loss interrupts blocking waits
        # instead of hanging them (runtime/ft.py)
        from ompi_tpu.runtime import ft as _ft
        _ft.start_watcher(state)
    if state.ulfm is not None:
        # ft_inject rank_kill: this rank is the victim — arm the
        # one-shot death timer (fires as a RankKilled interrupt out
        # of the next progress sweep)
        from ompi_tpu import ft_inject as _fi
        if ("rank_kill" in _fi.rank_faults(state.rank, state.size)
                and not state.respawn_joining):
            # a respawned replacement never re-arms its predecessor's
            # death — that would be an infinite kill/respawn loop
            _ulfm.arm_rank_kill(state, _fi.after_s())
        if os.environ.get("TPUMPI_ULFM"):
            # launcher runs the ulfm errmgr policy: consume job-wide
            # ulfm:note:<n> failure/revoke records from the KV store
            _ulfm.start_watcher(state)
    return state


def extend_universe(state: ProcState, new_size: int) -> None:
    """Make universe ranks [state.size, new_size) addressable: grow
    the endpoint table and let each btl prepare for the new peers
    (the dynamic-peer half of the reference's connect/accept
    MCA_PML_CALL(add_procs) path, ref: ompi/dpm/dpm.c)."""
    if new_size <= state.size:
        return
    old = state.size
    state.size = new_size
    for m in state.btls:
        ext = getattr(m, "extend", None)
        if ext is not None:
            ext(new_size)
    eps = list(state.pml.endpoints)
    for peer in range(old, new_size):
        reach = sorted((m for m in state.btls if m.reaches(peer)),
                       key=lambda m: -m.exclusivity)
        eps.append(btl_base.Endpoint(peer, reach) if reach else None)
    state.pml.add_procs(eps)


def mpi_finalize(state: ProcState) -> None:
    if state.finalized:
        return
    # past this point a JobRecovery interrupt has nothing to recover
    # and must not escape finalize as an unrelated error (ADVICE r5
    # #5); the watcher may still arm one mid-teardown, so suppression
    # is a standing flag, not a one-shot disarm
    state.progress.suppress_interrupts = True
    state.progress.interrupt = None
    # flush deferred work (fused device collectives)
    # BEFORE the fence: a flush may need one last cross-rank
    # rendezvous, so peers must still be alive and symmetric here
    state.progress.run_finalize_hooks()
    # mpisync clock-offset measurement BEFORE the fence (it is itself
    # collective — Barrier/Send/Recv/Bcast need a live pml): embeds
    # the offset table into every rank's trace dump so traceview /
    # critpath align timelines without a hand-plumbed --sync file
    from ompi_tpu import trace as _trace
    _trace.sync_state(state)
    # pml/monitoring traffic-matrix dump BEFORE the fence: every
    # rank's .prof file must exist by the time the fence releases
    # rank 0 to aggregate them (profile2mat semantics)
    _pml_monitoring.finalize_dump(state)
    # barrier, then teardown in reverse (ref: ompi_mpi_finalize.c:101)
    state.rte.fence()
    _pml_monitoring.finalize_aggregate(state)
    if state.ulfm is not None:
        # store hygiene: drop this job's ULFM notes and put-once
        # tickets so looped worlds (pytest re-entry, warm pools) never
        # replay a finished run's failure records.  After the fence —
        # every rank is in finalize, nobody consumes notes anymore —
        # and before rte.finalize closes the KV client.  Idempotent,
        # so every rank calling it is fine.
        from ompi_tpu.ft import ulfm as _fin_ulfm
        _fin_ulfm.purge_store(state)
    for m in state.btls:
        m.finalize()
    # autotune deregistration before the tracer dump: the process
    # tuner must stop reading this world's histograms
    from ompi_tpu.coll import autotune as _autotune
    _autotune.detach(state)
    state.rte.finalize()
    # stop the telemetry scrape tick for this world (the recorder and
    # registered gauges are process-scoped and survive into the next
    # looped world)
    from ompi_tpu import obs as _obs_fin
    _obs_fin.detach(state)
    # trace dump LAST: teardown spans (flush rendezvous, btl close)
    # are part of the timeline (_trace imported above for sync_state)
    _trace.dump_state(state)
    _trace.detach(state)
    state.finalized = True
    clear_current(state)
