"""Thread-rank world harness.

Runs N MPI ranks as threads in one process — the TPU-host execution
model (one process drives all local chips; ranks map to devices) and
the fast path for exercising the full stack in tests, mirroring how
the reference tests mapping logic without a cluster via ras/simulator
(ref: orte/mca/ras/simulator/ras_sim_module.c:67-91).
"""

from __future__ import annotations

import threading
import time
import traceback
from typing import Any, Callable, List, Optional

from ompi_tpu.runtime.init import mpi_finalize, mpi_init
from ompi_tpu.runtime.rte import InprocWorld
from ompi_tpu.runtime.state import ProcState


class RankError(RuntimeError):
    def __init__(self, rank: int, exc: BaseException, tb: str) -> None:
        super().__init__(f"rank {rank} failed: {exc}\n{tb}")
        self.rank = rank
        self.exc = exc


def run_ranks(n: int, fn: Callable, devices: bool = False,
              timeout: float = 120.0, device_map=None,
              allow_failures: bool = False,
              respawn: bool = False) -> List[Any]:
    """Run fn(comm_world) on n thread-ranks; returns per-rank results.

    devices=True maps rank i to jax.devices()[i % ndev] so coll/tpu
    and coll/hbm become eligible.  device_map overrides: a callable
    rank -> jax device (e.g. lambda r: jax.devices()[0] to co-locate
    every rank on one chip and exercise coll/hbm).

    allow_failures=True treats a rank dying with ulfm.RankKilled as
    the scenario, not an error: its failure is published ULFM-style
    (survivors get ERR_PROC_FAILED and may revoke/agree/shrink), its
    result slot stays None, and only survivor errors raise.

    respawn=True is the thread-world analog of mpirun's respawn
    policy (ft/respawn): a RankKilled death is published like
    allow_failures, then this driver waits for the survivors' rejoin
    decision (respawn.thread_decision) and starts a REPLACEMENT
    thread under the same world rank — fresh ProcState flagged
    respawn_joining at the failure's epoch.  fn runs again on the
    replacement (applications branch on respawn.joining(state) to
    rejoin + restore instead of starting over) and its return value
    fills the rank's result slot.  Kills reaped in the same window are
    replaced in ONE rejoin epoch (the decision's failed set), so
    correlated multi-kill scenarios — a rank plus all its buddy
    partners — exercise a single batched recovery; kills that land
    later degrade to sequential epochs.
    """
    world = InprocWorld(n)
    results: List[Any] = [None] * n
    errors: List[Optional[RankError]] = [None] * n
    devs = None
    if devices or device_map is not None:
        import jax

        from ompi_tpu.runtime import jaxcache, x64
        x64.apply()
        jaxcache.enable()
        devs = jax.devices()
    respawn_cv = threading.Condition()
    respawn_q: List[int] = []  # killed ranks awaiting replacement

    def runner(rank: int, joining_epoch: Optional[int] = None) -> None:
        try:
            rte = world.make_rte(rank)
            state = ProcState(rank, n, rte)
            if joining_epoch is not None:
                # replacement rank: mpi_init must not re-arm the fault
                # that killed the predecessor, and the app must see
                # respawn.joining(state) truthy (threads share the
                # environment, so the TPUMPI_RESPAWN env signal used
                # by process jobs cannot work here)
                state.respawn_joining = True
                state.respawn_epoch = joining_epoch - 1
            world.states[rank] = state
            if device_map is not None:
                dev = device_map(rank)
            else:
                dev = devs[rank % len(devs)] if devs else None
            mpi_init(state, device=dev)

            def _abort_check() -> int:
                if world.aborted and world.aborted[0] != rank:
                    raise RuntimeError(
                        f"peer rank {world.aborted[0]} aborted: "
                        f"{world.aborted[2]}")
                return 0

            state.progress.register(_abort_check, low_priority=True)
            results[rank] = fn(state.comm_world)
            # finalize only on success: its fence would deadlock
            # against peers that died before reaching it
            mpi_finalize(state)
        except BaseException as e:  # noqa: BLE001
            if allow_failures or respawn:
                from ompi_tpu.ft import ulfm as _ulfm
                if isinstance(e, _ulfm.RankKilled):
                    # the injected death IS the test scenario: the
                    # rank is gone, survivors mitigate via ULFM.
                    # Mark the corpse for process-wide accounting —
                    # whatever raised RankKilled, this incarnation
                    # will never run mpi_finalize
                    try:
                        state.ulfm_dead = True
                    except UnboundLocalError:
                        pass
                    _ulfm.publish_world_failure(world, rank)
                    if respawn:
                        with respawn_cv:
                            respawn_q.append(rank)
                            respawn_cv.notify_all()
                    return
            errors[rank] = RankError(rank, e, traceback.format_exc())
            if world.aborted is None:
                world.aborted = (rank, 1, str(e))
            try:
                world.barrier.abort()
            except Exception:
                pass
            for st in world.states:
                if st is not None:
                    st.progress.wakeup()

    def _spawn(rank: int,
               joining_epoch: Optional[int] = None) -> threading.Thread:
        t = threading.Thread(
            target=runner, args=(rank, joining_epoch), daemon=True,
            name=f"mpi-rank-{rank}" if joining_epoch is None
            else f"mpi-rank-{rank}-e{joining_epoch}")
        t.start()
        return t

    live = {r: _spawn(r) for r in range(n)}

    if respawn:
        # supervision loop (the inproc analog of mpirun's respawn
        # branch): reap kills, wait out each epoch's rejoin decision,
        # start the replacements, until every rank thread has finished.
        # Kills that land in the same reap window ride ONE epoch — the
        # rejoin decision is a set, so a correlated multi-kill (a rank
        # plus its buddy partners) is replaced in a single rejoin, the
        # way mpirun batches simultaneous child exits.  The survivors'
        # union can also decide ranks whose kill note has not reached
        # this driver yet; those are remembered in `owed` so the late
        # queue entry does not double-respawn them.
        from ompi_tpu.ft import respawn as _respawn
        deadline = time.monotonic() + timeout
        epoch = 0
        owed: set = set()
        while True:
            alive = any(t.is_alive() for t in live.values())
            with respawn_cv:
                pending, respawn_q[:] = list(respawn_q), []
            batch = [r for r in pending if r not in owed]
            owed.difference_update(pending)
            if batch:
                epoch += 1
                d = _respawn.thread_decision(
                    world, epoch,
                    timeout=max(1.0, deadline - time.monotonic()))
                decided = sorted(int(x) for x in d["failed"])
                owed.update(r for r in decided if r not in batch)
                for rank in decided:
                    live[rank] = _spawn(rank, joining_epoch=epoch)
            if not alive and not batch:
                break
            if world.aborted is not None and not pending:
                # a real error (not a kill): let the join path below
                # surface it instead of spinning to the deadline
                break
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"respawn world did not finish within {timeout}s "
                    f"(epoch {epoch}); errors so far: "
                    f"{[e for e in errors if e]}")
            time.sleep(0.002)

    for t in live.values():
        t.join(timeout)
        if t.is_alive():
            raise TimeoutError(
                f"rank thread {t.name} did not finish within {timeout}s "
                f"(likely deadlock); errors so far: "
                f"{[e for e in errors if e]}")
    # surface the root cause: the rank that aborted first, not the
    # peers that failed reacting to the abort
    if world.aborted is not None and errors[world.aborted[0]] is not None:
        raise errors[world.aborted[0]]
    for e in errors:
        if e is not None:
            raise e
    return results


def mpirun_run(np_, prog, *args, mca=(), extra=(), timeout=120,
               job_timeout=90, cwd=None):
    """Run `prog` under our mpirun as a subprocess and return the
    CompletedProcess — the one shared recipe for integration tests
    (PYTHONPATH for children, JAX pinned to CPU so examples never
    touch the real chip, belt-and-braces timeouts)."""
    import os
    import subprocess
    import sys

    import ompi_tpu as _pkg
    repo = os.path.dirname(os.path.dirname(os.path.abspath(
        _pkg.__file__)))
    cmd = [sys.executable, "-m", "ompi_tpu.tools.mpirun",
           "-np", str(np_)]
    if job_timeout:
        cmd += ["--timeout", str(job_timeout)]
    for k, v in mca:
        cmd += ["--mca", k, v]
    cmd += [*extra, prog if os.path.isabs(prog)
            else os.path.join(repo, prog), *args]
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(cmd, capture_output=True, timeout=timeout,
                          env=env, cwd=cwd or repo)
