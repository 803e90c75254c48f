"""hostrun: the per-host app shell of the hybrid launch model.

On a TPU host ONE process drives every local chip (that is how the
XLA runtime hands out devices), so a "node" in this framework runs
its ranks as threads of a single app-shell process — this module.
mpirun --ranks-per-proc spawns one hostrun per host-slot; hostrun
builds a HybridWorld, assigns each rank-thread a local jax device,
injects a HybridRTE per thread, and runs the user program in every
thread via runpy (each execution gets a fresh __main__ namespace).

This is the odls/orted analog re-shaped for TPU: the reference's
per-node daemon fork/execs N processes
(ref: orte/mca/odls/default/odls_default_module.c:338-437); here the
N local "procs" must share the process that owns the chips, so they
are rank-threads — which is exactly what makes coll/tpu's
rendezvous-assembled XLA collectives reachable from a real launch.

Env contract (set by mpirun): TPUMPI_SIZE, TPUMPI_RANK_BASE,
TPUMPI_LOCAL_RANKS, TPUMPI_KV_ADDR, TPUMPI_NODE, TPUMPI_JOBID,
TPUMPI_SESSION_DIR, TPUMPI_DEVICES (auto|none).
"""

from __future__ import annotations

import os
import runpy
import sys
import threading
import traceback
from typing import List, Optional

from ompi_tpu.runtime.rte import HybridRTE, HybridWorld, set_thread_rte


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    prog, prog_args = argv[0], argv[1:]

    size = int(os.environ["TPUMPI_SIZE"])
    base = int(os.environ["TPUMPI_RANK_BASE"])
    nlocal = int(os.environ["TPUMPI_LOCAL_RANKS"])
    kv_addr = os.environ["TPUMPI_KV_ADDR"]
    node_id = int(os.environ.get("TPUMPI_NODE", "0"))
    jobid = os.environ.get("TPUMPI_JOBID", "job0")
    session = os.environ.get("TPUMPI_SESSION_DIR", "/tmp")

    devices = None
    if os.environ.get("TPUMPI_DEVICES", "auto") != "none":
        import jax

        from ompi_tpu.runtime import jaxcache, x64

        x64.apply()
        jaxcache.enable()
        devices = jax.devices()

    world = HybridWorld(size, base, nlocal)
    failure: List[Optional[int]] = [None]
    flock = threading.Lock()

    def fail_rank(rank: int, rte, code: int, why: str) -> None:
        """The thread analog of a rank process dying: record it and
        report to the launcher so its errmgr policy kills the job —
        local peers may be parked in global KV fences that the
        in-process abort flag cannot reach."""
        with flock:
            failure[0] = failure[0] or code
        if world.aborted is None:
            world.aborted = (rank, code, why)
        for st in world.states:
            if st is not None and getattr(st, "progress", None):
                st.progress.wakeup()
        try:
            if rte is not None:
                rte.kv.abort(rank, code, why)
            else:  # setup died before the rte existed
                from ompi_tpu.runtime.kvstore import KVClient

                kv = KVClient(kv_addr)
                kv.abort(rank, code, why)
                kv.close()
        except Exception:  # noqa: BLE001
            pass

    def run_rank(local_rank: int) -> None:
        rank = base + local_rank
        rte = None
        try:
            rte = HybridRTE(world, rank, kv_addr, node_id=node_id,
                            jobid=jobid, session_dir=session)
            if devices:
                rte.default_device = devices[rank % len(devices)]
            set_thread_rte(rte)
            runpy.run_path(prog, run_name="__main__")
        except SystemExit as e:
            code = e.code if isinstance(e.code, int) else (
                0 if e.code is None else 1)
            if code != 0:
                fail_rank(rank, rte, code, f"rank exited with {code}")
        except BaseException as e:  # noqa: BLE001
            sys.stderr.write(f"[rank {rank}] uncaught exception:\n"
                             f"{traceback.format_exc()}")
            sys.stderr.flush()
            fail_rank(rank, rte, 1, f"uncaught exception: {e!r}")

    # argv seen by the user program (shared across rank-threads, like
    # every process-rank seeing the same argv)
    sys.argv = [prog] + prog_args
    threads = [threading.Thread(target=run_rank, args=(lr,), daemon=True,
                                name=f"mpi-rank-{base + lr}")
               for lr in range(nlocal)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return failure[0] or 0


if __name__ == "__main__":
    sys.exit(main())
