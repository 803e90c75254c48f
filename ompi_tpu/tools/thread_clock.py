"""thread_clock: is this host's thread CPU clock fit to be read?

``time.thread_time_ns`` (CLOCK_THREAD_CPUTIME_ID) would tell a
rank-thread's work from its waiting: under the GIL a wall-clock
interval cannot.  On Linux the clock is exact and a read costs a
fraction of a microsecond.  On a sandboxed kernel it need not be: the
chip tool's host (gVisor) serves it by a system call of 6 us from a
10 ms tick that samples the thread's state AT the tick, and the tick
keeps step with the timers that wake the thread, so a thread that only
sleeps reads 70% busy and one that works 2 ms in every 5 reads nothing
(PERF.md section 6, PR 37).  Run this on the host BEFORE a CPU clock
goes into the layer account; exit code 0 says fit, 1 unfit.

A cycle is ``threads`` threads taking turns (a ring of semaphores: the
GIL's stand-in); a turn spins for ``spin_us`` of the wall clock, which
the one thread that is awake spends on a core; then all sleep up to
the same instant, ``sleep_us`` after the last turn (a device program's
wait: the whole process asleep); for ``seconds``.  ``ratio`` is the
threads' clock over the spins they timed themselves: 1 where the clock
is fair, some percent above at most (handing the turn on and going to
sleep are on the clock and not in the spins).  A cycle that only
sleeps has no ratio: its ``clock_ms`` should be next to nothing.

Usage: ``python -m ompi_tpu.tools.thread_clock [--seconds S]
[name:threads:spin_us:sleep_us ...]``; one JSON line a cycle, the
verdict last.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from typing import List

# one thread against the tick (a spinner; sleeps alone; a period of one
# tick, of a little under one, of half a one), then the benchmark's
# cells as duty cycles (ledger, PR 36, traced: ranks,
# (traced_iter_us - kernel_us) / ranks, kernel_us)
CYCLES = ("spin:1:1000:0", "sleep-1ms:1:0:1000", "sleep-10ms:1:0:10000",
          "period-10ms:1:3000:7000", "period-9.5ms:1:3000:6500",
          "period-5ms:1:2000:3000",
          "allreduce-4KiB.hbm8:8:180:0", "alltoall-4MiB.tpu4:4:350:280",
          "alltoall-4MiB.hbm8:8:250:830", "bcast-64MiB.tpu4:4:375:1530",
          "rsb-max-f64-vector-16MiB.hbm8:8:300:1350",
          "allreduce-128MiB.tpu4:4:390:2430",
          "allreduce-256MiB.hbm8:8:200:3290")
#: a fair clock's ratio lies in here (the low end: a spinner that the
#: host preempts; the high end: the hand-offs' and sleeps' own work)
FAIR = (0.85, 1.25)
#: and a thread that only sleeps stays under this share of the wall
IDLE = 0.10


def read_cost_ns(n: int = 2000) -> float:
    """One ``time.thread_time_ns()``, in ns of the wall clock."""
    t0 = time.perf_counter_ns()
    for _ in range(n):
        time.thread_time_ns()
    return (time.perf_counter_ns() - t0) / n


def cycle(threads: int, spin_us: float, sleep_us: float,
          seconds: float) -> dict:
    spin_ns = int(spin_us * 1000)
    period_ns = int((threads * spin_us + sleep_us) * 1000)
    iters = max(1, int(seconds * 1e9 / period_ns))
    # a ring of semaphores hands the turn on; the last thread's hands
    # it to the first of the next iteration
    turn = [threading.Semaphore(0) for _ in range(threads)]
    turn[0].release()
    rows: List[tuple] = []

    def work(i: int) -> None:
        pc = time.perf_counter_ns
        mine, nxt = turn[i], turn[(i + 1) % threads]
        spun = 0
        c0 = time.thread_time_ns()
        for k in range(1, iters + 1):
            mine.acquire()
            t0 = t = pc()
            while t - t0 < spin_ns:
                t = pc()
            spun += t - t0
            nxt.release()
            if sleep_us:
                # everybody sleeps up to the same instant
                pause = t_open + k * period_ns - pc()
                if pause > 0:
                    time.sleep(pause / 1e9)
        rows.append((time.thread_time_ns() - c0, spun))

    ths = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
    t_open = time.perf_counter_ns()
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    wall = (time.perf_counter_ns() - t_open) / 1e9
    clock = sum(r[0] for r in rows)
    spun = sum(r[1] for r in rows)
    return {"threads": threads, "spin_us": spin_us, "sleep_us": sleep_us,
            "iters": iters, "wall_s": round(wall, 3),
            "spun_ms": round(spun / 1e6, 2),
            "clock_ms": round(clock / 1e6, 2),
            "ratio": round(clock / spun, 3) if spun else None}


def fair(row: dict) -> bool:
    """Whether one cycle's reading is what a fair clock gives."""
    if row["ratio"] is None:
        return row["clock_ms"] <= IDLE * row["wall_s"] * 1e3 * row["threads"]
    return FAIR[0] <= row["ratio"] <= FAIR[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="thread_clock")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("cycles", nargs="*", default=list(CYCLES),
                    metavar="name:threads:spin_us:sleep_us")
    opts = ap.parse_args(argv)
    unfit = []
    for spec in opts.cycles:
        name, n, spin, sleep = spec.rsplit(":", 3)
        row = cycle(int(n), float(spin), float(sleep), opts.seconds)
        if not fair(row):
            unfit.append(name)
        print(json.dumps({"cycle": name, **row}), flush=True)
    print(json.dumps({"fit": not unfit, "unfit": unfit,
                      "read_ns": round(read_cost_ns(), 1)}), flush=True)
    return 1 if unfit else 0


if __name__ == "__main__":
    sys.exit(main())
