#!/usr/bin/env python3
"""chip_smoke.py: does the device data plane still start on the chip?

    python3 chip_smoke.py [--seed N]

Drives the main path once, through the entry point a user calls —

    python -m ompi_tpu.tools.mpirun -np N --ranks-per-proc all \\
        examples/device_smoke.py

→ tools/hostrun.py → comm.*_arr / send_arr / Win → coll/device.py
rendezvous → jitted XLA collective — at BASELINE.md's real sizes, and
checks every result against numpy (examples/device_smoke.py says how).
One chip runs 8 rank-threads on it (coll/hbm); k >= 2 chips run k
ranks, one per chip (coll/tpu over ICI).

This process never imports jax: a parent that has touched JAX holds
the chip and its child then fails or hangs.  It starts one child at a
time (a probe that names the device, then the mpirun job), so exactly
one process owns the chip(s), kills the child's whole process group on
a timeout, and exits non-zero — printing no result line — when the
platform is not ``tpu``, when the device kind has no published peaks
in benchmarks/device_sweep.py, when a child fails or hangs, or when
any check in the rank program fails.  On success the last line of
stdout is one JSON object naming the device as JAX reports it.

``--allow-cpu --tiny`` is the development mode for a sandbox without
a chip: sizes and the pipeline tier's thresholds divided by 256, every
line labelled.  It proves control flow, never speed.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import NoReturn

HERE = os.path.dirname(os.path.abspath(__file__))
RANK_PROGRAM = os.path.join(HERE, "examples", "device_smoke.py")
ONE_CHIP_RANKS = 8
TINY_SCALE = 256

PROBE = r"""
import json, jax, jaxlib
from benchmarks.device_sweep import hbm_peak
d = jax.devices()
if d[0].platform == "tpu":
    hbm_peak(d[0].device_kind)  # raises for a kind with no published peaks
print("SMOKE_PROBE " + json.dumps({
    "platform": d[0].platform, "kind": d[0].device_kind, "count": len(d),
    "jax": jax.__version__, "jaxlib": jaxlib.__version__}))
"""


def fail(msg: str) -> NoReturn:
    sys.stderr.write(f"chip_smoke: FAILED: {msg}\n")
    sys.exit(1)


def run_child(cmd, timeout):
    """Run one child in its own process group, echoing its stdout as it
    arrives; returns (exit code, stdout lines).  A child that outlives
    ``timeout`` is killed with its whole group and reports 124."""
    # cwd is the checkout, so ``-m ompi_tpu...`` and ``-c`` find it
    proc = subprocess.Popen(cmd, cwd=HERE, text=True,
                            stdout=subprocess.PIPE, start_new_session=True)
    lines = []

    def pump():
        for line in proc.stdout:
            lines.append(line.rstrip("\n"))
            sys.stdout.write(line)
            sys.stdout.flush()

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        rc = 124
    finally:
        # the group outlives a failed mpirun only by accident; make sure
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    t.join(timeout=10)
    return rc, lines


def tagged(lines, tag):
    """The JSON payload of the last stdout line carrying ``tag``."""
    for line in reversed(lines):
        at = line.find(tag + " ")
        if at >= 0:
            return json.loads(line[at + len(tag) + 1:])
    return None


def cache_entries(path):
    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="development mode: accept a non-TPU platform")
    ap.add_argument("--tiny", action="store_true",
                    help="development mode: every size divided by "
                         f"{TINY_SCALE}")
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="seconds the mpirun job may take")
    opts = ap.parse_args()
    if opts.tiny and not opts.allow_cpu:
        fail("--tiny is only for the --allow-cpu development mode; the "
             "chip runs the real sizes")
    label = ""
    if opts.allow_cpu:
        label = "[DEV MODE allow-cpu%s: not a chip result] " % (
            " tiny" if opts.tiny else "")
    t_start = time.perf_counter()

    if not (os.path.isfile(RANK_PROGRAM)
            and os.path.isdir(os.path.join(HERE, "ompi_tpu"))
            and os.path.isfile(os.path.join(HERE, "native", "Makefile"))):
        fail(f"{HERE} does not hold the repository (ompi_tpu/, native/, "
             "examples/device_smoke.py)")

    # built from tracked files only: drop whatever the disk carried in;
    # the rank program's first native.load() rebuilds from native/*.cpp
    r = subprocess.run(["make", "-C", os.path.join(HERE, "native"),
                        "clean"], capture_output=True, text=True)
    if r.returncode != 0:
        fail(f"make -C native clean: {r.stderr.strip()[-300:]}")

    from ompi_tpu.runtime import jaxcache  # jax-free until enable()
    cdir = jaxcache.cache_dir()
    entries0 = cache_entries(cdir)

    rc, out = run_child([sys.executable, "-c", PROBE], 300)
    dev = tagged(out, "SMOKE_PROBE")
    if rc != 0 or dev is None:
        fail(f"device probe exited {rc}")
    print(f"{label}probe: {json.dumps(dev)}", flush=True)
    if dev["platform"] != "tpu" and not opts.allow_cpu:
        fail(f"platform is {dev['platform']!r}, not 'tpu': this check "
             "does not fall back to the CPU (--allow-cpu --tiny is the "
             "development mode)")

    nranks = ONE_CHIP_RANKS if dev["count"] == 1 else dev["count"]
    cmd = [sys.executable, "-m", "ompi_tpu.tools.mpirun",
           "-np", str(nranks), "--ranks-per-proc", "all",
           "--timeout", str(int(opts.timeout)),
           # config5 is MPI_DOUBLE on the device: a job-level statement
           "--mca", "mpi_device_x64", "1"]
    if opts.tiny:
        # the same code paths at 1/256 of the bytes: the large-message
        # tier's crossover and segment shrink with the sizes
        cmd += ["--mca", "coll_pipeline_min_bytes",
                str((4 << 20) // TINY_SCALE),
                "--mca", "coll_seg_size", str((1 << 20) // TINY_SCALE)]
    cmd += [RANK_PROGRAM, "--seed", str(opts.seed),
            "--expect-devices", str(dev["count"]), "--label", label]
    if opts.allow_cpu:
        cmd.append("--allow-cpu")
    if opts.tiny:
        cmd.append("--tiny")
    print(f"{label}leg: {' '.join(cmd[2:])}", flush=True)
    rc, out = run_child(cmd, opts.timeout + 60)
    leg = tagged(out, "SMOKE_LEG")
    if rc != 0:
        fail(f"the mpirun job exited {rc}"
             + (" (timed out)" if rc == 124 else ""))
    if leg is None:
        fail("the mpirun job exited 0 without its SMOKE_LEG summary")
    seen = {k: leg[k] for k in ("platform", "kind", "count")}
    if seen != {k: dev[k] for k in seen}:
        fail(f"the job ran on {seen}, the probe saw {dev}")

    new_entries = cache_entries(cdir) - entries0
    print(f"{label}summary: " + json.dumps({
        "layout": f"{leg['ranks']} ranks -> coll/{leg['layout']}",
        "operations": len(leg["ops"]),
        "config5_dtype": leg["config5_dtype"],
        "block_until_ready_waits": leg["block_until_ready_waits"],
        "xla_compile_or_load_s": leg["xla_compile_or_load_s"],
        "persistent_cache_hits": leg["persistent_cache_hits"],
        "persistent_cache_misses": leg["persistent_cache_misses"],
        "compile_cache_dir": cdir if dev["platform"] != "cpu" else "off",
        "compile_cache_new_entries": new_entries,
        "wall_s": round(time.perf_counter() - t_start, 1)}), flush=True)

    if "jax" in sys.modules:
        fail("the parent process imported jax")
    result = {"ok": True, "device": {"platform": dev["platform"],
                                     "kind": dev["kind"],
                                     "count": dev["count"]}}
    if opts.allow_cpu:
        result["dev_mode"] = label.strip()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
