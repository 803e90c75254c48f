#!/usr/bin/env python3
"""cellbench/run.py: one run of one cell of BENCHMARK.json.

    python3 cellbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

This process never imports jax: a parent that has touched JAX holds the
chip and its child then fails or hangs.  It validates the manifest
(cellbench/validate.py), starts ONE child in its own process group,

    python -m ompi_tpu.tools.mpirun -np N --ranks-per-proc all \\
        cellbench/rank.py ...

kills the whole group on a timeout, and prints the child's result as
the last line of stdout: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (``breakdown`` in a traced run) and the numbers
compared under ``checks``.  There is no probe child: the rank program
is the gate, and when it refuses (off the chip, an unknown
``device_kind``, the wrong device count or layout) this exits non-zero
and prints no result.  The numbers compared are also the last lines of
stderr.

``--trace 1`` is a run of its own with the library's phase spans on
(``--mca trace_enable 1 --mca trace_phase_enable 1``) and
``jax.profiler`` around the window; it reports the cell's per-layer
metrics.

``--allow-cpu --tiny`` is the development mode for a sandbox without a
chip: sizes divided by the mix's ``tiny_divisor``, four virtual CPU
devices for a four-chip layout, every line labelled, and no number
under a device metric's name.  It proves control flow, never speed.
``--control bf16`` hands the library inputs rounded to bfloat16: the
lower-precision control that ``correct`` has to fail; the driver never
passes it.
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

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from cellbench import manifest, validate  # noqa: E402

TAG = "CELLBENCH_RESULT"
RUN_LIMIT_S = 330   # a run has 360 s; leave the parent time to report


def fail(msg: str) -> NoReturn:
    sys.stderr.write(f"cellbench: FAILED: {msg}\n")
    sys.exit(1)


def run_child(cmd, env, timeout):
    """Run one child in its own process group, echoing its stdout as it
    arrives; (exit code, lines).  A child that outlives ``timeout`` is
    killed with its whole group and reports 124."""
    proc = subprocess.Popen(cmd, cwd=ROOT, env=env, text=True,
                            stdout=subprocess.PIPE, start_new_session=True)
    lines = []

    def pump():
        for line in proc.stdout:
            lines.append(line.rstrip("\n"))
            if TAG not in line:       # the result is printed once, last
                sys.stdout.write(line)
                sys.stdout.flush()

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        rc = 124
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    t.join(timeout=10)
    return rc, lines


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=[0, 1])
    ap.add_argument("--allow-cpu", action="store_true",
                    help="development mode: accept a non-TPU platform")
    ap.add_argument("--tiny", action="store_true",
                    help="development mode: sizes divided")
    ap.add_argument("--control", default=None, choices=["bf16"],
                    help="run the lower-precision control (must come "
                         "out not correct)")
    ap.add_argument("--describe-trace", default=None, metavar="PREFIX",
                    help="with --trace 1: write what the profiler's "
                         "trace holds to PREFIX.txt and a 50 ms "
                         "recording of it to PREFIX.json")
    ap.add_argument("--seeds", default="",
                    help="readings mode: further seeds, comma-separated, "
                         "run in the same process (for setting a limit)")
    ap.add_argument("--control-seeds", default="",
                    help="readings mode: seeds of the bf16 control")
    opts = ap.parse_args()
    if opts.tiny and not opts.allow_cpu:
        fail("--tiny is only for the --allow-cpu development mode; the "
             "chip runs the real sizes")
    if not (os.path.isdir(os.path.join(ROOT, "ompi_tpu"))
            and os.path.isfile(os.path.join(ROOT, "native", "Makefile"))):
        fail(f"{ROOT} does not hold the system under test (ompi_tpu/, "
             "native/): the benchmark alone measures nothing")

    problems = validate.check(ROOT)
    if problems:
        fail("BENCHMARK.json is not valid:\n  " + "\n  ".join(problems))
    man = manifest.manifest(ROOT)
    spec = manifest.cell(opts.workload, ROOT)
    cfg, traffic = spec["config"], spec["traffic"]
    seconds = float(man["run_seconds"]) if opts.seconds is None \
        else opts.seconds

    label = ""
    if opts.allow_cpu:
        label = "[DEV MODE allow-cpu%s: not a chip result] " % (
            " tiny" if opts.tiny else "")
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)     # the driver's own; nothing here reads it
    cmd = [sys.executable, "-m", "ompi_tpu.tools.mpirun", *cfg["launch"],
           "--timeout", str(RUN_LIMIT_S)]
    if opts.trace:
        cmd += ["--mca", "trace_enable", "1",
                "--mca", "trace_phase_enable", "1",
                "--mca", "trace_buffer_events", "65536"]
    if opts.allow_cpu:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cfg['chips']}"
            if cfg["chips"] > 1 else "")
        env.setdefault("JAX_PLATFORMS", "cpu")
    if opts.tiny:
        # the same code paths at a fraction of the bytes: the
        # large-message tier's crossover and segment shrink with them
        d = traffic["tiny_divisor"]
        cmd += ["--mca", "coll_pipeline_min_bytes", str((4 << 20) // d),
                "--mca", "coll_seg_size", str((1 << 20) // d)]
    cmd += [os.path.join(HERE, "rank.py"), "--workload", opts.workload,
            "--seed", str(opts.seed), "--seconds", str(seconds),
            "--trace", str(opts.trace), "--t0-epoch", repr(T0),
            "--out-dir", os.path.join(ROOT, "cellbench_out"),
            "--label", label]
    if opts.allow_cpu:
        cmd.append("--allow-cpu")
    if opts.tiny:
        cmd.append("--tiny")
    if opts.control:
        cmd += ["--control", opts.control]
    if opts.describe_trace:
        cmd += ["--describe-trace", os.path.abspath(opts.describe_trace)]
    limit = RUN_LIMIT_S
    if opts.seeds or opts.control_seeds:
        cmd += ["--seeds", opts.seeds, "--control-seeds", opts.control_seeds]
        limit = 1500      # not a benchmark run: many windows, one process
        cmd[cmd.index("--timeout") + 1] = str(limit)
    print(f"{label}launch: {' '.join(cmd[2:])}", flush=True)

    rc, out = run_child(cmd, env, limit + 15)
    result = None
    for line in reversed(out):
        at = line.find(TAG + " ")
        if at >= 0:
            result = json.loads(line[at + len(TAG) + 1:])
            break
    if rc != 0:
        fail(f"the mpirun job exited {rc}"
             + (" (timed out)" if rc == 124 else ""))
    if result is None:
        fail("the mpirun job exited 0 without a result")
    if "jax" in sys.modules:
        fail("the parent process imported jax")
    print(f"{label}run_wall_s={time.time() - T0:.3f}", flush=True)
    for name, c in result["checks"].items():
        sys.stderr.write(f"{label}check {name}: "
                         + " ".join(f"{k}={v}" for k, v in c.items())
                         + "\n")
    sys.stderr.write(f"{label}correct={result['correct']}\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
