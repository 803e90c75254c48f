"""The rank program cellbench/run.py launches through mpirun:

    python -m ompi_tpu.tools.mpirun -np N --ranks-per-proc all \
        cellbench/rank.py --workload <cell> --seed <n> --seconds <s> ...

Every rank-thread of the one app shell runs this file.  It is the gate
(no probe child): it fails, and rank 0 prints no result, when the
platform is not ``tpu``, when the device kind has no row in
cellbench/peaks.json, when the device count is not the cell's
``chips``, or when the ranks do not sit as the configuration says.
Then it hands the cell to the generator its traffic mix names
(cellbench/traffic/<generator>.py) and rank 0 prints what that returns
as one ``CELLBENCH_RESULT`` line for the parent.
"""
import argparse
import copy
import importlib
import json
import os
import sys
import time

T_MAIN = time.time()   # first statement a rank-thread reaches here


def process_age_s() -> float:
    """Seconds since this process (the app shell) was started."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start_ticks / os.sysconf("SC_CLK_TCK")


SHELL_AGE = process_age_s()   # app shell start to here: imports, chip init

import numpy as np  # noqa: E402

import ompi_tpu  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from cellbench import manifest  # noqa: E402


class CellFailure(RuntimeError):
    pass


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--t0-epoch", type=float, required=True)
    ap.add_argument("--out-dir", required=True)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--control", default=None, choices=["bf16"])
    ap.add_argument("--label", default="")
    ap.add_argument("--describe-trace", default=None)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    opts = ap.parse_args()
    opts.rank_main_epoch = T_MAIN

    comm = ompi_tpu.init()
    import jax

    spec = manifest.cell(opts.workload)
    cfg = spec["config"]
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind

    def say(msg):
        sys.stdout.write(f"{opts.label}{msg}\n")
        sys.stdout.flush()

    opts.say = say
    opts.xla = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}
    if comm.rank == 0:
        # process-wide listeners: every rank-thread's compiles land here
        def on_duration(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                opts.xla["compile_s"] += secs

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                opts.xla["cache_hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                opts.xla["cache_misses"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    # -- the gate -----------------------------------------------------------
    if platform != "tpu" and not opts.allow_cpu:
        raise CellFailure(
            f"platform is {platform!r}, not 'tpu': no result off the chip "
            "(--allow-cpu --tiny is the development mode)")
    peaks = manifest.load_json(os.path.join(HERE, "peaks.json"))
    opts.peaks = peaks.get(kind)
    if opts.peaks is None and not opts.allow_cpu:
        raise CellFailure(
            f"device_kind {kind!r} has no row in cellbench/peaks.json "
            f"(known: {sorted(k for k in peaks if k[0] != '_')})")
    if len(devs) != cfg["chips"]:
        raise CellFailure(
            f"{len(devs)} device(s) here, the cell asks for "
            f"{cfg['chips']}")
    if comm.size != cfg["ranks"]:
        raise CellFailure(f"{comm.size} ranks, the configuration "
                          f"states {cfg['ranks']}")
    ids = np.empty((comm.size, 1), np.int64) if comm.rank == 0 else None
    comm.Gather(np.array([comm.device.id], np.int64), ids, root=0)
    if comm.rank == 0:
        distinct = sorted(set(ids[:, 0].tolist()))
        if len(distinct) != cfg["chips"]:
            raise CellFailure(
                f"{comm.size} ranks sit on device ids {ids[:, 0].tolist()}"
                f"; {cfg['name']} needs {cfg['chips']} distinct")
        say(f"launch parts: run.py to app shell "
            f"{T_MAIN - SHELL_AGE - opts.t0_epoch:.3f} s (mpirun, KV "
            f"server, spawn), app shell to rank main {SHELL_AGE:.3f} s "
            f"(imports, jax, chip init), rank main to here "
            f"{time.time() - T_MAIN:.3f} s (ompi_tpu.init, gate)")
        say(f"device: platform={platform} kind={kind} count={len(devs)} "
            f"jax={jax.__version__}; layout: {comm.size} ranks on device "
            f"ids {distinct}; compile_cache="
            f"{jax.config.jax_compilation_cache_dir or 'off'}")

    gen = importlib.import_module(
        "cellbench.traffic." + spec["traffic"]["generator"])
    # readings mode (how a limit's two readings are taken where set-up
    # is long): further seeds, then the control's, in this one process
    runs = [(opts.seed, opts.control)] \
        + [(int(s), None) for s in opts.seeds.split(",") if s] \
        + [(int(s), "bf16") for s in opts.control_seeds.split(",") if s]
    for seed, control in runs:
        o = copy.copy(opts)
        o.seed, o.control = seed, control
        result = gen.run(comm, spec, o)
        if comm.rank == 0 and len(runs) > 1:
            say(f"READING seed={seed} control={control} "
                f"gap={result['checks']['gap']['value']!r} "
                f"correct={result['correct']} failed={result['failed']}")
    if comm.rank == 0:
        if opts.allow_cpu:
            # no number of a CPU run stands under a device metric's name
            result["cpu_rehearsal"] = {
                "dev_" + k: v for k, v in result.pop("metrics").items()}
            result["metrics"] = {}
            result["dev_mode"] = opts.label.strip()
            result["checks"] = result.pop("checks")   # stays last
        say("CELLBENCH_RESULT " + json.dumps(result, separators=(",", ":")))
    ompi_tpu.finalize()


if __name__ == "__main__":
    main()
