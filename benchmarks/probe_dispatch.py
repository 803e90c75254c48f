"""--probe-dispatch microbench: the measured dispatch constant, the
device-vs-host crossover per collective, and the fusion amortization
ratio (ISSUE 2 acceptance: a batch of 8 fused small allreduces must
land under 3x the single-op dispatch constant, vs ~8x unfused).

Thread-rank worlds (ompi_tpu.testing.run_ranks): the device world maps
ranks onto jax devices (coll/tpu or coll/hbm, whichever the layout
makes eligible); the host world runs the same collectives through the
arr_host staging path (coll/tuned over the inproc btl) — the seg-path
proxy of the 4-64 KiB band.  Each rep is timed individually and the
MEDIAN is reported: blocking collectives synchronize the world each
call, so a rep measures exactly the dispatch + rendezvous cost a
program pays, and the median rejects scheduler-preemption outliers.

Results are persisted under ``probe_dispatch`` in BENCH_DETAIL.json
(read-modify-write: the sweep data of a prior full run is preserved)
and the swept crossovers refresh the coll/calibrate per-host profile,
so ``--mca coll_tuned_use_measured_rules 1`` consumes *measured* data.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict

import numpy as np

SIZES = (4096, 16384, 65536)
FUSED_OPS = 8
FUSED_BYTES = 16384
_CAP = 4 << 20  # mirror calibrate._CROSSOVER_CAP


def _time_loop(comm, call, reps: int) -> float:
    """Median us/op over individually-timed reps (every rank loops;
    the collective itself synchronizes each rep).  Median, not mean:
    on an oversubscribed host a single scheduler preemption inflates
    one rep by milliseconds, and the dispatch constant being probed is
    the typical-rep cost, not the tail."""
    call()  # warm: compile + first-dispatch costs stay out
    call()
    comm.Barrier()
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        call()
        samples.append(time.perf_counter() - t0)
    comm.Barrier()
    samples.sort()
    mid = len(samples) // 2
    med = samples[mid] if len(samples) % 2 else \
        (samples[mid - 1] + samples[mid]) / 2
    return med * 1e6


def _payload(comm, kind: str, nbytes: int, device: bool):
    n = max(comm.size, nbytes // 4)
    if kind == "alltoall":
        n -= n % comm.size
    if device:
        import jax.numpy as jnp
        return jnp.arange(n, dtype=jnp.float32) + comm.rank
    return np.arange(n, dtype=np.float32) + comm.rank


def _call(comm, kind: str, x):
    from ompi_tpu.op.op import SUM
    if kind == "allreduce":
        return lambda: comm.allreduce_arr(x, SUM)
    if kind == "bcast":
        return lambda: comm.bcast_arr(x, 0)
    return lambda: comm.alltoall_arr(x)


def _world_sweep(device: bool, nranks: int, reps: int) -> Dict:
    """One world: per-kind latency at each probe size (+ fusion batch
    timings in the device world)."""
    from ompi_tpu.testing import run_ranks

    def fn(comm):
        out: Dict = {"lat_us": {}}
        for kind in ("allreduce", "bcast", "alltoall"):
            out["lat_us"][kind] = {
                str(nb): round(_time_loop(
                    comm, _call(comm, kind, _payload(comm, kind, nb,
                                                     device)), reps), 1)
                for nb in SIZES}
        if device:
            import jax.numpy as jnp
            from ompi_tpu.op.op import SUM
            xs = [jnp.arange(FUSED_BYTES // 4, dtype=jnp.float32) * (i + 1)
                  for i in range(FUSED_OPS)]

            def fused():
                reqs = [comm.iallreduce_arr(x, SUM) for x in xs]
                comm.flush_arr()
                return reqs

            def sequential():
                return [comm.allreduce_arr(x, SUM) for x in xs]

            out["fused_batch_us"] = round(
                _time_loop(comm, fused, reps), 1)
            out["sequential_us"] = round(
                _time_loop(comm, sequential, reps), 1)
        return out

    res = run_ranks(nranks, fn, devices=device, timeout=600)
    return res[0]  # rank 0's medians (each rep is world-synchronized)


def _crossover(dev_lat: Dict[str, float], host_lat: Dict[str, float]) -> int:
    """Smallest probed size where the device path wins; 0 when it
    always wins, capped when it never does."""
    for nb in SIZES:
        d, h = dev_lat.get(str(nb)), host_lat.get(str(nb))
        if d is not None and h is not None and d <= h:
            return 0 if nb == SIZES[0] else nb
    return _CAP


def run_probe(nranks: int = 8, reps: int = 20) -> Dict:
    dev = _world_sweep(True, nranks, reps)
    host = _world_sweep(False, nranks, reps)
    probe: Dict = {
        "nranks": nranks,
        "sizes": list(SIZES),
        "device_us": dev["lat_us"],
        "host_us": host["lat_us"],
        # the per-op dispatch constant: smallest-payload device
        # latency (the op itself is ~free there)
        "dispatch_us": {k: dev["lat_us"][k][str(SIZES[0])]
                        for k in dev["lat_us"]},
        "crossover_bytes": {k: _crossover(dev["lat_us"][k],
                                          host["lat_us"][k])
                            for k in dev["lat_us"]},
    }
    single = probe["dispatch_us"]["allreduce"]
    fused_us = dev.get("fused_batch_us")
    seq_us = dev.get("sequential_us")
    if fused_us and single:
        probe["fused"] = {
            "batch_ops": FUSED_OPS,
            "payload_bytes": FUSED_BYTES,
            "fused_batch_us": fused_us,
            "sequential_us": seq_us,
            "single_op_us": single,
            "ratio_vs_single": round(fused_us / single, 2),
            "meets_3x_target": bool(fused_us < 3 * single),
        }
    return probe


def persist(probe: Dict, detail_path: str) -> Dict:
    """Merge under 'probe_dispatch' in BENCH_DETAIL.json (preserving
    sweep data from prior rounds) and refresh the calibrate profile
    with the swept crossovers."""
    notes = {}
    try:
        with open(detail_path) as fh:
            detail = json.load(fh)
        if not isinstance(detail, dict):
            detail = {}
    except (OSError, ValueError):
        detail = {}
    detail["probe_dispatch"] = probe
    try:
        tmp = f"{detail_path}.tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump(detail, fh, indent=1)
        os.replace(tmp, detail_path)
    except OSError as e:
        notes["detail_error"] = str(e)[:120]

    try:
        from ompi_tpu.coll import calibrate
        prof = calibrate.get_profile(create=True) or {}
        prof = dict(prof)
        prof["source"] = "probe_dispatch_sweep"
        prof["dispatch_us"] = probe["dispatch_us"]["allreduce"]
        prof["crossover_bytes"] = probe["crossover_bytes"]
        notes["profile_path"] = calibrate.save_profile(prof)
    except Exception as e:  # noqa: BLE001
        notes["profile_error"] = str(e)[:120]
    return notes
