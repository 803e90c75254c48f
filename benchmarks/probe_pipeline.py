"""--probe-pipeline microbench: the large-message busbw curve per
device algorithm — fused single-dispatch, segmented ring, per-segment
recursive doubling, and the hierarchical tier — over an OSU-style size
ladder (64 KiB ... 256 MiB; the in-container default caps the ladder
so a CI run finishes, real hardware raises --pipeline-max-bytes).

One thread-rank device world runs every configuration: the pipeline
knobs are process-global and every rank writes the identical values
before its next collective (then drops its per-comm routing caches),
so the world never splits across algorithms.  Each rep is timed
individually and the MEDIAN is reported, as in probe_dispatch.

allreduce busbw follows the OSU convention 2*(P-1)/P * nbytes / t —
the bytes a rank actually moves on the wire, so ring and recursive
doubling curves are directly comparable.

Results are persisted under ``probe_pipeline`` in BENCH_DETAIL.json
(read-modify-write) and the measured fused-vs-segmented and
segmented-vs-hierarchical crossovers refresh the coll/calibrate
per-host profile, so ``--mca coll_tuned_use_measured_rules 1``
consumes *measured* data — the same contract as --probe-dispatch.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List

# full OSU-style ladder; run_probe caps it (1-core CI boxes cannot
# hold 8 ranks x 256 MiB, and the curve's knee sits far below that)
SIZES = tuple((64 << 10) * 4 ** k for k in range(7))  # 64K .. 256M
DEFAULT_MAX_BYTES = 16 << 20
_CAP = 4 << 20  # mirror calibrate._CROSSOVER_CAP

ALGS = ("fused", "segring", "segrd", "hier")

# knob overrides per configuration; every rank applies them before
# its next collective (identical values — the registry is shared)
_CONFIGS: Dict[str, Dict[str, object]] = {
    "fused": {"coll_pipeline_enable": False, "coll_hier_enable": False},
    "segring": {"coll_pipeline_enable": True, "coll_hier_enable": False,
                "coll_pipeline_min_bytes": 1,
                "coll_pipeline_rd_max_bytes": 0},
    "segrd": {"coll_pipeline_enable": True, "coll_hier_enable": False,
              "coll_pipeline_min_bytes": 1,
              "coll_pipeline_rd_max_bytes": 1 << 62},
    "hier": {"coll_pipeline_enable": True, "coll_hier_enable": True,
             "coll_pipeline_min_bytes": 1, "coll_hier_min_bytes": 1,
             "coll_pipeline_rd_max_bytes": 0},
}

# per-comm routing caches that must be dropped when knobs change
# (resolved Plan objects key on geometry the knobs move)
_ROUTE_KEYS = ("_pipeline_pick", "_hier_eligible", "_hier_plan",
               "_coll_plans")


def _median_us(samples: List[float]) -> float:
    samples = sorted(samples)
    mid = len(samples) // 2
    med = samples[mid] if len(samples) % 2 else \
        (samples[mid - 1] + samples[mid]) / 2
    return med * 1e6


def _time_loop(comm, call, reps: int) -> float:
    call()  # warm: compile + first-dispatch (and hier comm splits)
    call()
    comm.Barrier()
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        call()
        samples.append(time.perf_counter() - t0)
    comm.Barrier()
    return _median_us(samples)


def _apply(comm, alg: str, nranks: int) -> None:
    from ompi_tpu.mca.params import registry
    over = dict(_CONFIGS[alg])
    if alg == "hier":
        over["coll_hier_slice_size"] = max(2, nranks // 2)
    for k, v in over.items():
        registry.set(k, v)
    for k in _ROUTE_KEYS:
        comm.__dict__.pop(k, None)


def _busbw_gbs(nbytes: int, us: float, nranks: int) -> float:
    wire = 2.0 * (nranks - 1) / nranks * nbytes
    return round(wire / (us * 1e-6) / 1e9, 3) if us > 0 else 0.0


def run_probe(nranks: int = 8, reps: int = 7,
              max_bytes: int = DEFAULT_MAX_BYTES) -> Dict:
    from ompi_tpu.testing import run_ranks

    sizes = [nb for nb in SIZES if nb <= max_bytes] or [SIZES[0]]

    def fn(comm):
        import jax
        import jax.numpy as jnp
        from ompi_tpu.coll import plan as coll_plan
        from ompi_tpu.op.op import SUM

        curve: Dict[str, Dict[str, float]] = {a: {} for a in ALGS}
        # plan-cache traffic per alg x size: builds measured across the
        # whole block (all ranks add to the process-wide pvar), so a
        # steady-state regression — plans rebuilt per op — shows up as
        # builds >> nranks for a single size
        plan_cache: Dict[str, Dict[str, Dict[str, int]]] = \
            {a: {} for a in ALGS}
        seg_before = coll_plan.pv_segments.read()
        for alg in ALGS:
            for nb in sizes:
                _apply(comm, alg, comm.size)
                x = jax.device_put(
                    jnp.arange(nb // 4, dtype=jnp.float32) + comm.rank,
                    comm.device)
                b0 = coll_plan.pv_builds.read()
                h0 = coll_plan.pv_hits.read()
                # big payloads settle for fewer reps: the median of 3
                # at 16 MiB still rejects a single preemption
                r = max(3, reps - 2 * sizes.index(nb))
                curve[alg][str(nb)] = round(_time_loop(
                    comm, lambda: comm.allreduce_arr(x, SUM), r), 1)
                plan_cache[alg][str(nb)] = {
                    "builds": coll_plan.pv_builds.read() - b0,
                    "hits": coll_plan.pv_hits.read() - h0}
                del x

        # per-phase breakdown (ISSUE 13): a short pass per alg x size
        # with the phase profiler armed, so BENCH_DETAIL tracks WHERE
        # a segmented op's time goes (rendezvous / pack / dispatch /
        # execute / unpack) round over round — the dispatch-tax number
        # with a trajectory, not a guess.  The timing sweep above ran
        # untraced; knobs are restored before returning.
        from ompi_tpu import trace
        from ompi_tpu.mca.params import registry
        saved = {k: registry.get(k) for k in
                 ("trace_phase_enable", "trace_sample_auto")}
        registry.set("trace_phase_enable", True)
        registry.set("trace_sample_auto", 0)
        tr = trace.force_attach(comm.state)
        raw: Dict[str, Dict[str, Dict[str, List[float]]]] = {}
        for alg in ALGS:
            raw[alg] = {}
            for nb in sizes:
                _apply(comm, alg, comm.size)
                x = jax.device_put(
                    jnp.arange(nb // 4, dtype=jnp.float32) + comm.rank,
                    comm.device)
                comm.allreduce_arr(x, SUM)  # warm (compile spans out)
                comm.Barrier()
                mark = time.time() - 1e-3
                for _ in range(2):
                    comm.allreduce_arr(x, SUM)
                comm.Barrier()
                acc: Dict[str, List[float]] = {}
                for ev in tr.snapshot():
                    if ev.get("ph") != "X" or ev["ts"] < mark:
                        continue
                    label = trace.PHASE_LABELS.get(ev["name"])
                    if label is None or ev["cat"] != "phase":
                        continue
                    acc.setdefault(label, []).append(ev["dur"] * 1e6)
                raw[alg][str(nb)] = acc
                del x
        comm.state.tracer = None
        comm.state.progress.tracer = None
        for k, v in saved.items():
            registry.set(k, v)
        _apply(comm, "fused", comm.size)  # leave the world at defaults
        return {"lat_us": curve, "phase_raw": raw,
                "plan_cache": plan_cache,
                "segments": coll_plan.pv_segments.read() - seg_before}

    res = run_ranks(nranks, fn, devices=True, timeout=1800)
    lat = res[0]["lat_us"]
    # phase medians merged over EVERY rank's recorded spans: dispatch/
    # execute land on whichever rank arrived last at each rendezvous,
    # so a single rank's view would usually miss them entirely
    phase_us: Dict[str, Dict[str, Dict[str, float]]] = {}
    for alg in ALGS:
        phase_us[alg] = {}
        for s in (res[0].get("phase_raw") or {}).get(alg, {}):
            merged: Dict[str, List[float]] = {}
            for r in res:
                for label, durs in ((r.get("phase_raw") or {})
                                    .get(alg, {}).get(s) or {}).items():
                    merged.setdefault(label, []).extend(durs)
            phase_us[alg][s] = {
                label: round(_median_us([d * 1e-6 for d in durs]), 1)
                for label, durs in sorted(merged.items())}
    probe: Dict = {
        "nranks": nranks,
        "sizes": sizes,
        "lat_us": lat,
        "busbw_gbs": {a: {s: _busbw_gbs(int(s), us, nranks)
                          for s, us in lat[a].items()}
                      for a in ALGS},
        "phase_us": phase_us,
        "plan_cache": res[0].get("plan_cache") or {},
        "segments_rank0": res[0]["segments"],
    }
    # measured crossovers: smallest probed size where the tier wins
    best_seg = {s: min(lat["segring"][s], lat["segrd"][s])
                for s in lat["fused"]}
    probe["seg_crossover_bytes"] = next(
        (int(s) for s in sorted(lat["fused"], key=int)
         if best_seg[s] <= lat["fused"][s]), _CAP)
    probe["hier_min_bytes"] = next(
        (int(s) for s in sorted(lat["hier"], key=int)
         if lat["hier"][s] <= best_seg[s]), _CAP)
    return probe


def persist(probe: Dict, detail_path: str) -> Dict:
    """Merge under 'probe_pipeline' in BENCH_DETAIL.json and refresh
    the calibrate profile's segmented/hierarchical crossovers."""
    notes = {}
    try:
        with open(detail_path) as fh:
            detail = json.load(fh)
        if not isinstance(detail, dict):
            detail = {}
    except (OSError, ValueError):
        detail = {}
    detail["probe_pipeline"] = probe
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
        prof["source"] = "probe_pipeline_sweep"
        prof["seg_crossover_bytes"] = {
            kind: probe["seg_crossover_bytes"]
            for kind in ("allreduce", "bcast", "alltoall")}
        prof["hier_min_bytes"] = probe["hier_min_bytes"]
        notes["profile_path"] = calibrate.save_profile(prof)
    except Exception as e:  # noqa: BLE001
        notes["profile_error"] = str(e)[:120]
    return notes
