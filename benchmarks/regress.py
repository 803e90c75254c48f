"""regress: the perf-regression sentry over the BENCH_r* history.

``bench.py --regress`` is pure file analysis — it runs NO probes.  It
loads the per-round driver records (``BENCH_r*.json``: the parsed
headline metric plus the captured stdout tail) and the full-sweep
``BENCH_DETAIL.json``, compares the newest round against the history
with **noise-aware tolerances**, appends a trajectory row so probe
metrics become comparable round over round, and exits nonzero when a
metric regressed beyond what the history's own noise can explain.

Noise model: for each metric the baseline is the MEDIAN of the prior
samples and the tolerance is::

    tol = max(base_tol, NOISE_K * MAD / median)

where MAD is the median absolute deviation of the prior samples — a
flat history (74.4, 74.5, 74.3) keeps the tight base tolerance and a
20% drop trips the sentry; a history whose own scatter dwarfs any
plausible regression (74 -> 10 -> 12 across reworked sweeps) widens
the band automatically, because claiming a regression noisier than
the noise floor would be a lie.  Lower-is-better metrics (overhead
percentages) use the same model with the comparison flipped and an
absolute floor (percentages near zero make relative bands useless).

Rounds whose metric is missing or nonpositive (a failed sweep) are
excluded from baselines — a crashed round must not poison the noise
estimate OR hide as a fake regression.

``--dry`` evaluates everything but appends nothing: the tier-1 smoke
validates history parsing without mutating BENCH_DETAIL.json.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

#: scale factor on MAD when widening a tolerance band
NOISE_K = 3.0

#: cap on retained trajectory rows (oldest dropped first)
TRAJECTORY_CAP = 100

#: metric -> (direction, base tolerance).  Direction "higher" metrics
#: regress by dropping (relative tolerance); "lower" metrics regress
#: by rising (absolute tolerance, percentage points).
TOLERANCES: Dict[str, Tuple[str, float]] = {
    "headline_busbw_gbs": ("higher", 0.10),
    "pipeline_fused_busbw_gbs": ("higher", 0.25),
    "pipeline_segring_busbw_gbs": ("higher", 0.25),
    # compiled-plan sentries (ISSUE 17): the best segmented busbw
    # anywhere on the sweep, and segmented-vs-fused at 256 KiB — the
    # size where plan orchestration savings dominate, so a plan-path
    # regression (per-op rebuilds, a lost zero-copy pack) shows up
    # here before it shows in the 8 MiB headline
    "seg_best_busbw_gbs": ("higher", 0.25),
    "seg_vs_fused_ratio_256k": ("higher", 0.25),
    "trace_overhead_pct": ("lower", 2.0),
    "obs_overhead_pct": ("lower", 2.0),
    "dispatch_const_us": ("lower", 50.0),
    # one-sided busbw at the 1 MiB acceptance tier (ISSUE 14): same
    # noise band as the pipeline curves — thread-rank timing on a
    # shared host core is jittery, real drops are way past 25%
    "rma_device_put_busbw_gbs": ("higher", 0.25),
    "rma_device_get_busbw_gbs": ("higher", 0.25),
    "rma_pt2pt_put_busbw_gbs": ("higher", 0.25),
    # control-plane recovery MTTRs (ISSUE 15): "lower" metrics use an
    # ABSOLUTE band in the metric's own unit (ms here).  Warm KV
    # failover is detect+rotate+reconnect on localhost (~2 ms typical)
    # but the client's backoff ladder makes the tail jumpy — a real
    # regression (e.g. a lost sleepless-retry path) lands in seconds.
    # The DVM restart MTTR is dominated by the respawned server's
    # interpreter + import cold start (~600 ms), so its band is wide.
    "kv_failover_mttr_ms": ("lower", 150.0),
    "dvm_restart_mttr_ms": ("lower", 1500.0),
    # whole-host recovery (ISSUE 16): daemon SIGKILL -> silence
    # detection -> domain respawn.  Dominated by the probe's 3-beat
    # grace horizon (~600 ms at the probe's 0.2 s beat), so the band
    # absorbs a missed beat or two; a real regression (a detector
    # stuck on the default horizon, a respawn replaying whole
    # journals) lands in multiple seconds.
    "host_kill_mttr_ms": ("lower", 1500.0),
    # reqtrace sentries (ISSUE 18): queue-wait p99 of the probe's
    # 4-session Poisson workload (µs — admission scheduling drift
    # shows up here before goodput moves) and the hang doctor's
    # threshold-to-capture latency (ms — contractually within
    # 2 x obs_watchdog_ms; the band absorbs watchdog-tick phase)
    "queue_wait_p99_us": ("lower", 100000.0),
    "doctor_mttd_ms": ("lower", 200.0),
    # gray-failure plane sentries (ISSUE 19): slow-start -> quarantine
    # applied (budget 4x the probe's 300 ms health tick; the band
    # absorbs a tick or two of phase), mitigated-vs-unmitigated
    # goodput (relative — a broken drain/re-placement halves it), and
    # false quarantines on the healthy arm, which must stay EXACTLY
    # zero (the 0.5 absolute band means any nonzero count regresses)
    "grayfail_mttm_ms": ("lower", 2000.0),
    "grayfail_goodput_ratio": ("higher", 0.25),
    "false_quarantines": ("lower", 0.5),
    # sdc-integrity plane sentries (ISSUE 20): the detection rate on
    # the flip-every-op arm must stay EXACTLY 1.0 (the 1% relative
    # band means a single missed flip out of the probe's 40 regresses),
    # false positives on the clean armed arm must stay EXACTLY zero
    # (0.5 absolute band — same contract as false_quarantines), and
    # conviction-to-quarantine latency is bounded by a couple of
    # effective health sweeps (the band absorbs sweep phase; a real
    # regression — a lost decisive-signal path making sdc wait out the
    # beat-score hysteresis — lands in multiples of the budget)
    "sdc_detection_rate": ("higher", 0.01),
    "sdc_false_positives": ("lower", 0.5),
    "sdc_mttq_ms": ("lower", 1000.0),
    # the armed integrity plane's steady-state overhead rides the
    # trace_overhead budget model: an absolute percentage-point band
    "integrity_overhead_pct": ("lower", 2.0),
}


def _json_lines(text: str):
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                yield json.loads(line)
            except ValueError:
                continue


#: sanity bound on the device sweep's measured d2h read constant.  An
#: idle box reads 4 bytes in tens of microseconds; ~100 ms means the
#: quiet gate failed (polling peers or runtime threads contaminated
#: the probe — the r4 failure mode) and the constant-subtraction then
#: FABRICATES busbw.  Rounds in that state are not comparable.
READ_CONST_SANE_US = 5000.0


def headline_valid(doc: dict) -> bool:
    """True when a round's headline came from the chained-dependency
    methodology with a sane read constant.  Rounds predating the
    ``read_const_us`` field timed unforced dispatch (the
    block_until_ready floor), and rounds with a contaminated constant
    over-credit every op — neither number is a usable baseline."""
    parsed = doc.get("parsed") or {}
    rc = parsed.get("read_const_us")
    return isinstance(rc, (int, float)) and 0 <= rc < READ_CONST_SANE_US


def round_headline(doc: dict) -> Optional[float]:
    """GB/s of the headline metric for one BENCH_r record: the
    driver-parsed value, else the last parseable JSON line of the
    captured stdout tail (the r2 failure mode — a tail outgrowing the
    capture — leaves parsed null with the line still in the text)."""
    parsed = doc.get("parsed") or {}
    v = parsed.get("value")
    if isinstance(v, (int, float)) and v > 0:
        return float(v)
    for obj in _json_lines(doc.get("tail", "") or ""):
        if obj.get("unit") == "GB/s" and \
                isinstance(obj.get("value"), (int, float)) and \
                obj["value"] > 0:
            return float(obj["value"])
    return None


def load_rounds(bench_dir: str) -> List[Tuple[int, dict]]:
    """(round number, record) sorted ascending from BENCH_r*.json."""
    out = []
    for path in glob.glob(os.path.join(bench_dir, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            continue
        out.append((int(m.group(1)), doc))
    out.sort()
    return out


def _detail_metrics(detail: dict) -> Dict[str, float]:
    """Flatten the probe blocks of BENCH_DETAIL.json into the sentry's
    comparable scalar metrics (missing probes simply absent)."""
    out: Dict[str, float] = {}
    to = detail.get("trace_overhead") or {}
    if isinstance(to.get("overhead_pct"), (int, float)):
        out["trace_overhead_pct"] = float(to["overhead_pct"])
    if isinstance(to.get("integrity_overhead_pct"), (int, float)):
        out["integrity_overhead_pct"] = \
            float(to["integrity_overhead_pct"])
    ob = detail.get("probe_obs") or {}
    if isinstance(ob.get("overhead_pct"), (int, float)):
        out["obs_overhead_pct"] = float(ob["overhead_pct"])
    pd = detail.get("probe_dispatch") or {}
    const = (pd.get("fused") or {}).get("dispatch_const_us") \
        if isinstance(pd.get("fused"), dict) else None
    if const is None:
        const = pd.get("dispatch_const_us")
    if isinstance(const, (int, float)):
        out["dispatch_const_us"] = float(const)
    pp = detail.get("probe_pipeline") or {}
    bus = pp.get("busbw_gbs") or {}
    for alg in ("fused", "segring"):
        curve = bus.get(alg) or {}
        sizes = [k for k, v in curve.items()
                 if isinstance(v, (int, float)) and v > 0]
        if sizes:
            top = max(sizes, key=int)
            out[f"pipeline_{alg}_busbw_gbs"] = float(curve[top])
    # best segmented busbw across BOTH plan algs and ALL sizes
    seg_vals = [float(v)
                for alg in ("segring", "segrd")
                for v in (bus.get(alg) or {}).values()
                if isinstance(v, (int, float)) and v > 0]
    if seg_vals:
        out["seg_best_busbw_gbs"] = max(seg_vals)
    k256 = str(256 << 10)
    fused256 = (bus.get("fused") or {}).get(k256)
    seg256 = [v for v in ((bus.get("segring") or {}).get(k256),
                          (bus.get("segrd") or {}).get(k256))
              if isinstance(v, (int, float)) and v > 0]
    if isinstance(fused256, (int, float)) and fused256 > 0 and seg256:
        out["seg_vs_fused_ratio_256k"] = round(max(seg256) / fused256, 3)
    rma = (detail.get("probe_rma") or {}).get("components") or {}
    mib = str(1 << 20)
    for comp in ("device", "pt2pt"):
        for kind in ("put", "get"):
            if comp == "pt2pt" and kind == "get":
                continue  # pt2pt get ~= put; three metrics suffice
            v = ((rma.get(comp) or {}).get(f"{kind}_busbw_gbs")
                 or {}).get(mib)
            if isinstance(v, (int, float)) and v > 0:
                out[f"rma_{comp}_{kind}_busbw_gbs"] = float(v)
    cp = detail.get("probe_ctrlplane") or {}
    for key in ("kv_failover_mttr_ms", "dvm_restart_mttr_ms"):
        v = cp.get(key)
        if isinstance(v, (int, float)) and v > 0:
            out[key] = float(v)
    fl = (detail.get("probe_fleet") or {}).get("hosts") or {}
    v = fl.get("host_kill_mttr_ms") if isinstance(fl, dict) else None
    if isinstance(v, (int, float)) and v > 0:
        out["host_kill_mttr_ms"] = float(v)
    rp = detail.get("probe_reqtrace") or {}
    for key in ("queue_wait_p99_us", "doctor_mttd_ms"):
        v = rp.get(key)
        if isinstance(v, (int, float)) and v > 0:
            out[key] = float(v)
    gf = detail.get("probe_grayfail") or {}
    v = gf.get("mttm_ms")
    if isinstance(v, (int, float)) and v > 0:
        out["grayfail_mttm_ms"] = float(v)
    v = gf.get("goodput_ratio")
    if isinstance(v, (int, float)) and v > 0:
        out["grayfail_goodput_ratio"] = float(v)
    v = gf.get("false_quarantines")
    # v >= 0 on purpose: the required value IS zero — the v > 0
    # pattern used above would drop the healthy samples and leave the
    # sentry blind to the first false quarantine
    if isinstance(v, (int, float)) and v >= 0:
        out["false_quarantines"] = float(v)
    sd = detail.get("probe_sdc") or {}
    for key in ("sdc_detection_rate", "sdc_mttq_ms"):
        v = sd.get(key)
        if isinstance(v, (int, float)) and v > 0:
            out[key] = float(v)
    v = sd.get("sdc_false_positives")
    # v >= 0 for the same reason as false_quarantines: zero IS the
    # required value, and dropping it would blind the sentry
    if isinstance(v, (int, float)) and v >= 0:
        out["sdc_false_positives"] = float(v)
    return out


def current_metrics(rounds: List[Tuple[int, dict]],
                    detail: dict) -> Dict[str, float]:
    out = _detail_metrics(detail)
    if rounds:
        v = round_headline(rounds[-1][1])
        if v is not None:
            out["headline_busbw_gbs"] = v
    return out


def _median(vals: List[float]) -> float:
    s = sorted(vals)
    n = len(s)
    m = n // 2
    return s[m] if n % 2 else 0.5 * (s[m - 1] + s[m])


def check_metric(name: str, current: float,
                 history: List[float]) -> Optional[dict]:
    """One finding dict when ``current`` regressed vs ``history``
    beyond the noise-aware band, else None.  Needs >= 2 valid prior
    samples — a single point has no noise estimate."""
    direction, base = TOLERANCES.get(name, ("higher", 0.10))
    hist = [v for v in history if isinstance(v, (int, float)) and
            (v > 0 or direction == "lower")]
    if len(hist) < 2:
        return None
    med = _median(hist)
    mad = _median([abs(v - med) for v in hist])
    if direction == "higher":
        if med <= 0:
            return None
        tol = max(base, NOISE_K * mad / med)
        floor = med * (1.0 - tol)
        if current < floor:
            return {"metric": name, "current": round(current, 3),
                    "baseline_median": round(med, 3),
                    "floor": round(floor, 3),
                    "tolerance": round(tol, 3),
                    "n_history": len(hist)}
        return None
    # lower-is-better: absolute band in the metric's own units
    band = max(base, NOISE_K * mad)
    ceil = med + band
    if current > ceil:
        return {"metric": name, "current": round(current, 3),
                "baseline_median": round(med, 3),
                "ceiling": round(ceil, 3), "tolerance": round(band, 3),
                "n_history": len(hist)}
    return None


def evaluate(rounds: List[Tuple[int, dict]],
             detail: dict) -> Dict[str, Any]:
    """The sentry verdict document: current metrics, per-metric
    findings, and the trajectory row a non-dry run appends."""
    cur = current_metrics(rounds, detail)
    findings: List[dict] = []

    # headline: newest round vs the prior rounds' own records —
    # measurement-valid rounds only on BOTH sides (headline_valid):
    # an invalid current round cannot be judged, and invalid history
    # rows would anchor the baseline to fabricated numbers
    if "headline_busbw_gbs" in cur and len(rounds) >= 3 and \
            headline_valid(rounds[-1][1]):
        hist = []
        for _n, doc in rounds[:-1]:
            if not headline_valid(doc):
                continue
            v = round_headline(doc)
            if v is not None:
                hist.append(v)
        f = check_metric("headline_busbw_gbs",
                         cur["headline_busbw_gbs"], hist)
        if f:
            findings.append(f)

    # probe metrics: current BENCH_DETAIL vs the recorded trajectory
    traj = detail.get("regress_trajectory") or []
    for name, val in cur.items():
        if name == "headline_busbw_gbs":
            continue
        hist = [row["metrics"][name] for row in traj
                if isinstance(row, dict) and
                name in (row.get("metrics") or {})]
        f = check_metric(name, val, hist)
        if f:
            findings.append(f)

    row = {"round": rounds[-1][0] if rounds else None, "metrics": cur}
    return {"metrics": cur, "findings": findings, "trajectory_row": row,
            "rounds_seen": len(rounds),
            "trajectory_len": len(traj)}


def append_trajectory(detail_path: str, row: dict) -> None:
    """Read-modify-write the trajectory list in BENCH_DETAIL.json,
    capped so the file never grows without bound."""
    try:
        with open(detail_path) as fh:
            detail = json.load(fh)
        if not isinstance(detail, dict):
            detail = {}
    except (OSError, ValueError):
        detail = {}
    traj = detail.get("regress_trajectory")
    if not isinstance(traj, list):
        traj = []
    traj.append(row)
    detail["regress_trajectory"] = traj[-TRAJECTORY_CAP:]
    with open(detail_path, "w") as fh:
        json.dump(detail, fh, indent=1)


def run_regress(bench_dir: str, detail_path: str,
                dry: bool = False) -> int:
    """The ``bench.py --regress`` entry: 0 = no regression, 1 =
    regression detected, 2 = no usable history (CI treats that as a
    configuration error, not a pass)."""
    rounds = load_rounds(bench_dir)
    try:
        with open(detail_path) as fh:
            detail = json.load(fh)
        if not isinstance(detail, dict):
            detail = {}
    except (OSError, ValueError):
        detail = {}
    if not rounds and not detail:
        print(json.dumps({"regress": "no history",
                          "bench_dir": bench_dir}))
        return 2
    res = evaluate(rounds, detail)
    if not dry:
        append_trajectory(detail_path, res["trajectory_row"])
    line = {
        "metric": f"perf-regression sentry over {res['rounds_seen']} "
                  f"round(s) + {res['trajectory_len']} trajectory "
                  f"row(s)",
        "value": len(res["findings"]),
        "unit": "regressions",
        "dry": dry,
        "metrics": res["metrics"],
    }
    if res["findings"]:
        line["findings"] = res["findings"]
    print(json.dumps(line))
    if res["findings"]:
        import sys
        for f in res["findings"]:
            sys.stderr.write(f"REGRESSION: {json.dumps(f)}\n")
        return 1
    return 0
