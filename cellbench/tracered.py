"""Reduction from a jax.profiler trace to device numbers.

``load()`` turns an ``.xplane.pb`` (or a recorded JSON of the same
content, cellbench/fixtures/) into plain planes -> lines -> events
``[name, start_ns, dur_ns]``; ``reduce()`` works on that alone, so the
arithmetic is checked on the CPU against a small trace recorded on the
chip (cellbench/tests).  ``cellbench/run.py --trace 1 --describe-trace
PREFIX`` writes what a trace holds (``describe``) and such a recording
(``record``).

What a TPU trace holds (read by hand on a v5e, PERF.md section 6): one
plane per chip, ``/device:TPU:<id>``, whose line ``XLA Modules`` has
one event per executed program (``jit_<fn>(<fingerprint>)``) and whose
line ``XLA Ops`` has one event per HLO op inside it; host threads are
lines of the plane ``/host:CPU``, where this benchmark's own
``cellbench_window`` TraceAnnotation marks the window on the trace's
clock.

* busy: the union of the ``XLA Ops`` intervals of a device, clipped to
  the window; ``busy_s`` is its mean over the devices used and the idle
  share comes from the fullest device (the largest busy).
* kernel time: the summed duration of the ``XLA Modules`` events whose
  name matches the cell's ``kernel_events`` patterns, on the fullest
  device, over the iterations of the window.  The cell's data names
  the patterns; no kernel name is in this code.
* idle gaps: each gap between busy intervals is given to the library
  phase span (any rank) that covers its midpoint, or to
  ``host:no_kept_span`` where sampling kept none.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re

WINDOW = "cellbench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LOOK_BACK = 32   # host spans examined per idle gap


def find(path: str):
    """The newest .xplane.pb under a profiler log directory."""
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def load(path: str, everything: bool = False) -> dict:
    """{plane: {line: [[name, start_ns, dur_ns], ...]}}.  Of the host's
    planes only the window annotation is kept (a segmented cell's host
    threads log some 10**5 events a second), unless ``everything``."""
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData
    planes = {}
    for pl in ProfileData.from_file(path).planes:
        lines = planes.setdefault(pl.name, {})
        device = pl.name.startswith("/device:")
        for ln in pl.lines:
            evs = lines.setdefault(ln.name, [])
            for e in ln.events:
                if device or everything or e.name == WINDOW:
                    evs.append([e.name, int(e.start_ns),
                                int(e.duration_ns)])
    return planes


def device_planes(planes: dict) -> dict:
    return {k: v for k, v in planes.items()
            if k.startswith("/device:") and OPS_LINE in v}


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def window_of(planes: dict):
    """[start, end) of the cellbench_window annotation, else the span
    of all device ops."""
    for pname, lines in planes.items():
        if pname.startswith("/device:"):
            continue
        for evs in lines.values():
            for name, s, d in evs:
                if name == WINDOW:
                    return s, s + d
    spans = [(s, s + d) for lines in device_planes(planes).values()
             for _, s, d in lines[OPS_LINE]]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(planes: dict, iters: int, kernel_events=(), host_spans=(),
           device_ids=None) -> dict:
    """The device numbers of one traced window; {} when the trace shows
    no device plane.  ``host_spans``: (name, start_ns, end_ns) on the
    trace's clock, for naming idle gaps.  ``device_ids``: the devices
    the layout uses (all, when None)."""
    devs = device_planes(planes)
    if device_ids is not None:
        devs = {k: v for k, v in devs.items()
                if int(k.rsplit(":", 1)[-1]) in device_ids}
    win = window_of(planes)
    if not devs or win is None:
        return {}
    w0, w1 = win
    pats = [re.compile(p) for p in kernel_events]
    per = {}
    for pname, lines in devs.items():
        busy = union([max(s, w0), min(s + d, w1)]
                     for _, s, d in lines[OPS_LINE]
                     if s + d > w0 and s < w1)
        kern = sum(d for name, s, d in lines.get(MODULES_LINE, [])
                   if w0 <= s < w1 and any(p.search(name) for p in pats))
        per[pname] = {"busy": busy,
                      "busy_ns": sum(e - s for s, e in busy),
                      "kernel_ns": kern}
    full = max(per, key=lambda k: per[k]["busy_ns"])
    ops = {}
    for name, s, d in devs[full][OPS_LINE]:
        if s + d > w0 and s < w1:
            ops[name] = ops.get(name, 0) + d
    gaps = {}
    edges = [w0] + [t for iv in per[full]["busy"] for t in iv] + [w1]
    spans = sorted(host_spans, key=lambda h: h[1])
    begins = [h[1] for h in spans]
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) // 2
        # the latest-starting spans at or before the midpoint: one per
        # rank-thread can be open at once, so a short look back is all
        at = bisect.bisect_right(begins, mid)
        who = next((h[0] for h in reversed(spans[max(0, at - LOOK_BACK):at])
                    if mid < h[2]), "host:no_kept_span")
        gaps[who] = gaps.get(who, 0) + (b - a)

    def top(d):
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(p["busy_ns"] for p in per.values()) / len(per) * 1e-9,
        "fullest": full,
        "fullest_busy_s": per[full]["busy_ns"] * 1e-9,
        "kernel_s_per_iter": per[full]["kernel_ns"] * 1e-9 / max(1, iters),
        "kernel_events_matched": per[full]["kernel_ns"] > 0,
        "devices": len(per),
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
    }


def reduce_dir(trace_dir: str, facts: dict, say) -> dict:
    """Reduce the trace a run just wrote.  The library's phase spans
    are on the wall clock; the trace's clock is tied to it through the
    window annotation, whose start is ``facts['wall_open']``."""
    path = find(trace_dir) if trace_dir else None
    if path is None:
        say("trace: the profiler wrote no .xplane.pb")
        return {}
    planes = load(path, everything=bool(facts.get("describe_to")))
    if facts.get("describe_to"):
        # the hand-read account and the small recorded trace of
        # cellbench/fixtures/ are made from this
        os.makedirs(os.path.dirname(facts["describe_to"]), exist_ok=True)
        with open(facts["describe_to"] + ".txt", "w") as f:
            describe(planes, f)
        w = window_of(planes)
        if w is not None:
            with open(facts["describe_to"] + ".json", "w") as f:
                json.dump(record(planes, w[0], w[0] + 50_000_000), f,
                          separators=(",", ":"))
    say(f"trace: {os.path.getsize(path)} bytes, planes "
        + str({k: {ln: len(ev) for ln, ev in v.items()}
               for k, v in planes.items() if k.startswith("/device:")}))
    win = window_of(planes)
    host = []
    if win is not None:
        off = win[0] - int(facts["wall_open"] * 1e9)
        for ph, ts, dur, _ in facts["spans"].reshape(-1, 4):
            if ph >= 0:
                host.append((facts["phases"][ph], ts + off, ts + off + dur))
    out = reduce(planes, facts["iters"], facts["kernel_events"], host,
                 facts["device_ids"] if facts["platform"] == "tpu"
                 else None)
    if not out:
        say("trace: no device plane in the profiler's trace")
    return out


def describe(planes: dict, out) -> None:
    for pname, lines in planes.items():
        out.write(f"plane {pname!r}\n")
        for lname, evs in lines.items():
            tot = {}
            for name, s, d in evs:
                c = tot.setdefault(name, [0, 0])
                c[0] += 1
                c[1] += d
            t0 = min((s for _, s, _ in evs), default=0)
            t1 = max((s + d for _, s, d in evs), default=0)
            out.write(f"  line {lname!r}: {len(evs)} events, "
                      f"[{t0}, {t1}] ns\n")
            for name, (c, d) in sorted(tot.items(),
                                       key=lambda kv: -kv[1][1])[:12]:
                out.write(f"    {c:7d} x {d / max(c, 1) / 1e3:12.3f} us  "
                          f"{name[:100]}\n")


def record(planes: dict, lo_ns: int, hi_ns: int) -> dict:
    """A small copy: device planes and the window annotation, events
    starting in [lo, hi)."""
    out = {}
    for pname, lines in planes.items():
        for lname, evs in lines.items():
            keep = [[e[0], e[1], min(e[2], hi_ns - e[1])]
                    for e in evs if e[0] == WINDOW
                    or (pname.startswith("/device:")
                        and lname in (OPS_LINE, MODULES_LINE)
                        and lo_ns <= e[1] and e[1] + e[2] <= hi_ns)]
            if keep:
                out.setdefault(pname, {})[lname] = keep
    return out
