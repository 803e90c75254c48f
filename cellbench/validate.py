#!/usr/bin/env python3
"""Validate BENCHMARK.json before a chip minute is spent.

    python3 cellbench/validate.py            # prints problems, exit 1

run.py calls ``check()`` before it starts a child.  jax-free, runs on
the CPU.  It holds the manifest to the letter of the rules a manifest
is refused over before any run (PR 22 was refused for a ``source`` with
a non-ASCII character): names, units, one-line strings of 1 to 200
printable ASCII characters, the keys each entry may have, that every
cell's and metric's files exist, that each per-layer metric names one
end-to-end metric that every cell of its ``workloads`` reports, and
that at most half the cells (rounded down, and always one) ask for 4
chips.
"""
from __future__ import annotations

import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|head|expan")


def line_ok(s) -> bool:
    """1 to 200 printable ASCII characters on one line, no tab."""
    return isinstance(s, str) and 1 <= len(s) <= 200 and all(
        32 <= ord(c) < 127 for c in s)


def safe_path(p) -> bool:
    return isinstance(p, str) and bool(PATH.match(p)) \
        and not p.startswith("/") and ".." not in p.split("/")


def check(root: str) -> list:
    """Every problem found, as one line each; [] when there is none."""
    bad = []
    path = os.path.join(root, "BENCHMARK.json")
    try:
        raw = open(path, "rb").read()
        man = json.loads(raw)
    except (OSError, ValueError) as e:
        return [f"BENCHMARK.json: {e}"]
    if len(raw) > 64 * 1024:
        bad.append(f"BENCHMARK.json is {len(raw)} bytes, over 64 KiB")
    if not isinstance(man, dict) or set(man) != TOP:
        return bad + [f"top-level keys must be exactly {sorted(TOP)}"]

    paths = man["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16
            and all(safe_path(p) for p in paths)):
        bad.append("paths: 1 to 16 relative directories of letters, "
                   "digits, '_', '.', '-', '/'")
        paths = []

    def under_paths(p):
        return safe_path(p) and any(
            p == d or p.startswith(d.rstrip("/") + "/") for d in paths)

    cmd = man["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(line_ok(w) for w in cmd)):
        bad.append("command: a list of 1 to 32 one-line strings")
    else:
        for w in cmd:
            if w.startswith("/") or ".." in w.split("/"):
                bad.append(f"command word {w!r} leaves the repo")
            elif os.path.exists(os.path.join(root, w)) and "/" in w \
                    and not under_paths(w):
                bad.append(f"command names {w!r}, a file outside paths")
    rs = man["run_seconds"]
    if not (isinstance(rs, int) and not isinstance(rs, bool)
            and 1 <= rs <= 51):
        bad.append("run_seconds: a whole number from 1 to 51")

    for sec, (need, may) in KEYS.items():
        items = man[sec]
        top = {"configs": 24, "workloads": 24, "end_to_end": 16,
               "per_layer": 128}[sec]
        if not (isinstance(items, list) and 1 <= len(items) <= top):
            bad.append(f"{sec}: 1 to {top} entries")
            man[sec] = []
            continue
        seen = set()
        for e in items:
            if not isinstance(e, dict):
                bad.append(f"{sec}: every entry is an object")
                continue
            nm = e.get("name")
            who = f"{sec[:-1] if sec.endswith('s') else sec} {nm}"
            if not need <= set(e) <= need | may:
                bad.append(f"{who}: keys must be {sorted(need)}"
                           + (f" (+ {sorted(may)})" if may else "")
                           + f", not {sorted(e)}")
                continue
            if not (isinstance(nm, str) and NAME.match(nm)):
                bad.append(f"{who}: name must match {NAME.pattern}")
            if nm in seen:
                bad.append(f"{who}: name appears twice")
            seen.add(nm)

    configs = {c.get("name"): c for c in man["configs"]
               if isinstance(c, dict)}
    files = set()
    for nm, c in configs.items():
        who = f"config {nm}"
        if not line_ok(c.get("source")):
            bad.append(f"{who}: source must be 1 to 200 printable ASCII "
                       f"characters on one line, not {c.get('source')!r}")
        if not line_ok(c.get("why")):
            bad.append(f"{who}: why must be 1 to 200 printable characters")
        f = c.get("file")
        if not under_paths(f):
            bad.append(f"{who}: file {f!r} is not under paths")
        elif not os.path.isfile(os.path.join(root, f)):
            bad.append(f"{who}: file {f} does not exist")
        elif f in files:
            bad.append(f"{who}: file {f} is another configuration's")
        else:
            try:
                body = json.load(open(os.path.join(root, f)))
                if body.get("source") != c.get("source"):
                    bad.append(f"{who}: {f} states another source")
            except ValueError as e:
                bad.append(f"{who}: {f}: {e}")
        files.add(f)
        red = c.get("reduced")
        if not (isinstance(red, list) and len(red) <= 16 and all(
                isinstance(k, str) and NAME.match(k) for k in red)):
            bad.append(f"{who}: reduced is a list of at most 16 names")
        else:
            for k in red:
                if WIDTH.search(k):
                    bad.append(f"{who}: reduced may not name a width "
                               f"({k!r})")

    cells = {w.get("name"): w for w in man["workloads"]
             if isinstance(w, dict)}
    pairs = set()
    used = set()
    for nm, w in cells.items():
        who = f"workload {nm}"
        if w.get("config") not in configs:
            bad.append(f"{who}: config {w.get('config')!r} is not defined")
        used.add(w.get("config"))
        t = w.get("traffic")
        if not (isinstance(t, str) and NAME.match(t)):
            bad.append(f"{who}: traffic must be a name")
        if (w.get("config"), t) in pairs:
            bad.append(f"{who}: this config and traffic appear twice")
        pairs.add((w.get("config"), t))
        if w.get("chips") not in (1, 4):
            bad.append(f"{who}: chips is 1 or 4")
        if not line_ok(w.get("why")):
            bad.append(f"{who}: why must be 1 to 200 printable characters")
        for rel in (f"workloads/{nm}.json", f"traffic/{t}.json"):
            p = os.path.join(root, "cellbench", rel)
            if not os.path.isfile(p):
                bad.append(f"{who}: cellbench/{rel} does not exist")
            elif rel.startswith("traffic/"):
                gen = json.load(open(p)).get("generator")
                if not os.path.isfile(os.path.join(
                        root, "cellbench", "traffic", f"{gen}.py")):
                    bad.append(f"{who}: generator cellbench/traffic/"
                               f"{gen}.py does not exist")
        cfgfile = configs.get(w.get("config"), {}).get("file")
        if cfgfile and os.path.isfile(os.path.join(root, cfgfile)):
            body = json.load(open(os.path.join(root, cfgfile)))
            if body.get("chips") != w.get("chips"):
                bad.append(f"{who}: chips {w.get('chips')} but {cfgfile} "
                           f"states {body.get('chips')}")
    for nm in configs:
        if nm not in used:
            bad.append(f"config {nm}: used by no cell")
    four = sum(1 for w in cells.values() if w.get("chips") == 4)
    if four > max(1, len(cells) // 2):
        bad.append(f"{four} of {len(cells)} cells ask for 4 chips; at most "
                   f"{max(1, len(cells) // 2)} may")

    def listed(m, who):
        ws = m.get("workloads")
        if ws is None:
            return list(cells)
        if not (isinstance(ws, list) and ws
                and all(x in cells for x in ws)):
            bad.append(f"{who}: workloads must list defined cells")
            return []
        return ws

    e2e = {}
    for m in man["end_to_end"]:
        if not isinstance(m, dict) or "name" not in m:
            continue
        who = f"end_to_end {m['name']}"
        e2e[m["name"]] = set(listed(m, who))
        if not (isinstance(m.get("unit"), str) and UNIT.match(m["unit"])):
            bad.append(f"{who}: unit must match {UNIT.pattern}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"{who}: better is lower or higher")
        if m.get("source") not in ("host_clock", "device_trace"):
            bad.append(f"{who}: source is host_clock or device_trace")
        b = m.get("bound")
        if not (isinstance(b, (int, float)) and not isinstance(b, bool)
                and 0.01 <= b <= 0.25):
            bad.append(f"{who}: bound must lie in [0.01, 0.25]")
    if "setup_s" not in e2e:
        bad.append("end_to_end: one metric must be setup_s")
    elif e2e["setup_s"] != set(cells):
        bad.append("end_to_end setup_s: every cell reports it")
    for nm in cells:
        if not any(nm in ws for k, ws in e2e.items() if k != "setup_s"):
            bad.append(f"workload {nm}: reports no end-to-end metric "
                       "besides setup_s")

    covered = set()
    for m in man["per_layer"]:
        if not isinstance(m, dict) or "name" not in m:
            continue
        who = f"per_layer {m['name']}"
        if not (isinstance(m.get("unit"), str) and UNIT.match(m["unit"])):
            bad.append(f"{who}: unit must match {UNIT.pattern}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"{who}: better is lower or higher")
        if m.get("source") not in SOURCES:
            bad.append(f"{who}: source is one of {SOURCES}")
        if not line_ok(m.get("layer")):
            bad.append(f"{who}: layer must be 1 to 200 printable "
                       "characters on one line")
        if "workloads" not in m:
            bad.append(f"{who}: carries no workloads list")
        ws = listed(m, who)
        covered.update(ws)
        mv = m.get("moves")
        if mv not in e2e:
            bad.append(f"{who}: moves {mv!r}, which is no end-to-end "
                       "metric")
        else:
            for w in ws:
                if w not in e2e[mv]:
                    bad.append(f"{who}: cell {w} does not report {mv}")
        spec = os.path.join(root, "cellbench", "metrics",
                            f"{m['name']}.json")
        if not os.path.isfile(spec):
            bad.append(f"{who}: cellbench/metrics/{m['name']}.json does "
                       "not exist")
        else:
            body = json.load(open(spec))
            for k in ("unit", "layer", "moves", "source"):
                if body.get(k) != m.get(k):
                    bad.append(f"{who}: metrics/{m['name']}.json states "
                               f"another {k}")
            if not os.path.isfile(os.path.join(
                    root, "cellbench", "readers",
                    f"{body.get('reader')}.py")):
                bad.append(f"{who}: reader cellbench/readers/"
                           f"{body.get('reader')}.py does not exist")
    for nm in cells:
        if nm not in covered:
            bad.append(f"workload {nm}: reports no per-layer metric")
    return bad


def main() -> int:
    root = sys.argv[1] if len(sys.argv) > 1 else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    bad = check(root)
    for b in bad:
        print("INVALID: " + b)
    if not bad:
        print("BENCHMARK.json is valid")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
