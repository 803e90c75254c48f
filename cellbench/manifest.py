"""Find a cell's files by the names in BENCHMARK.json.  jax-free.

Whatever belongs to one configuration, one traffic mix, one cell or one
per-layer metric sits in a file of its own:

    cellbench/configs/<config>.json     (BENCHMARK.json configs[].file)
    cellbench/traffic/<traffic>.json    parameters one generator reads
    cellbench/traffic/<generator>.py    the generator the mix names
    cellbench/workloads/<cell>.json     what belongs to the pairing
    cellbench/metrics/<metric>.json     the reader a metric uses
    cellbench/readers/<reader>.py       one per source

so a later PR adds files and BENCHMARK.json entries and edits nothing.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, root: str = ROOT) -> dict:
    """Everything one run needs to know about cell ``name``: the
    BENCHMARK.json entry, its configuration, its traffic mix, the
    pairing's own file and the metrics it reports."""
    man = manifest(root)
    entry = next((w for w in man["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r} (has: "
                       f"{[w['name'] for w in man['workloads']]})")
    cfg_entry = next(c for c in man["configs"]
                     if c["name"] == entry["config"])

    def reports(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in man["end_to_end"] if reports(m)]
    moved = {m["name"] for m in e2e}
    return {
        "entry": entry,
        "config": load_json(os.path.join(root, cfg_entry["file"])),
        "traffic": load_json(os.path.join(
            root, "cellbench", "traffic", entry["traffic"] + ".json")),
        "pairing": load_json(os.path.join(
            root, "cellbench", "workloads", name + ".json")),
        "end_to_end": e2e,
        # a metric with no workloads key is due wherever its end-to-end
        # metric is reported
        "per_layer": [m for m in man["per_layer"]
                      if reports(m) and m["moves"] in moved],
    }


def metric_spec(name: str, root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "cellbench", "metrics",
                                  name + ".json"))
