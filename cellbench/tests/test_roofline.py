"""The one share of a roofline (CPU; ``python -m pytest cellbench/tests``).

``collective_roofline`` is the only per-layer metric with ``roofline``
in its name since PR 38; ``cellbench/bytes.py`` is the one table of
required bytes behind it.  ONE parametrised test holds the table and
the reader together, a case for each of the seven collective cells at
its own size (read from the cell's files, so a cell that changes size
changes its case), one for a ragged 24,000,012 B on one chip (the
queue's first cell, ROADMAP S11), and one for each pair the table has
no row for; a second test holds ``BENCHMARK.json`` to "exactly one",
so that the next split of the rule fails here and not in a refused
claim (ledger, PR 35).

These take over from ``tests/test_cellbench_move.py``
(``test_required_bytes_across_chips`` x 2,
``test_no_required_bytes_rule_is_an_error``,
``test_move_roofline_reader``) and ``tests/test_cellbench_typed.py``
(``test_required_bytes_at_the_cells_size``,
``test_typed_roofline_reader``), which a ``benchmark`` PR may not edit.
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from cellbench import bytes as table  # noqa: E402
from cellbench import manifest, validate  # noqa: E402
from cellbench.readers import profiler_trace  # noqa: E402
from cellbench.traffic.blocking_collective import sizes  # noqa: E402

PEAKS = manifest.load_json(os.path.join(
    REPO, "cellbench", "peaks.json"))["TPU v5 lite"]
SPEC = manifest.metric_spec("collective_roofline")
GONE = ("move_roofline", "typed_roofline", "pack_unpack_us",
        "segments_per_iter", "inflight_segments")


def of_cell(name: str) -> tuple:
    """(op, ranks, bytes per rank, chips) as the cell's generator hands
    them to the readers: the packed stream where the call is typed."""
    spec = manifest.cell(name, REPO)
    cfg, traffic = spec["config"], spec["traffic"]
    return (traffic["op"], cfg["ranks"],
            sizes(traffic, cfg["ranks"], False) * 4, cfg["chips"])


# pair, required {"hbm", "ici"}, which peak bounds it, least us
CASES = [
    ("allreduce-4KiB.hbm8", 36864, 0, "hbm", 0.045),
    ("allreduce-128MiB.tpu4", 268435456, 201326592, "ici", 1006.633),
    ("allreduce-256MiB.hbm8", 2415919104, 0, "hbm", 2949.840),
    ("alltoall-4MiB.hbm8", 536870912, 0, "hbm", 655.520),
    ("bcast-64MiB.tpu4", 67108864, 67108864, "ici", 335.544),
    ("alltoall-4MiB.tpu4", 33554432, 12582912, "ici", 62.915),
    ("rsb-max-f64-vector-16MiB.hbm8", 150994944, 0, "hbm", 184.365),
    # not a multiple of anything: no pad is required work (PR 34 read
    # 88.98% of these 263.7 us on the chip)
    (("allreduce", 8, 24000012, 1), 216000108, 0, "hbm", 263.737),
    # no row: KeyError naming the pair, from the table and the reader
    (("reduce_scatter_block", 4, 16777216, 4), None, None, None, None),
    (("bcast", 8, 67108864, 1), None, None, None, None),
    (("sendrecv", 4, 33554432, 4), None, None, None, None),
    (("allgather", 8, 4096, 1), None, None, None, None),
]


@pytest.mark.parametrize(
    "pair,hbm,ici,bound,least_us", CASES,
    ids=[c[0] if isinstance(c[0], str) else "-".join(map(str, c[0]))
         for c in CASES])
def test_required_bytes_and_the_share(pair, hbm, ici, bound, least_us):
    op, ranks, nbytes, chips = of_cell(pair) if isinstance(pair, str) \
        else pair
    facts = {"op": op, "ranks": ranks, "bytes_per_rank": nbytes,
             "chips": chips, "platform": "tpu", "peaks": PEAKS,
             "kernel_events": ["^jit_"],
             "trace": {"kernel_events_matched": True,
                       "kernel_s_per_iter": 1e-3}}
    said = []
    if hbm is None:
        with pytest.raises(KeyError, match=op):
            table.required(op, ranks, nbytes, chips)
        with pytest.raises(KeyError, match=op):
            profiler_trace.read(SPEC, facts, said.append)
        return
    assert table.required(op, ranks, nbytes, chips) == {"hbm": hbm,
                                                        "ici": ici}
    least, by = table.least_seconds(op, ranks, nbytes, chips, PEAKS)
    assert by == bound
    assert least * 1e6 == pytest.approx(least_us, abs=0.001)
    # the reader: the table's least time over the kernel's, unclipped
    facts["trace"]["kernel_s_per_iter"] = 3 * least
    share = profiler_trace.read(SPEC, facts, said.append)
    assert share == pytest.approx(100.0 / 3)
    assert f"bound by {bound}" in said[0] and str(hbm) in said[0]
    fast = dict(facts, trace=dict(facts["trace"],
                                  kernel_s_per_iter=least / 2))
    assert profiler_trace.read(SPEC, fast, said.append) \
        == pytest.approx(200.0)            # a wrong count shows as one
    # nothing to read gives nothing, never a 0: no program matched (a
    # library that names its programs otherwise), no device plane, off
    # the chip
    unmatched = dict(facts, trace=dict(facts["trace"],
                                       kernel_events_matched=False))
    assert profiler_trace.read(SPEC, unmatched, said.append) is None
    assert "no program matched" in said[-1]
    assert profiler_trace.read(SPEC, dict(facts, trace={}),
                               said.append) is None
    assert profiler_trace.read(SPEC, dict(facts, platform="cpu"),
                               said.append) is None


def test_the_manifest_has_one_share_of_a_roofline():
    assert validate.check(REPO) == []
    man = manifest.manifest(REPO)
    shares = [m for m in man["per_layer"]
              if "roofline" in m["name"] or "mfu" in m["name"]]
    assert [m["name"] for m in shares] == ["collective_roofline"]
    (share,) = shares
    assert (share["moves"], share["unit"], share["better"],
            share["source"]) == ("iter_us", "%", "higher", "device_trace")
    # every collective cell is on its list, and the table serves each
    cells = {w["name"]: manifest.cell(w["name"], REPO)
             for w in man["workloads"]}
    with_programs = [n for n, c in cells.items()
                     if c["pairing"]["kernel_events"]]
    assert share["workloads"] == with_programs and len(with_programs) >= 7
    pairs = set()
    for name in share["workloads"]:
        op, ranks, nbytes, chips = of_cell(name)
        need = table.required(op, ranks, nbytes, chips)
        assert need["hbm"] > 0 and (need["ici"] > 0) == (chips > 1), name
        pairs.add((op, chips > 1))
        assert "collective_roofline" in {
            m["name"] for m in cells[name]["per_layer"]}
        assert "iter_us" in {m["name"] for m in cells[name]["end_to_end"]}
    assert pairs == set(table.RULES)        # no row without a cell
    # the ring is off the list: a device_put is no program (C8)
    ring = "sendrecv-ring-32MiB.tpu4"
    assert ring not in share["workloads"] and ring in SPEC["not_listed"]
    # the metric's own file repeats the manifest, list and all
    for k in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert SPEC[k] == share[k], k
    assert (SPEC["reader"], SPEC["field"]) == ("profiler_trace",
                                               "collective_roofline")
    # what PR 38 took out is out of both places
    names = {m["name"] for m in man["per_layer"]}
    for gone in GONE:
        assert gone not in names
        assert not os.path.exists(os.path.join(
            REPO, "cellbench", "metrics", gone + ".json"))
    assert not os.path.exists(os.path.join(
        REPO, "cellbench", "readers", "pvars.py"))
