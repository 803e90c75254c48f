"""The two readers of the library's layer account (CPU;
``python -m pytest cellbench/tests``): ``pvar_sum`` and ``facts_field``
on facts recorded from traced runs on the chip
(cellbench/fixtures/facts_layers.json: the deltas each reader printed
and the value the run reported), a divisor that did not move, a
program that has none of the variables, and the new metrics' files
against BENCHMARK.json."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from cellbench import manifest, validate  # noqa: E402
from cellbench.readers import facts_field, pvar_sum  # noqa: E402

NEW = ("entry_exit_us", "rdv_skew_us", "rdv_wake_us", "serve_us",
       "launch_us", "assemble_scatter_us",
       "pack_unpack_per_iter_us", "caller_us", "rdv_per_iter",
       "unaccounted_us", "traced_iter_us")
RECORDED = manifest.load_json(os.path.join(
    REPO, "cellbench", "fixtures", "facts_layers.json"))["runs"]


def quiet(msg):
    pass


def facts_of(run: dict) -> dict:
    return {"iters": run["iters"], "ranks": run["ranks"],
            "iter_us": run["iter_us"],
            "pvars_before": {k: 0 for k in run["pvar_deltas"]},
            "pvars_after": dict(run["pvar_deltas"])}


@pytest.mark.parametrize("run", RECORDED, ids=lambda r: r["cell"])
def test_readers_reproduce_the_recorded_run(run):
    """Every new metric the cell is due, from the recorded deltas,
    comes out as the run on the chip reported it."""
    facts = facts_of(run)
    due = [m["name"] for m in manifest.cell(run["cell"])["per_layer"]
           if m["name"] in NEW]
    assert set(due) == set(run["reported"])
    for name in due:
        spec = manifest.metric_spec(name)
        reader = {"pvar_sum": pvar_sum, "facts_field": facts_field}[
            spec["reader"]]
        got = reader.read(spec, facts, quiet)
        assert got == pytest.approx(run["reported"][name], rel=1e-9,
                                    abs=1e-9), name
    # the closure: what the nine intervals do not cover is small
    assert abs(run["reported"]["unaccounted_us"]) \
        <= 0.05 * run["reported"]["traced_iter_us"]
    assert run["reported"]["rdv_per_iter"] == run["rdv_per_iter"]


def test_divisor_that_did_not_move_gives_nothing():
    facts = facts_of(RECORDED[0])
    spec = dict(manifest.metric_spec("launch_us"),
                per={"pvar": "coll_pipeline_segments"})
    facts["pvars_after"]["coll_pipeline_segments"] = 0
    assert pvar_sum.read(spec, facts, quiet) is None     # never 0
    facts["pvars_after"]["coll_pipeline_segments"] = 4
    facts["pvars_before"]["coll_pipeline_segments"] = 0
    assert pvar_sum.read(spec, facts, quiet) > 0
    facts["iters"] = 0
    assert pvar_sum.read(manifest.metric_spec("serve_us"), facts,
                         quiet) is None


def test_program_without_the_counters_gives_nothing():
    """The parent of the PR that brought the counters has none: the
    readers return nothing and do not raise, so its line leaves the
    metrics out."""
    facts = {"iters": 100, "ranks": 8, "iter_us": 1800.0,
             "pvars_before": {"coll_hbm_offloaded_collectives": 0},
             "pvars_after": {"coll_hbm_offloaded_collectives": 800}}
    for name in NEW:
        spec = manifest.metric_spec(name)
        if spec["reader"] == "pvar_sum":
            assert pvar_sum.read(spec, facts, quiet) is None, name
    assert facts_field.read({"field": "iter_us"}, facts, quiet) == 1800.0
    assert facts_field.read({"field": "no_such"}, facts, quiet) is None


def test_signed_remainder_and_scales():
    facts = {"iters": 10, "ranks": 2, "iter_us": 100.0,
             "pvars_before": {"a_ns": 1000, "b_ns": 0, "n": 5},
             "pvars_after": {"a_ns": 1_201_000, "b_ns": 900_000, "n": 45}}
    base = {"name": "x", "pvars": ["a_ns", "b_ns"], "scale": 0.001}
    assert pvar_sum.read(dict(base, per="rank_iteration"), facts,
                         quiet) == pytest.approx(105.0)
    assert pvar_sum.read(dict(base, per="iteration"), facts,
                         quiet) == pytest.approx(210.0)
    assert pvar_sum.read(dict(base, per={"pvar": "n"}), facts,
                         quiet) == pytest.approx(52.5)
    # a reading a little past the whole is a small negative time
    assert pvar_sum.read(dict(base, per="rank_iteration",
                              subtract_from="iter_us"), facts,
                         quiet) == pytest.approx(-5.0)


@pytest.mark.parametrize("name", NEW)
def test_new_metric_files_repeat_the_manifest(name):
    assert validate.check(REPO) == []
    entry = next(m for m in manifest.manifest(REPO)["per_layer"]
                 if m["name"] == name)
    body = manifest.metric_spec(name)
    for k in ("unit", "layer", "moves", "source", "workloads"):
        assert body[k] == entry[k], k
    assert body["reader"] in ("pvar_sum", "facts_field")
    assert os.path.isfile(os.path.join(
        REPO, "cellbench", "readers", body["reader"] + ".py"))
