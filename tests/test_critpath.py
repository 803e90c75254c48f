"""Critical-path profiler (ISSUE 13 / docs/DESIGN.md §18): phase-span
sampling exactness, the gating-verdict rule on synthetic spans, a
4-rank injected-straggler world whose analysis must name the delayed
rank (and the rendezvous phase) as gating with >=90% of op wall time
attributed to named phases, embedded mpisync offsets in the dumps, the
flow-arrow-stitched Chrome trace, and the hotpath_audit declarations
for the new phase record points."""

import json
import os

import pytest

from ompi_tpu import trace
from ompi_tpu.mca.params import registry
from ompi_tpu.testing import run_ranks
from ompi_tpu.tools import critpath, traceview

# the large-message tier's knobs (the test_coll_pipeline PIPE_ON
# shape): a 16 KiB allreduce is a planned segring of 5 segments, one
# rendezvous, with a pack and an unpack around it (4,099 is ragged)
_PIPE_ON = {
    "coll_pipeline_enable": True,
    "coll_pipeline_min_bytes": 2048,
    "coll_seg_size": 4096,
    "coll_pipeline_rd_max_bytes": 0,
    "coll_hier_enable": False,
}


@pytest.fixture(autouse=True)
def _clean():
    yield
    registry.set("trace_enable", "0")
    registry.set("trace_dump_path", "")
    registry.set("trace_phase_enable", "0")
    registry.set("trace_sample_spec", "")
    registry.set("trace_sample_auto", "1024")
    registry.set("trace_sample_max", "64")
    registry.set("coll_pipeline_enable", "0")
    registry.set("coll_pipeline_min_bytes", "1048576")
    registry.set("coll_seg_size", "1048576")
    registry.set("coll_pipeline_rd_max_bytes", "0")
    registry.set("coll_hier_enable", "0")
    registry.set("ft_inject_plan", "")
    registry.set("ft_inject_skip", "8")
    registry.set("ft_inject_delay_ms", "20")


# -- sampling exactness for the new category --------------------------------

def test_phase_sampling_exact():
    """The phase category obeys the same exactness invariant as every
    other sampled category: kept + sampled-out == seen, and the pvar
    accessors agree with the manual count."""
    registry.set("trace_sample_spec", "phase:4")
    registry.set("trace_sample_auto", "0")   # pin the period
    tr = trace.Tracer(0, capacity=4096)
    kept = 0
    for i in range(100):
        t0 = tr.start_sampled(trace.CAT_PHASE)
        if t0:
            tr.end(t0, trace.NAME_PH_DISPATCH, trace.CAT_PHASE, 1, i, 0)
            kept += 1
    assert kept == 25                      # exactly 1-in-4
    assert tr.cat_seen("phase") == 100
    assert tr.dropped_by_cat()["phase"] == 100 - kept
    assert tr.span_count("phase") == kept
    assert tr.sampling_rates()["phase"] == 4


def test_phase_totals_label_merge():
    """phase_totals folds span names into report labels (fused_pack
    and ph_pack are both 'pack')."""
    registry.set("trace_sample_auto", "0")
    tr = trace.Tracer(0, capacity=64)
    tr.phase = True
    # ph_execute: no site records it since PR 26 took the fence away;
    # the label stays for dumps from before
    for name in (trace.NAME_PH_PACK, trace.NAME_FUSED_PACK,
                 trace.intern_name("ph_execute", ("cid", "seq", "nbytes"))):
        t0 = tr.start_sampled(trace.CAT_PHASE)
        tr.end(t0, name, trace.CAT_PHASE, 1, 0, 0)
    tot = tr.phase_totals()
    assert set(tot) == {"pack", "execute"}
    assert tot["pack"] >= 0 and tot["execute"] >= 0


# -- the gating rule on synthetic spans -------------------------------------

def _sp(rank, ts, dur, name, cat, **args):
    return {"rank": rank, "ts": ts, "dur": dur, "name": name,
            "cat": cat, "ph": "X", "args": args}


def test_gating_verdict_skew_vs_phase():
    """A gate whose recorded phases are dwarfed by the arrival skew is
    arrival-gated ('rendezvous'); a gate with a contained phase at
    least as large as the skew is gated by THAT phase."""
    events = [
        # group A: rank 1 arrives 5000 us late, tiny execute span
        _sp(0, 0.0, 5100.0, "meet", "coll_dispatch", cid=1, seq=0),
        _sp(1, 5000.0, 100.0, "meet", "coll_dispatch", cid=1, seq=0),
        _sp(1, 5010.0, 40.0, "ph_execute", "phase", cid=1, seq=0),
        # group B: rank 1 arrives 10 us late but burns 80 us executing
        _sp(0, 9000.0, 100.0, "meet", "coll_dispatch", cid=1, seq=1),
        _sp(1, 9010.0, 90.0, "meet", "coll_dispatch", cid=1, seq=1),
        _sp(1, 9012.0, 80.0, "ph_execute", "phase", cid=1, seq=1),
    ]
    idx = critpath.phase_index(events)
    groups = critpath.group_ops(events)
    ga, skew_a = critpath._gate_of(groups[("coll_dispatch", "meet", 1, 0)])
    gb, skew_b = critpath._gate_of(groups[("coll_dispatch", "meet", 1, 1)])
    assert ga["rank"] == 1 and skew_a == 5000.0
    assert critpath.gating_verdict(ga, skew_a, idx) == "rendezvous"
    assert gb["rank"] == 1 and skew_b == 10.0
    assert critpath.gating_verdict(gb, skew_b, idx) == "execute"


def test_clipped_attribution_never_exceeds_op():
    """Phase time is clipped to the op window — a finish-wait overlap
    can never attribute more than 100% of an op span."""
    op = _sp(0, 100.0, 50.0, "meet", "coll_dispatch", cid=1, seq=0)
    phases = [
        _sp(0, 90.0, 40.0, "ph_dispatch", "phase", cid=1, seq=0),
        _sp(0, 120.0, 400.0, "ph_execute", "phase", cid=1, seq=0),
    ]
    assert critpath._clipped_phase_us(op, phases) <= op["dur"]


# -- the acceptance world: injected straggler named as gating ---------------

def _segring_world(tmp_path, victim=None):
    """One 4-rank planned-segring world, phase-profiled at full
    fidelity, dumped to tmp_path; when ``victim`` is set that rank
    straggles 40 ms at every rendezvous deposit (ft_inject)."""
    registry.set("trace_enable", "1")
    registry.set("trace_dump_path", str(tmp_path))
    registry.set("trace_phase_enable", "1")
    registry.set("trace_sample_auto", "0")   # full fidelity
    for k, v in _PIPE_ON.items():
        registry.set(k, v)
    if victim is not None:
        registry.set("ft_inject_plan", "delay:1.0")
        registry.set("ft_inject_skip", "0")
        registry.set("ft_inject_delay_ms", "40")

    def fn(comm):
        import jax
        import jax.numpy as jnp
        from ompi_tpu.op.op import SUM
        if victim is not None and comm.rank != victim:
            # disarm the injector cache: only the victim straggles
            comm.state._coll_delay_inj = False
        x = jax.device_put(
            jnp.arange(4099, dtype=jnp.float32) + comm.rank,
            comm.device)
        for _ in range(3):
            x = comm.allreduce_arr(x, SUM)
        comm.Barrier()
        return float(x[0])

    res = run_ranks(4, fn, devices=True, timeout=240)
    assert len(set(res)) == 1              # the collectives agreed
    dumps = traceview.load_dumps([str(tmp_path / "trace-r*.json")])
    assert len(dumps) == 4
    offsets = traceview.embedded_offsets(dumps)
    assert len(offsets) == 4               # satellite: auto-embedded
    return dumps, offsets


def test_phase_coverage_on_clean_segring(tmp_path):
    """Acceptance: on a clean 4-rank planned-segring run, >=90% of op
    wall time is attributed to named phases, and the dispatch-tax
    table has per-phase medians for the segring tier."""
    dumps, offsets = _segring_world(tmp_path)
    doc = critpath.analyze(dumps, offsets)
    assert doc["coverage"] >= 0.90, doc
    assert doc["multi_rank_ops"] > 0
    assert any("segring" in k for k in doc["tax"]), doc["tax"]


def test_injected_delay_names_gating_rank(tmp_path):
    """4-rank planned-segring world with a deterministic ft_inject
    rendezvous delay on ONE rank: the critical-path analysis must name
    that rank as gating (arrival-gated: 'rendezvous') and stitch flow
    arrows into the Chrome trace."""
    victim = 2
    dumps, offsets = _segring_world(tmp_path, victim=victim)

    # judge only ops whose arrival skew clears scheduler noise: every
    # surviving stall should trace back to the injected straggler
    doc = critpath.analyze(dumps, offsets, min_skew_us=20000.0)
    gating = doc["gating"]
    assert gating, doc
    victim_gated = sum(v for k, v in gating.items()
                       if k.startswith(f"r{victim}:"))
    assert victim_gated > sum(gating.values()) / 2, gating
    top_key = next(iter(gating))
    assert top_key == f"r{victim}:rendezvous", gating
    # the injected 40 ms stall shows up as arrival skew
    assert doc["skew_us"]["max"] >= 20000.0, doc["skew_us"]

    # CLI smoke: --json output parses, -o writes flow arrows
    out = tmp_path / "stitched.json"
    rc = critpath.main([str(tmp_path / "trace-r*.json"),
                        "-o", str(out), "--json"])
    assert rc == 0
    stitched = json.loads(out.read_text())
    phs = {e.get("ph") for e in stitched["traceEvents"]}
    assert "s" in phs and "f" in phs       # perfetto flow arrows


# -- audit wiring -----------------------------------------------------------

def test_hotpath_audit_declares_phase_helpers():
    """The per-op phase record points are held to the zero-allocation
    budget by the same AST lint as the tracer itself."""
    from ompi_tpu.tools import hotpath_audit
    assert "_phase_fn" in hotpath_audit.HOT_FUNCTIONS[
        "ompi_tpu/coll/device.py"]
    # the rendezvous' own record points are inline in Rendezvous
    # (PR 26); what they call is audited with the tracer
    assert "_stacked_exec" in hotpath_audit.HOT_FUNCTIONS[
        "ompi_tpu/coll/device.py"]
    assert "Tracer.end_at2" in hotpath_audit.HOT_FUNCTIONS[
        "ompi_tpu/trace/__init__.py"]
    assert "Plan.execute" in hotpath_audit.HOT_FUNCTIONS[
        "ompi_tpu/coll/plan.py"]
    # the router holds no hot function of its own any more
    assert "ompi_tpu/coll/pipeline.py" not in hotpath_audit.HOT_FUNCTIONS
    assert hotpath_audit.audit() == []
