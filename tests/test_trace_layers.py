"""The phase profiler's account of a blocking device collective
(ISSUE 26 / docs/DESIGN.md §18): sampling of the operation categories
by sequence number (an operation kept on one member is kept on all,
with its one meeting and every phase), the exact layer accumulators and their
closure against the caller's own clock, no execute fence on the
blocking path, the off-cost guard at the new sites, and the
process-wide ``trace_layer_*`` pvars."""

import threading
import time

import pytest

from ompi_tpu import trace
from ompi_tpu.mca.params import registry
from ompi_tpu.op import op as mpi_op
from ompi_tpu.testing import run_ranks
from ompi_tpu.tools import critpath, traceview

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import ompi_tpu.coll.pipeline  # noqa: E402,F401  (registers the knobs)
import ompi_tpu.coll.plan  # noqa: E402,F401
from ompi_tpu.coll import device  # noqa: E402

# the large-message tier at test sizes: from 2 KiB up an operation is
# a compiled plan of 4 KiB segments behind one rendezvous
PIPE_ON = {"coll_pipeline_enable": True, "coll_pipeline_min_bytes": 2048,
           "coll_seg_size": 4096, "coll_pipeline_rd_max_bytes": 0,
           "coll_hier_enable": False}
TRACE_ON = {"trace_enable": True, "trace_phase_enable": True,
            "trace_buffer_events": 65536, "trace_sample_auto": 0}
OP_CATS = ("coll", "coll_dispatch", "coll_segment", "phase")


def _set(vals):
    saved = {k: registry.get(k) for k in vals}
    for k, v in vals.items():
        registry.set(k, v)
    return saved


def _restore(saved):
    for k, v in saved.items():
        registry.set(k, v)


def _world(n, fn, knobs, **kw):
    saved = _set(knobs)
    try:
        return run_ranks(n, fn, timeout=240, **kw)
    finally:
        _restore(saved)


def _one_dev(rank):
    return jax.devices()[0]


# -- B: an operation kept on one member is kept on all ----------------------

N_FUSED, N_SEG = 24, 6


@pytest.fixture(scope="module")
def period4(tmp_path_factory):
    """One 4-rank world at period 4 in every operation category:
    24 allreduces under the tier's crossover and 6 planned ones (4,099
    elements through the hop-explicit ring, which pads what four ranks
    cannot split: packed and unpacked); every rank's events, counters
    and dump."""
    dumps = tmp_path_factory.mktemp("period4")

    def fn(comm):
        tr = comm.state.tracer
        x = jax.device_put(jnp.arange(64, dtype=jnp.float32) + comm.rank,
                           comm.device)
        big = jax.device_put(
            jnp.arange(4099, dtype=jnp.float32) + comm.rank, comm.device)
        comm.Barrier()
        seen0 = {c: tr.cat_seen(c) for c in OP_CATS}
        kept0 = {c: tr.span_count(c) for c in OP_CATS}
        seq0 = comm._coll_seq
        for _ in range(N_FUSED):
            jax.block_until_ready(comm.allreduce_arr(x, mpi_op.SUM))
        for _ in range(N_SEG):
            jax.block_until_ready(comm.allreduce_arr(big, mpi_op.SUM))
        return {
            "rank": comm.rank, "cid": comm.cid, "seq0": seq0,
            "seq1": comm._coll_seq,
            "seen": {c: tr.cat_seen(c) - seen0[c] for c in OP_CATS},
            "kept": {c: tr.span_count(c) - kept0[c] for c in OP_CATS},
            "dropped": tr.dropped_by_cat(),
            "rates": tr.sampling_rates(),
            "events": [e for e in tr.snapshot()
                       if e["ph"] == "X"
                       and e["args"].get("cid") == comm.cid],
        }

    knobs = dict(PIPE_ON, coll_plan_native_reduce=False, **TRACE_ON)
    knobs.update(trace_dump_path=str(dumps), trace_sample_spec=",".join(
        f"{c}:4" for c in OP_CATS))
    res = _world(4, fn, knobs, devices=True)
    registry.set("trace_dump_path", "")
    return res, dumps


def _op_key(e):
    """The operation a span belongs to: (cid, collective sequence)."""
    a = e["args"]
    return (a["cid"], a["op"] if "op" in a else a["seq"])


@pytest.mark.parametrize("cat", OP_CATS)
def test_kept_operations_are_kept_on_every_member(period4, cat):
    """Every (cid, seq) a rank kept in this category, every other
    rank kept too; at period 4 the kept operations are exactly those
    whose sequence number divides by 4."""
    res, _ = period4
    per_rank = []
    for r in res:
        lo, hi = r["seq0"], r["seq1"]
        keys = {_op_key(e) for e in r["events"] if e["cat"] == cat
                and lo < _op_key(e)[1] <= hi}
        per_rank.append(keys)
    assert per_rank[0], f"no {cat} span kept at all"
    assert all(k == per_rank[0] for k in per_rank), per_rank
    assert all(seq % 4 == 0 for _cid, seq in per_rank[0])
    lo, hi = res[0]["seq0"], res[0]["seq1"]
    want = {s for s in range(lo + 1, hi + 1) if s % 4 == 0}
    if cat == "coll_segment":
        # only the planned operations have a plan_exec: the last N_SEG
        want = {s for s in want if s > hi - N_SEG}
    assert {seq for _cid, seq in per_rank[0]} == want
    assert res[0]["rates"][cat] == 4


def test_kept_operation_keeps_its_meeting_and_phases(period4):
    """A kept planned operation has, on every rank, its one plan_exec,
    its one meet, both rendezvous waits, its pack and its unpack, all
    carrying the key of its coll span (their parent); the publisher's
    steps are there once an operation, on the one rank that ran them."""
    res, _ = period4
    hi = res[0]["seq1"]
    seg_ops = {s for s in range(hi - N_SEG + 1, hi + 1) if s % 4 == 0}
    assert seg_ops
    published = {s: [] for s in seg_ops}
    for r in res:
        assert r["seq1"] == hi
        colls = {e["args"]["seq"] for e in r["events"]
                 if e["cat"] == "coll" and e["name"] == "allreduce_arr"}
        for s in seg_ops:
            assert s in colls       # the parent span is there

            def count(name, key="seq"):
                return sum(1 for e in r["events"] if e["name"] == name
                           and e["args"][key] == s)

            assert count("plan_exec", "op") == 1
            assert count("meet", "op") == 1
            assert count("ph_rdv_wait") == 2    # slot side, collect side
            assert count("ph_pack") == 1 and count("ph_unpack") == 1
            assert count("ph_entry") == 1
            steps = [count(n) for n in ("ph_dispatch", "ph_assemble",
                                        "ph_launch", "ph_scatter")]
            assert steps in ([0] * 4, [1] * 4)
            published[s].append(steps[0])
    assert all(sum(v) == 1 for v in published.values()), published


def test_sampling_accounts_exactly_per_category(period4):
    """kept + sampled_out == seen per category and rank, with seen
    counted from the operations the test issued: one coll and one
    phase decision per operation, one coll_segment sighting per
    planned operation."""
    res, _ = period4
    n_ops = N_FUSED + N_SEG
    for r in res:
        assert r["seq1"] - r["seq0"] == n_ops
        assert r["seen"]["coll"] == n_ops
        assert r["seen"]["coll_segment"] == N_SEG
        kept_ops = sum(1 for s in range(r["seq0"] + 1, r["seq1"] + 1)
                       if s % 4 == 0)
        assert r["kept"]["coll"] == kept_ops
        # phase: one decision per op; a kept op writes many spans
        phase_ops = {e["args"]["seq"] for e in r["events"]
                     if e["cat"] == "phase"
                     and r["seq0"] < e["args"]["seq"] <= r["seq1"]}
        assert len(phase_ops) == kept_ops
        # span counts and the skip counter are one account
        tr_seen = r["seen"]
        for c in OP_CATS:
            assert tr_seen[c] >= r["kept"][c]
    # every member saw alike (in the phase category a member's count
    # of KEPT spans differs by the publisher's own, so that one is
    # compared on what it skipped)
    assert len({tuple(r["seen"][c] for c in OP_CATS[:3])
                for r in res}) == 1
    assert len({r["seen"]["phase"] - r["kept"]["phase"]
                for r in res}) == 1


def test_critpath_correlates_every_kept_operation(period4):
    """The gating table's input at period 4: every kept whole-op span
    (coll, meet) has a member from every rank, so every kept
    operation is correlated."""
    _, dumps_dir = period4
    dumps = traceview.load_dumps([str(dumps_dir / "trace-r*.json")])
    assert len(dumps) == 4
    events = traceview.corrected_events(
        dumps, traceview.embedded_offsets(dumps))
    groups = critpath.group_ops(events)
    checked = 0
    for key, members in groups.items():
        if key[0] not in ("coll_dispatch", "coll_segment") \
                and not (key[0] == "coll" and key[1] == "allreduce_arr"):
            continue
        assert {m["rank"] for m in members} == {0, 1, 2, 3}, key
        checked += 1
    assert checked >= (N_FUSED + N_SEG) // 4
    doc = critpath.analyze(dumps, traceview.embedded_offsets(dumps))
    assert doc["multi_rank_ops"] >= checked
    assert sum(doc["gating"].values()) >= checked
    # the new span names carry their labels into the report, and the
    # collect-side waits say how they split
    assert critpath.PHASE_OF["ph_launch"] == "launch"
    assert {"entry", "exit", "launch", "scatter"} <= set(
        doc["phase_wall_us"])
    rs = doc["rendezvous_split_us"]
    assert rs["skew"] > 0 and rs["wake"] > 0
    assert "rendezvous wait split" in critpath.report(doc)


def test_fused_flushes_tick_the_sequence_and_sample_alike():
    """A FusedRequest draws no sequence number, so each flush ticks
    the communicator's itself: 16 flushes of fused iallreduce at
    period 4 keep exactly the 4 whose number divides by 4, on every
    member alike, and every flush's meet carries its own key."""
    n = 16

    def fn(comm):
        tr = comm.state.tracer
        x = jax.device_put(jnp.ones(32, jnp.float32), comm.device)
        comm.Barrier()
        seq0 = comm._coll_seq
        skipped0 = tr.dropped_by_cat()["coll_dispatch"]
        for _ in range(n):
            a = comm.iallreduce_arr(x, mpi_op.SUM)
            b = comm.iallreduce_arr(x, mpi_op.MAX)
            a.wait()
            b.wait()
        ev = [e for e in tr.snapshot() if e["ph"] == "X"
              and e["args"].get("cid") == comm.cid]
        flushes = sorted(e["args"]["seq"] for e in ev
                         if e["name"] == "fused_flush")
        meets = sorted(e["args"]["op"] for e in ev if e["name"] == "meet"
                       and e["args"]["op"] > seq0)
        return (comm._coll_seq - seq0, flushes, meets,
                tr.dropped_by_cat()["coll_dispatch"] - skipped0)

    knobs = dict(TRACE_ON, trace_sample_spec=",".join(
        f"{c}:4" for c in OP_CATS))
    res = _world(4, fn, knobs, devices=True)
    assert len({(t, tuple(f), tuple(m), d) for t, f, m, d in res}) == 1
    ticked, flushes, meets, skipped = res[0]
    assert ticked == n
    assert len(flushes) == n // 4 and all(s % 4 == 0 for s in flushes)
    assert meets == flushes               # one kept meet per kept flush
    assert skipped == n - n // 4          # the rest counted sampled-out


def test_keep_is_a_pure_function_of_shared_parameters():
    """Two tracers built from the same parameters decide alike for
    every sequence number, whatever each saw before; the period backs
    off with the sequence number, by ring-fulls."""
    saved = _set({"trace_sample_spec": "coll:2", "trace_sample_auto": 16,
                  "trace_sample_max": 16})
    try:
        a, b = trace.Tracer(0, 8192), trace.Tracer(3, 8192)
        for s in range(7, 90, 3):        # b saw other operations
            b.keep(trace.CAT_COLL, s)
        da = [a.keep(trace.CAT_COLL, s) for s in range(1, 200)]
        db = [b.kept(trace.CAT_COLL, s) for s in range(1, 200)]
        assert da == db
        assert a.sampling_rates()["coll"] == 16     # 2 -> 16 by seq 48
        kept = sum(da)
        assert a.cat_seen("coll") == 199 - kept     # end() counts kept
        # a ring 8 times the default backs off 8 times later: the
        # benchmark's traced runs (65,536 slots, a few thousand
        # operations a rank) stay at period 1
        registry.set("trace_sample_spec", "")
        registry.set("trace_sample_auto", 1024)
        big = trace.Tracer(0, 65536)
        assert all(big.kept(trace.CAT_PHASE, s) for s in range(1, 8192))
        assert not big.kept(trace.CAT_PHASE, 8193)
    finally:
        _restore(saved)


# -- A: the layer accumulators close ----------------------------------------

def _allreduce_sum(comm, x):
    return comm.allreduce_arr(x, mpi_op.SUM)


def _closure_world(n_ops, make_x, knobs, call=_allreduce_sum, **kw):
    """Per rank (wall ns of the loop, the layer accumulators' deltas
    over it).

    ``wall`` is stamped at the closing Barrier's ENTRY, where the
    account ends: a host collective closes the open caller interval at
    its entry, banks nothing else and opens none.  Stamped after the
    Barrier (as it was until ISSUE 37) the closure came out short by
    the barrier itself, a constant 6.4 to 7.9 ms whatever the loop
    took on the four-device plan path (63.0 of 69.5 ms, 64.4 of 72.3,
    83.4 of 90.3, 70.1 of 77.3): the Barrier read 7.30 and 7.44 ms on
    two ranks and 0.047 ms on the last to arrive.  The account was
    right; the test measured a barrier the account leaves out."""
    def fn(comm):
        tr = comm.state.tracer
        x = make_x(comm)
        for _ in range(5):
            jax.block_until_ready(call(comm, x))
        comm.Barrier()
        before = tr.layer_totals()
        t0 = time.perf_counter_ns()
        for _ in range(n_ops):
            jax.block_until_ready(call(comm, x))
        wall = time.perf_counter_ns() - t0
        comm.Barrier()    # its entry closes the last caller interval
        after = tr.layer_totals()
        return wall, {k: after[k] - before[k] for k in after}

    return _world(4, fn, dict(TRACE_ON, **knobs), **kw)


def test_layer_account_closes_over_blocking_allreduces():
    """200 blocking allreduce_arr calls on 4 thread-ranks: a rank's
    accumulators sum to its loop's wall time within 3%, one
    rendezvous per operation exactly, and the serve interval is the
    same on every member."""
    res = _closure_world(
        200, lambda comm: jax.device_put(
            jnp.arange(1024, dtype=jnp.float32) + comm.rank, comm.device),
        {"coll_pipeline_enable": False}, devices=True)
    serves = set()
    for wall, d in res:
        total = sum(d[k] for k in trace.LAYER_CLOSURE)
        assert abs(total - wall) <= 0.03 * wall, (total, wall, d)
        assert d["rendezvous"] == 200
        assert d["caller"] > 0 and d["exit"] > 0 and d["entry"] > 0
        assert d["pack"] == 0 and d["unpack"] == 0
        serves.add(d["rdv_serve"])
    # one t_full and one t_release per generation: every member adds
    # the same serve (clamped into its own wait: within a hair)
    assert max(serves) - min(serves) <= 0.02 * max(serves)
    # the unplanned mesh computation brings no traced twin: its steps
    # are not split (ph_dispatch covers them), the serve still banks
    assert all(d["launch"] == 0 for _w, d in res)


@pytest.mark.parametrize("where", ["mesh", "mesh_ring", "one_chip"])
def test_layer_account_on_the_plan_path(where):
    """The compiled plans (the benchmark's large allreduce shapes,
    test-sized), over four devices and on one chip: one rendezvous per
    operation exactly, closure within 3%.  A plan brings its traced
    twin, so the one publisher of each rendezvous banks the launch and
    the split.  The mesh cases are ragged (4,099 elements): the
    publisher assembles, and coll_pipeline_segments advances by 5
    segments an operation.  Under the native lowering the plan runs at
    the payload's own length and packs nothing; through the
    hop-explicit ring (mesh_ring) four ranks cannot split 4,099, so a
    pad and a trim sum with the rest.  The one-chip case (4,096) packs
    nothing and assembles nothing (the shards are the kernel's
    arguments)."""
    from ompi_tpu.coll import plan
    mesh = where != "one_chip"
    padded = where == "mesh_ring"
    n_ops, n_elems = (30, 4099) if mesh else (100, 4096)
    n0, p0 = plan.pv_segments.read(), plan.pv_padded.read()
    res = _closure_world(
        n_ops, lambda comm: jax.device_put(
            jnp.arange(n_elems, dtype=jnp.float32) + comm.rank,
            comm.device),
        dict(PIPE_ON, coll_plan_native_reduce=not padded),
        **({"devices": True} if mesh else {"device_map": _one_dev}))
    for wall, d in res:
        total = sum(d[k] for k in trace.LAYER_CLOSURE)
        assert abs(total - wall) <= 0.03 * wall, (total, wall, d)
        assert d["rendezvous"] == n_ops
        assert (d["pack"] > 0 and d["unpack"] > 0) if padded else (
            d["pack"] == 0 and d["unpack"] == 0)
    launch = sum(d["launch"] for _w, d in res)
    serve = max(d["rdv_serve"] for _w, d in res)
    assert 0 < launch <= serve
    assert sum(d["scatter"] for _w, d in res) > 0
    assert (sum(d["assemble"] for _w, d in res) > 0) == mesh
    # over all four rank-threads, the 5 warm-up operations of
    # _closure_world too (a plain += shared by the threads: allow it a
    # lost update or two)
    nsegs = 5 if mesh else 4
    assert abs((plan.pv_segments.read() - n0)
               - 4 * (n_ops + 5) * nsegs) <= 3 * nsegs
    moved = plan.pv_padded.read() - p0
    assert abs(moved - 4 * (n_ops + 5)) <= 3 if padded else moved == 0


def test_layer_account_on_one_chip_alltoall_above_threshold():
    """coll/hbm alltoall above coll_pipeline_min_bytes (the benchmark's
    alltoall cell, test-sized): the stacked path at every size, so one
    rendezvous per operation, nothing packed or unpacked, the
    pipeline's counters at rest, and closure within 3%."""
    from ompi_tpu.coll import pipeline, plan
    moved0 = (pipeline.pv_ops.read(), plan.pv_segments.read())
    knobs = {"coll_pipeline_enable": True, "coll_pipeline_min_bytes": 2048,
             "coll_seg_size": 4096}
    res = _closure_world(
        100, lambda comm: jax.device_put(
            jnp.arange(4096, dtype=jnp.float32) + comm.rank, comm.device),
        knobs, call=lambda comm, x: comm.alltoall_arr(x),
        device_map=_one_dev)
    for wall, d in res:
        total = sum(d[k] for k in trace.LAYER_CLOSURE)
        assert abs(total - wall) <= 0.03 * wall, (total, wall, d)
        assert d["rendezvous"] == 100
        assert d["pack"] == 0 and d["unpack"] == 0
        assert d["assemble"] == 0
    assert 0 < sum(d["launch"] for _w, d in res) <= max(
        d["rdv_serve"] for _w, d in res)
    assert (pipeline.pv_ops.read(), plan.pv_segments.read()) == moved0


def test_host_collectives_stay_out_of_the_account():
    """coll/sm borrows the device meeting point for host buffers: a
    job's own barriers bank nothing and count nothing, so the counts
    of a measured region repeat exactly."""
    def fn(comm):
        tr = comm.state.tracer
        x = jax.device_put(jnp.ones(16, jnp.float32), comm.device)
        jax.block_until_ready(comm.allreduce_arr(x, mpi_op.SUM))
        comm.Barrier()
        before = tr.layer_totals()
        for _ in range(10):
            comm.Barrier()
        after = tr.layer_totals()
        return before == after and tr._t_ret == 0

    assert all(_world(4, fn, TRACE_ON, devices=True))


# -- C: no execute fence on the blocking path -------------------------------

def test_rendezvous_publishes_before_the_result_is_ready():
    """With phase tracing on, the publisher publishes and every member
    leaves the rendezvous while the computation's result is still not
    ready: nothing on the path asks the result whether it is.
    Asserted by order of events, on a stand-in result that logs every
    such question and becomes ready only when the test says so."""
    saved = _set(TRACE_ON)
    log = []

    class Pending:
        """Stands for a device array still being computed."""
        def block_until_ready(self):
            log.append("asked")
            return self

        def is_ready(self):
            log.append("asked")
            return False

    def traced(shards, ph):
        log.append("computed")
        return [Pending(), Pending()]

    def fn(shards):
        raise AssertionError("the traced twin runs when a ctx is there")

    fn.traced = traced
    try:
        rv = device.Rendezvous(2)
        trs = [trace.Tracer(r, 256) for r in range(2)]
        outs = [None, None]

        def member(r):
            ph = (trs[r], 7, 16, 64, True)
            outs[r] = rv.run(r, r + 1, fn, ph=ph)
            log.append(f"left_{r}")

        ts = [threading.Thread(target=member, args=(r,)) for r in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        # both members are out with the pending result in hand, and
        # nobody asked it anything
        assert all(isinstance(o, Pending) for o in outs)
        assert log[0] == "computed" and set(log[1:]) == {"left_0", "left_1"}
        names = {e["name"] for tr in trs for e in tr.snapshot()}
        assert "ph_dispatch" in names and "ph_rdv_wait" in names
        assert "ph_execute" not in names    # gone with the fence
    finally:
        _restore(saved)


def test_no_fence_reachable_from_the_blocking_path():
    """Structural: neither Rendezvous.begin / finish nor the helpers
    they call on the publisher name block_until_ready, and no thread
    exists to wait on a result."""
    import inspect
    for fn in (device.Rendezvous.begin, device.Rendezvous.finish,
               device._phase_fn,
               device._mesh_exec, device._stacked_exec):
        src = inspect.getsource(fn)
        assert "block_until_ready" not in src, fn.__name__
    assert not hasattr(device, "_block_ready")
    assert not hasattr(device, "_completer")


# -- D: off costs nothing at the new sites ----------------------------------

def test_new_sites_read_no_clock_when_tracing_is_off(monkeypatch):
    """trace_enable off: the rendezvous, the publisher's steps, the
    plan resolution and the plans' pack and unpack stages take no
    timestamp (coll/device's clock explodes; no Tracer exists to read
    its own).  The ragged allreduce packs and unpacks on the mesh and
    on one chip; the mesh alltoall is planned, the one-device alltoall
    the stacked path."""
    assert not trace.enable_var.value

    def boom():
        raise AssertionError("clock read with tracing off")

    monkeypatch.setattr(device, "_now", boom)

    def fn(comm):
        assert comm.state.tracer is None
        x = jax.device_put(jnp.arange(4099, dtype=jnp.float32), comm.device)
        s = jax.device_put(jnp.ones(8, jnp.float32), comm.device)
        a = comm.allreduce_arr(s, mpi_op.SUM)          # fused
        b = comm.allreduce_arr(x, mpi_op.SUM)          # planned, ragged
        c = comm.alltoall_arr(jax.device_put(
            jnp.arange(4096, dtype=jnp.float32), comm.device))
        comm.Barrier()
        return float(a[0]), float(b[1]), c.shape

    for devs in ({"devices": True}, {"device_map": _one_dev}):
        res = _world(4, fn, PIPE_ON, **devs)
        assert {r[0] for r in res} == {4.0}
        assert {r[1] for r in res} == {4.0}
    assert not trace.live_tracers() or all(
        tr.rank >= 0 for tr in trace.live_tracers())


# -- E: the pvars sum every rank-thread's tracer ----------------------------

def test_layer_pvars_sum_all_rank_threads():
    """One pvar for each accumulator a metric reads, no other, each
    the sum over every rank-thread's tracer."""
    def fn(comm):
        tr = comm.state.tracer
        x = jax.device_put(jnp.ones(64, jnp.float32), comm.device)
        for _ in range(12):
            jax.block_until_ready(comm.allreduce_arr(x, mpi_op.SUM))
        comm.Barrier()
        mine = tr.layer_totals()
        pv = {p.full_name: p.read() for p in registry.all_pvars()
              if p.full_name.startswith("trace_layer_")}
        comm.Barrier()    # nobody moves on before everybody has read
        return mine, pv

    res = _world(4, fn, TRACE_ON, device_map=_one_dev)
    names = {f"trace_layer_{n}_ns" for n in trace.LAYERS} | {
        "trace_layer_rendezvous_count"}
    for mine, pv in res:
        assert set(pv) == names
        for lay in trace.LAYERS:
            assert pv[f"trace_layer_{lay}_ns"] == sum(
                m[lay] for m, _ in res)
        assert pv["trace_layer_launch_ns"] > 0
        assert pv["trace_layer_rendezvous_count"] == 4 * 12
        assert mine["rendezvous"] == 12   # one thread's is a quarter


# -- audit wiring -----------------------------------------------------------

def test_hotpath_audit_declares_the_layer_boundaries():
    from ompi_tpu.tools import hotpath_audit
    hot = hotpath_audit.HOT_FUNCTIONS
    for fn in ("Tracer.keep", "Tracer.kept", "Tracer.end_at",
               "Tracer.end_at2", "Tracer.lap", "Tracer.lap_to",
               "coll_begin", "coll_end"):
        assert fn in hot["ompi_tpu/trace/__init__.py"]
    for fn in ("_phase_fn", "_mesh_exec", "_stacked_exec"):
        assert fn in hot["ompi_tpu/coll/device.py"]
    assert hotpath_audit.audit() == []
