"""Overload-robustness tests for the serving control plane (ISSUE 12):
priority admission + preemption must never fail a preempted job (it
resumes from checkpoint, byte-identical); per-session quotas degrade
then reject without poisoning the pool; deadline shedding rejects
infeasible work with a typed error at admission; live pool resize
grows/shrinks capacity under traffic with zero failed jobs; and the
FleetController closes the loop — all proven under ft_inject chaos
(dvm_disconnect, rank_kill) with ScopedPvar band-sum exactness held
across resize epochs."""

import os
import threading
import time

import pytest

from ompi_tpu.mca.params import registry

jax = pytest.importorskip("jax")

from ompi_tpu import obs as _obs  # noqa: E402
from ompi_tpu.tools.dvm import (DVMServer, DvmBusy,  # noqa: E402
                                DvmClient, DvmDeadline, DvmError,
                                _pv_preempts, _pv_resizes, _pv_sheds,
                                _send)

HERE = os.path.dirname(__file__)
PROG = os.path.join(HERE, "_dvm_session_prog.py")
SLOW_PROG = os.path.join(HERE, "_dvm_slow_prog.py")
CKPT_PROG = os.path.join(HERE, "_fleet_ckpt_prog.py")
HOST_PROG = os.path.join(HERE, "_fleet_host_prog.py")
BUDDY_PROG = os.path.join(HERE, "_fleet_buddy_prog.py")


def _set(vals):
    saved = {k: registry.get(k) for k in vals}
    for k, v in vals.items():
        registry.set(k, v)
    return saved


def _restore(saved):
    for k, v in saved.items():
        registry.set(k, v)


def _pool(tmp_path, capacity):
    uri = str(tmp_path / "dvm.uri")
    srv = DVMServer(capacity, devices=jax.devices(),
                    uri_file=uri).start()
    return srv, uri


def _digest(stdout, tag):
    for line in stdout.splitlines():
        parts = line.split()
        if len(parts) == 3 and parts[0] == "DIGEST" and parts[1] == tag:
            return parts[2]
    raise AssertionError(f"no DIGEST {tag} in: {stdout!r}")


def _resumed_at(stdout, tag):
    for line in stdout.splitlines():
        parts = line.split()
        if len(parts) == 3 and parts[0] == "STEPS" and parts[1] == tag:
            return int(parts[2])
    raise AssertionError(f"no STEPS {tag} in: {stdout!r}")


def _wait_for(pred, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


def _assert_band_sums_exact():
    """global == sum(bands) for every ScopedPvar — attribution never
    leaks or double-counts, including across resize epochs."""
    for sp in _obs.scoped_items():
        g = sp.pvar.read()
        s = sum(sp.bands)
        assert g == s, f"{sp.pvar.full_name}: global {g} != Σbands {s}"


# -- satellite 1: queue timeout knob ----------------------------------------


def test_queue_timeout_then_retry(tmp_path):
    """dvm_queue_timeout_s bounds an untimed queued attach with a
    friendly DvmBusy naming the knob; a later retry (after capacity
    frees) succeeds — timeout-then-retry is a working pattern."""
    srv, uri = _pool(tmp_path, 2)
    saved = _set({"dvm_queue_timeout_s": 1.0})
    try:
        c1 = DvmClient(uri)
        s1 = c1.attach(2)["sid"]
        c2 = DvmClient(uri)
        t0 = time.monotonic()
        with pytest.raises(DvmBusy, match="dvm_queue_timeout_s"):
            c2.attach(2)  # no client timeout: the knob bounds it
        assert time.monotonic() - t0 < 20
        c1.detach(s1)
        r = c2.attach(2)  # retry now succeeds
        c2.detach(r["sid"])
        c1.close()
        c2.close()
    finally:
        _restore(saved)
        srv.stop()


# -- satellite 2: dead queued client swept ----------------------------------


def test_dead_queued_client_swept_and_successor_admitted(tmp_path):
    """A client that dies WHILE QUEUED must not hold its place in
    line: the heartbeat sweep abandons its waiter and the session
    queued behind it is admitted as soon as capacity frees."""
    srv, uri = _pool(tmp_path, 2)
    saved = _set({"dvm_heartbeat_s": 0.3})
    try:
        c1 = DvmClient(uri)
        s1 = c1.attach(2)["sid"]
        doomed = DvmClient(uri)
        # fire the attach without waiting for the reply, so we can
        # kill the connection while the waiter sits in the queue
        _send(doomed.sock, {"op": "attach", "np": 2, "wait": True})
        _wait_for(lambda: len(srv._waiters) == 1,
                  what="doomed attach queued")
        got = {}

        def behind():
            with DvmClient(uri) as c3:
                r = c3.attach(2, timeout=60)
                got.update(r)
                c3.detach(r["sid"])

        th = threading.Thread(target=behind)
        th.start()
        _wait_for(lambda: len(srv._waiters) == 2,
                  what="successor queued behind the doomed client")
        doomed.sock.close()  # dies in line
        _wait_for(lambda: len(srv._waiters) == 1, timeout=15,
                  what="heartbeat sweep of the dead waiter")
        c1.detach(s1)  # frees capacity -> the SUCCESSOR admits
        th.join(timeout=60)
        assert "sid" in got, got
        c1.close()
    finally:
        _restore(saved)
        srv.stop()


# -- tentpole: priority admission -------------------------------------------


def test_priority_orders_admission_queue(tmp_path):
    """A higher-priority attach queued later is admitted first (FIFO
    within a priority level, priority across levels)."""
    srv, uri = _pool(tmp_path, 2)
    c1 = DvmClient(uri)
    s1 = c1.attach(2)["sid"]
    order = []

    def waiter(prio, name):
        with DvmClient(uri) as c:
            r = c.attach(2, timeout=60, priority=prio)
            order.append(name)
            time.sleep(0.3)  # hold briefly so admissions serialize
            c.detach(r["sid"])

    lo = threading.Thread(target=waiter, args=(0, "lo"))
    lo.start()
    _wait_for(lambda: len(srv._waiters) == 1, what="low-prio queued")
    hi = threading.Thread(target=waiter, args=(5, "hi"))
    hi.start()
    _wait_for(lambda: len(srv._waiters) == 2, what="high-prio queued")
    with srv.lock:
        assert srv._waiters[0].priority == 5, \
            "priority attach did not sort ahead of the FIFO waiter"
    c1.detach(s1)
    hi.join(timeout=60)
    lo.join(timeout=60)
    assert order == ["hi", "lo"]
    c1.close()
    srv.stop()


# -- tentpole: preemption (running + idle victims) --------------------------


def test_preempt_running_session_resumes_byte_identical(tmp_path):
    """A high-priority attach preempts a running preemptible session:
    the victim checkpoints-resumes (STEPS shows a nonzero restart),
    its client sees ONE successful slower run whose digest is
    byte-identical to an unpreempted baseline — never a failed job."""
    srv, uri = _pool(tmp_path, 2)
    steps, sleep_s = 10, 0.2
    # unpreempted baseline in its own store
    store_a = str(tmp_path / "store_a")
    cb = DvmClient(uri)
    sb = cb.attach(2)["sid"]
    rb = cb.run(sb, CKPT_PROG, ["base", store_a, str(steps)],
                timeout=240)
    assert rb["code"] == 0, rb["stderr"][-2000:]
    base_dig = _digest(rb["stdout"], "base")
    cb.detach(sb)
    cb.close()

    p0 = _pv_preempts.read()
    store_v = str(tmp_path / "store_v")
    cv = DvmClient(uri)
    sv = cv.attach(2, preemptible=True)["sid"]
    res = {}

    def victim_run():
        res["r"] = cv.run(sv, CKPT_PROG,
                          ["vic", store_v, str(steps), str(sleep_s)],
                          timeout=240)

    th = threading.Thread(target=victim_run)
    th.start()
    time.sleep(1.0)  # the victim is mid-run, a few steps checkpointed
    hi = DvmClient(uri)
    rh = hi.attach(2, priority=5, timeout=120)
    # the preemptor got the victim's ranks and can run immediately
    rr = hi.run(rh["sid"], PROG, ["hi"], timeout=120)
    assert rr["code"] == 0, rr["stderr"][-2000:]
    hi.detach(rh["sid"])
    hi.close()
    th.join(timeout=240)
    r = res["r"]
    assert r["code"] == 0, r["stderr"][-2000:]  # never a failed job
    assert r.get("preempted", 0) >= 1
    assert _pv_preempts.read() >= p0 + 1
    assert _resumed_at(r["stdout"], "vic") > 0, \
        "victim restarted from scratch instead of its checkpoint"
    assert _digest(r["stdout"], "vic") == base_dig
    cv.detach(sv)
    cv.close()
    srv.stop()


def test_preempt_idle_session_parks_then_resumes_transparently(tmp_path):
    """An idle preemptible victim is parked immediately (its ranks
    reclaimed for the preemptor); its next run re-admits and re-brings
    it up behind the scenes."""
    srv, uri = _pool(tmp_path, 2)
    p0 = _pv_preempts.read()
    cv = DvmClient(uri)
    sv = cv.attach(2, preemptible=True)["sid"]
    r0 = cv.run(sv, PROG, ["idle"], timeout=120)
    assert r0["code"] == 0, r0["stderr"][-2000:]
    hi = DvmClient(uri)
    rh = hi.attach(2, priority=1, timeout=60)
    with srv.lock:
        assert srv.sessions[sv].parked, "idle victim was not parked"
        assert srv.active_ranks == 2
    assert _pv_preempts.read() == p0 + 1
    hi.detach(rh["sid"])
    hi.close()
    # next run on the parked session: transparent re-admission
    r1 = cv.run(sv, PROG, ["idle"], timeout=240)
    assert r1["code"] == 0, r1["stderr"][-2000:]
    assert r1.get("preempted", 0) == 1
    assert r1["stdout"] == r0["stdout"]
    cv.detach(sv)
    cv.close()
    srv.stop()


# -- tentpole: live resize under traffic + chaos (satellite 4) --------------


def test_resize_under_traffic_zero_failed_jobs(tmp_path):
    """Grow 4->8 and shrink 8->4 while sessions are actively running:
    zero failed jobs, byte-identical outputs, both epochs recorded,
    and ScopedPvar band sums stay exact across the resize epochs."""
    srv, uri = _pool(tmp_path, 4)
    c0 = DvmClient(uri)
    s0 = c0.attach(2)["sid"]
    baseline = c0.run(s0, PROG, ["rz"], timeout=120)
    assert baseline["code"] == 0, baseline["stderr"][-2000:]
    c0.detach(s0)
    c0.close()
    z0 = _pv_resizes.read()
    errors = []
    outs = []

    def worker(nruns):
        try:
            with DvmClient(uri) as c:
                sid = c.attach(2, timeout=120)["sid"]
                for _ in range(nruns):
                    r = c.run(sid, PROG, ["rz"], timeout=120)
                    assert r["code"] == 0, r["stderr"][-2000:]
                    outs.append(r["stdout"])
                c.detach(sid)
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    t1 = threading.Thread(target=worker, args=(4,))
    t2 = threading.Thread(target=worker, args=(4,))
    t1.start()
    t2.start()
    time.sleep(0.3)  # traffic in flight
    admin = DvmClient(uri)
    gr = admin.resize(8)
    assert gr["was"] == 4 and gr["epoch"] == 1
    t3 = threading.Thread(target=worker, args=(2,))
    t3.start()  # uses the grown headroom
    time.sleep(0.3)
    sh = admin.resize(4)
    assert sh["was"] == 8 and sh["epoch"] == 2
    for t in (t1, t2, t3):
        t.join(timeout=240)
    assert not errors, errors
    assert len(outs) == 10 and all(o == baseline["stdout"]
                                   for o in outs), \
        "a run under resize diverged from the baseline"
    assert _pv_resizes.read() == z0 + 2
    st = admin.stats()
    assert st["capacity"] == 4 and st["epoch"] == 2
    admin.close()
    _assert_band_sums_exact()
    srv.stop()


def test_resize_with_client_disconnect_chaos(tmp_path):
    """ft_inject dvm_disconnect during the resize window: the doomed
    client's session unwinds, the pool resizes anyway, survivors stay
    byte-identical, and new sessions keep being admitted."""
    srv, uri = _pool(tmp_path, 4)
    cb = DvmClient(uri)
    sb = cb.attach(2)["sid"]
    base = cb.run(sb, PROG, ["sv"], timeout=120)
    assert base["code"] == 0, base["stderr"][-2000:]
    saved = _set({"ft_inject_plan": "dvm_disconnect:1",
                  "ft_inject_skip": 0})
    try:
        ca = DvmClient(uri)  # injector armed at construction
        sa = ca.attach(2)["sid"]
        with pytest.raises(DvmError, match="dvm_disconnect"):
            ca.run(sa, PROG, ["doomed"])
    finally:
        _restore(saved)
    admin = DvmClient(uri)
    admin.resize(8)
    r1 = cb.run(sb, PROG, ["sv"], timeout=120)
    assert r1["code"] == 0 and r1["stdout"] == base["stdout"]
    admin.resize(4)
    r2 = cb.run(sb, PROG, ["sv"], timeout=120)
    assert r2["code"] == 0 and r2["stdout"] == base["stdout"]
    # the orphaned session is reaped; the pool still admits
    _wait_for(lambda: len(srv.sessions) == 1, timeout=60,
              what="orphaned session reaped")
    with DvmClient(uri) as cn:
        rn = cn.attach(2, timeout=60)
        cn.detach(rn["sid"])
    _assert_band_sums_exact()
    cb.detach(sb)
    cb.close()
    admin.close()
    srv.stop()


def test_rank_kill_chaos_confined_to_victim_session(tmp_path):
    """ft_inject rank_kill inside one session of the pool: that run
    fails and the session dies, but a peer session's output stays
    byte-identical and the pool keeps admitting new sessions."""
    srv, uri = _pool(tmp_path, 4)
    cb = DvmClient(uri)
    sb = cb.attach(2)["sid"]
    base = cb.run(sb, PROG, ["pk"], timeout=120)
    assert base["code"] == 0, base["stderr"][-2000:]
    # arm the kill ONLY around the doomed session's bring-up (the
    # death timer arms at mpi_init); the peer attached before, the
    # post-mortem session attaches after the restore
    saved = _set({"ft_inject_plan": "rank_kill",
                  "ft_inject_skip": 0,
                  "ft_inject_victim_rank": "1",
                  "ft_inject_after": 0.3})
    try:
        ca = DvmClient(uri)
        sa = ca.attach(2)["sid"]
    finally:
        _restore(saved)
    store = str(tmp_path / "store_kill")
    ra = ca.run(sa, CKPT_PROG, ["doom", store, "20", "0.2"],
                timeout=240)
    assert ra["code"] != 0, "the armed rank_kill never fired"
    # the victim's RankKilled is published ULFM-style; this program
    # is not ULFM-aware, so its surviving peer dies session-confined
    # on the resulting ERR_PROC_FAILED naming the corpse
    assert "MPI_ERR_PROC_FAILED" in ra["stderr"]
    assert "rank_kill" in ra["stderr"]
    with pytest.raises(DvmError, match="dead"):
        ca.run(sa, PROG, ["again"])
    # the peer is untouched, byte for byte
    rb = cb.run(sb, PROG, ["pk"], timeout=120)
    assert rb["code"] == 0 and rb["stdout"] == base["stdout"]
    ca.detach(sa)  # releases the dead session's ranks
    with DvmClient(uri) as cn:
        rn = cn.attach(2, timeout=60)
        r = cn.run(rn["sid"], PROG, ["fresh"], timeout=120)
        assert r["code"] == 0, r["stderr"][-2000:]
        cn.detach(rn["sid"])
    ca.close()
    cb.detach(sb)
    cb.close()
    srv.stop()


# -- tentpole: deadline shedding --------------------------------------------


def test_deadline_shed_typed_reject_keeps_session_alive(tmp_path):
    """An infeasible deadline is shed at admission with a typed
    DvmDeadline in microseconds — and shedding a run must NOT poison
    the session: a feasible run right after succeeds."""
    srv, uri = _pool(tmp_path, 4)
    c = DvmClient(uri)
    sid = c.attach(2)["sid"]
    warm = c.run(sid, SLOW_PROG, timeout=120)  # seeds est_wall_us
    assert warm["code"] == 0, warm["stderr"][-2000:]
    assert srv.est_wall_us > 1_000_000  # the 1.5s sleep dominates
    h0 = _pv_sheds.read()
    with pytest.raises(DvmDeadline, match="shed at admission"):
        c.run(sid, SLOW_PROG, deadline_ms=100)
    assert _pv_sheds.read() == h0 + 1
    r = c.run(sid, PROG, ["ok"], deadline_ms=60_000, timeout=120)
    assert r["code"] == 0, r["stderr"][-2000:]
    c.detach(sid)
    c.close()
    srv.stop()


# -- tentpole: per-session quotas -------------------------------------------


def test_hbm_quota_degrades_then_rejects_without_poisoning_pool(
        tmp_path):
    """Over-budget HBM deposits: first breach degrades (evicts the
    offender's own cache band), continued breach fails THAT run with
    QuotaExceeded — the peer session and the pool keep working."""
    from ompi_tpu.serve import quota

    srv, uri = _pool(tmp_path, 4)
    hog = str(tmp_path / "_hog.py")
    with open(hog, "w") as f:
        f.write(
            "import numpy as np\n"
            "import ompi_tpu\n"
            "from ompi_tpu.op import op as mpi_op\n"
            "comm = ompi_tpu.init()\n"
            "for i in range(8):\n"
            "    x = np.full(8192, float(comm.rank + i), np.float32)\n"
            "    comm.allreduce_arr(x, mpi_op.SUM)\n"
            "ompi_tpu.finalize()\n")
    cb = DvmClient(uri)
    sb = cb.attach(2)["sid"]
    # each of the 8 iterations deposits 2 ranks x 32 KiB = 64 KiB;
    # a 100 KB budget breaches on the 4th deposit (degrade) and
    # rejects on the 5th
    saved = _set({"dvm_quota_hbm_bytes": 100_000})
    rej0 = quota.pv_rejects.read()
    try:
        ca = DvmClient(uri)
        sa = ca.attach(2)["sid"]
        ra = ca.run(sa, hog, timeout=120)
        assert ra["code"] != 0, "the quota never rejected"
        assert "quota" in ra["stderr"]
        assert quota.pv_rejects.read() > rej0
        assert quota.pv_hbm.read_band(sa) > 0  # attributed to the hog
        ca.close()
    finally:
        _restore(saved)
    rb = cb.run(sb, PROG, ["peer"], timeout=120)
    assert rb["code"] == 0, rb["stderr"][-2000:]
    cb.detach(sb)
    cb.close()
    _assert_band_sums_exact()
    srv.stop()


def test_cache_share_quota_evicts_own_entries():
    """dvm_quota_cache_share_pct caps one band's CompiledLRU share at
    insert time by evicting that band's own oldest entries — nobody
    else's."""
    import types

    from ompi_tpu.coll.device import compile_cache
    from ompi_tpu.runtime import state as statemod

    saved = _set({"dvm_quota_cache_share_pct": 5})
    fake = types.SimpleNamespace(cid_band=777)
    statemod.set_current(fake)
    ev0 = compile_cache.pv_band_evictions.read()
    cap = max(1, registry.get("coll_device_cache_max", 256))
    band_cap = max(1, cap * 5 // 100)
    try:
        for i in range(band_cap + 3):
            compile_cache.get(("fleet-test", 777, i), lambda: object())
        assert compile_cache.count_band(777) == band_cap
        assert compile_cache.pv_band_evictions.read() == ev0 + 3
    finally:
        statemod.set_current(None)
        compile_cache.drop_band(777)
        _restore(saved)
    assert compile_cache.count_band(777) == 0


# -- tentpole: FleetController closed loop (satellite 6 audit tie-in) -------


def test_controller_grows_under_backlog_and_shrinks_idle(tmp_path):
    """dvm_ctrl=1: queued attaches make the controller grow the pool
    (admitting the backlog with no manual resize), and a sustained
    idle pool shrinks back to its floor."""
    saved = _set({"dvm_ctrl": 1,
                  "dvm_ctrl_max_ranks": 4,
                  "ctrl_tick_interval_ms": 50,
                  "ctrl_grow_queue_depth": 1,
                  "ctrl_grow_step": 2,
                  "ctrl_shrink_idle_ticks": 2,
                  "dvm_heartbeat_s": 0.3})
    try:
        srv, uri = _pool(tmp_path, 2)  # floor 2, ceiling 4
        assert srv.ctrl is not None
        c1 = DvmClient(uri)
        s1 = c1.attach(2)["sid"]
        c2 = DvmClient(uri)
        r2 = c2.attach(2, timeout=60)  # backlog -> controller grows
        assert srv.capacity == 4
        m = c2.metrics(events=4)
        assert m["ctrl"]["ticks"] > 0
        assert m["ctrl"]["shed_margin_pct"] >= 100
        assert m["epoch"] >= 1
        assert registry._pvars["ctrl_loop_ticks"].read() > 0
        c2.detach(r2["sid"])
        c1.detach(s1)
        # idle now: the loop shrinks back to the floor
        _wait_for(lambda: srv.capacity == 2, timeout=30,
                  what="idle shrink back to the floor")
        c1.close()
        c2.close()
        srv.stop()
    finally:
        _restore(saved)


# -- ISSUE 16: host failure domains (DESIGN.md §21) -------------------------


def _pool2(tmp_path, capacity, hosts=2):
    """A multi-host pool: ranks band contiguously across `hosts`
    failure domains (rank's node_id = rank * hosts // np)."""
    uri = str(tmp_path / "dvm.uri")
    srv = DVMServer(capacity, devices=jax.devices(), uri_file=uri,
                    hosts=hosts).start()
    return srv, uri


def _lines(stdout, kind, tag):
    out = []
    for line in stdout.splitlines():
        parts = line.split()
        if len(parts) >= 3 and parts[0] == kind and parts[1] == tag:
            out.append(parts[2:])
    return out


def test_ring_offsets_prefers_off_host_partners():
    """satellite: buddy placement skips same-host partners whenever
    the topology allows, and degrades to the classic ring when it
    cannot (single host, or no host-safe offset exists)."""
    from ompi_tpu.cr.buddy import ring_offsets

    # 2 hosts x 2 ranks: offset 1 pairs within-host ranks (0<->1),
    # offset 2 is the unique host-safe choice
    assert ring_offsets([0, 0, 1, 1], 1) == [2]
    # degree past the host-safe supply falls back to plain offsets
    assert ring_offsets([0, 0, 1, 1], 3) == [2, 1, 3]
    # interleaved placement: every odd offset crosses hosts
    assert ring_offsets([0, 1, 0, 1], 1) == [1]
    # one host: the classic SCR partner ring
    assert ring_offsets([0, 0, 0, 0], 2) == [1, 2]
    # no offset is host-safe for an asymmetric band: plain ring
    assert ring_offsets([0, 0, 1], 1) == [1]
    assert ring_offsets([7], 1) == []


def test_two_host_attach_cross_host_fence_byte_identical(tmp_path):
    """One attach commands a world spanning both host domains: the
    init/finalize fences cross the DCN KV path, the proctable stamps
    each rank's failure domain, and the output matches a single-host
    run byte for byte."""
    (tmp_path / "one").mkdir(exist_ok=True)
    srv1, uri1 = _pool(tmp_path / "one", 4)
    c1 = DvmClient(uri1)
    s1 = c1.attach(4)["sid"]
    base = c1.run(s1, PROG, ["xh"], timeout=120)
    assert base["code"] == 0, base["stderr"][-2000:]
    c1.detach(s1)
    c1.close()
    srv1.stop()

    srv, uri = _pool2(tmp_path, 4)
    c = DvmClient(uri)
    r = c.attach(4)
    assert r["hosts"] == 2
    sid = r["sid"]
    out = c.run(sid, PROG, ["xh"], timeout=120)
    assert out["code"] == 0, out["stderr"][-2000:]
    assert out["stdout"] == base["stdout"], \
        "a DCN-spanning world diverged from the single-host run"
    st = c.stats()
    assert st["hosts"] == 2 and st["hosts_lost"] == 0
    # the gray-failure plane arms on multi-host pools: stats carries
    # its counters (all quiet here) alongside the liveness ones
    assert st["hosts_degraded"] == 0 and st["hosts_quarantined"] == 0
    # the proctable stamps which host's death takes each rank down
    import json
    with open(f"{uri}.proctable.json") as fh:
        table = json.load(fh)
    doms = sorted(ent["hdom"] for ent in table if "hdom" in ent)
    assert doms == [0, 0, 1, 1], table
    c.detach(sid)
    c.close()
    srv.stop()


def test_host_kill_shrink_arm_single_failure_set(tmp_path):
    """host_kill mid-collective under ULFM: every rank on the dead
    host lands in ONE atomic failure set, so each survivor shrinks
    exactly once and all survivors' digests are byte-identical after
    redoing the run on the shrunk world."""
    srv, uri = _pool2(tmp_path, 4)
    c = DvmClient(uri)
    sid = c.attach(4)["sid"]
    res = {}

    def run():
        res["r"] = c.run(sid, HOST_PROG, ["sa", "120"], timeout=240)

    th = threading.Thread(target=run)
    th.start()
    _wait_for(lambda: srv.sessions[sid].running, what="session running")
    time.sleep(0.6)  # mid-loop, well before step 120
    srv.kill_host(1)
    assert srv._host_dead[1] == 1
    assert srv.hosts_rehydrating == 1
    th.join(timeout=240)
    r = res["r"]
    assert r["code"] == 0, r["stderr"][-2000:]
    shrinks = _lines(r["stdout"], "SHRINKS", "sa")
    digs = _lines(r["stdout"], "DIGEST", "sa")
    # survivors = ranks 0,1 (host 0); victims 2,3 exited silently
    assert sorted(int(s[0]) for s in shrinks) == [0, 1], shrinks
    assert all(int(s[1]) == 1 for s in shrinks), \
        f"a survivor saw a torn failure set: {shrinks}"
    assert len(digs) == 2 and digs[0] == digs[1], digs
    # host-granularity respawn reports a real MTTR and refills the
    # fleet (the RPC path the operator and the probe both use)
    rr = c.respawn_host(1)
    assert rr["mttr_ms"] > 0
    assert srv.hosts_rehydrating == 0 and srv._host_dead[1] == 0
    st = c.stats()
    assert st["hosts_lost"] == 0  # live count back to full
    assert registry._pvars["fleet_hosts_lost"].read() >= 1  # lifetime
    c.detach(sid)
    c.close()
    _assert_band_sums_exact()
    srv.stop()


def test_host_kill_replay_arm_byte_identical(tmp_path):
    """host_kill against a session that is NOT ULFM-aware
    (mpi_ft_ulfm=0): the whole session parks, waits out the domain
    rehydration, and replays from its checkpoint — the client sees
    one successful slower run, digest byte-identical to an unkilled
    baseline, never a failed job."""
    saved = _set({"mpi_ft_ulfm": 0})
    try:
        srv, uri = _pool2(tmp_path, 4)
        steps, sleep_s = 12, 0.2
        store_a = str(tmp_path / "store_a")
        cb = DvmClient(uri)
        sb = cb.attach(2)["sid"]
        rb = cb.run(sb, CKPT_PROG, ["hbase", store_a, str(steps)],
                    timeout=240)
        assert rb["code"] == 0, rb["stderr"][-2000:]
        base_dig = _digest(rb["stdout"], "hbase")
        cb.detach(sb)
        cb.close()

        store_v = str(tmp_path / "store_v")
        cv = DvmClient(uri)
        sv = cv.attach(2)["sid"]
        res = {}

        def run():
            res["r"] = cv.run(sv, CKPT_PROG,
                              ["hvic", store_v, str(steps),
                               str(sleep_s)], timeout=240)

        th = threading.Thread(target=run)
        th.start()
        _wait_for(lambda: srv.sessions[sv].running,
                  what="victim running")
        time.sleep(0.8)  # a few steps checkpointed
        srv.kill_host(1)
        time.sleep(0.3)
        mttr = srv.respawn_host(1)
        assert mttr > 0
        th.join(timeout=240)
        r = res["r"]
        assert r["code"] == 0, r["stderr"][-2000:]  # zero failed jobs
        assert r.get("preempted", 0) >= 1
        assert _resumed_at(r["stdout"], "hvic") > 0, \
            "victim restarted from scratch instead of its checkpoint"
        assert _digest(r["stdout"], "hvic") == base_dig
        cv.detach(sv)
        cv.close()
        srv.stop()
    finally:
        _restore(saved)


def test_buddy_restore_from_off_host_partner(tmp_path):
    """satellite: on a 2-host pool the buddy ring places every
    replica off-host, so host 1's ranks restore their state from
    host 0 partners after losing their own copies."""
    srv, uri = _pool2(tmp_path, 4)
    c = DvmClient(uri)
    sid = c.attach(4)["sid"]
    r = c.run(sid, BUDDY_PROG, ["bd"], timeout=240)
    assert r["code"] == 0, r["stderr"][-2000:]
    oks = _lines(r["stdout"], "BUDDY", "bd")
    assert sorted(int(o[0]) for o in oks) == [0, 1, 2, 3], r["stdout"]
    c.detach(sid)
    c.close()
    srv.stop()


def test_ft_inject_host_kill_class(tmp_path):
    """satellite: the deterministic host_kill fault class severs the
    victim host at the armed op count — same lost-domain handling as
    heartbeat silence, no process needed."""
    saved = _set({"ft_inject_plan": "host_kill:3",
                  "ft_inject_skip": 0,
                  "ft_inject_victim_host": 1})
    try:
        srv, uri = _pool2(tmp_path, 4)  # injector armed in _setup
        assert srv._hkill is not None
        c = DvmClient(uri)
        c.stats()   # op 1
        c.stats()   # op 2
        c.stats()   # op 3 -> fires
        assert srv._host_dead[1] == 1
        assert srv.hosts_rehydrating == 1
        st = c.stats()
        assert st["hosts_lost"] == 1
        c.close()
        srv.stop()
    finally:
        _restore(saved)


def test_host_journal_federation_and_bounded_replay(tmp_path):
    """satellite: per-host write-ahead journals federate under one
    incarnation; completed-jobid replay memory stays bounded at 64
    across torn-tail recovery, compaction, and TWO successive
    incarnations."""
    import json
    srv, uri = _pool2(tmp_path, 4)
    c = DvmClient(uri)
    sid = c.attach(2)["sid"]  # sid 1 -> host 1's journal (1 % 2)
    r = c.run(sid, PROG, ["fj"], timeout=120)
    assert r["code"] == 0, r["stderr"][-2000:]
    h0_path = f"{uri}.journal.jsonl"
    h1_path = f"{uri}.journal.h1.jsonl"

    def _h1():
        with open(h1_path) as fh:
            return fh.read()

    # run/run_done append asynchronously; the heartbeat tick flushes
    _wait_for(lambda: '"run_done"' in _h1(), timeout=30,
              what="run_done flushed to the host journal")
    with open(h0_path) as fh:
        h0 = fh.read()
    h1 = _h1()
    # the session's records route to its OWNING host's journal
    assert '"attach"' not in h0
    assert '"attach"' in h1 and '"run_done"' in h1
    # both journals are stamped with the same fleet incarnation
    inc0 = json.loads(h0.splitlines()[0])["inc"]
    inc1 = json.loads(h1.splitlines()[0])["inc"]
    assert inc0 == inc1
    c.sock.close()  # vanish without detach: the session must replay
    srv.stop()      # deletes both journals

    # resurrect the fleet's journals with 80 extra completed jobs and
    # a torn tail on the HOST journal (the host died mid-append)
    fakes = "".join(
        json.dumps({"t": "run_done", "sid": sid,
                    "jobid": f"fake-{i}", "code": 0}) + "\n"
        for i in range(80))
    with open(h0_path, "w") as fh:
        fh.write(h0)
    with open(h1_path, "w") as fh:
        fh.write(h1 + fakes + '{"t": "run_done", "sid')  # torn tail
    srv2 = DVMServer(4, devices=jax.devices(), uri_file=uri,
                     hosts=2).start()
    assert srv2.rehydrated == 1
    sess = srv2.sessions[sid]
    assert sess.parked and len(sess.completed) <= 64, \
        f"replay memory unbounded: {len(sess.completed)}"
    # the compacted host journal carries the bound forward too
    with open(h1_path) as fh:
        compacted = fh.read()
    assert compacted.count('"run_done"') <= 64
    with open(h1_path) as fh:
        h1b = fh.read()
    with open(h0_path) as fh:
        h0b = fh.read()
    srv2.stop()

    # second incarnation: the bound holds again, no re-accretion
    with open(h0_path, "w") as fh:
        fh.write(h0b)
    with open(h1_path, "w") as fh:
        fh.write(h1b)
    srv3 = DVMServer(4, devices=jax.devices(), uri_file=uri,
                     hosts=2).start()
    assert srv3.rehydrated == 1
    assert len(srv3.sessions[sid].completed) <= 64
    srv3.stop()


def test_clean_halt_deletes_federated_journals(tmp_path):
    """A journal on disk always means a crash — the RPC halt path
    must delete the per-host federated journals along with the
    primary, or the next incarnation resurrects sessions nobody
    wants back."""
    srv, uri = _pool2(tmp_path, 4)
    c = DvmClient(uri)
    sid = c.attach(2)["sid"]  # sid 1 -> host 1's journal
    assert os.path.exists(f"{uri}.journal.jsonl")
    assert os.path.exists(f"{uri}.journal.h1.jsonl")
    c.halt()
    assert not os.path.exists(f"{uri}.journal.jsonl")
    assert not os.path.exists(f"{uri}.journal.h1.jsonl"), \
        "clean halt left a host journal behind"
    c.close()
    srv.stop()
    del sid


def test_controller_tick_is_audited_hot():
    """The controller's decision tick rides the progress sweep, so it
    must be declared to the hot-path audit — and pass it."""
    from ompi_tpu.tools.hotpath_audit import HOT_FUNCTIONS, audit
    assert "FleetController.tick" in HOT_FUNCTIONS[
        "ompi_tpu/serve/controller.py"]
    assert audit() == []
