"""ULFM rank-failure mitigation tests (ompi_tpu/ft/ulfm): detect ->
ERR_PROC_FAILED -> revoke / agree / shrink, survivor-mesh rebuild
(ref: the MPI-4 FT proposal MPIX_Comm_revoke/shrink/agree)."""

import time

import numpy as np
import pytest

from ompi_tpu import errhandler as eh
from ompi_tpu.errhandler import MPIException
from ompi_tpu.ft import ulfm
from ompi_tpu.mca.params import registry
from ompi_tpu.op import op as mpi_op
from ompi_tpu.testing import mpirun_run, run_ranks

PF = eh.ERR_PROC_FAILED
PFP = eh.ERR_PROC_FAILED_PENDING
RV = eh.ERR_REVOKED


# ---- detect + report ------------------------------------------------

def test_parked_recv_raises_proc_failed():
    """A receive parked on a peer that dies completes with
    ERR_PROC_FAILED instead of hanging (the tentpole's report leg)."""
    def fn(comm):
        if comm.rank == 1:
            time.sleep(0.2)
            ulfm.kill_now(comm.state)
        buf = np.zeros(4)
        with pytest.raises(MPIException) as ei:
            comm.Recv(buf, source=1, tag=7)
        return ei.value.code

    r = run_ranks(3, fn, allow_failures=True)
    assert r == [PF, None, PF]
    assert ulfm._pv_failures.read() >= 1


def test_detection_latency_bound():
    """arm_rank_kill (the ft_inject rank_kill path) fires out of the
    victim's blocking wait; survivors learn of the death and drain
    within a small multiple of the kill delay — never a fence/recv
    timeout."""
    def fn(comm):
        if comm.rank == 1:
            ulfm.arm_rank_kill(comm.state, 0.25)
            buf = np.zeros(4)
            comm.Recv(buf, source=0, tag=99)  # parked until the kill
            return "victim survived"
        t0 = time.monotonic()
        buf = np.zeros(4)
        with pytest.raises(MPIException) as ei:
            comm.Recv(buf, source=1, tag=42)
        return (ei.value.code, time.monotonic() - t0)

    r = run_ranks(2, fn, allow_failures=True)
    assert r[1] is None  # the victim died, it did not "survive"
    code, dt = r[0]
    assert code == PF
    assert 0.2 <= dt < 10.0, dt


def test_parked_allreduce_raises_proc_failed():
    """Survivors parked inside a blocking collective drain with an
    ULFM error when a member dies mid-operation.  Rank 0 (every
    algorithm's root / chain head) is the victim, so no survivor can
    complete without noticing."""
    def fn(comm):
        if comm.rank == 0:
            time.sleep(0.25)
            ulfm.kill_now(comm.state)
        x = np.full(32, comm.rank + 1.0)
        r = np.empty_like(x)
        with pytest.raises(MPIException) as ei:
            comm.Allreduce(x, r, mpi_op.SUM)
        return ei.value.code

    r = run_ranks(4, fn, allow_failures=True)
    assert r[0] is None
    assert all(c in (PF, PFP, RV) for c in r[1:])


def test_send_to_failed_peer_raises_at_entry():
    """Once a failure is known, NEW ops naming the dead peer fail fast
    at post time (isend/irecv entry check), not at wait time."""
    def fn(comm):
        if comm.rank == 1:
            ulfm.kill_now(comm.state)
        time.sleep(0.3)  # let the failure record arrive + ingest
        comm.state.ulfm.poll()
        with pytest.raises(MPIException) as ei:
            comm.Send(np.zeros(4), dest=1, tag=3)
        return ei.value.code

    r = run_ranks(3, fn, allow_failures=True)
    assert r == [PF, None, PF]


def test_anysource_pending_then_ack():
    """ANY_SOURCE with an unacknowledged failure raises
    ERR_PROC_FAILED_PENDING; after Comm.ack_failed() ANY_SOURCE works
    again and matches a live sender (MPIX_Comm_failure_ack)."""
    def fn(comm):
        if comm.rank == 1:
            ulfm.kill_now(comm.state)
        time.sleep(0.3)
        comm.state.ulfm.poll()
        if comm.rank == 2:
            comm.Send(np.full(4, 7.0), dest=0, tag=5)
            return "sent"
        # rank 0: pending until the failure is acknowledged
        buf = np.zeros(4)
        with pytest.raises(MPIException) as ei:
            comm.Recv(buf, source=-1, tag=5)
        assert ei.value.code == PFP
        assert comm.ack_failed() == 1
        comm.Recv(buf, source=-1, tag=5)
        return float(buf[0])

    r = run_ranks(3, fn, allow_failures=True)
    assert r == [7.0, None, "sent"]


def test_get_failed_and_epoch():
    def fn(comm):
        if comm.rank == 2:
            ulfm.kill_now(comm.state)
        time.sleep(0.3)
        u = comm.state.ulfm
        u.poll()
        assert u.epoch >= 1
        return comm.get_failed()

    r = run_ranks(3, fn, allow_failures=True)
    assert r == [[2], [2], None]


# ---- revoke ---------------------------------------------------------

def test_revoke_drains_all_ranks():
    """Comm.revoke poisons the communicator job-wide: every parked op
    drains with ERR_REVOKED, later ops fail at entry, and the parent
    communicator is untouched."""
    def fn(comm):
        sub = comm.dup(name="revokee")
        if comm.rank == 0:
            time.sleep(0.25)
            sub.revoke()
            code = RV
        else:
            buf = np.zeros(4)
            with pytest.raises(MPIException) as ei:
                sub.Recv(buf, source=0, tag=1)  # parked, then drained
            code = ei.value.code
        assert sub.is_revoked()
        # new ops on the revoked comm fail fast at entry
        with pytest.raises(MPIException) as ei2:
            sub.Send(np.zeros(2), dest=(comm.rank + 1) % comm.size)
        assert ei2.value.code == RV
        comm.Barrier()  # the parent communicator still works
        return code

    r = run_ranks(4, fn, allow_failures=True)
    assert r == [RV] * 4
    assert ulfm._pv_revokes.read() >= 1


# ---- agree ----------------------------------------------------------

def test_agree_healthy():
    def fn(comm):
        a = comm.agree(comm.rank != 2)  # one False poisons the AND
        b = comm.agree(True)
        return (a, b)

    assert run_ranks(4, fn) == [(False, True)] * 4


@pytest.mark.parametrize("phase", ["pre_contrib", "post_contrib",
                                   "pre_decision", "post_decision"])
def test_agree_identical_under_kill(phase):
    """The acceptance-critical property: every survivor returns the
    SAME flag no matter at which protocol phase a member dies.  The
    victim is rank 0 — the initial leader — so leader-death promotion
    is exercised, not just contributor loss."""
    def fn(comm):
        u = comm.state.ulfm
        if comm.rank == 0:
            def hook(p):
                if p == phase:
                    raise ulfm.RankKilled(f"killed at {p}")
            u._agree_test_hook = hook
        return comm.agree(comm.rank != 2)

    r = run_ranks(4, fn, allow_failures=True)
    assert r[0] is None, f"victim must die at {phase}"
    assert [x for x in r[1:]] == [False] * 3, (phase, r)


# ---- shrink ---------------------------------------------------------

def test_shrink_host_path():
    """shrink returns a survivor communicator every member agrees on:
    dense new ranks, same cid everywhere, errhandler inherited, and
    host-path collectives work on it."""
    def fn(comm):
        if comm.rank == 0:
            ulfm.kill_now(comm.state)
        time.sleep(0.3)
        new = comm.shrink(name="survivors")
        assert new.errhandler is comm.errhandler
        x = np.full(16, new.rank + 1.0)
        r = np.empty_like(x)
        new.Allreduce(x, r, mpi_op.SUM)
        return (new.size, new.rank, new.cid, float(r[0]))

    r = run_ranks(4, fn, allow_failures=True)
    assert r[0] is None
    live = [x for x in r if x is not None]
    assert [(s, rk) for s, rk, _, _ in live] == [(3, 0), (3, 1), (3, 2)]
    assert len({cid for _, _, cid, _ in live}) == 1  # agreed cid
    assert all(v == 6.0 for _, _, _, v in live)


def test_shrink_device_allreduce_byte_identical():
    """The chaos-demo acceptance check, thread-world edition: a device
    allreduce on the shrunk 3-rank communicator is byte-identical to
    the same allreduce on a fresh 3-rank world."""
    def survivor_bytes(comm):
        if comm.rank == 0:
            ulfm.kill_now(comm.state)
        time.sleep(0.3)
        new = comm.shrink()
        x = np.arange(8, dtype=np.float32) * (new.rank + 1)
        return np.asarray(new.allreduce_arr(x, mpi_op.SUM)).tobytes()

    def fresh_bytes(comm):
        x = np.arange(8, dtype=np.float32) * (comm.rank + 1)
        return np.asarray(comm.allreduce_arr(x, mpi_op.SUM)).tobytes()

    got = run_ranks(4, survivor_bytes, devices=True,
                    allow_failures=True)
    ref = run_ranks(3, fresh_bytes, devices=True)
    assert got[0] is None
    assert got[1] == got[2] == got[3] == ref[0] == ref[1] == ref[2]


def test_shrink_invalidates_compiled_cache():
    """Executables compiled against the dead mesh shape are dropped
    from the bounded CompiledLRU (they could never be hit again)."""
    from ompi_tpu.coll import device

    def fn(comm):
        x = np.arange(8, dtype=np.float32)
        comm.allreduce_arr(x, mpi_op.SUM)  # compile on the 4-mesh
        mesh = comm.__dict__.get("_mesh")
        dev_key = (tuple(d.id for d in mesh.devices.reshape(-1))
                   if mesh is not None else None)
        time.sleep(0.2)  # everyone clear of the collective first
        if comm.rank == 0:
            ulfm.kill_now(comm.state)
        time.sleep(0.3)
        comm.shrink()
        if dev_key is None:
            return 0
        with device.compile_cache._lock:
            return sum(1 for k in device.compile_cache._d
                       if dev_key in k)

    r = run_ranks(4, fn, devices=True, allow_failures=True)
    assert all(x == 0 for x in r[1:]), r  # no stale-mesh entries


def test_compiled_lru_drop_mesh_unit():
    from ompi_tpu.coll.device import CompiledLRU
    c = CompiledLRU()
    old, new = (0, 1, 2, 3), (1, 2, 3)
    c.get(("allreduce", old, "f32"), lambda: (lambda: None))
    c.get(("bcast", old, "f32"), lambda: (lambda: None))
    c.get(("allreduce", new, "f32"), lambda: (lambda: None))
    assert c.drop_mesh(old) == 2
    assert len(c) == 1 and c.drop_mesh(old) == 0


# ---- chaos demo -----------------------------------------------------

def test_chaos_demo_threadworld():
    """The ISSUE's acceptance demo: a 4-rank job loses rank 0 mid-loop,
    survivors catch the failure, shrink, and COMPLETE the remaining
    iterations on 3 — with the final device allreduce byte-identical
    to a fresh 3-rank world's."""
    steps = 30

    def chaos(comm):
        work = comm
        out = None
        step = 0
        while step < steps:
            if comm.rank == 0 and step == 5:
                ulfm.kill_now(comm.state)  # dies mid-loop
            try:
                x = np.arange(8, dtype=np.float32) * (work.rank + 1)
                out = np.asarray(work.allreduce_arr(x, mpi_op.SUM))
                step += 1
                time.sleep(0.02)
            except MPIException as e:
                assert e.code in (PF, PFP, RV), e.code
                work = work.shrink(name="survivors")
        return (work.size, out.tobytes())

    def fresh(comm):
        x = np.arange(8, dtype=np.float32) * (comm.rank + 1)
        return np.asarray(comm.allreduce_arr(x, mpi_op.SUM)).tobytes()

    got = run_ranks(4, chaos, devices=True, allow_failures=True,
                    timeout=180.0)
    ref = run_ranks(3, fresh, devices=True)
    assert got[0] is None
    assert all(x == (3, ref[0]) for x in got[1:]), got


@pytest.mark.slow
def test_mpirun_ulfm_policy_process_ranks(tmp_path):
    """End-to-end over real processes: ft_inject kills rank 1, the
    'ulfm' errmgr policy publishes the failure instead of tearing the
    job down, survivors shrink and the job EXITS 0 on 3 ranks."""
    r = mpirun_run(
        4, "tests/_ulfm_prog.py",
        mca=(("errmgr_base_policy", "ulfm"),
             ("ft_inject_plan", "rank_kill"),
             ("ft_inject_victim_rank", "1"),
             ("ft_inject_after", "0.8")),
        timeout=180, job_timeout=120)
    out = r.stdout.decode()
    assert r.returncode == 0, (r.returncode, out[-500:],
                               r.stderr.decode()[-2000:])
    lines = [ln for ln in out.splitlines() if ln.startswith("rank=")]
    assert len(lines) == 3, out[-800:]
    assert all("size=3" in ln and "sum=6.0" in ln for ln in lines), lines
    assert "ulfm policy" in r.stderr.decode()


# ---- knobs / zero-cost-when-off -------------------------------------

def test_ulfm_disabled_is_absent():
    """mpi_ft_ulfm=0: no UlfmState is attached (hot paths see None —
    the zero-cost contract) and the mitigation API refuses."""
    registry.set("mpi_ft_ulfm", "0")
    try:
        def fn(comm):
            assert comm.state.ulfm is None
            with pytest.raises(RuntimeError, match="ULFM is disabled"):
                comm.agree(True)
            with pytest.raises(RuntimeError, match="ULFM is disabled"):
                comm.shrink()
            return True

        assert run_ranks(2, fn) == [True, True]
    finally:
        registry.set("mpi_ft_ulfm", "1")


def test_ft_inject_rank_faults_gating():
    from ompi_tpu import ft_inject
    assert ft_inject.rank_faults(0) == []  # plan empty: fully passive
    registry.set("ft_inject_plan", "rank_kill")
    registry.set("ft_inject_victim_rank", "2")
    try:
        assert ft_inject.rank_faults(2) == ["rank_kill"]
        assert ft_inject.rank_faults(0) == []
        assert ft_inject.rank_kill_victim() == 2
    finally:
        registry.set("ft_inject_plan", "")
        registry.set("ft_inject_victim_rank", "1")
