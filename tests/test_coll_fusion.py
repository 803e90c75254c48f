"""Fusion/coalescing tests: the small-message device-collective fast
path (coll/fusion).  Interleaved nonblocking allreduce/bcast across
rank-threads must be byte-identical to the unfused blocking path —
with mixed dtypes/ops, under ft_inject delay faults, and through the
finalize-time flush.  Also covers the dispatcher drain satellite and
the measured-crossover selection plane (coll/calibrate).
"""

import json

import numpy as np
import pytest

from ompi_tpu.mca.params import registry
from ompi_tpu.op import op as mpi_op
from ompi_tpu.testing import run_ranks

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


def _set(monkeypatch_vals):
    """registry.set with restore; returns a finalizer-style context."""
    saved = {k: registry.get(k) for k in monkeypatch_vals}
    for k, v in monkeypatch_vals.items():
        registry.set(k, v)
    return saved


def _restore(saved):
    for k, v in saved.items():
        registry.set(k, v)


def _interleaved(comm):
    """The canonical fused batch: mixed kinds, ops, dtypes, a scalar.
    Returns (fused results, unfused references) as byte strings."""
    r = comm.rank
    a = jnp.arange(16, dtype=jnp.int32) * (r + 1)
    b = (jnp.ones((8,), jnp.float32) * (r + 1)).at[0].set(-r)
    c = jnp.full((5,), r * 3 + 1, jnp.int32)
    d = jnp.int32(r + 2)
    reqs = [comm.iallreduce_arr(a, mpi_op.SUM),
            comm.iallreduce_arr(b, mpi_op.MAX),
            comm.ibcast_arr(c, 1 % comm.size),
            comm.iallreduce_arr(d, mpi_op.PROD)]
    for q in reqs:
        q.wait()
    fused = [np.asarray(q.result).tobytes() for q in reqs]
    unfused = [np.asarray(comm.allreduce_arr(a, mpi_op.SUM)).tobytes(),
               np.asarray(comm.allreduce_arr(b, mpi_op.MAX)).tobytes(),
               np.asarray(comm.bcast_arr(c, 1 % comm.size)).tobytes(),
               np.asarray(comm.allreduce_arr(d, mpi_op.PROD)).tobytes()]
    return fused, unfused


@pytest.mark.parametrize("n", [2, 4, 8])
def test_fused_byte_identical_mesh(n):
    """Interleaved small iallreduce/ibcast (mixed dtypes/ops/scalar)
    fused into one dispatch == the unfused blocking path, byte for
    byte, on the multi-device mesh path."""
    def fn(comm):
        assert comm.coll.providers["iallreduce_arr"] == "nbc"
        assert comm.coll.providers["allreduce_arr"] == "tpu"
        return _interleaved(comm)

    for fused, unfused in run_ranks(n, fn, devices=True):
        assert fused == unfused


def test_fused_byte_identical_hbm():
    """Same batch on a single-chip comm (coll/hbm fused path)."""
    def fn(comm):
        assert comm.coll.providers["allreduce_arr"] == "hbm"
        return _interleaved(comm)

    dev0 = jax.devices()[0]
    for fused, unfused in run_ranks(3, fn, device_map=lambda r: dev0):
        assert fused == unfused


def test_fused_counts_one_batch():
    """A wait on the FIRST request flushes the whole pending batch as
    ONE fused dispatch; the pvars record batch vs per-collective
    counts."""
    pv_b = registry.register_pvar("coll", "device", "fused_batches")
    pv_c = registry.register_pvar("coll", "device", "fused_collectives")
    b0, c0 = pv_b.read(), pv_c.read()

    def fn(comm):
        qs = [comm.iallreduce_arr(
                  jnp.arange(4, dtype=jnp.int32) + k, mpi_op.SUM)
              for k in range(6)]
        qs[0].wait()  # flushes all six
        assert all(q.complete for q in qs)
        return [np.asarray(q.result).sum() for q in qs]

    run_ranks(4, fn, devices=True)
    assert pv_b.read() - b0 == 4       # one batch per rank-thread
    assert pv_c.read() - c0 == 24      # six collectives each


def test_fused_auto_flush_at_max_ops():
    saved = _set({"coll_device_fusion_max_ops": 3})
    try:
        def fn(comm):
            qs = [comm.iallreduce_arr(jnp.int32(k), mpi_op.SUM)
                  for k in range(3)]
            # the third enqueue crossed the bound: batch already ran
            assert all(q.complete for q in qs)
            return [int(np.asarray(q.result)) for q in qs]

        res = run_ranks(2, fn, devices=True)
        for vals in res:
            assert vals == [0, 2, 4]
    finally:
        _restore(saved)


def test_fusion_disabled_knob_runs_immediately():
    saved = _set({"coll_device_fusion": False})
    try:
        def fn(comm):
            q = comm.iallreduce_arr(jnp.arange(4, dtype=jnp.int32),
                                    mpi_op.SUM)
            assert q.complete  # immediate blocking execution
            return np.asarray(q.result).tolist()

        res = run_ranks(2, fn, devices=True)
        assert res[0] == [0, 2, 4, 6]
    finally:
        _restore(saved)


def test_large_payload_bypasses_fusion():
    """Above coll_device_fusion_threshold the op runs unfused
    immediately (bandwidth-dominated; coalescing buys nothing)."""
    def fn(comm):
        big = jnp.ones((65536 // 4 + 1,), jnp.float32)
        q = comm.iallreduce_arr(big, mpi_op.SUM)
        assert q.complete
        return float(np.asarray(q.result)[0])

    assert run_ranks(2, fn, devices=True) == [2.0, 2.0]


def test_fused_flush_at_finalize():
    """A batch enqueued and never waited on must flush at
    MPI_Finalize (the dispatcher-drain hook), not die with the rank."""
    reqs = {}

    def fn(comm):
        reqs[comm.rank] = comm.iallreduce_arr(
            jnp.arange(8, dtype=jnp.int32), mpi_op.SUM)
        return comm.rank

    run_ranks(4, fn, devices=True)
    exp = (np.arange(8, dtype=np.int32) * 4).tobytes()
    for r, q in reqs.items():
        assert q.complete, f"rank {r} not flushed at finalize"
        assert np.asarray(q.result).tobytes() == exp


def test_fused_under_delay_faults():
    """ft_inject 'delay' at the rendezvous choke point (seed-driven
    stragglers, the chaos-harness discipline of tests/test_chaos.py):
    arbitrary arrival orders must not change a single byte."""
    def fn(comm):
        return _interleaved(comm)

    clean = run_ranks(4, fn, devices=True)
    saved = _set({"ft_inject_plan": "delay", "ft_inject_seed": 7,
                  "ft_inject_rate": 0.5, "ft_inject_delay_ms": 5,
                  "ft_inject_skip": 0})
    try:
        chaotic = run_ranks(4, fn, devices=True)
    finally:
        _restore(saved)
    for (cf, cu), (df, du) in zip(clean, chaotic):
        assert cf == cu and df == du
        assert cf == df  # delay faults change nothing


def test_fused_batch_mismatch_is_clear_error():
    """Divergent batches across ranks (an SPMD bug) must raise a
    diagnosable error on every rank, never deadlock."""
    def fn(comm):
        if comm.rank == 0:
            comm.iallreduce_arr(jnp.int32(1), mpi_op.SUM)
        comm.iallreduce_arr(jnp.arange(4, dtype=jnp.int32), mpi_op.SUM)
        with pytest.raises(RuntimeError, match="batch mismatch|failed"):
            comm.flush_arr()
        return True

    assert run_ranks(2, fn, devices=True) == [True, True]


# ---------------------------------------------------------------------------
# measured crossover selection (coll/calibrate)
# ---------------------------------------------------------------------------

def _fake_profile(tmp_path, crossovers, alpha=5.0, gbs=10.0,
                  dispatch=600.0):
    prof = {"host": "test", "backend": "cpu", "source": "test",
            "host_alpha_us": alpha, "host_gbs": gbs,
            "dispatch_us": dispatch, "crossover_bytes": crossovers}
    p = tmp_path / "profile.json"
    p.write_text(json.dumps(prof))
    return str(p)


def test_measured_rules_off_by_default_and_static_fallback(tmp_path):
    from ompi_tpu.coll import calibrate

    path = _fake_profile(tmp_path, {"allreduce": 1 << 20})
    saved = _set({"coll_tuned_profile_path": path})
    calibrate.reset_cache()
    try:
        assert not calibrate.use_measured_rules()
        # rules off: thresholds stay static, no reroute
        assert calibrate.measured_threshold(
            "allreduce_small", 8, 10000) == 10000
    finally:
        _restore(saved)
        calibrate.reset_cache()


def test_measured_crossover_reroutes_device_path(tmp_path):
    """With measured rules on and a profile whose crossover is above
    the payload, the device module must host-stage the collective —
    visible as a frozen offload pvar (and unchanged results)."""
    from ompi_tpu.coll import calibrate

    pv = registry.register_pvar("coll", "tpu", "offloaded_collectives")
    path = _fake_profile(
        tmp_path, {"allreduce": 1 << 20, "bcast": 0, "alltoall": 0})
    saved = _set({"coll_tuned_profile_path": path,
                  "coll_tuned_use_measured_rules": True})
    calibrate.reset_cache()
    try:
        assert calibrate.crossover_bytes("allreduce", 4) == 1 << 20

        def fn(comm):
            x = jnp.arange(16, dtype=jnp.float32) + comm.rank
            return np.asarray(comm.allreduce_arr(x, mpi_op.SUM))

        n0 = pv.read()
        res = run_ranks(4, fn, devices=True)
        assert pv.read() == n0, "small allreduce was not rerouted"
        exp = sum(np.arange(16, dtype=np.float32) + k for k in range(4))
        np.testing.assert_allclose(res[0], exp)

        # bcast crossover is 0: stays on the device path
        def fb(comm):
            return np.asarray(comm.bcast_arr(
                jnp.arange(4, dtype=jnp.int32), 0))

        n1 = pv.read()
        run_ranks(4, fb, devices=True)
        assert pv.read() > n1
    finally:
        _restore(saved)
        calibrate.reset_cache()


def test_measured_thresholds_move_with_profile(tmp_path):
    """The alpha-beta ladder must actually consume the measured
    numbers: a high-alpha profile pushes the recursive-doubling
    cutoff above a low-alpha one."""
    from ompi_tpu.coll import calibrate

    saved = _set({"coll_tuned_use_measured_rules": True})
    try:
        p1 = _fake_profile(tmp_path, {}, alpha=1.0, gbs=5.0)
        registry.set("coll_tuned_profile_path", p1)
        calibrate.reset_cache()
        low = calibrate.measured_threshold("allreduce_small", 8, 10000)

        p2 = _fake_profile(tmp_path, {}, alpha=200.0, gbs=5.0)
        registry.set("coll_tuned_profile_path", p2)
        calibrate.reset_cache()
        high = calibrate.measured_threshold("allreduce_small", 8, 10000)
        assert high > low > 0
    finally:
        _restore(saved)
        calibrate.reset_cache()


@pytest.mark.slow
def test_calibration_probe_real():
    """The real one-shot probe: sane dispatch constant and host alpha,
    crossovers solved for every kind."""
    from ompi_tpu.coll import calibrate

    prof = calibrate.measure_profile()
    assert prof["host_alpha_us"] > 0
    assert prof["host_gbs"] > 0
    assert prof["dispatch_us"] is None or prof["dispatch_us"] > 0
    assert set(prof["crossover_bytes"]) == {"allreduce", "bcast",
                                            "alltoall"}
    for v in prof["crossover_bytes"].values():
        assert 0 <= v <= 4 << 20


@pytest.mark.slow
def test_fusion_stress_interleaved_shapes():
    """Many rounds of randomized (but rank-agreed) fused batches:
    shapes/ops vary per round, every round byte-identical to the
    unfused path."""
    import random

    rng = random.Random(11)
    rounds = []
    for _ in range(20):
        batch = []
        for _ in range(rng.randint(2, 6)):
            kind = rng.choice(["allreduce", "bcast"])
            shape = (rng.randint(1, 512),)
            dt = rng.choice(["int32", "float32"])
            op = rng.choice(["SUM", "MAX", "MIN"])
            batch.append((kind, shape, dt, op, rng.randint(0, 3)))
        rounds.append(batch)

    def fn(comm):
        out = []
        for batch in rounds:
            reqs, refs = [], []
            for kind, shape, dt, opname, root in batch:
                x = (jnp.arange(shape[0], dtype=dt) * (comm.rank + 1)
                     - comm.rank)
                if kind == "allreduce":
                    reqs.append(comm.iallreduce_arr(
                        x, getattr(mpi_op, opname)))
                    refs.append(lambda x=x, o=opname: comm.allreduce_arr(
                        x, getattr(mpi_op, o)))
                else:
                    reqs.append(comm.ibcast_arr(x, root))
                    refs.append(lambda x=x, r=root: comm.bcast_arr(x, r))
            comm.flush_arr()
            for q, ref in zip(reqs, refs):
                q.wait()
                out.append(np.asarray(q.result).tobytes()
                           == np.asarray(ref()).tobytes())
        return all(out)

    assert all(run_ranks(4, fn, devices=True))
