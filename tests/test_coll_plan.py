"""Compiled collective plans (coll/plan, DESIGN.md §12): byte
identity against the fused path across algorithms / dtypes / ragged
tails, exactly ONE rendezvous per op, cache lifetime across ULFM
epochs and autotone-style purges, and the shared staging utility the
pack bypass rides."""

import numpy as np
import pytest

from ompi_tpu.mca.params import registry
from ompi_tpu.op import op as mpi_op
from ompi_tpu.testing import run_ranks

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

# register pipeline + plan knobs before any _set() snapshot
import ompi_tpu.coll.pipeline  # noqa: E402,F401
import ompi_tpu.coll.plan  # noqa: E402,F401


def _put(comm, a):
    return jax.device_put(a, comm.device)


def _set(vals):
    saved = {k: registry.get(k) for k in vals}
    for k, v in vals.items():
        registry.set(k, v)
    return saved


def _restore(saved):
    for k, v in saved.items():
        registry.set(k, v)


# everything >= 2 KiB routes through the plan path with a 4 KiB
# calibrated segment: multi-segment programs, ragged tails, sub-
# segment pow2 quantization all exercised at test-sized arrays
PLAN_ON = {"coll_pipeline_enable": True, "coll_pipeline_min_bytes": 2048,
           "coll_seg_size": 4096, "coll_pipeline_rd_max_bytes": 0,
           "coll_hier_enable": False}
FUSED = {"coll_pipeline_enable": False, "coll_hier_enable": False}


def _reduce_ops(comm):
    """Allreduce over counts leaving count % seg in {0, +1, -1}
    territory, dtypes int8/f16/f32/f64, ops SUM/MAX/PROD — all values
    exact at any fold order.  Returns concatenated result bytes."""
    r = comm.rank
    out = []
    for n in (4096, 4097, 4095):
        x = _put(comm, (jnp.arange(n, dtype=jnp.float32) % 11) + r)
        out.append(np.asarray(comm.allreduce_arr(x, mpi_op.SUM))
                   .tobytes())
        xi = _put(comm, ((jnp.arange(n) % 17) * (r + 1))
                  .astype(jnp.int32))
        out.append(np.asarray(comm.allreduce_arr(xi, mpi_op.MAX))
                   .tobytes())
    x8 = _put(comm, ((jnp.arange(4097) % 3) + (r % 2)).astype(jnp.int8))
    out.append(np.asarray(comm.allreduce_arr(x8, mpi_op.SUM)).tobytes())
    xh = _put(comm, (jnp.arange(3072) % 7).astype(jnp.float16) + r)
    out.append(np.asarray(comm.allreduce_arr(xh, mpi_op.MAX)).tobytes())
    xd = _put(comm, ((jnp.arange(4099) % 5) + 1).astype(jnp.float64))
    out.append(np.asarray(comm.allreduce_arr(xd, mpi_op.PROD))
               .tobytes())
    return b"".join(out)


def _odd_dtype_ops(comm):
    """Odd dtypes through the identity-padded tail: int8 (sum stays in
    range), float16, float64, int64 PROD (never native)."""
    r = comm.rank
    out = []
    x8 = _put(comm, (jnp.arange(4097) % 3).astype(jnp.int8)
              + np.int8(r % 2))
    out.append(np.asarray(comm.allreduce_arr(x8, mpi_op.SUM)).tobytes())
    h = _put(comm, ((jnp.arange(2050) % 8) + r).astype(jnp.float16))
    out.append(np.asarray(comm.allreduce_arr(h, mpi_op.MAX)).tobytes())
    d = _put(comm, (jnp.arange(1025, dtype=jnp.float64) % 9) + r)
    out.append(np.asarray(comm.allreduce_arr(d, mpi_op.SUM)).tobytes())
    i64 = _put(comm, (jnp.arange(1000, dtype=jnp.int64) % 13) * (r + 1))
    out.append(np.asarray(comm.allreduce_arr(i64, mpi_op.PROD)).tobytes())
    return b"".join(out)


def _mixed_ops(comm):
    """Every operation the tier plans, over sizes that leave tails:
    allreduce SUM / MAX / BAND, a bcast from a middle root, an
    alltoall of odd blocks.  Returns (rank-symmetric bytes, this
    rank's alltoall rows)."""
    r = comm.rank
    P = comm.size
    out = []
    base = (jnp.arange(4099, dtype=jnp.float32) % 11).astype(jnp.float32)
    x = _put(comm, base + r)
    out.append(np.asarray(comm.allreduce_arr(x, mpi_op.SUM)).tobytes())
    xi = _put(comm, (jnp.arange(3072, dtype=jnp.int32) % 17) * (r + 1))
    out.append(np.asarray(comm.allreduce_arr(xi, mpi_op.MAX)).tobytes())
    xb = _put(comm, jnp.full(2048 + 1, 0xFF ^ (1 << r), jnp.uint32))
    out.append(np.asarray(comm.allreduce_arr(xb, mpi_op.BAND)).tobytes())
    b = _put(comm, base * (r + 1))
    out.append(np.asarray(comm.bcast_arr(b, root=min(2, P - 1)))
               .tobytes())
    a = _put(comm, jnp.arange(1031 * P, dtype=jnp.int32) + 100000 * r)
    return b"".join(out), np.asarray(comm.alltoall_arr(a)).tobytes()


def _run_vs_fused(fn, n=4, plan_knobs=None, **kw):
    saved = _set(dict(PLAN_ON, **(plan_knobs or {})))
    try:
        plan = run_ranks(n, fn, **kw)
    finally:
        _restore(saved)
    saved = _set(FUSED)
    try:
        fused = run_ranks(n, fn, **kw)
    finally:
        _restore(saved)
    return plan, fused


# ---------------------------------------------------------------------------
# byte identity + the one-rendezvous contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ops", [_reduce_ops, _odd_dtype_ops])
def test_plan_mesh_byte_identical_mixed_dtypes(ops):
    """Plan-path mesh allreduce (segring pick): bytes equal to fused
    across dtypes and ragged tails, every rank agreeing, and the plan
    pvars actually moving."""
    def fn(comm):
        from ompi_tpu.coll import plan
        b0, h0 = plan.pv_builds.read(), plan.pv_hits.read()
        out = ops(comm)
        again = ops(comm)  # second pass: every geometry hits
        comm.Barrier()
        return (out, again,
                plan.pv_builds.read() - b0, plan.pv_hits.read() - h0)

    plan_res, fused = _run_vs_fused(fn, 4, devices=True)
    assert len({b for b, *_ in plan_res}) == 1
    for (pb, pb2, dbuilds, dhits), (fb, _, _, fh) in zip(plan_res,
                                                         fused):
        assert pb == fb
        assert pb2 == pb                  # deterministic on repeat
        assert dbuilds > 0 and dhits > 0  # plan tier engaged + reused
        assert fh == 0                    # fused run untouched


def test_tier_mesh_byte_identical_and_counted():
    """The fast deterministic 4-rank gate: every operation of the tier
    returns the same bytes as the fused path, all ranks agree, and the
    tier's counters say what it did: one ``coll_pipeline_ops`` an
    operation, and ``coll_pipeline_segments`` advancing by a planned
    allreduce's segment count (the movers add none)."""
    from ompi_tpu.coll import pipeline, plan

    def fn(comm):
        comm.Barrier()   # thread-ranks share the process-wide pvars
        ops0 = pipeline.pv_ops.read()
        segs0 = plan.pv_segments.read()
        comm.Barrier()
        common, a2a = _mixed_ops(comm)
        comm.Barrier()
        return common, a2a, pipeline.pv_ops.read() - ops0, \
            plan.pv_segments.read() - segs0

    seg, fused = _run_vs_fused(fn, 4, devices=True)
    assert len({c for c, _, _, _ in seg}) == 1   # ranks byte-agree
    # 1,024-element segments of 4-byte items: 4,099 floats are 5
    # segments, 3,072 ints 3, 2,049 words 3; on each of 4 ranks
    for (sc, sa, dops, dsegs), (fc, fa, fops, fsegs) in zip(seg, fused):
        assert sc == fc and sa == fa             # tier is invisible
        assert dops == 5 * 4                     # ...but engaged
        assert dsegs == (5 + 3 + 3) * 4
        assert fops == 0 and fsegs == 0          # fused run untouched


@pytest.mark.parametrize("nranks", [4, 3])
def test_plan_movers_byte_identical_mixed_dtypes(nranks):
    """Plan-path mesh bcast and alltoall (coll/plan.mesh_move): every
    dtype the tier takes, 1-D and 2-D, whole and ragged counts, every
    root, on a power-of-two and an odd comm: bytes equal to fused."""
    def fn(comm):
        r, size = comm.rank, comm.size
        out = []
        for dt, n in ((jnp.int8, 4097), (jnp.float16, 2050),
                      (jnp.bfloat16, 1500), (jnp.int32, 1024),
                      (jnp.uint32, 700)):
            x = _put(comm, ((jnp.arange(n) % 13) * (r + 1)).astype(dt))
            for root in range(size):
                out.append(np.asarray(comm.bcast_arr(x, root)).tobytes())
            a = _put(comm, ((jnp.arange(size * n) % 11) + 17 * r)
                     .astype(dt))
            out.append(np.asarray(comm.alltoall_arr(a)).tobytes())
        m = _put(comm, jnp.arange(4 * size * 300, dtype=jnp.float32)
                 .reshape(4 * size, 300) + 1000 * r)
        got = comm.alltoall_arr(m)
        assert got.shape == m.shape
        out.append(np.asarray(got).tobytes())
        got = comm.bcast_arr(m, size - 1)
        assert got.shape == m.shape
        out.append(np.asarray(got).tobytes())
        return b"".join(out)

    plan_res, fused = _run_vs_fused(fn, nranks, devices=True)
    assert plan_res == fused


def test_plan_segrd_and_hop_explicit_byte_identical():
    """The recursive-doubling pick and the hop-explicit (native off)
    lowering of both algs: still byte-identical to fused."""
    def fn(comm):
        return _reduce_ops(comm)

    for knobs in ({"coll_pipeline_rd_max_bytes": 1 << 30},
                  {"coll_plan_native_reduce": False},
                  {"coll_pipeline_rd_max_bytes": 1 << 30,
                   "coll_plan_native_reduce": False}):
        plan_res, fused = _run_vs_fused(fn, 4, plan_knobs=knobs,
                                        devices=True)
        assert plan_res == fused


def test_plan_one_rendezvous_per_op():
    """THE structural claim: on the plan path an N-segment collective
    is ONE meet: one plan_exec span per op, and meet-span count ==
    op count."""
    def fn(comm):
        ops = 0
        for n in (4096, 4097, 6144):  # multi-segment sizes
            x = _put(comm, (jnp.arange(n, dtype=jnp.float32) % 11)
                     + comm.rank)
            comm.allreduce_arr(x, mpi_op.SUM)
            ops += 1
        tr = comm.state.tracer
        names = [e["name"] for e in tr.snapshot() if e["ph"] == "X"]
        return ops, names.count("meet"), names.count("plan_exec")

    saved = _set(dict(PLAN_ON, trace_enable="1", trace_dump_path=""))
    try:
        res = run_ranks(4, fn, devices=True)
    finally:
        _restore(saved)
    for ops, meets, plan_execs in res:
        assert meets == ops == plan_execs == 3


def _hbm_allreduce_and_alltoall(comm):
    """One ragged allreduce, which the tier plans, and one alltoall,
    which on one chip is the stacked whole-payload kernel at every
    size: its bytes must not depend on the tier's knobs."""
    r = comm.rank
    x = _put(comm, (jnp.arange(5003, dtype=jnp.float32) % 7) + r)
    a = _put(comm, jnp.arange(1009 * comm.size, dtype=jnp.int32)
             + 1000 * r)
    return (np.asarray(comm.allreduce_arr(x, mpi_op.SUM)).tobytes()
            + np.asarray(comm.alltoall_arr(a)).tobytes())


@pytest.mark.parametrize("ops", [_reduce_ops,
                                 _hbm_allreduce_and_alltoall])
def test_plan_hbm_byte_identical(ops):
    """Plan path over the intra-chip (one shared device) module:
    stacked whole-payload kernel, one rendezvous, fused-identical."""
    import jax as _jax
    _one_dev = lambda r: _jax.devices()[0]  # noqa: E731

    def fn(comm):
        from ompi_tpu.coll import plan
        b0 = plan.pv_builds.read()
        out = ops(comm)
        comm.Barrier()
        return out, plan.pv_builds.read() - b0

    saved = _set(PLAN_ON)
    try:
        plan_res = run_ranks(4, fn, device_map=_one_dev)
    finally:
        _restore(saved)
    saved = _set(FUSED)
    try:
        fused = run_ranks(4, fn, device_map=_one_dev)
    finally:
        _restore(saved)
    for (pb, dbuilds), (fb, _) in zip(plan_res, fused):
        assert pb == fb
        assert dbuilds > 0


# ---------------------------------------------------------------------------
# chaos: delay faults and epoch boundaries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fn", [_reduce_ops, _mixed_ops])
def test_plan_under_delay_faults(fn):
    """ft_inject 'delay' at the (single) rendezvous: straggler arrival
    order through the plan path changes nothing, for the reductions
    and for the data movers."""
    saved = _set(PLAN_ON)
    try:
        clean = run_ranks(4, fn, devices=True)
        chaos = _set({"ft_inject_plan": "delay", "ft_inject_seed": 7,
                      "ft_inject_rate": 0.5, "ft_inject_delay_ms": 5,
                      "ft_inject_skip": 0})
        try:
            chaotic = run_ranks(4, fn, devices=True)
        finally:
            _restore(chaos)
    finally:
        _restore(saved)
    assert clean == chaotic
    if fn is _reduce_ops:   # rank-symmetric results
        assert len(set(clean)) == 1


# the planned operations of the epoch tests: the allreduce plans (SUM
# lowers to the native program, PROD to the hop-explicit ring) and the
# two data movers of coll/plan.mesh_move; 4092 divides by the 4 ranks
# of the old epoch and the 3 of the shrunk one
_EPOCH_OPS = {
    "allreduce": (4099, lambda comm, x: comm.allreduce_arr(x, mpi_op.SUM)),
    "allreduce_ring": (4099,
                       lambda comm, x: comm.allreduce_arr(x, mpi_op.PROD)),
    "bcast": (4099, lambda comm, x: comm.bcast_arr(x, 1)),
    "alltoall": (4092, lambda comm, x: comm.alltoall_arr(x)),
}


@pytest.mark.parametrize("kind", list(_EPOCH_OPS))
def test_plan_across_shrink_epoch(kind):
    """A rank dies mid-job: the shrink epoch must purge the resolved
    plan cache AND evict the old mesh's plan executables from the
    compile cache, so no plan resolved before the epoch is served
    after it; then the shrunk world recomputes fresh, byte-identical
    to a never-failed world of the survivor size."""
    import time
    from ompi_tpu.coll.device import compile_cache
    from ompi_tpu.ft import ulfm
    n, call = _EPOCH_OPS[kind]

    def survivor(comm):
        old_dev_key = tuple(
            d.id for d in comm.mesh().devices.reshape(-1))
        _ = np.asarray(call(
            comm, _put(comm, (jnp.arange(n, dtype=jnp.float32) % 11)
                       + comm.rank)))  # old-epoch plan op
        old = list(comm.__dict__["_coll_plans"].values())
        assert old
        if comm.rank == 0:
            # a peer still leaving the op's one meeting would see the
            # death inside the old-epoch op, which is not this test
            time.sleep(0.2)
            ulfm.kill_now(comm.state)
        time.sleep(0.5)
        new = comm.shrink()
        assert "_coll_plans" not in comm.__dict__  # epoch hygiene
        assert "_pipeline_pick" not in comm.__dict__
        assert "_hier_plan" not in comm.__dict__
        stale = [k for k in list(compile_cache._d)
                 if isinstance(k, tuple) and k
                 and isinstance(k[0], str) and k[0].startswith("plan_")
                 and old_dev_key in k]
        assert not stale  # no stale-mesh executables survive
        x = _put(new, (jnp.arange(n, dtype=jnp.float32) % 11)
                 + new.rank)
        out = np.asarray(call(new, x)).tobytes()
        served = new.__dict__["_coll_plans"].values()
        assert served and not any(p is q for p in served for q in old)
        return out

    def fresh(comm):
        x = _put(comm, (jnp.arange(n, dtype=jnp.float32) % 11)
                 + comm.rank)
        return np.asarray(call(comm, x)).tobytes()

    saved = _set(PLAN_ON)
    try:
        got = run_ranks(4, survivor, devices=True, allow_failures=True)
        ref = run_ranks(3, fresh, devices=True)
    finally:
        _restore(saved)
    assert got[0] is None
    assert got[1:] == ref


@pytest.mark.parametrize("kind", list(_EPOCH_OPS))
def test_plan_across_respawn_epoch(kind):
    """Kill + in-job respawn between plan-path collectives: the
    replacement's epoch sees no stale plans and the completed job's
    bytes match a fault-free run exactly."""
    n, call = _EPOCH_OPS[kind]
    from ompi_tpu import errhandler as eh
    from ompi_tpu.cr import buddy
    from ompi_tpu.errhandler import MPIException
    from ompi_tpu.ft import respawn, ulfm

    ft_codes = (eh.ERR_PROC_FAILED, eh.ERR_PROC_FAILED_PENDING,
                eh.ERR_REVOKED)

    def make_fn(kill_at=None, iters=3):
        kill_at = kill_at or {}

        def fn(comm):
            state = comm.state
            was_joining = respawn.joining(state)
            if was_joining:
                comm = respawn.rejoin(comm)
                st = buddy.restore(comm)
                i, acc = int(st["i"]), np.asarray(st["acc"])
            else:
                i, acc = 0, np.zeros(n, np.float32)
            did_kill = False
            base = (jnp.arange(n, dtype=jnp.float32) % 11)
            while i < iters:
                try:
                    buddy.checkpoint(comm, {"i": i, "acc": acc})
                    if (not was_joining and not did_kill
                            and kill_at.get(comm.rank) == i):
                        did_kill = True
                        ulfm.kill_now(state)
                    x = _put(comm, base * (i + 1) + comm.rank)
                    acc = np.asarray(call(comm, x))
                    i += 1
                except MPIException as e:
                    if e.code not in ft_codes:
                        raise
                    comm = respawn.rejoin(comm)
                    assert "_coll_plans" not in comm.__dict__
                    st = buddy.restore(comm)
                    i, acc = int(st["i"]), np.asarray(st["acc"])
            return acc.tobytes()
        return fn

    saved = _set(PLAN_ON)
    registry.set("cr_buddy_degree", "1")
    try:
        clean = run_ranks(4, make_fn(), devices=True, timeout=120)
        faulty = run_ranks(4, make_fn(kill_at={1: 1}), devices=True,
                           timeout=180, respawn=True)
    finally:
        registry.set("cr_buddy_degree", "0")
        _restore(saved)
    assert faulty == clean
    assert all(r is not None for r in faulty)


# ---------------------------------------------------------------------------
# cache bounds, pvars, staging
# ---------------------------------------------------------------------------

def test_plan_cache_lru_and_compile_stability():
    """Plan resolution is once per geometry (hits climb, builds flat
    on repeats), the per-comm LRU obeys coll_plan_cache_max, and a
    repeated identical world compiles ZERO new executables."""
    from ompi_tpu.coll import plan
    from ompi_tpu.coll.device import compile_cache

    def fn(comm):
        for _rep in range(3):
            for n in (2048, 4096, 6000):
                x = _put(comm, jnp.ones((n,), jnp.float32))
                comm.allreduce_arr(x, mpi_op.SUM)
        comm.Barrier()
        return len(comm.__dict__["_coll_plans"])

    saved = _set(PLAN_ON)
    try:
        run_ranks(4, fn, devices=True)  # warm: compile the programs
        builds0 = compile_cache.builds
        # thread-ranks share the process: read the process-wide pvars
        # here, where no rank is mid-flight
        b0, h0 = plan.pv_builds.read(), plan.pv_hits.read()
        res = run_ranks(4, fn, devices=True)
        assert compile_cache.builds == builds0  # zero new executables
        # 3 geometries x 4 ranks resolve fresh per-comm plans; every
        # repeat after the first hits
        assert plan.pv_builds.read() - b0 == 3 * 4
        assert plan.pv_hits.read() - h0 == 6 * 4
        assert res == [3] * 4
    finally:
        _restore(saved)

    # LRU bound: more geometries than the cap leaves <= cap entries
    def fn_lru(comm):
        for n in (2048, 4096, 6000, 8192, 10240):
            x = _put(comm, jnp.ones((n,), jnp.float32))
            comm.allreduce_arr(x, mpi_op.SUM)
        return len(comm.__dict__["_coll_plans"])

    saved = _set(dict(PLAN_ON, coll_plan_cache_max=2))
    try:
        res = run_ranks(4, fn_lru, devices=True)
    finally:
        _restore(saved)
    assert all(n <= 2 for n in res)


def test_plan_live_purge_rebuilds():
    """SELECTION_CACHE_KEYS includes _coll_plans: a live purge (what
    an autotune fold does when the calibrated segment moves) drops the
    resolved plans and the next op rebuilds rank-locally — same
    bytes."""
    from ompi_tpu.ft import ulfm

    def fn(comm):
        x = _put(comm, (jnp.arange(4099, dtype=jnp.float32) % 11)
                 + comm.rank)
        a = np.asarray(comm.allreduce_arr(x, mpi_op.SUM)).tobytes()
        assert "_coll_plans" in comm.__dict__
        ulfm.purge_comm_caches(comm, ulfm.SELECTION_CACHE_KEYS)
        assert "_coll_plans" not in comm.__dict__
        b = np.asarray(comm.allreduce_arr(x, mpi_op.SUM)).tobytes()
        return a == b

    saved = _set(PLAN_ON)
    try:
        res = run_ranks(4, fn, devices=True)
    finally:
        _restore(saved)
    assert all(res)


def test_staging_shared_utility():
    """The hoisted runtime/staging module: alignment guarantee, the
    probe's cached verdict, MirrorPool take/park reuse and bound, and
    osc/device actually riding the shared names."""
    from ompi_tpu.runtime import staging

    buf = staging.aligned_empty(1024)
    assert buf.ctypes.data % staging.STAGE_ALIGN == 0
    assert buf.nbytes == 1024

    v1 = staging.runtime_zero_copy()
    assert isinstance(v1, bool)
    assert staging.runtime_zero_copy() is v1  # cached

    pool = staging.MirrorPool(max_buffers=2)
    a = pool.take(256)
    assert a.ctypes.data % staging.STAGE_ALIGN == 0
    pool.park(a)
    b = pool.take(256)
    assert b.ctypes.data == a.ctypes.data  # reused, no fresh pages
    pool.park(b)
    pool.park(staging.aligned_empty(256))
    pool.park(staging.aligned_empty(256))  # beyond the bound: dropped
    assert len(pool._free) == 2
    pool.park(None)  # tolerated no-op
    assert len(pool._free) == 2
    small = pool.take(4096)  # nothing parked is big enough
    assert small.nbytes == 4096

    # osc/device is re-pointed at the shared discipline
    from ompi_tpu.osc import device as osc_device
    assert osc_device._aligned_empty is staging.aligned_empty
    assert osc_device._runtime_zero_copy is staging.runtime_zero_copy
    assert osc_device._STAGE_ALIGN == staging.STAGE_ALIGN
