"""Device collective tests: coll/tpu (XLA mesh) on the 8-device
virtual CPU mesh, coll/hbm (co-located ranks, one chip), and the
host-staged fallback.  This is the north-star path (BASELINE.json):
MPI collectives on device-resident buffers lowered to
psum/psum_scatter/all_gather/all_to_all/ppermute.
"""

import numpy as np
import pytest

from ompi_tpu.op import op as mpi_op
from ompi_tpu.testing import run_ranks

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


def _put(comm, a):
    return jax.device_put(a, comm.device)


# ---------------------------------------------------------------------------
# coll/tpu: one rank per device
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 4, 8])
def test_tpu_allreduce_sum(n):
    def fn(comm):
        assert comm.coll.providers["allreduce_arr"] == "tpu"
        x = _put(comm, jnp.arange(32, dtype=jnp.float32) + comm.rank)
        r = comm.allreduce_arr(x, mpi_op.SUM)
        return np.asarray(r)

    res = run_ranks(n, fn, devices=True)
    exp = sum(np.arange(32, dtype=np.float32) + k for k in range(n))
    for r in res:
        np.testing.assert_allclose(r, exp)


@pytest.mark.parametrize("opname", ["MAX", "MIN", "PROD", "BXOR"])
def test_tpu_allreduce_ops(opname):
    n = 4
    op = getattr(mpi_op, opname)
    dtype = jnp.int32 if not op.float_ok else jnp.float32

    def fn(comm):
        x = _put(comm, jnp.array([comm.rank + 1, 7 - comm.rank],
                                 dtype=dtype))
        return np.asarray(comm.allreduce_arr(x, op))

    res = run_ranks(n, fn, devices=True)
    vals = [np.array([k + 1, 7 - k]) for k in range(n)]
    npop = {"MAX": np.maximum, "MIN": np.minimum,
            "PROD": np.multiply, "BXOR": np.bitwise_xor}[opname]
    exp = vals[0]
    for v in vals[1:]:
        exp = npop(exp, v)
    for r in res:
        np.testing.assert_array_equal(r, exp)


def test_tpu_bcast():
    def fn(comm):
        val = comm.rank * 100.0 if comm.rank == 3 else 0.0
        x = _put(comm, jnp.full((8,), val, dtype=jnp.float32))
        return float(np.asarray(comm.bcast_arr(x, root=3))[0])

    res = run_ranks(8, fn, devices=True)
    assert res == [300.0] * 8


def test_tpu_reduce_scatter():
    n = 4

    def fn(comm):
        x = _put(comm, jnp.arange(n * 3, dtype=jnp.float32) * (comm.rank + 1))
        return np.asarray(comm.reduce_scatter_arr(x, mpi_op.SUM))

    res = run_ranks(n, fn, devices=True)
    total = np.arange(n * 3, dtype=np.float32) * sum(range(1, n + 1))
    for k, r in enumerate(res):
        np.testing.assert_allclose(r, total[3 * k:3 * (k + 1)])


def test_tpu_allgather_alltoall():
    n = 8

    def fn(comm):
        ag = comm.allgather_arr(_put(comm, jnp.array([comm.rank * 2],
                                                     jnp.int32)))
        a2a = comm.alltoall_arr(_put(
            comm, jnp.arange(n, dtype=jnp.int32) + comm.rank * 10))
        return np.asarray(ag).tolist(), np.asarray(a2a).tolist()

    res = run_ranks(n, fn, devices=True)
    for k, (ag, a2a) in enumerate(res):
        assert ag == [2 * i for i in range(n)]
        assert a2a == [i * 10 + k for i in range(n)]


def test_tpu_ppermute_ring():
    """The ring-attention primitive: shift along the mesh axis."""
    n = 8

    def fn(comm):
        x = _put(comm, jnp.array([comm.rank], jnp.int32))
        fwd = comm.ppermute_arr(
            x, [(i, (i + 1) % n) for i in range(n)])
        return int(np.asarray(fwd)[0])

    res = run_ranks(n, fn, devices=True)
    assert res == [(k - 1) % n for k in range(n)]


def test_tpu_subcomm_mesh():
    """Split comm maps onto a sub-mesh; collectives stay on-device."""
    def fn(comm):
        sub = comm.split(comm.rank % 2)
        assert sub.coll.providers["allreduce_arr"] == "tpu"
        x = _put(comm, jnp.array([float(comm.rank)], jnp.float32))
        r = sub.allreduce_arr(x, mpi_op.SUM)
        return float(np.asarray(r)[0])

    res = run_ranks(8, fn, devices=True)
    assert res == [12.0, 16.0] * 4  # 0+2+4+6, 1+3+5+7


def test_tpu_unsupported_op_falls_back():
    """MAXLOC (pair type) is not XLA-lowered; falls back through the
    host path and still returns correct results."""
    def fn(comm):
        x = _put(comm, jnp.full((4,), float(comm.rank), jnp.float32))
        # user op → host fallback
        fold = mpi_op.create(
            lambda a, b, _: np.copyto(b, np.maximum(a, b)), commute=True)
        r = comm.allreduce_arr(x, fold)
        return float(np.asarray(r)[0])

    res = run_ranks(4, fn, devices=True)
    assert res == [3.0] * 4


def test_tpu_bf16():
    """bf16 allreduce — the MXU-native dtype."""
    def fn(comm):
        x = _put(comm, jnp.full((16,), comm.rank + 1, dtype=jnp.bfloat16))
        r = comm.allreduce_arr(x, mpi_op.SUM)
        return float(np.asarray(r, dtype=np.float32)[0])

    res = run_ranks(4, fn, devices=True)
    assert res == [10.0] * 4


# ---------------------------------------------------------------------------
# coll/hbm: all ranks co-located on one device
# ---------------------------------------------------------------------------

def _one_dev(rank):
    return jax.devices()[0]


def test_hbm_selected_and_allreduce():
    def fn(comm):
        assert comm.coll.providers["allreduce_arr"] == "hbm"
        x = _put(comm, jnp.arange(8, dtype=jnp.float32) * (comm.rank + 1))
        r = comm.allreduce_arr(x, mpi_op.SUM)
        return np.asarray(r)

    res = run_ranks(4, fn, device_map=_one_dev)
    exp = np.arange(8, dtype=np.float32) * 10
    for r in res:
        np.testing.assert_allclose(r, exp)


def test_hbm_alltoall_allgather_bcast():
    n = 4

    def fn(comm):
        a2a = comm.alltoall_arr(_put(
            comm, jnp.arange(n, dtype=jnp.int32) + comm.rank * 10))
        ag = comm.allgather_arr(_put(comm, jnp.array([comm.rank],
                                                     jnp.int32)))
        b = comm.bcast_arr(_put(comm, jnp.array(
            [comm.rank * 5.0], jnp.float32)), root=2)
        rs = comm.reduce_scatter_arr(_put(
            comm, jnp.ones(n * 2, jnp.float32)), mpi_op.SUM)
        return (np.asarray(a2a).tolist(), np.asarray(ag).tolist(),
                float(np.asarray(b)[0]), np.asarray(rs).tolist())

    res = run_ranks(n, fn, device_map=_one_dev)
    for k, (a2a, ag, b, rs) in enumerate(res):
        assert a2a == [i * 10 + k for i in range(n)]
        assert ag == list(range(n))
        assert b == 10.0
        assert rs == [float(n)] * 2


def test_hbm_ppermute():
    n = 4

    def fn(comm):
        x = _put(comm, jnp.array([comm.rank], jnp.int32))
        y = comm.ppermute_arr(x, [(i, (i + 1) % n) for i in range(n)])
        return int(np.asarray(y)[0])

    res = run_ranks(n, fn, device_map=_one_dev)
    assert res == [(k - 1) % n for k in range(n)]


# ---------------------------------------------------------------------------
# host-staged fallback (no devices assigned)
# ---------------------------------------------------------------------------

def test_arr_host_fallback():
    def fn(comm):
        assert comm.coll.providers["allreduce_arr"] == "arr_host"
        x = jnp.full((4,), float(comm.rank + 1))
        r = comm.allreduce_arr(x, mpi_op.SUM)
        return float(np.asarray(r)[0])

    res = run_ranks(3, fn)  # no devices => host staging
    assert res == [6.0] * 3


def test_tpu_numpy_input_falls_back():
    """numpy buffers through the _arr surface still work (float32:
    a float64 one is refused while mpi_device_x64 is off, below)."""
    def fn(comm):
        x = np.full(4, comm.rank + 1.0, dtype=np.float32)
        r = comm.allreduce_arr(x, mpi_op.SUM)
        return float(np.asarray(r)[0])

    res = run_ranks(4, fn, devices=True)
    assert res == [10.0] * 4


# ---------------------------------------------------------------------------
# review-finding regressions
# ---------------------------------------------------------------------------

def test_tpu_scalar_allreduce():
    """0-d arrays (a loss value) must work on the device path."""
    def fn(comm):
        x = jax.device_put(jnp.float32(comm.rank + 1.0), comm.device)
        r = comm.allreduce_arr(x, mpi_op.SUM)
        assert np.asarray(r).shape == ()
        return float(r)

    res = run_ranks(4, fn, devices=True)
    assert res == [10.0] * 4


def test_hbm_alltoall_2d():
    """Multi-dimensional alltoall through the stacked path."""
    n = 4

    def fn(comm):
        x = _put(comm, jnp.arange(n * 3, dtype=jnp.float32).reshape(n, 3)
                 + comm.rank * 100)
        r = comm.alltoall_arr(x)
        return np.asarray(r)

    res = run_ranks(n, fn, device_map=_one_dev)
    for k, r in enumerate(res):
        assert r.shape == (n, 3)
        for src in range(n):
            np.testing.assert_allclose(
                r[src], np.arange(n * 3, dtype=np.float32).reshape(n, 3)[k]
                + src * 100)


_A2A_SHAPES = {
    "flat": lambda p: (1024 * p,),
    "trailing": lambda p: (64 * p, 3, 5),
    "odd_block": lambda p: (1009 * p,),
}
_A2A_OPS = 3


def _a2a_input(shape, dtype, rank):
    return (np.arange(int(np.prod(shape))) % 251 + rank) \
        .reshape(shape).astype(dtype)


def _a2a_world(shape, dtype, min_bytes):
    """One 4-rank world on one device: _A2A_OPS alltoall_arr calls of
    ``shape`` with the large-message tier starting at ``min_bytes``,
    traced so that the layer account counts the rendezvous.  Per rank:
    the bytes, what the result is, and what moved."""
    from ompi_tpu.coll import pipeline, plan
    from ompi_tpu.mca.params import registry
    hbm = registry.register_pvar("coll", "hbm", "offloaded_collectives")

    def fn(comm):
        assert comm.coll.providers["alltoall_arr"] == "hbm"
        tr = comm.state.tracer
        x = _put(comm, _a2a_input(shape, dtype, comm.rank))
        assert x.nbytes >= 2048

        def counters():
            return (pipeline.pv_ops.read(), plan.pv_segments.read(),
                    hbm.read(), tr.layer_totals()["rendezvous"])

        comm.Barrier()
        before = counters()
        for _ in range(_A2A_OPS):
            r = comm.alltoall_arr(x)
        comm.Barrier()
        return (np.asarray(r).tobytes(), r.shape, str(r.dtype),
                isinstance(r, jax.Array) and r.devices() == {comm.device},
                [b - a for a, b in zip(before, counters())])

    knobs = {"coll_pipeline_enable": True, "coll_seg_size": 4096,
             "coll_pipeline_min_bytes": min_bytes, "trace_enable": True,
             "trace_phase_enable": True, "trace_dump_path": ""}
    saved = {k: registry.get(k) for k in knobs}
    for k, v in knobs.items():
        registry.set(k, v)
    try:
        return run_ranks(4, fn, device_map=_one_dev)
    finally:
        for k, v in saved.items():
            registry.set(k, v)


@pytest.mark.parametrize("dtype", ["int32", "float32", "bfloat16"])
@pytest.mark.parametrize("shape_name", sorted(_A2A_SHAPES))
def test_hbm_alltoall_is_one_stacked_kernel_at_every_size(shape_name, dtype):
    """On one device an alltoall above coll_pipeline_min_bytes is the
    stacked path too (ISSUE 27): the bytes of numpy's exchange and of
    the below-threshold call, in the caller's shape, a jax.Array on
    the rank's device, one rendezvous an operation, and the pipeline's
    counters at rest."""
    P = 4
    shape = _A2A_SHAPES[shape_name](P)
    dt = jnp.dtype(dtype)
    above = _a2a_world(shape, dt, 2048)
    below = _a2a_world(shape, dt, 1 << 40)
    send = [_a2a_input(shape, dt, k) for k in range(P)]
    m = shape[0] // P
    for k, (got, want) in enumerate(zip(above, below)):
        data, rshape, rdtype, on_device, moved = got
        ref = np.concatenate([send[src][k * m:(k + 1) * m]
                              for src in range(P)])
        assert data == ref.tobytes()
        assert data == want[0]
        assert rshape == shape and rdtype == dtype
        assert on_device
        d_ops, d_segs, d_hbm, d_rdv = moved
        assert d_ops == 0 and d_segs == 0
        assert d_rdv == _A2A_OPS == want[4][3]
        # a process-wide counter, bumped by every rank-thread (a plain
        # += shared by the threads: allow it a lost update)
        assert abs(d_hbm - P * _A2A_OPS) <= 1


def test_arr_shapes_consistent_across_providers():
    """allgather/alltoall/reduce_scatter must return identical shapes
    whether served by tpu, hbm, or the host fallback."""
    def fn(comm):
        x = _put(comm, jnp.ones((comm.size * 2, 3), jnp.float32))
        ag = comm.allgather_arr(x)
        a2a = comm.alltoall_arr(x)
        rs = comm.reduce_scatter_arr(x, mpi_op.SUM)
        return (comm.coll.providers["allgather_arr"],
                np.asarray(ag).shape, np.asarray(a2a).shape,
                np.asarray(rs).shape)

    n = 4
    tpu_res = run_ranks(n, fn, devices=True)
    hbm_res = run_ranks(n, fn, device_map=_one_dev)
    host_res = run_ranks(n, fn)
    shapes = {r[1:] for r in tpu_res + hbm_res + host_res}
    assert len(shapes) == 1, shapes
    assert {r[0] for r in tpu_res} == {"tpu"}
    assert {r[0] for r in host_res} == {"arr_host"}


def test_mixed_residency_no_deadlock():
    """One rank passes numpy, the rest jax arrays — eligibility must
    not diverge (the device path moves stray buffers)."""
    def fn(comm):
        if comm.rank == 0:
            x = np.full(8, 1.0, dtype=np.float32)  # forgot device_put
        else:
            x = _put(comm, jnp.full((8,), 1.0, jnp.float32))
        r = comm.allreduce_arr(x, mpi_op.SUM)
        return float(np.asarray(r)[0])

    res = run_ranks(4, fn, devices=True, timeout=60)
    assert res == [4.0] * 4


def test_hbm_peer_abort_unblocks_rendezvous():
    """A rank dying before the rendezvous must not hang the others."""
    def fn(comm):
        if comm.rank == 1:
            raise ValueError("dead rank")
        x = _put(comm, jnp.ones((4,), jnp.float32))
        comm.allreduce_arr(x, mpi_op.SUM)
        return True

    with pytest.raises(Exception, match="dead rank|aborted"):
        run_ranks(3, fn, device_map=_one_dev, timeout=30)


def test_comm_free_drops_rendezvous():
    def fn(comm):
        sub = comm.dup()
        x = _put(comm, jnp.ones((4,), jnp.float32))
        sub.allreduce_arr(x, mpi_op.SUM)
        key = ("coll_rv", sub.cid, tuple(sub.group))
        world = comm.state.rte.world
        comm.Barrier()
        had = key in world.shared
        comm.Barrier()
        sub.Free()
        comm.Barrier()
        return had, key in world.shared

    res = run_ranks(2, fn, devices=True)
    assert res[0][0] is True and res[0][1] is False


def test_ring_attention_example_exact():
    """The long-context flagship: ring attention via ppermute_arr is
    EXACT full attention over the comm-wide sequence (online-softmax
    accumulation while KV blocks rotate the mesh ring)."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "ring_attention_example",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "examples",
            "ring_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.main()


# ---------------------------------------------------------------------------
# bounded compiled-collective cache (CompiledLRU)
# ---------------------------------------------------------------------------

def test_compile_cache_hit_skips_recompilation():
    """Repeating a (kind, mesh, shape, dtype, op) must reuse the
    compiled executable — asserted via the build trace counter, never
    timing."""
    from ompi_tpu.coll.device import compile_cache
    from ompi_tpu.mca.params import registry

    pv_hits = registry.register_pvar("coll", "device", "cache_hits")

    def fn(comm):
        x = _put(comm, jnp.arange(128, dtype=jnp.float32) + comm.rank)
        return np.asarray(comm.allreduce_arr(x, mpi_op.SUM)).sum()

    run_ranks(4, fn, devices=True)  # warm: compiles at most once
    builds0, hits0 = compile_cache.builds, pv_hits.read()
    run_ranks(4, fn, devices=True)  # identical world + shape: all hits
    assert compile_cache.builds == builds0
    assert pv_hits.read() > hits0


def test_compile_cache_lru_bound_under_shape_churn():
    """coll_device_cache_max is enforced: a churn of distinct shapes
    evicts LRU entries instead of growing without bound, and the
    eviction pvar moves."""
    from ompi_tpu.coll.device import compile_cache
    from ompi_tpu.mca.params import registry

    pv_evict = registry.register_pvar("coll", "device",
                                      "cache_evictions")
    old = registry.get("coll_device_cache_max")
    registry.set("coll_device_cache_max", 4)
    try:
        def fn(comm):
            tot = 0.0
            for n in range(1, 11):  # 10 distinct shapes
                x = _put(comm, jnp.ones((8 * n,), jnp.float32))
                tot += float(np.asarray(
                    comm.allreduce_arr(x, mpi_op.SUM))[0])
            return tot

        e0 = pv_evict.read()
        res = run_ranks(2, fn, devices=True)
        assert res == [20.0, 20.0]
        assert len(compile_cache) <= 4
        assert pv_evict.read() > e0
    finally:
        registry.set("coll_device_cache_max", old)


def test_compile_cache_fusion_signature_keys():
    """Fused batches key the cache on their full fusion signature:
    two different batch compositions are distinct fused entries (plus
    the per-rank pack helpers), and replaying the same compositions
    compiles nothing new."""
    from ompi_tpu.coll.device import compile_cache

    def fn(comm):
        q1 = comm.iallreduce_arr(jnp.arange(4, dtype=jnp.int32),
                                 mpi_op.SUM)
        comm.flush_arr()
        q2 = comm.iallreduce_arr(jnp.arange(4, dtype=jnp.int32),
                                 mpi_op.SUM)
        q3 = comm.ibcast_arr(jnp.ones((2,), jnp.float32), 0)
        comm.flush_arr()
        return q1.complete and q2.complete and q3.complete

    def fused_keys():
        return {k for k in compile_cache._d if k[0] == "fused"}

    k0 = fused_keys()
    assert all(run_ranks(2, fn, devices=True))
    assert len(fused_keys() - k0) == 2  # one fused exe per signature
    b1 = compile_cache.builds
    assert all(run_ranks(2, fn, devices=True))
    assert compile_cache.builds == b1  # warm replay: all cache hits


# ---------------------------------------------------------------------------
# mpi_device_x64 off (the default): an 8-byte element is refused, never
# narrowed.  The tests with it ON live in tests/test_cellbench_typed.py.
# ---------------------------------------------------------------------------

_ON_ONE_DEVICE = {"hbm": (4, lambda r: jax.devices()[0]), "tpu": (4, None)}


@pytest.mark.parametrize("layout", ["hbm", "tpu"])
@pytest.mark.parametrize("call", ["allreduce", "reduce_scatter", "bcast",
                                  "typed_host_buffer", "typed_f32_buffer"])
def test_x64_off_refuses_an_8_byte_buffer(layout, call):
    from ompi_tpu import errhandler
    from ompi_tpu.datatype import engine as dt

    assert not jax.config.jax_enable_x64
    vec = dt.vector(8, 1, 2, dt.DOUBLE).commit()

    def fn(comm):
        assert comm.coll.providers["allreduce_arr"] == layout
        host = np.arange(16, dtype=np.float64) + comm.rank
        with pytest.raises(errhandler.MPIException) as e:
            if call == "allreduce":
                comm.allreduce_arr(host, mpi_op.SUM)
            elif call == "reduce_scatter":
                comm.reduce_scatter_arr(host, mpi_op.MAX)
            elif call == "bcast":
                comm.bcast_arr(host, root=0)
            elif call == "typed_host_buffer":
                comm.reduce_scatter_arr(host, mpi_op.MAX, vec, 1)
            else:
                # what jax.device_put made of the doubles: float32
                comm.reduce_scatter_arr(_put(comm, host), mpi_op.MAX,
                                        vec, 1)
        assert "mpi_device_x64" in str(e.value)
        return e.value.code

    n, device_map = _ON_ONE_DEVICE[layout]
    res = run_ranks(n, fn, devices=True, device_map=device_map)
    assert set(res) == {errhandler.ERR_TYPE}


def test_x64_off_host_staged_fallback_refuses_too():
    """A call no device module takes (a pair dtype is one; here a
    1-rank comm) lands in coll/arr_host, whose way back to the device
    would narrow."""
    from ompi_tpu import errhandler

    def fn(comm):
        self_comm = comm.Split(comm.rank, 0)
        with pytest.raises(errhandler.MPIException) as e:
            self_comm.allreduce_arr(np.ones(4, np.float64), mpi_op.SUM)
        # 8 bytes that are not narrowed pass: complex64
        z = self_comm.allreduce_arr(np.ones(4, np.complex64), mpi_op.SUM)
        return e.value.code, str(z.dtype)

    res = run_ranks(2, fn, devices=True)
    assert res == [(errhandler.ERR_TYPE, "complex64")] * 2


def test_untyped_calls_keep_their_float32_path():
    """The two new arguments default to None: an untyped call resolves
    the same plan key as before (extra None) and moves no typed
    counter."""
    from ompi_tpu.mca.params import registry

    def fn(comm):
        pv = {p.full_name: p for p in registry.all_pvars()}
        t0 = pv["coll_typed_device_ops"].read()
        x = _put(comm, jnp.arange(16, dtype=jnp.float32))
        comm.reduce_scatter_arr(x, mpi_op.MAX)
        comm.allreduce_arr(x, mpi_op.SUM)
        keys = list(comm.__dict__["_hbm_plans"])
        comm.Barrier()
        return [k[-1] for k in keys], pv["coll_typed_device_ops"].read() - t0

    res = run_ranks(4, fn, devices=True,
                    device_map=lambda r: jax.devices()[0])
    assert all(extras == [None, None] and typed == 0
               for extras, typed in res)
