"""coll/pipeline: the router of the large-message device tier and its
hierarchical allreduce (DESIGN.md §12).

Byte-identity discipline: every result of the tier is compared
bytewise against the fused single-dispatch path on the SAME world,
using exact-representable float values (small integers), so any
reordering bug — stripe bookkeeping, tail padding — shows as a hard
byte diff, never a tolerance argument.  Which algorithm the tier
picks, what it caches and what it traces are held here; the plans' own
byte identity, counters, fault and epoch coverage is
tests/test_coll_plan.py's, and the router's table
tests/test_coll_router.py's.
"""

import numpy as np
import pytest

from ompi_tpu.mca.params import registry
from ompi_tpu.op import op as mpi_op
from ompi_tpu.testing import run_ranks

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

# register the pipeline + plan knobs before any _set() snapshot, so
# saved values are real defaults (not the unregistered-knob None
# sentinel)
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


# route everything >= 2 KiB through 4 KiB segments: several segments
# per op, tails included, in test-sized arrays
PIPE_ON = {"coll_pipeline_enable": True, "coll_pipeline_min_bytes": 2048,
           "coll_seg_size": 4096, "coll_pipeline_rd_max_bytes": 0,
           "coll_hier_enable": False}
PIPE_OFF = {"coll_pipeline_enable": False, "coll_hier_enable": False}


def test_recursive_doubling_window():
    """Power-of-two comm inside the rd window: segrd must be picked
    (not segring) and stay byte-identical across ranks and vs fused —
    the operand-order-swap discipline under test."""
    from ompi_tpu.coll import tuned

    def fn(comm):
        x = _put(comm, (jnp.arange(4099, dtype=jnp.float32) % 11)
                 + comm.rank)
        alg = tuned.device_algorithm(comm, "allreduce", int(x.nbytes))
        return np.asarray(comm.allreduce_arr(x, mpi_op.SUM)).tobytes(), \
            alg

    saved = _set(dict(PIPE_ON, coll_pipeline_rd_max_bytes=1 << 30))
    try:
        seg = run_ranks(4, fn, devices=True)
    finally:
        _restore(saved)
    saved = _set(PIPE_OFF)
    try:
        fused = run_ranks(4, fn, devices=True)
    finally:
        _restore(saved)
    assert all(alg == "segrd" for _, alg in seg)
    assert len({b for b, _ in seg}) == 1
    assert [b for b, _ in seg] == [b for b, _ in fused]


def test_hierarchical_allreduce():
    """Forced 2x4 slices on 8 ranks: the hier tier engages (pvar) and
    the result is bitwise-consistent across every rank and equal to
    the fused reference."""
    from ompi_tpu.coll import pipeline

    def fn(comm):
        h0 = pipeline.pv_hier.read()
        base = (jnp.arange(3001, dtype=jnp.float32) % 9)
        x = _put(comm, base + comm.rank)
        out = np.asarray(comm.allreduce_arr(x, mpi_op.SUM)).tobytes()
        return out, pipeline.pv_hier.read() - h0

    saved = _set({"coll_pipeline_enable": True, "coll_hier_enable": True,
                  "coll_hier_slice_size": 4, "coll_hier_min_bytes": 1024,
                  "coll_pipeline_min_bytes": 2048,
                  "coll_seg_size": 4096})
    try:
        seg = run_ranks(8, fn, devices=True)
    finally:
        _restore(saved)
    saved = _set(PIPE_OFF)
    try:
        fused = run_ranks(8, fn, devices=True)
    finally:
        _restore(saved)
    assert len({b for b, _ in seg}) == 1
    assert all(d > 0 for _, d in seg)
    assert seg[0][0] == fused[0][0]


# ---------------------------------------------------------------------------
# cache bounds and observability
# ---------------------------------------------------------------------------

def test_plan_cache_holds_one_program_a_length():
    """A plan runs at the payload's own length (ISSUE 39): a sweep of
    distinct message sizes compiles one program a size, exactly as a
    sweep under the tier's crossover does, each of the payload's shape
    and none of a padded one; a second identical sweep compiles
    nothing, the hits pvar climbs and nothing is evicted (the
    CompiledLRU bounds what is held)."""
    from ompi_tpu.coll.device import compile_cache

    pv_hits = registry.register_pvar("coll", "device", "cache_hits")
    pv_evict = registry.register_pvar("coll", "device",
                                      "cache_evictions")
    sizes = [513 * n + n % 3 for n in range(1, 13)]   # 12, one dtype

    def fn(comm):
        tot = 0.0
        for n in sizes:
            x = _put(comm, jnp.ones((n,), jnp.float32))
            tot += float(np.asarray(
                comm.allreduce_arr(x, mpi_op.SUM))[0])
        return tot

    saved = _set(PIPE_ON)
    try:
        run_ranks(4, fn, devices=True)  # warm: compile the programs
        builds0, hits0, evict0 = (compile_cache.builds, pv_hits.read(),
                                  pv_evict.read())
        res = run_ranks(4, fn, devices=True)
        assert res == [4.0 * 12] * 4
        # identical world: zero new executables across 12 sizes
        assert compile_cache.builds == builds0
        assert pv_hits.read() > hits0
        assert pv_evict.read() == evict0
        # 513 to 6,156 floats: twelve programs, each its payload's
        # length, none donated (nothing was padded)
        keys = [k for k in list(compile_cache._d)
                if isinstance(k, tuple) and k
                and k[0] == "plan_native" and len(k[1]) == 4
                and k[2] in {(n,) for n in sizes} and k[3] == "<f4"
                and k[4] == "MPI_SUM"]
        assert len(keys) == 12 and not any(k[5] for k in keys)
        assert not [k for k in list(compile_cache._d)
                    if k[0] == "plan_pad_trim" and k[1] in sizes]
    finally:
        _restore(saved)


def test_coll_segment_histogram_and_spans():
    """A planned operation feeds the coll_segment trace category: ONE
    plan_exec span an operation, carrying (cid, nbytes, alg, op); the
    HIST_COLL_SEGMENT histogram counts them (coll/autotune folds it:
    the tier's latency pulse), and the MPI_T pvar surface exports it."""
    from ompi_tpu import trace

    def fn(comm):
        x = _put(comm, (jnp.arange(4099, dtype=jnp.float32) % 11)
                 + comm.rank)
        comm.allreduce_arr(x, mpi_op.SUM)
        comm.bcast_arr(x, 1)
        tr = comm.state.tracer
        segs = [e for e in tr.snapshot() if e["cat"] == "coll_segment"]
        assert [(e["name"], e["args"]["alg"], e["args"]["nbytes"])
                for e in segs] == [("plan_exec", "segring", 4 * 4099),
                                   ("plan_exec", "segbcast", 4 * 4099)]
        assert all(e["args"]["cid"] == comm.cid for e in segs)
        assert [e["args"]["op"] for e in segs] == [
            comm._coll_seq - 1, comm._coll_seq]
        assert tr.hist_total(trace.HIST_COLL_SEGMENT) == len(segs)
        from ompi_tpu import mpit
        mpit.init_thread()
        try:
            sess = mpit.pvar_session_create()
            ph = mpit.pvar_handle_alloc(sess, "trace_hist_coll_segment")
            assert sum(mpit.pvar_read(ph)) == len(segs)
        finally:
            mpit.finalize()
        return len(segs)

    saved = _set(dict(PIPE_ON, trace_enable="1", trace_dump_path=""))
    try:
        res = run_ranks(4, fn, devices=True)
    finally:
        _restore(saved)
    assert res == [2] * 4   # one span an operation, however many segments


# ---------------------------------------------------------------------------
# stress (excluded from the tier-1 fast gate)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_pipeline_stress_8rank():
    """8 ranks, repeated mixed large-message collectives
    with rotating sizes: byte-identical to the fused path and across
    ranks every iteration."""
    def fn(comm):
        common, a2a = [], []
        for it in range(6):
            n = 3001 + 997 * it
            base = (jnp.arange(n, dtype=jnp.float32) % 13)
            x = _put(comm, base + comm.rank * (it + 1))
            common.append(np.asarray(
                comm.allreduce_arr(x, mpi_op.SUM)).tobytes())
            a = _put(comm, jnp.arange(257 * comm.size, dtype=jnp.int64)
                     + 10**6 * comm.rank + it)
            a2a.append(np.asarray(comm.alltoall_arr(a)).tobytes())
            b = _put(comm, base * (comm.rank + it + 1))
            common.append(np.asarray(
                comm.bcast_arr(b, root=it % comm.size)).tobytes())
        return b"".join(common), b"".join(a2a)

    saved = _set(PIPE_ON)
    try:
        seg = run_ranks(8, fn, devices=True, timeout=600)
    finally:
        _restore(saved)
    saved = _set(PIPE_OFF)
    try:
        fused = run_ranks(8, fn, devices=True, timeout=600)
    finally:
        _restore(saved)
    # allreduce/bcast are rank-symmetric; alltoall rows are per-rank
    assert len({common for common, _ in seg}) == 1
    assert seg == fused
