"""coll/pipeline: the segmented / pipelined / hierarchical
large-message device tier (DESIGN.md §12).

Byte-identity discipline: every segmented result is compared bytewise
against the fused single-dispatch path on the SAME world, using
exact-representable float values (small integers), so any reordering
bug — stripe bookkeeping, tail padding, pipeline depth — shows as a
hard byte diff, never a tolerance argument.  Fault and epoch tests
assert the same identity under ft_inject delay chaos and across ULFM
shrink + respawn epochs (segment state must not leak across epochs).
"""

import time

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
# per op, tails included, in test-sized arrays.  The compiled-plan
# path is pinned OFF: this file is the per-segment pipelined tier's
# coverage (tests/test_coll_plan.py covers the plan path)
PIPE_ON = {"coll_pipeline_enable": True, "coll_pipeline_min_bytes": 2048,
           "coll_seg_size": 4096, "coll_pipeline_rd_max_bytes": 0,
           "coll_hier_enable": False, "coll_plan_enable": False}
PIPE_OFF = {"coll_pipeline_enable": False, "coll_hier_enable": False}


def _mixed_ops(comm):
    """The canonical segmented workload: allreduce/bcast/alltoall over
    sizes that leave tails (count % seg in {0, 1, seg-1} territory),
    exact-representable values.  Returns concatenated result bytes."""
    r = comm.rank
    P = comm.size
    out = []
    # 4099 floats = 16 KiB + tail; values exact at any fold order
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
    m = 1031 * P  # odd per-rank block size
    a = _put(comm, jnp.arange(m, dtype=jnp.int32) + 100000 * r)
    a2a = np.asarray(comm.alltoall_arr(a)).tobytes()
    # (rank-symmetric results, rank-specific alltoall rows)
    return b"".join(out), a2a


def _run_twice(fn, n=4, **kw):
    """fn under the segmented tier, then under the fused path."""
    saved = _set(PIPE_ON)
    try:
        seg = run_ranks(n, fn, **kw)
    finally:
        _restore(saved)
    saved = _set(PIPE_OFF)
    try:
        fused = run_ranks(n, fn, **kw)
    finally:
        _restore(saved)
    return seg, fused


# ---------------------------------------------------------------------------
# correctness: segmented vs fused, byte for byte (tier-1 fast gate)
# ---------------------------------------------------------------------------

def test_segmented_mesh_byte_identical():
    """The fast deterministic 4-rank gate: every segmented mesh
    algorithm returns the same bytes as the fused path, the tier
    actually engaged (pvars moved), and all ranks agree."""
    from ompi_tpu.coll import pipeline

    def fn(comm):
        ops0 = pipeline.pv_ops.read()
        segs0 = pipeline.pv_segments.read()
        common, a2a = _mixed_ops(comm)
        return common, a2a, pipeline.pv_ops.read() - ops0, \
            pipeline.pv_segments.read() - segs0

    seg, fused = _run_twice(fn, 4, devices=True)
    assert len({c for c, _, _, _ in seg}) == 1   # ranks byte-agree
    for (sc, sa, dops, dsegs), (fc, fa, fops, _) in zip(seg, fused):
        assert sc == fc and sa == fa             # tier is invisible
        assert dops >= 5                         # ...but engaged
        assert dsegs > dops                      # multiple segments/op
        assert fops == 0                         # fused run untouched


def test_segmented_mixed_dtypes():
    """Odd dtypes through the identity-padded tail: int8 (sum stays in
    range), float16, float64, int64 — bytewise equal to fused."""
    def fn(comm):
        r = comm.rank
        out = []
        x8 = _put(comm, (jnp.arange(4097) % 3).astype(jnp.int8)
                  + np.int8(r % 2))
        out.append(np.asarray(comm.allreduce_arr(x8, mpi_op.SUM))
                   .tobytes())
        h = _put(comm, ((jnp.arange(2050) % 8) + r).astype(jnp.float16))
        out.append(np.asarray(comm.allreduce_arr(h, mpi_op.MAX))
                   .tobytes())
        d = _put(comm, (jnp.arange(1025, dtype=jnp.float64) % 9) + r)
        out.append(np.asarray(comm.allreduce_arr(d, mpi_op.SUM))
                   .tobytes())
        i64 = _put(comm, (jnp.arange(1000, dtype=jnp.int64) % 13)
                   * (r + 1))
        out.append(np.asarray(comm.allreduce_arr(i64, mpi_op.PROD))
                   .tobytes())
        return b"".join(out)

    seg, fused = _run_twice(fn, 4, devices=True)
    assert seg == fused
    assert len(set(seg)) == 1


def test_segmented_hbm_byte_identical():
    """Co-located ranks (one shared device): the segmented hbm
    allreduce (per-segment stacked kernels) is bytewise the monolithic
    one.  The hbm alltoall is the stacked whole-payload path on both
    sides (never segmented on one device): its bytes must not depend
    on the tier's knobs."""
    def _one_dev(r):
        return jax.devices()[0]

    def fn(comm):
        r = comm.rank
        base = (jnp.arange(5003, dtype=jnp.float32) % 7)
        x = _put(comm, base + r)
        a = _put(comm, jnp.arange(1009 * comm.size, dtype=jnp.int32)
                 + 1000 * r)
        return (np.asarray(comm.allreduce_arr(x, mpi_op.SUM)).tobytes(),
                np.asarray(comm.alltoall_arr(a)).tobytes())

    seg, fused = _run_twice(fn, 4, device_map=_one_dev)
    assert seg == fused
    # allreduce output is rank-symmetric; alltoall rows are per-rank
    assert len({ar for ar, _ in seg}) == 1


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
# chaos: delay faults and epoch boundaries
# ---------------------------------------------------------------------------

def test_segmented_under_delay_faults():
    """ft_inject 'delay' at the rendezvous choke point: arbitrary
    straggler arrival orders through the pipelined begin/finish
    schedule must not change a single byte."""
    def fn(comm):
        return _mixed_ops(comm)

    saved = _set(PIPE_ON)
    try:
        clean = run_ranks(4, fn, devices=True)
        chaos_knobs = _set({"ft_inject_plan": "delay",
                            "ft_inject_seed": 7, "ft_inject_rate": 0.5,
                            "ft_inject_delay_ms": 5, "ft_inject_skip": 0})
        try:
            chaotic = run_ranks(4, fn, devices=True)
        finally:
            _restore(chaos_knobs)
    finally:
        _restore(saved)
    assert clean == chaotic
    # cross-rank identity holds for the rank-symmetric ops (alltoall
    # rows are legitimately per-rank)
    assert len({common for common, _ in clean}) == 1


def test_segmented_across_shrink_epoch():
    """A rank dies mid-job: segmented collectives ran on the old
    epoch, the shrunk comm must route and compute freshly — results
    byte-identical to a never-failed world of the survivor size, and
    the old epoch's routing caches are gone from the parent comm."""
    from ompi_tpu.ft import ulfm

    def survivor(comm):
        _ = np.asarray(comm.allreduce_arr(
            _put(comm, (jnp.arange(4099, dtype=jnp.float32) % 11)
                 + comm.rank), mpi_op.SUM))  # old-epoch segmented op
        if comm.rank == 0:
            # a peer still leaving the op's last meeting would see the
            # death inside the old-epoch op, which is not this test
            time.sleep(0.2)
            ulfm.kill_now(comm.state)
        time.sleep(0.5)
        new = comm.shrink()
        assert "_pipeline_pick" not in comm.__dict__  # epoch hygiene
        assert "_hier_plan" not in comm.__dict__
        x = _put(new, (jnp.arange(4099, dtype=jnp.float32) % 11)
                 + new.rank)
        return np.asarray(new.allreduce_arr(x, mpi_op.SUM)).tobytes()

    def fresh(comm):
        x = _put(comm, (jnp.arange(4099, dtype=jnp.float32) % 11)
                 + comm.rank)
        return np.asarray(comm.allreduce_arr(x, mpi_op.SUM)).tobytes()

    saved = _set(PIPE_ON)
    try:
        got = run_ranks(4, survivor, devices=True, allow_failures=True)
        ref = run_ranks(3, fresh, devices=True)
    finally:
        _restore(saved)
    assert got[0] is None
    assert got[1] == got[2] == got[3] == ref[0]


def test_segmented_across_respawn_epoch():
    """Kill + in-job respawn between segmented collectives: the
    replacement's epoch must not see stale segment/routing state, and
    the completed job's bytes match a fault-free run exactly."""
    from ompi_tpu import errhandler as eh
    from ompi_tpu.cr import buddy
    from ompi_tpu.errhandler import MPIException
    from ompi_tpu.ft import respawn, ulfm

    ft_codes = (eh.ERR_PROC_FAILED, eh.ERR_PROC_FAILED_PENDING,
                eh.ERR_REVOKED)

    def make_fn(kill_at=None, iters=4):
        kill_at = kill_at or {}

        def fn(comm):
            state = comm.state
            was_joining = respawn.joining(state)
            if was_joining:
                comm = respawn.rejoin(comm)
                st = buddy.restore(comm)
                i, acc = int(st["i"]), np.asarray(st["acc"])
            else:
                i, acc = 0, np.zeros(4099, np.float32)
            did_kill = False
            base = (jnp.arange(4099, dtype=jnp.float32) % 11)
            while i < iters:
                try:
                    buddy.checkpoint(comm, {"i": i, "acc": acc})
                    if (not was_joining and not did_kill
                            and kill_at.get(comm.rank) == i):
                        did_kill = True
                        ulfm.kill_now(state)
                    x = _put(comm, base * (i + 1) + comm.rank)
                    acc = np.asarray(
                        comm.allreduce_arr(x, mpi_op.SUM))
                    i += 1
                except MPIException as e:
                    if e.code not in ft_codes:
                        raise
                    comm = respawn.rejoin(comm)
                    st = buddy.restore(comm)
                    i, acc = int(st["i"]), np.asarray(st["acc"])
            return acc.tobytes()
        return fn

    saved = _set(PIPE_ON)
    registry.set("cr_buddy_degree", "1")
    try:
        # devices=True: the point is the SEGMENTED DEVICE tier across
        # the epoch (the rendezvous waits poll ulfm, so every survivor
        # detects the failure — the host p2p tree would leave a rank
        # waiting on a live peer that already left for rejoin)
        clean = run_ranks(4, make_fn(), devices=True, timeout=120)
        faulty = run_ranks(4, make_fn(kill_at={1: 2}), devices=True,
                           timeout=180, respawn=True)
    finally:
        registry.set("cr_buddy_degree", "0")
        _restore(saved)
    assert faulty == clean
    assert all(r is not None for r in faulty)


# ---------------------------------------------------------------------------
# cache bounds and observability
# ---------------------------------------------------------------------------

def test_seg_kernel_cache_not_blown_by_message_sizes():
    """The eviction-pressure satellite: a sweep of distinct message
    sizes all routes through ONE identity-padded segment shape, so the
    CompiledLRU gains ~one segmented entry, the hits pvar climbs, and
    eviction pressure stays flat."""
    from ompi_tpu.coll.device import compile_cache

    pv_hits = registry.register_pvar("coll", "device", "cache_hits")
    pv_evict = registry.register_pvar("coll", "device",
                                      "cache_evictions")

    def fn(comm):
        tot = 0.0
        for n in range(1, 13):  # 12 distinct message sizes, one dtype
            x = _put(comm, jnp.ones((513 * n + n % 3,), jnp.float32))
            tot += float(np.asarray(
                comm.allreduce_arr(x, mpi_op.SUM))[0])
        return tot

    saved = _set(PIPE_ON)
    try:
        run_ranks(4, fn, devices=True)  # warm: compile the seg kernel
        builds0, hits0, evict0 = (compile_cache.builds, pv_hits.read(),
                                  pv_evict.read())
        res = run_ranks(4, fn, devices=True)
        assert res == [4.0 * 12] * 4
        # identical world: zero new executables across 12 sizes
        assert compile_cache.builds == builds0
        assert pv_hits.read() > hits0
        assert pv_evict.read() == evict0
        # the segmented entries are keyed by segment shape, not
        # message size: at most a couple of seg keys exist for this
        # 4-device world (other tests' shrunk worlds may add theirs)
        seg_keys = [k for k in list(compile_cache._d)
                    if isinstance(k, tuple) and k
                    and k[0] == "segring" and len(k[1]) == 4]
        assert 0 < len(seg_keys) <= 2
    finally:
        _restore(saved)


def test_coll_segment_histogram_and_spans():
    """Per-segment meets feed the coll_segment trace category: spans
    carry (cid, seq, nbytes), the HIST_COLL_SEGMENT histogram counts
    them, and the MPI_T pvar surface exports it."""
    from ompi_tpu import trace

    def fn(comm):
        x = _put(comm, (jnp.arange(4099, dtype=jnp.float32) % 11)
                 + comm.rank)
        comm.allreduce_arr(x, mpi_op.SUM)
        tr = comm.state.tracer
        segs = [e for e in tr.snapshot() if e["cat"] == "coll_segment"]
        assert segs and all("cid" in e["args"] for e in segs)
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
    assert all(n > 1 for n in res)  # several segments traced


# ---------------------------------------------------------------------------
# stress (excluded from the tier-1 fast gate)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_pipeline_stress_8rank():
    """8 ranks, deeper pipeline, repeated mixed segmented collectives
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

    saved = _set(dict(PIPE_ON, coll_pipeline_depth=3))
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
