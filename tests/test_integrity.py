"""Silent-data-corruption plane tests (DESIGN.md §25): a chip that
computes wrong answers while passing every heartbeat must be caught by
the sampled algebraic cross-check on the rendezvous path, attributed
to the corrupting rank by the bisection round, convicted into the §24
health plane (immediate quarantine — never a failed job), and the
poisoned op retried from pristine sources byte-identically.  The
chaos matrix composes device_sdc with host_slow and rank_kill on a
2-host pool; satellites cover the wire payload digest above CRC and
the buddy-tier CRC restore fallback."""

import os
import time
import types

import numpy as np
import pytest

from ompi_tpu.mca.params import registry

jax = pytest.importorskip("jax")

# knob registration happens at import: an unregistered knob reads back
# None from the registry, which _restore would then "restore" as a
# None override and crash the coercion
import ompi_tpu.ft_inject  # noqa: E402,F401
import ompi_tpu.cr.buddy  # noqa: E402,F401
import ompi_tpu.cr.ckpt  # noqa: E402,F401
from ompi_tpu.obs import integrity as ig  # noqa: E402
from ompi_tpu.obs.health import (HEALTHY, QUARANTINED,  # noqa: E402
                                 HealthPlane)
from ompi_tpu.op import op as mpi_op  # noqa: E402
from ompi_tpu.testing import run_ranks  # noqa: E402
from ompi_tpu.tools.dvm import DVMServer, DvmClient  # noqa: E402

HERE = os.path.dirname(__file__)
SDC_PROG = os.path.join(HERE, "_sdc_prog.py")
HOST_PROG = os.path.join(HERE, "_fleet_host_prog.py")


def _set(vals):
    saved = {k: registry.get(k) for k in vals}
    for k, v in vals.items():
        registry.set(k, v)
    return saved


def _restore(saved):
    for k, v in saved.items():
        registry.set(k, v)
    ig.refresh()


def _pv(name):
    return registry._pvars[name].read()


def _lines(stdout, kind, tag):
    out = []
    for line in stdout.splitlines():
        parts = line.split()
        if len(parts) >= 3 and parts[0] == kind and parts[1] == tag:
            out.append(parts[2:])
    return out


ARM = {
    "integrity_enable": 1,
    "integrity_sample": 1,
    "integrity_sample_auto": 0,
}

INJECT = dict(ARM, **{
    "ft_inject_plan": "device_sdc:1",
    "ft_inject_victim_rank": "1",
    "ft_inject_sdc_period": 1,
})


# -- tentpole: digest algebra ------------------------------------------------


def test_digest_modular_int_exactness():
    """Int SUM digests are exact mod 2^width: a uint8 reduction that
    overflows on-device still matches the python-int fold of per-rank
    claims under the width mask — overflow is never a false
    positive."""
    a = np.array([200], np.uint8)
    b = np.array([100], np.uint8)
    da = ig.digest(a, ig.F_INTSUM)
    db = ig.digest(b, ig.F_INTSUM)
    # device result wraps: 300 mod 256 = 44
    out = np.array([44], np.uint8)
    dout = ig.digest(out, ig.F_INTSUM)
    assert ig._eq(ig.F_INTSUM, da + db, dout, 1, 0.0)
    # and a genuinely wrong result is NOT masked by the wrap
    bad = np.array([45], np.uint8)
    assert not ig._eq(ig.F_INTSUM, da + db,
                      ig.digest(bad, ig.F_INTSUM), 1, 0.0)


def test_digest_float_tolerance_band():
    """Float SUM digests compare within the relative band (device
    reassociation rounds differently from the float64 host fold);
    MAX/MIN are exact; non-finite digests fail open (NaN poisoning is
    a model problem, not chip corruption)."""
    assert ig._eq(ig.F_FSUM, 1.0, 1.0 + 5e-5, 4, 1e-4)
    assert not ig._eq(ig.F_FSUM, 1.0, 1.001, 4, 1e-4)
    assert ig._eq(ig.F_MAX, 9.0, 9.0, 4, 1e-4)
    assert not ig._eq(ig.F_MAX, 9.0, 9.0 + 1e-9, 4, 1e-4)
    assert ig._eq(ig.F_FSUM, float("nan"), 1.0, 4, 1e-4)
    assert ig._eq(ig.F_FSUM, float("inf"), 1.0, 4, 1e-4)


def test_digest_kinds_and_empty():
    x = np.array([3, 9, 2], np.int32)
    assert ig.digest(x, ig.F_MAX) == 9
    assert ig.digest(x, ig.F_MIN) == 2
    assert ig.digest(np.empty(0, np.float32), ig.F_FSUM) == 0.0
    assert ig.digest(np.empty(0, np.int32), ig.F_INTSUM) == 0
    # int digests view bytes as unsigned — negative ints digest too
    assert ig.digest(np.array([-1], np.int32), ig.F_INTSUM) \
        == 0xFFFFFFFF


def test_spec_gating():
    """spec() is None unarmed; armed, it classifies exactly the
    algebraically-checkable (kind, op, dtype) set — bool and exotic
    reduce ops are excluded rather than false-positived."""
    saved = _set(ARM)
    try:
        ig.set_armed(False)
        assert ig.spec("allreduce", "MPI_SUM",
                       np.zeros(2, np.float32)) is None
        ig.set_armed(True)
        f = np.zeros(2, np.float32)
        i = np.zeros(2, np.int32)
        assert ig.spec("allreduce", "MPI_SUM", f) \
            == ("allreduce", ig.F_FSUM, 4)
        assert ig.spec("allreduce", "MPI_MAX", i) \
            == ("allreduce", ig.F_MAX, 4)
        assert ig.spec("redscat", "MPI_MIN", i) \
            == ("redscat", ig.F_MIN, 4)
        assert ig.spec("allreduce", "MPI_PROD", f) is None
        assert ig.spec("allreduce", "MPI_SUM",
                       np.zeros(2, np.bool_)) is None
        assert ig.spec("gather", "", i) == ("gather", ig.F_INTSUM, 4)
        assert ig.spec("alltoall", "", f) \
            == ("alltoall", ig.F_FSUM, 4)
        assert ig.spec("bcast", "", f, root=2) \
            == ("bcast", ig.F_FSUM, 4, 2)
    finally:
        _restore(saved)


def test_sampler_adaptive_and_deterministic():
    """The per-comm countdown starts dense (period 1) and doubles
    toward the cap as clean checks bank — and two comms walking the
    same op sequence make identical decisions (the comm-consistency
    invariant the last-arriver execution model requires)."""
    saved_cap, saved_auto = ig._cap, ig._auto
    ig._cap, ig._auto = 8, 2
    try:
        c1, c2 = types.SimpleNamespace(), types.SimpleNamespace()
        s1 = [ig.sample(c1) for _ in range(100)]
        s2 = [ig.sample(c2) for _ in range(100)]
        assert s1 == s2
        assert s1[0] == 1  # fresh world: checked immediately
        # the period ramps: early ops sample denser than late ops
        assert sum(s1[:20]) > sum(s1[-20:])
        assert c1.__dict__["_ig_state"][1] == 8  # ramped to the cap
        # steady state at the cap: exactly 1-in-8 from here on
        tail = [ig.sample(c1) for _ in range(80)]
        assert sum(tail) == 10
    finally:
        ig._cap, ig._auto = saved_cap, saved_auto


# -- tentpole: detect / attribute / survive on the device path ---------------


def _conviction_ranks():
    return sorted({r["rank"] for r in ig.convicted_snapshot()})


def test_mesh_detect_convict_retry_across_op_kinds():
    """device_sdc flips rank 1's operand on every mesh collective;
    with 1-in-1 sampling every flip is detected at the rendezvous,
    bisection convicts exactly rank 1, and the retry-from-source makes
    every result analytically exact — never a failed job, never a
    wrong answer."""
    saved = _set(INJECT)
    ig.refresh()
    ig.reset()
    base_m = _pv("integrity_mismatches")
    base_c = _pv("integrity_convictions")
    base_r = _pv("integrity_retry_ops")

    def fn(comm):
        import jax.numpy as jnp
        rank, size = comm.rank, comm.size
        outs = []
        s = comm.allreduce_arr(
            jnp.full((32,), float(rank + 1), jnp.float32), mpi_op.SUM)
        outs.append(np.array_equal(
            np.asarray(s), np.full(32, 10.0, np.float32)))
        m = comm.allreduce_arr(
            jnp.full((8,), (rank + 1) * 100, jnp.int32), mpi_op.MAX)
        outs.append(np.array_equal(
            np.asarray(m), np.full(8, 400, np.int32)))
        # victim as root: the flip propagates unless caught
        b = comm.bcast_arr(
            jnp.full((16,), float(rank * 10 + 7), jnp.float32), root=1)
        outs.append(np.array_equal(
            np.asarray(b), np.full(16, 17.0, np.float32)))
        rs = comm.reduce_scatter_arr(
            jnp.full((size * 4,), float(rank + 1), jnp.float32),
            mpi_op.SUM)
        outs.append(np.array_equal(
            np.asarray(rs), np.full(4, 10.0, np.float32)))
        ag = comm.allgather_arr(jnp.full((2,), rank + 1, jnp.int32))
        outs.append(np.array_equal(
            np.asarray(ag).ravel(),
            np.repeat(np.arange(1, size + 1, dtype=np.int32), 2)))
        at = comm.alltoall_arr(jnp.full((size,), rank + 1, jnp.int32))
        outs.append(np.array_equal(
            np.asarray(at).ravel(),
            np.arange(1, size + 1, dtype=np.int32)))
        return all(outs)
    try:
        assert all(run_ranks(4, fn, devices=True))
        assert _conviction_ranks() == [1]
        assert _pv("integrity_mismatches") > base_m
        assert _pv("integrity_convictions") > base_c
        assert _pv("integrity_retry_ops") > base_r
    finally:
        ig.reset()
        _restore(saved)


@pytest.mark.parametrize("inject", [False, True])
def test_planned_mesh_movers_take_one_decision_a_call(inject):
    """A large mesh bcast or alltoall is one compiled plan behind one
    rendezvous (coll/plan.mesh_move), so the integrity plane takes one
    decision a call, however many segments the call covers; a flip on
    the root is caught, rank 1 convicted and the answers
    exact.  The ragged allreduce beside them is padded by the plan's
    pack stage, which must not donate what the plane re-reads after
    a mismatch."""
    import ompi_tpu.coll.pipeline  # noqa: F401  (registers the knobs)
    import ompi_tpu.coll.plan  # noqa: F401
    tier = {"coll_pipeline_min_bytes": 2048, "coll_seg_size": 4096}
    n = 4 * 1024 + 4            # five 4 KiB segments a call, a tail

    def fn(comm):
        import jax.numpy as jnp
        rank, size = comm.rank, comm.size
        b = comm.bcast_arr(
            jnp.full((n,), float(rank * 10 + 7), jnp.float32), root=1)
        at = comm.alltoall_arr(
            jnp.repeat(jnp.arange(size, dtype=jnp.int32) + 10 * rank,
                       n // size))
        s = comm.allreduce_arr(
            jnp.full((n,), rank + 1, jnp.int32), mpi_op.SUM)
        return (np.array_equal(np.asarray(b), np.full(n, 17.0, np.float32))
                and np.array_equal(np.asarray(s), np.full(n, 10, np.int32))
                and np.array_equal(
                    np.asarray(at),
                    np.repeat(np.arange(size, dtype=np.int32) * 10 + rank,
                              n // size)))

    saved = _set(dict(INJECT if inject else ARM, **tier))
    ig.refresh()
    ig.reset()
    base_k, base_m = _pv("integrity_checks"), _pv("integrity_mismatches")
    try:
        assert all(run_ranks(4, fn, devices=True))
        if inject:
            assert _conviction_ranks() == [1]
            assert _pv("integrity_mismatches") > base_m
        else:
            assert _pv("integrity_mismatches") == base_m
            assert ig.convicted_snapshot() == []
            # three calls on four ranks
            assert _pv("integrity_checks") - base_k == 3 * 4
    finally:
        ig.reset()
        _restore(saved)


def test_hbm_detect_convict_retry():
    """Same contract on the co-located (hbm) dispatcher: every rank on
    one chip, victim rank 1 flipping — detection, attribution to rank
    1, byte-exact retried results."""
    saved = _set(INJECT)
    ig.refresh()
    ig.reset()
    dev0 = jax.devices()[0]

    def fn(comm):
        import jax.numpy as jnp
        rank = comm.rank
        s = comm.allreduce_arr(
            jnp.full((16,), float(rank + 1), jnp.float32), mpi_op.SUM)
        b = comm.bcast_arr(
            jnp.full((8,), float(rank + 5), jnp.float32), root=1)
        return (np.array_equal(np.asarray(s),
                               np.full(16, 10.0, np.float32))
                and np.array_equal(np.asarray(b),
                                   np.full(8, 6.0, np.float32)))
    try:
        assert all(run_ranks(4, fn, device_map=lambda r: dev0))
        assert _conviction_ranks() == [1]
    finally:
        ig.reset()
        _restore(saved)


@pytest.mark.parametrize("mode", ["mesh", "hbm"])
def test_fused_batch_detect(mode):
    """The nonblocking fusion engine batches ops into ONE rendezvous;
    the fused check spec carries one entry per group/slot so a flip
    inside the batch is still detected and attributed to rank 1, and
    the whole batch retries from pristine sources."""
    saved = _set(INJECT)
    ig.refresh()
    ig.reset()
    dev0 = jax.devices()[0]

    def fn(comm):
        import jax.numpy as jnp
        rank, size = comm.rank, comm.size
        qs = [comm.iallreduce_arr(
                  jnp.full((16,), float(rank + 1), jnp.float32),
                  mpi_op.SUM),
              comm.iallreduce_arr(
                  jnp.full((4,), (rank + 1) * 10, jnp.int32),
                  mpi_op.MAX),
              comm.ibcast_arr(
                  jnp.full((8,), rank * 2 + 3, jnp.int32), 1 % size)]
        for q in qs:
            q.wait()
        return (np.array_equal(np.asarray(qs[0].result),
                               np.full(16, 10.0, np.float32))
                and np.array_equal(np.asarray(qs[1].result),
                                   np.full(4, 40, np.int32))
                and np.array_equal(np.asarray(qs[2].result),
                                   np.full(8, 5, np.int32)))
    try:
        if mode == "mesh":
            assert all(run_ranks(4, fn, devices=True))
        else:
            assert all(run_ranks(4, fn, device_map=lambda r: dev0))
        assert _conviction_ranks() == [1]
    finally:
        ig.reset()
        _restore(saved)


def test_clean_run_zero_false_positives():
    """Armed at 1-in-1 sampling with NO fault injected: a full op mix
    (float sums included — the reassociation-band case) must bank
    checks without a single mismatch."""
    saved = _set(ARM)
    ig.refresh()
    ig.reset()
    base_k = _pv("integrity_checks")
    base_m = _pv("integrity_mismatches")

    def fn(comm):
        import jax.numpy as jnp
        rank, size = comm.rank, comm.size
        comm.allreduce_arr(
            jnp.full((1024,), 0.1 * (rank + 1), jnp.float32),
            mpi_op.SUM)
        comm.allreduce_arr(
            jnp.full((16,), rank, jnp.int32), mpi_op.MIN)
        comm.bcast_arr(jnp.arange(32, dtype=jnp.float32), root=0)
        comm.allgather_arr(jnp.full((4,), rank + 1, jnp.float32))
        qs = [comm.iallreduce_arr(
                  jnp.full((8,), float(rank), jnp.float32),
                  mpi_op.SUM),
              comm.ibcast_arr(jnp.full((4,), 3, jnp.int32), 1 % size)]
        for q in qs:
            q.wait()
        return True
    try:
        assert all(run_ranks(4, fn, devices=True))
        assert _pv("integrity_checks") > base_k
        assert _pv("integrity_mismatches") == base_m
        assert ig.convicted_snapshot() == []
    finally:
        ig.reset()
        _restore(saved)


def test_bisect_convicts_executing_rank_on_compute_corruption():
    """When every deposited operand still matches its gate claim, the
    reduction itself was computed wrong — the executing chip (the
    last-arriver running this closure) is the culprit."""
    saved = _set(ARM)
    ig.refresh()
    ig.reset()
    comm = types.SimpleNamespace(rank=2, cid=0, _dev_seq=0,
                                 group=[0, 1, 2, 3])
    ck = ("allreduce", ig.F_INTSUM, 4)
    shards = []
    for r in range(4):
        a = np.full(4, r + 1, np.int32)
        shards.append(ig._Checked(a, a.copy(),
                                  ig.digest(a, ig.F_INTSUM), r))

    def bad_fn(parts):
        out = np.sum(np.stack([np.asarray(p) for p in parts]), axis=0,
                     dtype=np.int32)
        out[0] += 1  # the "chip" mis-computes the reduction
        return [out]
    base_r = _pv("integrity_retry_ops")
    try:
        ig._run_checked(comm, bad_fn, ck, shards)
        recs = ig.convicted_snapshot()
        assert len(recs) == 1
        assert recs[0]["rank"] == 2  # the executing rank, by fallback
        assert recs[0]["kind"] == "allreduce"
        assert _pv("integrity_retry_ops") == base_r + 1
    finally:
        ig.reset()
        _restore(saved)


def test_checker_defect_fails_open():
    """A defect inside the verifier must never take down the datapath
    (the plane's contract is 'never a failed job'): a ck whose claims
    blow up the comparison passes the op through untouched."""
    comm = types.SimpleNamespace(rank=0, cid=0, _dev_seq=0,
                                 group=[0, 1])
    a = np.full(4, 1, np.int32)
    shards = [ig._Checked(a, a.copy(), object(), 0),
              ig._Checked(a, a.copy(), object(), 1)]
    out = ig._run_checked(
        comm, lambda parts: [np.asarray(parts[0]) * 2],
        ("allreduce", ig.F_INTSUM, 4), shards)
    assert np.array_equal(out[0], a * 2)
    assert ig.convicted_snapshot() == []


# -- tentpole: the injector and flip shape -----------------------------------


def test_sdc_injector_deterministic():
    from ompi_tpu.ft_inject import SdcInjector, sdc_injector
    inj = SdcInjector(1, 3, 2)
    seq = [inj.should_flip() for _ in range(12)]
    # armed at op 3, then every 2nd op after
    assert seq == [False, False, True, False, True, False, True,
                   False, True, False, True, False]
    assert inj.flips == 5
    assert inj.last_flip_ns > 0
    one_shot = SdcInjector(1, 2, 0)
    assert [one_shot.should_flip() for _ in range(8)] \
        == [False, True] + [False] * 6
    assert sdc_injector(0, 4) is None  # plan empty: fully passive


def test_flip_targets_checked_carrier():
    """flip_value on a _Checked carrier retargets only the datapath
    binding: the pristine source and the gate claim survive — exactly
    the divergence _bisect attributes.  On an unwrapped value the flip
    mutates a COPY (device buffers are donated; the corruption must
    not write back into application arrays)."""
    a = np.full(9, 1.0, np.float32)
    c = ig._Checked(a, a.copy(), ig.digest(a, ig.F_FSUM), 0)
    ig.flip_value(c)
    assert not np.array_equal(np.asarray(c.v), a)  # datapath corrupted
    assert np.array_equal(c.src, a)                # source pristine
    assert ig._eq(ig.F_FSUM, c.d, ig.digest(c.src, ig.F_FSUM), 4, 0.0)
    raw = np.full(5, 7, np.int32)
    flipped = ig.flip_value(raw)
    assert not np.array_equal(flipped, raw)
    assert np.array_equal(raw, np.full(5, 7, np.int32))


# -- tentpole: conviction drives the health plane ----------------------------


def test_health_sdc_signal_is_decisive():
    """One conviction quarantines the host on the next tick — no
    hysteresis ladder, no hope of widening around a corrupting chip —
    and it works even on a host that never beat (the conviction proves
    the chip is alive; only dead/rehydrating hosts are excluded)."""
    hp = HealthPlane(2, 100 * 1_000_000, 50 * 1_000_000)
    assert hp.enabled
    hp.note_sdc(0)
    assert hp.sdc_n == 1
    hp.next_ns = 0
    hp.tick(time.monotonic_ns())
    assert hp.state[0] == QUARANTINED
    assert hp.score[0] == 100
    assert hp.state[1] == HEALTHY
    assert "sdc" in hp.tripped(0)
    assert "sdc" not in hp.tripped(1)
    rows = hp.snapshot()
    assert rows[0]["sdc"] == 1 and rows[1]["sdc"] == 0
    assert hp.collect() == [0]  # latched exactly once
    assert not hp.placement_ok(0)
    # excluded (dead) hosts stay the liveness plane's case
    hp.excluded[1] = 1
    hp.note_sdc(1)
    hp.next_ns = 0
    hp.tick(time.monotonic_ns())
    assert hp.state[1] == HEALTHY
    hp.excluded[1] = 0
    hp.reset_host(0)
    hp.reset_host(1)
    assert hp.sdc == [0, 0] and hp.sdc_n == 0
    assert hp.state[0] == HEALTHY


def test_doctor_sdc_verdict():
    from ompi_tpu.tools import doctor
    doc = {"sid": 1, "np": 4, "ns": 0,
           "sdc": [{"rank": 1, "host": 0, "cid": 0,
                    "kind": "allreduce"}]}
    text = "\n".join(doctor.verdict(doc))
    assert "SDC VERDICT" in text
    assert "CONVICTED: rank 1 on host 0" in text
    clean = "\n".join(doctor.verdict({"sid": 1, "np": 4, "ns": 0}))
    assert "SDC VERDICT" not in clean


def test_integrity_hot_functions_audited():
    """sample/fold are DECLARED hot (a refactor that starts allocating
    on the per-op countdown fails tier-1) and currently pass."""
    from ompi_tpu.tools import hotpath_audit
    assert "ompi_tpu/obs/integrity.py" in hotpath_audit.HOT_FUNCTIONS
    fns = hotpath_audit.HOT_FUNCTIONS["ompi_tpu/obs/integrity.py"]
    assert "sample" in fns and "fold" in fns
    assert hotpath_audit.audit() == []


# -- satellite: wire payload digest above CRC --------------------------------


def test_wire_payload_crc():
    """The payload digest covers exactly the bytes the header CRC does
    NOT: sender computes from (hdr, payload) before the gather, the
    receiver from the contiguous frame — identical digests; a flipped
    payload byte (which the header CRC can never see) fails it."""
    from ompi_tpu.btl import wire
    hdr, payload = wire.encode(("F", 11, 0, b"payload-bytes-here"))
    frame = hdr + payload
    crc = wire.payload_crc(hdr, payload)
    assert crc == wire.payload_crc(frame)
    wire.check_payload_crc(frame, crc)  # no raise
    bad = bytearray(frame)
    bad[len(hdr) + 4] ^= 0x10
    assert wire.frame_crc(bytes(bad)) == wire.frame_crc(frame)
    with pytest.raises(wire.CorruptFrame):
        wire.check_payload_crc(bytes(bad), crc)
    # pickle frames: the tail past the covered span is payload too
    phdr, ppay = wire.encode(("weird", list(range(100))))
    assert ppay is None
    wire.check_payload_crc(phdr, wire.payload_crc(phdr))


# -- satellite: buddy-tier CRC fallback on restore ---------------------------


def test_buddy_restore_crc_fallback_to_fs_epoch(tmp_path):
    """A corrupting host flips bits in parked buddy blobs too: restore
    CRC-verifies every replica, AGREES on the verdict (one corrupt
    rank sends the whole world down together — never a split across
    sequences), falls one ladder rung to the fs epoch, and re-seeds
    the buddy tier."""
    saved = _set({"cr_buddy_degree": 1,
                  "cr_fs_dir": str(tmp_path / "ckpt")})
    base_fb = _pv("cr_buddy_restore_crc_fallbacks")
    base_fs = _pv("cr_ckpt_restore_fs")

    def fn(comm):
        from ompi_tpu.cr import ckpt
        payload = {"arr": np.arange(64, dtype=np.float64) + comm.rank}
        bseq, epoch = ckpt.checkpoint(comm, payload, fs=True)
        assert bseq >= 0 and epoch >= 0
        comm.Barrier()
        if comm.rank == 1:  # flip a bit inside the parked blob
            bs = comm.state.extra["cr_buddy"]
            blob = bytearray(bs["self"][bseq])
            blob[len(blob) // 2] ^= 0x08
            bs["self"][bseq] = bytes(blob)
        out = ckpt.restore(comm)
        assert out is not None
        return bool(np.array_equal(
            out["arr"], np.arange(64, dtype=np.float64) + comm.rank))
    try:
        assert all(run_ranks(2, fn, devices=True))
        assert _pv("cr_buddy_restore_crc_fallbacks") == base_fb + 1
        assert _pv("cr_ckpt_restore_fs") == base_fs + 2
    finally:
        _restore(saved)


# -- satellite: chaos matrix — device_sdc x host_slow x rank_kill ------------


def test_chaos_matrix_sdc_host_slow_rank_kill(tmp_path):
    """The silent failure composed with the gray and the hard one on a
    2-host pool: run 1 arms device_sdc on rank 1 (host 0) while host 1
    crawls — every flip must be convicted against exactly that chip
    and every rank's analytic result stays exact; the pool's convict
    hook feeds the health plane, whose next tick quarantines host 0.
    Run 2 switches to host_slow + rank_kill: ULFM shrink completes
    byte-identically.  Zero failed jobs across the whole matrix."""
    saved = _set({
        "health_tick_ms": 600_000,  # ticks under test control only
        "integrity_enable": 1,
        "integrity_sample": 1,
        "integrity_sample_auto": 0,
        "ft_inject_plan": "device_sdc:3,host_slow",
        "ft_inject_skip": 0,
        "ft_inject_victim_rank": "1",
        "ft_inject_victim_host": 1,
        "ft_inject_sdc_period": 1,
        "ft_inject_after": 0.3,
        "ft_inject_delay_ms": 5,
    })
    ig.refresh()
    ig.reset()
    base_c = _pv("integrity_convictions")
    uri = str(tmp_path / "dvm.uri")
    srv = DVMServer(4, devices=jax.devices(), uri_file=uri,
                    hosts=2).start()
    # ticks stay under test control: next_ns starts at 0, so without
    # this the pool's FIRST heartbeat sweep would tick right after the
    # convictions land and quarantine host 0 mid-matrix (the designed
    # mitigation — but this test pins tick timing to assert the signal
    # itself, then drives the quarantine tick by hand)
    srv.health.next_ns = time.perf_counter_ns() + 3_600 * 1_000_000_000
    c = DvmClient(uri)
    try:
        sid = c.attach(4)["sid"]
        # run 1: every step self-verifies — detection + retry keep the
        # results exact even though rank 1 flips every op from op 3 on
        r = c.run(sid, SDC_PROG, ["cm", "6"], timeout=240)
        assert r["code"] == 0, r["stderr"][-2000:]
        rows = _lines(r["stdout"], "SDC", "cm")
        assert sorted(int(x[0]) for x in rows) == [0, 1, 2, 3], rows
        assert all(x[1] == "ok" for x in rows), rows
        # conviction pinned to the corrupting chip: rank 1, host 0
        recs = ig.convicted_snapshot()
        assert recs and {rec["rank"] for rec in recs} == {1}, recs
        assert {rec["host"] for rec in recs} == {0}, recs
        assert _pv("integrity_convictions") > base_c
        # the pool's hook fed the health plane; the next tick
        # quarantines host 0 outright
        hp = srv.health
        assert hp.sdc[0] > 0 and hp.sdc[1] == 0
        assert c.metrics()["sdc"], "metrics RPC must carry the rows"

        # run 2: the hard + gray composition on the same pool — a
        # FRESH session so the plan switch is seen at mpi_init.  The
        # kill is the prog's deterministic step-boundary kill_now
        # (rank 1 dies at step 5), not the timer-armed rank_kill
        # class: a wall-clock timer can land in the victim's init
        # window when the suite loads the box, and this test pins
        # WHICH faults compose, not WHEN they land (the timer race
        # is test_grayfail's chaos matrix)
        c.detach(sid)
        registry.set("ft_inject_plan", "host_slow")
        sid = c.attach(4)["sid"]
        r2 = c.run(sid, HOST_PROG, ["cm2", "30", "1:5"], timeout=240)
        assert r2["code"] == 0, r2["stderr"][-2000:]  # never a failed job
        shrinks = _lines(r2["stdout"], "SHRINKS", "cm2")
        digs = _lines(r2["stdout"], "DIGEST", "cm2")
        assert sorted(int(s[0]) for s in shrinks) == [0, 2, 3], shrinks
        assert all(int(s[1]) == 1 for s in shrinks), shrinks
        assert len(digs) == 3 and len({d[0] for d in digs}) == 1, digs

        hp.next_ns = 0
        hp.tick(time.monotonic_ns())
        assert hp.state[0] == QUARANTINED
        assert "sdc" in hp.tripped(0)
        assert srv._host_dead[0] == 0  # quarantined, never dead
        c.detach(sid)
    finally:
        c.sock.close()
        ig.reset()
        hp = srv.health
        if hp is not None:
            for h in range(hp.hosts):
                hp.reset_host(h)
            hp.collect()
        srv.stop()
        _restore(saved)
