"""The one way through the large-message tier (ISSUE 30, DESIGN.md
§12): ``pipeline.maybe_device_coll`` as a table, ``plan._plan_segments``
and ``_stripe_total`` as pure functions, the identity pad of every
reduction the tier still pads, the thread a planned collective computes on, and the error path
of the one ``Rendezvous.begin`` body."""

import functools
import threading

import numpy as np
import pytest

from ompi_tpu.mca.params import registry
from ompi_tpu.op import op as mpi_op
from ompi_tpu.testing import run_ranks

jax = pytest.importorskip("jax")

# register the knobs before any snapshot of them
import ompi_tpu.coll.pipeline as pipeline  # noqa: E402
import ompi_tpu.coll.plan as plan  # noqa: E402
from ompi_tpu.coll import device  # noqa: E402

P = 4
SEG_ELEMS = 1024             # coll_seg_size 4096 B of 4-byte items
TIER = {"coll_pipeline_enable": True, "coll_pipeline_min_bytes": 4096,
        "coll_seg_size": 4 * SEG_ELEMS, "coll_pipeline_rd_max_bytes": 0,
        "coll_hier_enable": False}


def _set(vals):
    saved = {k: registry.get(k) for k in vals}
    for k, v in vals.items():
        registry.set(k, v)
    return saved


def _world(fn, knobs, one_chip=False, **kw):
    saved = _set(knobs)
    try:
        if one_chip:
            kw["device_map"] = lambda rank: jax.devices()[0]
        else:
            kw["devices"] = True
        return run_ranks(P, fn, timeout=240, **kw)
    finally:
        _set(saved)


# -- (a) the router as a table ----------------------------------------------

# float32 elements a rank: 4,080 B, 4,096 B (coll_pipeline_min_bytes)
# and 4,112 B; every count divides by the four ranks of an alltoall
SIZES = {"below": 1020, "at": 1024, "above": 1028}
# who serves a call the tier takes: the plan_exec span's alg id
SERVED_BY = {("tpu", "allreduce"): "segring", ("tpu", "bcast"): "segbcast",
             ("tpu", "alltoall"): "sega2a", ("hbm", "allreduce"): "hbm",
             # coll/hbm consults the tier for allreduce alone
             ("hbm", "bcast"): None, ("hbm", "alltoall"): None}


def _inputs(n):
    return [((np.arange(n) * 7 + 3 * r) % 11 - 5).astype(np.float32)
            for r in range(P)]


def _expected(kind, n, rank):
    xs = _inputs(n)
    if kind == "allreduce":
        return functools.reduce(np.add, xs)
    if kind == "bcast":
        return xs[1]
    m = n // P
    return np.concatenate([x[rank * m:(rank + 1) * m] for x in xs])


@pytest.mark.parametrize("enable", [True, False], ids=["on", "off"])
@pytest.mark.parametrize("kind", ["allreduce", "bcast", "alltoall"])
@pytest.mark.parametrize("module", ["tpu", "hbm"])
def test_router_table(module, kind, enable):
    """Below coll_pipeline_min_bytes, or with coll_pipeline_enable off,
    the caller keeps its single-dispatch path (the tier's counters and
    the plan_exec span at rest); at and above it the plan the table
    names serves the call, once; the answer is numpy's either way."""
    call = {"allreduce": lambda c, x: c.allreduce_arr(x, mpi_op.SUM),
            "bcast": lambda c, x: c.bcast_arr(x, 1),
            "alltoall": lambda c, x: c.alltoall_arr(x)}[kind]

    def fn(comm):
        assert comm.coll.providers[kind + "_arr"] == module
        tr = comm.state.tracer
        out = {}
        for where, n in SIZES.items():
            x = jax.device_put(_inputs(n)[comm.rank], comm.device)
            comm.Barrier()
            before = (pipeline.pv_ops.read(),
                      plan.pv_builds.read() + plan.pv_hits.read())
            comm.Barrier()    # nobody calls before everybody has read
            got = np.asarray(call(comm, x))
            seq = comm._coll_seq
            comm.Barrier()
            spans = tr.snapshot()
            out[where] = (
                got.tobytes() == _expected(kind, n, comm.rank).tobytes(),
                pipeline.pv_ops.read() - before[0],
                plan.pv_builds.read() + plan.pv_hits.read() - before[1],
                [e["args"]["alg"] for e in spans
                 if e["name"] == "plan_exec" and e["args"]["op"] == seq],
                [e["args"]["alg"] for e in spans
                 if e["name"] == "pipeline_" + kind
                 and e["args"]["op"] == seq])
        return out

    knobs = dict(TIER, coll_pipeline_enable=enable, trace_enable=True,
                 trace_sample_auto=0, trace_dump_path="")
    alg = SERVED_BY[module, kind]
    for res in _world(fn, knobs, one_chip=module == "hbm"):
        for where in SIZES:
            if enable and alg is not None and where != "below":
                # the process-wide counters move by all four ranks'
                routed = "segring" if alg == "hbm" else alg
                assert res[where] == (True, P, P, [alg], [routed]), where
            else:
                assert res[where] == (True, 0, 0, [], []), where


# -- (b) plan._plan_segments as a pure function -----------------------------

@pytest.mark.parametrize("size,n,seg,want", [
    (4, 1, 1024, (1, 4)),           # one element: one segment; a ring
    (4, 100, 1024, (1, 100)),       # of 4 runs it at a multiple of 4
    (3, 100, 1023, (1, 102)),       # ... of 3 at the next multiple of 3
    (3, 1000, 1023, (1, 1002)),
    (4, 1023, 1024, (1, 1024)),
    (4, 1024, 1024, (1, 1024)),     # exactly one segment
    (4, 5 * 1024, 1024, (5, 5 * 1024)),         # k segments
    (4, 5 * 1024 + 1, 1024, (6, 5 * 1024 + 4)),  # k segments and one more
    (3, 2 * 1023 + 2, 1023, (3, 2 * 1023 + 3)),
])
def test_plan_segments_is_a_count_and_stripes_pad_to_the_comm(
        size, n, seg, want):
    """What coll_pipeline_segments advances by (a count: no program
    has a segment's shape), and the length a stripe schedule runs an
    n-element payload at: n itself where it divides by the comm size,
    else the next multiple, never a segment's."""
    nsegs, total = plan._plan_segments(n, seg), plan._stripe_total(n, size)
    assert (nsegs, total) == want
    assert (nsegs - 1) * seg < n <= nsegs * seg
    assert 0 <= total - n < size and total % size == 0


# -- (c) the identity pad of every planned reduction ------------------------

N_RAGGED = SEG_ELEMS + 3     # 1,027 over four ranks: one padded element


def _ragged_inputs(case):
    i = np.arange(N_RAGGED)
    make = {
        "SUM-f32": lambda r: (i % 5 + r).astype(np.float32),
        # a zero pad would win every MAX of negatives, MIN of positives
        "MAX-f32-negative": lambda r: -(i % 7 + 1.5 + r).astype(np.float32),
        "MIN-f32-positive": lambda r: (i % 7 + 1.5 + r).astype(np.float32),
        "MAX-i32-negative": lambda r: -(i % 9 + 1 + r).astype(np.int32),
        "MIN-i32-positive": lambda r: (i % 9 + 1 + r).astype(np.int32),
        # a zero pad would annihilate a product and a bitwise AND
        "PROD-i32": lambda r: (i % 3 + 1 + (r % 2)).astype(np.int32),
        "BAND-u32": lambda r: (0xFFFFFFFF ^ (1 << (r + i % 8))).astype(
            np.uint32),
        "BOR-u32": lambda r: (1 << (r + i % 8)).astype(np.uint32),
        "BXOR-u32": lambda r: ((i * 2654435761 + r) % (1 << 32)).astype(
            np.uint32),
        "LAND-i32": lambda r: ((i + r) % 5 != 0).astype(np.int32) * (r + 2),
        "LOR-i32": lambda r: ((i + r) % 5 == 0).astype(np.int32) * (r + 2),
        "LXOR-i32": lambda r: ((i + r) % 3 == 0).astype(np.int32) * (r + 2),
    }[case]
    return [make(r) for r in range(P)]


@pytest.mark.parametrize("case", [
    "SUM-f32", "MAX-f32-negative", "MIN-f32-positive", "MAX-i32-negative",
    "MIN-i32-positive", "PROD-i32", "BAND-u32", "BOR-u32", "BXOR-u32",
    "LAND-i32", "LOR-i32", "LXOR-i32"])
def test_ragged_planned_allreduce_pads_with_the_identity(case):
    """A ragged allreduce through the hop-explicit ring
    (coll_plan_native_reduce off, so the plan's own binop and pad serve
    every op): the identity pad never reaches a real element; numpy's
    fold of the four inputs, bit for bit, on every rank."""
    op = getattr(mpi_op, case.split("-")[0])
    assert op.name in plan._BINOPS
    xs = _ragged_inputs(case)
    want = functools.reduce(op.np_fn, xs)
    assert want.dtype == xs[0].dtype
    # the pad is the op's identity (a logical op normalizes to 0 / 1)
    padded = op.np_fn(xs[0], np.full_like(
        xs[0], plan._pad_value(op.name, xs[0].dtype)))
    unchanged = (xs[0] != 0).astype(xs[0].dtype) if case[0] == "L" \
        else xs[0]
    assert np.array_equal(padded, unchanged)

    def fn(comm):
        b0 = plan.pv_builds.read() + plan.pv_hits.read()
        got = comm.allreduce_arr(
            jax.device_put(xs[comm.rank], comm.device), op)
        comm.Barrier()
        return (np.asarray(got).tobytes(),
                plan.pv_builds.read() + plan.pv_hits.read() - b0)

    res = _world(fn, dict(TIER, coll_plan_native_reduce=False))
    assert [r[0] for r in res] == [want.tobytes()] * P
    assert all(r[1] >= 1 for r in res)       # a plan served it


# -- (d) the last arriver computes, inline ----------------------------------

@pytest.mark.parametrize("one_chip", [False, True], ids=["tpu", "hbm"])
def test_planned_collective_computes_on_a_rank_thread(one_chip, monkeypatch):
    """Every computation of a planned operation runs on the thread of
    one of its ranks (the last arriver), and no thread is left behind
    to run them: nothing named coll-device-dispatch exists."""
    computed_on = []
    real = device._phase_fn

    def spy(fn, shards, ph):
        computed_on.append(threading.get_ident())
        return real(fn, shards, ph)

    monkeypatch.setattr(device, "_phase_fn", spy)

    def fn(comm):
        x = jax.device_put(
            np.full(3 * SEG_ELEMS + 1, comm.rank + 1.0, np.float32),
            comm.device)
        for _ in range(3):
            got = comm.allreduce_arr(x, mpi_op.SUM)
        if not one_chip:
            comm.alltoall_arr(jax.device_put(
                np.arange(2 * SEG_ELEMS, dtype=np.float32), comm.device))
        return threading.get_ident(), float(np.asarray(got)[-1])

    res = _world(fn, TIER, one_chip=one_chip)
    assert [v for _t, v in res] == [10.0] * P
    assert len(computed_on) >= 3
    assert set(computed_on) <= {t for t, _v in res}
    assert "coll-device-dispatch" not in {
        t.name for t in threading.enumerate()}


# -- (e) a computation that raises ------------------------------------------

@pytest.mark.parametrize("one_chip", [False, True], ids=["tpu", "hbm"])
def test_failed_planned_computation_reaches_every_member(one_chip):
    """The computation of a planned operation raises on whichever rank
    arrived last: every member gets RuntimeError("device collective
    failed on a peer"), the cause attached, and the next operation on
    the same communicator meets and answers."""
    boom = [True]            # one shot, whoever computes

    def fn(comm):
        x = jax.device_put(
            np.full(2 * SEG_ELEMS + 5, comm.rank + 1.0, np.float32),
            comm.device)
        first = float(np.asarray(comm.allreduce_arr(x, mpi_op.SUM))[0])
        (pl,) = comm.__dict__["_coll_plans"].values()
        good = pl.fn

        def bad(shards):
            if boom:
                boom.pop()
                raise ValueError("injected into the computation")
            return good(shards)

        pl.fn = bad
        comm.Barrier()
        with pytest.raises(RuntimeError,
                           match="device collective failed on a peer") as e:
            comm.allreduce_arr(x, mpi_op.SUM)
        cause = e.value.__cause__
        again = float(np.asarray(comm.allreduce_arr(x, mpi_op.SUM))[-1])
        # the failed generation's error left with its last reader
        left = dict(comm.__dict__["_device_rv"].errors)
        comm.Barrier()
        return first, again, type(cause).__name__, str(cause), left

    res = _world(fn, TIER, one_chip=one_chip)
    assert res == [(10.0, 10.0, "ValueError",
                    "injected into the computation", {})] * P
    assert boom == []
