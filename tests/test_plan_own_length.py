"""A plan runs at the payload's own length (ISSUE 39, DESIGN.md §12):
every program of the large-message tier, over payloads that fit a
whole number of segments, that do not, and that are shorter than one.
Numpy's answer bit for bit; one plan built a length and hits after;
``coll_plan_padded`` at rest but where a stripe schedule cannot split
the length by the comm size (the hop-explicit ring, segbcast), where
ONE jitted pad and ONE jitted trim run; and no ``jax.numpy`` call
outside a jit anywhere on the path, on any backend (the branch that
``runtime_zero_copy()`` chose, ``_pack_rows`` and ``_unpack_rows`` are
gone: these cases run where they ran)."""

import functools

import numpy as np
import pytest

from ompi_tpu.mca.params import registry
from ompi_tpu.op import op as mpi_op
from ompi_tpu.testing import run_ranks

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

# register the knobs before any snapshot of them
import ompi_tpu.coll.pipeline as pipeline  # noqa: E402
import ompi_tpu.coll.plan as plan  # noqa: E402

P = 4
SEG = 1024                   # coll_seg_size 4,096 B of 4-byte items
# the tier from 2 KiB up, so that a payload under one segment reaches it
TIER = {"coll_pipeline_enable": True, "coll_pipeline_min_bytes": 2048,
        "coll_seg_size": 4 * SEG, "coll_pipeline_rd_max_bytes": 0,
        "coll_hier_enable": False}
RING = dict(TIER, coll_plan_native_reduce=False)

# path -> (knobs, one chip?, the call's kind, whether the program is a
# stripe schedule, which pads a length the four ranks cannot split)
PATHS = {
    "hbm": (TIER, True, "allreduce", False),
    "mesh-native": (TIER, False, "allreduce", False),
    "mesh-rd-hop": (dict(RING, coll_pipeline_rd_max_bytes=1 << 30),
                    False, "allreduce", False),
    "mesh-ring-hop": (RING, False, "allreduce", True),
    "sega2a": (TIER, False, "alltoall", False),
    "segbcast": (TIER, False, "bcast", True),
}
# elements a rank: (a length the ranks split, one they do not); a whole
# number of segments always splits (segment_elems is a multiple of the
# comm size), so its second length is one element past the fit
SIZES = {"fit": (3 * SEG, 3 * SEG + 1),
         "ragged": (2 * SEG + 700, 2 * SEG + 701),
         "under-a-segment": (600, 601)}
CASES = [(path, size, residue)
         for path, (_k, _one, kind, stripes) in PATHS.items()
         for size in SIZES
         # an alltoall's blocks are equal: its length always splits
         for residue in ((False, True) if kind != "alltoall"
                         else (False,))
         # the residue is a stripe schedule's alone to pad; the others
         # run one residue length each, to show they do not
         if not residue or stripes or size == "ragged"]
ROOT = 1


def _inputs(n):
    return [((np.arange(n) * 7 + 3 * r) % 11 - 5).astype(np.float32)
            for r in range(P)]


def _expected(kind, n, rank):
    xs = _inputs(n)
    if kind == "allreduce":
        return functools.reduce(np.add, xs)
    if kind == "bcast":
        return xs[ROOT]
    m = n // P
    return np.concatenate([x[rank * m:(rank + 1) * m] for x in xs])


def _set(vals):
    saved = {k: registry.get(k) for k in vals}
    for k, v in vals.items():
        registry.set(k, v)
    return saved


def _no_eager_numpy(monkeypatch):
    """The three jax.numpy functions the pad and the trim were made
    of: on the path they may not be called at all (the pad program is
    lax.pad, inside its jit)."""
    def raiser(name):
        def boom(*_a, **_k):
            raise AssertionError(f"jax.numpy.{name} called on a plan path")
        return boom

    for name in ("concatenate", "full", "pad"):
        monkeypatch.setattr(jnp, name, raiser(name))


@pytest.mark.parametrize(
    "path,size,residue", CASES,
    ids=[f"{p}-{s}-{'residue' if r else 'splits'}" for p, s, r in CASES])
def test_plan_runs_at_the_payloads_own_length(path, size, residue,
                                              monkeypatch):
    knobs, one_chip, kind, stripes = PATHS[path]
    n = SIZES[size][residue]
    # a second length of the same class (splits, or does not), ragged
    n2 = n + 2 * P
    padded = stripes and residue
    assert (n % P != 0) == residue and (n2 % P != 0) == residue
    call = {"allreduce": lambda c, x: c.allreduce_arr(x, mpi_op.SUM),
            "bcast": lambda c, x: c.bcast_arr(x, ROOT),
            "alltoall": lambda c, x: c.alltoall_arr(x)}[kind]

    def counters():
        return (plan.pv_builds.read(), plan.pv_hits.read(),
                plan.pv_padded.read(), pipeline.pv_ops.read())

    def fn(comm):
        out = []
        for length, calls in ((n, 3), (n2, 1)):
            x = jax.device_put(_inputs(length)[comm.rank], comm.device)
            comm.Barrier()   # thread-ranks share the process-wide pvars
            before = counters()
            comm.Barrier()   # nobody calls before everybody has read
            got = [call(comm, x) for _ in range(calls)]
            comm.Barrier()
            want = _expected(kind, length, comm.rank).tobytes()
            out.append((
                all(np.asarray(g).tobytes() == want for g in got),
                all(g.devices() == {comm.device} for g in got),
                [b - a for a, b in zip(before, counters())]))
        return out, len(comm.__dict__["_coll_plans"])

    _no_eager_numpy(monkeypatch)
    saved = _set(knobs)
    try:
        if one_chip:
            res = run_ranks(P, fn, timeout=240,
                            device_map=lambda rank: jax.devices()[0])
        else:
            res = run_ranks(P, fn, timeout=240, devices=True)
    finally:
        _set(saved)
    for (first, second), held in res:
        # numpy's answer, bit for bit, on the rank's own device; one
        # plan a rank built and two hits after; a pad and a trim a call
        # in the residue of a stripe schedule and nowhere else
        assert first == (True, True,
                         [P, 2 * P, 3 * P if padded else 0, 3 * P])
        # a second length is a second plan
        assert second == (True, True, [P, 0, P if padded else 0, P])
        assert held == 2


def test_pad_and_trim_are_one_named_program_each():
    """What is left of pad and trim is two jitted programs with names
    a device trace shows (never a lambda: the benchmark's one-chip
    allreduce cells count every jit__lambda as the kernel), cached
    under one key, and a plan at its own length holds neither."""
    dt = np.dtype(np.int32)
    assert plan._pad_trim(1024, 1024, dt, "MPI_MAX") == (None, None)
    pad, trim = plan._pad_trim(1021, 1024, dt, "MPI_MAX")
    assert plan._pad_trim(1021, 1024, dt, "MPI_MAX") == (pad, trim)
    assert (pad.__name__, trim.__name__) == ("ompi_plan_pad",
                                             "ompi_plan_trim")
    x = jax.device_put(np.arange(-1021, 0, dtype=np.int32))
    padded = pad(x)
    assert padded.shape == (1024,)
    assert np.asarray(padded)[1021:].tolist() == [np.iinfo(np.int32).min] * 3
    assert np.array_equal(np.asarray(trim(padded)), np.asarray(x))
    assert "pad" in str(jax.make_jaxpr(pad)(x))
