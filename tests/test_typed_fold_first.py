"""ISSUE 36: a typed reduction on coll/hbm whose fold rounds nowhere
folds the P ranks' whole buffers first and packs the ONE result
(``datatype/device.Typed.folds_first``), instead of packing P times
and folding the streams.

* the fold-first program's answers are the pack-first program's and
  numpy's, bit for bit, over layouts x operations x carriers x kinds;
* the rule itself, read from the call: the counter
  ``coll_typed_folded_first`` moves once a rank-call for an exact fold
  through a layout that skips little, and stays for a float SUM, one
  block, a layout that skips much, and a mesh (coll/tpu);
* the program holds one pack where the other holds P.

The file runs with ``mpi_device_x64`` on (the uint64 carrier needs
it): one module fixture, as in tests/test_cellbench_typed.py.
"""
import functools
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from cellbench import reference_typed  # noqa: E402
from ompi_tpu.coll import device as coll_device  # noqa: E402
from ompi_tpu.datatype import device as dtdev, engine as dtmod  # noqa: E402
from ompi_tpu.mca.params import registry  # noqa: E402
from ompi_tpu.op import op as mpi_op  # noqa: E402
from ompi_tpu.testing import run_ranks  # noqa: E402

jax = pytest.importorskip("jax")

SEED = 3600000011            # the driver's seeds pass 2**31
P = 8
COUNTERS = ("coll_typed_device_ops", "coll_typed_folded_first",
            "coll_typed_host_packs", "coll_arr_host_staged_collectives")
# carrier -> (the buffer's dtype, the datatype's base)
CARRIERS = {"float32": (np.dtype(np.float32), dtmod.FLOAT),
            "int32": (np.dtype(np.int32), dtmod.INT),
            "bits": (np.dtype(np.uint64), dtmod.DOUBLE)}
NUMPY = {"MPI_MAX": np.maximum, "MPI_MIN": np.minimum,
         "MPI_BOR": np.bitwise_or, "MPI_SUM": np.add}
ENTRY = {"reduce_scatter": "reduce_scatter_arr", "allreduce": "allreduce_arr"}


@pytest.fixture(scope="module", autouse=True)
def x64():
    registry.set("mpi_device_x64", 1)
    yield
    registry.set("mpi_device_x64", 0)
    jax.config.update("jax_enable_x64", False)


def pvar(name):
    return next(int(p.read()) for p in registry.all_pvars()
                if p.full_name == name)


def irregular(base):
    """8 blocks of unequal lengths at uneven displacements, 32 elements:
    more runs than a concatenation of slices takes."""
    return dtmod.indexed([3, 1, 4, 8, 2, 5, 6, 3],
                         [40, 2, 9, 20, 60, 70, 80, 90], base).commit()


# layout -> (datatype of a base, elements a rank's buffer holds beyond
# the span)
LAYOUTS = {
    # the cell's vector(n, 1, 2) in a buffer of 2 n elements
    "cell_vector": (lambda b: dtmod.vector(64, 1, 2, b).commit(),
                    lambda rank: 1),
    # vector(16, 3, 5) from element 7
    "vector_3_5_from_7": (
        lambda b: dtmod.indexed([3] * 16, range(7, 7 + 5 * 16, 5),
                                b).commit(), lambda rank: 0),
    "rows_1024_of_2048": (lambda b: dtmod.vector(4, 1024, 2048, b).commit(),
                          lambda rank: 0),
    "gathered": (irregular, lambda rank: 0),
    # every rank its own length past the span
    "longer_buffer": (lambda b: dtmod.vector(64, 1, 2, b).commit(),
                      lambda rank: 40 + 3 * rank),
    # the buffer ends with the span, as the cell's does: 2 n - 1
    "odd_length": (lambda b: dtmod.vector(64, 1, 2, b).commit(),
                   lambda rank: 0),
}
COMBOS = [("MPI_MAX", "float32"), ("MPI_MAX", "int32"), ("MPI_MAX", "bits"),
          ("MPI_MIN", "float32"), ("MPI_MIN", "int32"), ("MPI_MIN", "bits"),
          ("MPI_BOR", "int32"), ("MPI_SUM", "int32")]
CASES = [("reduce_scatter", lay, op, c)
         for lay in LAYOUTS for op, c in COMBOS] + [
    ("allreduce", lay, op, c)
    for lay in ("cell_vector", "gathered")
    for op, c in (("MPI_MAX", "bits"), ("MPI_MIN", "float32"),
                  ("MPI_BOR", "int32"), ("MPI_SUM", "int32"))]


def host_buffer(carrier: str, rank: int, n: int) -> np.ndarray:
    dtype = CARRIERS[carrier][0]
    if carrier == "bits":
        # the benchmark's binary64 stream: 53-bit significands, the
        # whole exponent range, both signs; as bit patterns
        return reference_typed.values_at(SEED, rank, np.arange(n)).view(dtype)
    rng = np.random.default_rng([SEED, rank])
    if carrier == "int32":
        return rng.integers(-2 ** 31, 2 ** 31, n).astype(dtype)
    return rng.standard_normal(n).astype(dtype)


def numpy_fold(opname: str, carrier: str, packed):
    """The reference: numpy on the host's packed streams, in rank
    order (a uint64 carrier holds doubles)."""
    if carrier == "bits":
        packed = [p.view(np.float64) for p in packed]
    out = functools.reduce(NUMPY[opname], packed)
    return out.view(np.uint64) if carrier == "bits" else out


@pytest.mark.parametrize("kind,layout,opname,carrier", CASES,
                         ids=["-".join(c) for c in CASES])
def test_fold_first_equals_pack_first_and_numpy(monkeypatch, kind, layout,
                                                opname, carrier):
    dtype, base = CARRIERS[carrier]
    make, beyond = LAYOUTS[layout]
    dt = make(base)
    t = dtdev.resolve(dt, 1).carried(dtype)
    assert t.folds_first(opname) and t.elems % P == 0
    hosts = [host_buffer(carrier, r, t.span + beyond(r)) for r in range(P)]
    red = getattr(mpi_op, opname.replace("MPI_", ""))

    def fn(comm):
        assert comm.coll.providers["allreduce_arr"] == "hbm"
        x = jax.device_put(hosts[comm.rank], comm.device)
        comm.Barrier()
        before = [pvar(n) for n in COUNTERS]
        comm.Barrier()
        out = getattr(comm, ENTRY[kind])(x, red, dt, 1)
        comm.Barrier()
        assert comm.device in out.devices()
        return np.asarray(out), [pvar(n) - b for n, b in zip(COUNTERS,
                                                             before)]

    res = run_ranks(P, fn, devices=True,
                    device_map=lambda r: jax.devices()[0])
    # the pack-first program of the same call, built with the rule off
    with monkeypatch.context() as m:
        m.setattr(dtdev, "FOLD_FIRST_SPAN", 0)
        assert not t.folds_first(opname)
        jbody, out = coll_device.HbmCollModule._build_stacked(
            kind, opname, t)
        packed_first = out(jbody(*[jax.device_put(h) for h in hosts]), P)
    whole = numpy_fold(opname, carrier, [h[t.idx] for h in hosts])
    m_ = t.elems // P
    for r, (got, moved) in enumerate(res):
        assert moved == [P, P, 0, 0]
        want = whole[r * m_:(r + 1) * m_] if kind == "reduce_scatter" \
            else whole
        assert got.dtype == dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
        # with jax's x64 on, jnp.sum widens an int32 stack: the
        # pack-first SUM answers in int64, the same low 32 bits
        assert got.tobytes() == np.asarray(packed_first[r]).astype(
            dtype).tobytes()


# -- the rule ------------------------------------------------------------------

R = dtdev.FOLD_FIRST_SPAN
RULE = {
    # name: (provider, datatype, buffer dtype, op, folds first)
    "max_through_the_cells_vector": (
        "hbm", lambda: dtmod.vector(64, 1, 2, dtmod.FLOAT), np.float32,
        mpi_op.MAX, True),
    "integer_sum": (
        "hbm", lambda: dtmod.vector(64, 1, 2, dtmod.INT), np.int32,
        mpi_op.SUM, True),
    "skips_as_much_as_allowed": (
        "hbm", lambda: dtmod.vector(64, 1, R, dtmod.FLOAT), np.float32,
        mpi_op.MAX, True),
    "float_sum_keeps_its_association": (
        "hbm", lambda: dtmod.vector(64, 1, 2, dtmod.FLOAT), np.float32,
        mpi_op.SUM, False),
    "float_prod_keeps_its_association": (
        "hbm", lambda: dtmod.vector(64, 1, 2, dtmod.FLOAT), np.float32,
        mpi_op.PROD, False),
    "contiguous_has_no_pack": (
        "hbm", lambda: dtmod.contiguous(64, dtmod.FLOAT), np.float32,
        mpi_op.MAX, False),
    "one_block_from_a_base_is_a_slice": (
        "hbm", lambda: dtmod.indexed([64], [5], dtmod.FLOAT), np.float32,
        mpi_op.MAX, False),
    "skips_too_much": (
        "hbm", lambda: dtmod.vector(64, 1, 2 * R, dtmod.FLOAT), np.float32,
        mpi_op.MAX, False),
    "a_mesh_packs_its_own_shard": (
        "tpu", lambda: dtmod.vector(64, 1, 2, dtmod.FLOAT), np.float32,
        mpi_op.MAX, False),
}


@pytest.mark.parametrize("case", RULE)
def test_the_counter_says_which_calls_folded_first(case):
    provider, make, dtype, red, folds = RULE[case]
    dt = make().commit()
    t = dtdev.resolve(dt, 1)
    assert t.folds_first(red.name) == (folds or provider == "tpu")
    n, device_map = (P, lambda r: jax.devices()[0]) if provider == "hbm" \
        else (4, None)

    def fn(comm):
        assert comm.coll.providers["allreduce_arr"] == provider
        host = np.random.default_rng([SEED, comm.rank]).uniform(
            0.5, 1.5, t.span).astype(dtype)
        x = jax.device_put(host, comm.device)
        comm.Barrier()
        before = [pvar(c) for c in COUNTERS]
        comm.Barrier()
        out = [comm.reduce_scatter_arr(x, red, dt, 1),
               comm.allreduce_arr(x, red, dt, 1)]
        comm.Barrier()
        return host, [np.asarray(o) for o in out], \
            [pvar(c) - b for c, b in zip(COUNTERS, before)]

    res = run_ranks(n, fn, devices=True, device_map=device_map)
    m = t.elems // n
    whole = functools.reduce(red.np_fn, [h[t.idx] for h, _, _ in res])
    for r, (_, (rs, ar), moved) in enumerate(res):
        # served on the device either way; folded first or not at all
        assert moved == [2 * n, 2 * n if folds else 0, 0, 0]
        assert rs.dtype == ar.dtype == dtype
        np.testing.assert_allclose(rs, whole[r * m:(r + 1) * m], rtol=1e-5)
        np.testing.assert_allclose(ar, whole, rtol=1e-5)


@pytest.mark.parametrize("kind", ["reduce_scatter", "allreduce"])
def test_the_folded_program_packs_once(kind):
    """An irregular layout packs as a gather, which the program's text
    shows: one where the fold is exact, P where it rounds."""
    t = dtdev.resolve(irregular(dtmod.FLOAT), 1)
    x = jax.ShapeDtypeStruct((t.span + 3,), np.float32)
    texts = {}
    for opname in ("MPI_MAX", "MPI_SUM"):
        jbody, _ = coll_device.HbmCollModule._build_stacked(kind, opname, t)
        low = jbody.lower(*[x] * P)
        assert "ompi_typed_" + kind in low.as_text()
        texts[opname] = low.compile().as_text()
    assert texts["MPI_MAX"].count(" gather(") == 1
    assert texts["MPI_SUM"].count(" gather(") == P
