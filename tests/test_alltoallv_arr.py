"""``comm.alltoallv_arr`` (DESIGN.md section 12): MPI_Alltoallv on
device arrays of rows, served by coll/hbm on one chip and by coll/tpu
over a mesh, each in ONE program whose counts are operands.  NAS
Parallel Benchmarks IS class S on 8 ranks against
cellbench/reference_ragged.py, exact, then IS's full verification; the
argument contract case by case; 40 count matrices, one program; the
host-staged fallback; the counters and the span; the host path's
plain-integer counts against the numpy formulas they replaced, kept
here as the reference.  Rows on both providers and the fallback; the
mesh half's meeting (its operand against numpy formulas, one program
for 40 count matrices, deposits of other shapes through the host) on
CPU devices with its collective emulated, since XLA:CPU cannot lower
``ragged-all-to-all``; the slab body's rule, operand and Pallas pass on
arrival (interpreted) against the host's answer; the count operand's
one transfer a meeting, onto the first rank's device; and both bodies
compiled for a described 2x2 v5e."""

import re

import numpy as np
import pytest

from cellbench import reference_ragged as ref
from ompi_tpu import errhandler as eh
from ompi_tpu.coll import device as dev
from ompi_tpu.coll import ragged
from ompi_tpu.mca.params import registry
from ompi_tpu.testing import run_ranks

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


def one_chip(P, fn, **kw):
    """P rank-threads on one device: coll/hbm's layout."""
    return run_ranks(P, fn, device_map=lambda r: jax.devices()[0], **kw)


def pvar(name):
    """A counter's reading; 0 before its module registered it."""
    return next((int(p.read()) for p in registry.all_pvars()
                 if p.full_name == name), 0)


def packed(counts):
    """Exclusive prefix sums along the last axis."""
    return np.cumsum(counts, axis=-1) - counts


def expected(xs, counts, sdispls, rdispls, rank, cap):
    """(answer, mask of the elements that are part of it)."""
    out = np.zeros(cap, xs[0].dtype)
    live = np.zeros(cap, bool)
    for i in range(len(xs)):
        c = counts[i][rank]
        out[rdispls[rank][i]:rdispls[rank][i] + c] = \
            xs[i][sdispls[i][rank]:sdispls[i][rank] + c]
        live[rdispls[rank][i]:rdispls[rank][i] + c] = True
    return out, live


def exchange(P, xs, counts, cap, sdispls=None, rdispls=None, world=one_chip):
    """Run one exchange; assert every rank's answer; return providers."""
    counts = np.asarray(counts)
    sd = packed(counts) if sdispls is None else np.asarray(sdispls)
    rd = packed(counts.T) if rdispls is None else np.asarray(rdispls)

    def fn(comm):
        r = comm.rank
        out = comm.alltoallv_arr(
            jax.device_put(xs[r], comm.device), counts[r], counts[:, r],
            None if sdispls is None else sd[r],
            None if rdispls is None else rd[r], capacity=cap)
        assert isinstance(out, jax.Array) and comm.device in out.devices()
        return np.asarray(out), comm.coll.providers["alltoallv_arr"]

    res = world(P, fn)
    for r, (out, _prov) in enumerate(res):
        want, live = expected(xs, counts, sd, rd, r, cap)
        assert out.shape == (cap,) and out.dtype == xs[0].dtype
        assert np.array_equal(out[live].view(np.uint8),
                              want[live].view(np.uint8)), r
    return {prov for _o, prov in res}


def random_counts(rng, P, n, zeros=0.0):
    counts = np.zeros((P, P), np.int64)
    for i in range(P):
        cuts = np.sort(rng.integers(0, n + 1, P - 1))
        counts[i] = np.diff(np.concatenate([[0], cuts, [n]]))
        drop = rng.random(P) < zeros
        counts[i][drop] = 0
    return counts


def test_npb_is_class_s_exact_then_fully_verified():
    """IS class S, 8 ranks: the exchange of both parities through the
    library equals the reference's key_buff2; then IS's full
    verification: the received keys, sorted on each rank and
    concatenated in rank order, are globally sorted and are the
    multiset that was sent."""
    P, cls, seed = 8, ref.CLASSES["S"], 4000000007
    cap = ref.size_of_buffers(cls, P)
    exs = [ref.exchange(seed, parity, cls, P) for parity in (0, 1)]
    before = pvar("coll_arr_host_staged_collectives")

    def fn(comm):
        outs = []
        for ex in exs:
            c = ex["counts"]
            outs.append(np.asarray(comm.alltoallv_arr(
                jax.device_put(ex["buff1"][comm.rank], comm.device),
                c[comm.rank], c[:, comm.rank], capacity=cap)))
        return outs, comm.coll.providers["alltoallv_arr"]

    res = one_chip(P, fn)
    assert pvar("coll_arr_host_staged_collectives") == before
    for parity, ex in enumerate(exs):
        got = []
        for r in range(P):
            outs, prov = res[r]
            assert prov == "hbm"
            owed = ref.owed(ex, r)
            assert ref.gap(outs[parity][:owed.size], owed) == 0.0
            lo, hi = ref.owned(ex["last"], r)
            b = owed >> ref.shift_of(cls)
            assert not owed.size or (b.min() >= lo and b.max() <= hi)
            got.append(np.sort(outs[parity][:owed.size]))
        whole = np.concatenate(got)
        assert np.all(np.diff(whole) >= 0)
        sent = np.sort(np.concatenate(
            [ref.keys(seed, r, parity, cls, P) for r in range(P)]))
        assert np.array_equal(whole, sent)


CASES = {
    "random-with-zeros-8": (8, "int32", "zeros"),
    "random-4": (4, "int32", "random"),
    "a-rank-sends-nothing": (4, "int32", "silent"),
    "a-rank-receives-nothing": (4, "int32", "deaf"),
    "all-to-one": (8, "int32", "one"),
    "float32": (4, "float32", "random"),
    "bfloat16": (4, "bfloat16", "random"),
    # blocks of several chunks at the real tile (chunk 8,192 here)
    "long-blocks": (4, "int32", "long"),
    "long-blocks-bfloat16": (4, "bfloat16", "long"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_argument_contract(case):
    P, dtype, how = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    n = 40000 if how == "long" else 600
    counts = random_counts(rng, P, n, zeros=0.4 if how == "zeros" else 0.0)
    if how == "silent":
        counts[1] = 0
    elif how == "deaf":
        counts[:, 2] = 0
    elif how == "one":
        counts[:] = 0
        counts[:, 3] = n
    xs = [rng.integers(0, 1 << 15, n).astype(np.int32) for _ in range(P)]
    if dtype != "int32":
        xs = [np.asarray(jnp.asarray(x, jnp.float32).astype(dtype))
              for x in xs]
    cap = int(counts.sum(0).max()) + 7
    assert exchange(P, xs, counts, cap) == {"hbm"}


def test_uint64_bit_patterns_and_the_refusal_without_x64():
    """An 8-byte element travels whole where the job enabled x64, and
    is refused (MPI_ERR_TYPE) where jax would narrow it."""
    P, n = 4, 300
    rng = np.random.default_rng(64)
    counts = random_counts(rng, P, n)
    xs = [rng.integers(0, 1 << 63, n, dtype=np.uint64) | (1 << 63)
          for _ in range(P)]
    cap = int(counts.sum(0).max())

    def fn(comm):
        r = comm.rank
        with pytest.raises(eh.MPIException) as e:
            comm.alltoallv_arr(xs[r], counts[r], counts[:, r], capacity=cap)
        assert e.value.error_class == eh.ERR_TYPE
        with jax.enable_x64(True):
            out = comm.alltoallv_arr(
                jax.device_put(xs[r], comm.device), counts[r],
                counts[:, r], capacity=cap)
            return np.asarray(out), comm.coll.providers["alltoallv_arr"]

    sd, rd = packed(counts), packed(counts.T)
    for r, (out, prov) in enumerate(one_chip(P, fn)):
        want, live = expected(xs, counts, sd, rd, r, cap)
        assert prov == "hbm" and out.dtype == np.uint64
        assert np.array_equal(out[live], want[live])


def test_displacements_with_gaps_in_any_order():
    """Explicit displacements: gaps on both sides, the receive blocks
    in falling order; what no block covers is not compared."""
    P, n = 4, 400
    rng = np.random.default_rng(5)
    counts = rng.integers(0, 60, (P, P))
    sd = packed(counts) + 9 * np.arange(P)            # gaps of 9
    slots = 70 * np.arange(P)[::-1]                   # falling order
    rd = np.tile(slots, (P, 1))
    xs = [rng.integers(0, 1 << 20, n).astype(np.int32) for _ in range(P)]
    assert exchange(P, xs, counts, 300, sd, rd) == {"hbm"}


def test_capacity_met_exactly_and_passed_by_one():
    P, n = 4, 200
    rng = np.random.default_rng(6)
    counts = random_counts(rng, P, n)
    xs = [rng.integers(0, 99, n).astype(np.int32) for _ in range(P)]
    need = counts.sum(0)                 # what each rank receives
    cap = int(need.max())
    assert exchange(P, xs, counts, cap) == {"hbm"}

    def fn(comm):
        r = comm.rank
        with pytest.raises(eh.MPIException) as e:
            comm.alltoallv_arr(xs[r], counts[r], counts[:, r],
                               capacity=int(need[r]) - 1)
        return e.value.error_class

    assert set(one_chip(P, fn)) == {eh.ERR_TRUNCATE}


def test_what_the_entry_refuses():
    """A count matrix of the wrong length, a negative count, a send
    block past the buffer, no capacity, a scalar for a buffer of rows:
    each raises on the caller's own side, before any rank waits."""
    x = np.arange(10, dtype=np.int32)

    def fn(comm):
        seen = []
        for args, kw in (
                (([5, 5, 0], [5, 5]), dict(capacity=10)),
                (([5, -5], [5, 5]), dict(capacity=10)),
                (([5, 6], [5, 5]), dict(capacity=10)),
                (([5, 5], [5, 5]), dict()),
                (([5, 5], [5, 5]), dict(capacity=10, x=x[0]))):
            with pytest.raises(eh.MPIException) as e:
                comm.alltoallv_arr(kw.pop("x", x), *args, **kw)
            seen.append(e.value.error_class)
        return seen

    assert one_chip(2, fn)[0] == [eh.ERR_COUNT, eh.ERR_COUNT, eh.ERR_BUFFER,
                                  eh.ERR_ARG, eh.ERR_BUFFER]


def test_counts_that_do_not_meet_raise_on_every_rank():
    """What rank 0 sends rank 1 is not what rank 1 expects: only the
    meeting can see it, and every rank hears of it."""
    x = np.arange(8, dtype=np.int32)

    def fn(comm):
        sc = [4, 4] if comm.rank == 0 else [4, 4]
        rc = [4, 4] if comm.rank == 0 else [3, 4]
        with pytest.raises(RuntimeError, match="expects 3"):
            comm.alltoallv_arr(x, sc, rc, capacity=8)
        return True

    assert one_chip(2, fn) == [True, True]


def test_forty_count_matrices_build_one_program(monkeypatch):
    """40 calls, 40 different count matrices, long blocks and short
    (the chunk is 64 and the tile 16 here, so the chunked middles, the
    edges and the short blocks' windows all run): ONE
    program built, in the compile cache and in the comm's plans; every
    answer right."""
    P, n, cap, calls = 8, 1024, 8 * 1024, 40
    monkeypatch.setattr(ragged, "CHUNK", 64)
    monkeypatch.setattr(ragged, "TILE", 16)
    rng = np.random.default_rng(40)
    mats = [random_counts(rng, P, n, zeros=0.2 * (k % 3))
            for k in range(calls)]
    assert len({m.tobytes() for m in mats}) == calls
    xs = [rng.integers(0, 1 << 30, n).astype(np.int32) for _ in range(P)]
    dev.compile_cache.clear()
    builds = dev.compile_cache.builds

    def fn(comm):
        x = jax.device_put(xs[comm.rank], comm.device)
        outs = [np.asarray(comm.alltoallv_arr(
            x, m[comm.rank], m[:, comm.rank], capacity=cap)) for m in mats]
        return outs, len(comm.__dict__["_hbm_plans"])

    res = one_chip(P, fn)
    assert dev.compile_cache.builds - builds == 1
    for r, (outs, plans) in enumerate(res):
        assert plans == 1
        for m, out in zip(mats, outs):
            want, live = expected(xs, m, packed(m), packed(m.T), r, cap)
            assert np.array_equal(out[live], want[live])


def test_ranks_of_different_lengths_and_capacities():
    """A rank sizes its own buffers: the program is keyed by all of
    them."""
    P = 4
    rng = np.random.default_rng(9)
    lens = [100, 0, 257, 64]
    counts = np.zeros((P, P), np.int64)
    for i, n in enumerate(lens):
        counts[i] = random_counts(rng, P, n)[0] if n else 0
    caps = [int(c) + 3 * r for r, c in enumerate(counts.sum(0))]
    xs = [rng.integers(0, 1 << 20, n).astype(np.int32) for n in lens]
    sd, rd = packed(counts), packed(counts.T)

    def fn(comm):
        r = comm.rank
        return np.asarray(comm.alltoallv_arr(
            jax.device_put(xs[r], comm.device), counts[r], counts[:, r],
            capacity=caps[r]))

    for r, out in enumerate(one_chip(P, fn)):
        want, live = expected(xs, counts, sd, rd, r, caps[r])
        assert out.shape == (caps[r],)
        assert np.array_equal(out[live], want[live])


@pytest.mark.parametrize("why", ["one-byte-elements", "ranks-across-devices"])
def test_the_fallback_gives_the_same_answer_and_is_counted(why):
    """What the device does not serve (an element size outside
    coll/ragged.ITEMSIZES; a comm whose ranks own different devices of
    XLA:CPU, which cannot lower coll/tpu's ragged-all-to-all) goes to
    HostArrModule:
    the same answer, counted host-staged once a rank-call, the device
    counters at rest."""
    P, n = 4, 300
    rng = np.random.default_rng(11)
    counts = random_counts(rng, P, n, zeros=0.2)
    xs = [rng.integers(0, 100, n).astype(
        np.int8 if why == "one-byte-elements" else np.int32)
        for _ in range(P)]
    cap = int(counts.sum(0).max()) + 1
    world = one_chip if why == "one-byte-elements" else (
        lambda P, fn: run_ranks(P, fn, devices=True))
    staged = pvar("coll_arr_host_staged_collectives")
    ops = pvar("coll_alltoallv_device_ops")
    provs = exchange(P, xs, counts, cap, world=world)
    assert provs == {"hbm" if why == "one-byte-elements" else "tpu"}
    assert pvar("coll_arr_host_staged_collectives") - staged == P
    assert pvar("coll_alltoallv_device_ops") == ops


def test_the_two_pvars_and_the_span():
    """``coll_alltoallv_device_ops`` moves once a rank-call,
    ``coll_alltoallv_elems`` by the sum of scounts (no padded bound);
    the call's ``coll`` span carries the elements sent and the
    capacity."""
    P, n, cap = 4, 500, 900
    rng = np.random.default_rng(12)
    counts = random_counts(rng, P, n, zeros=0.3)
    xs = [rng.integers(0, 100, n).astype(np.int32) for _ in range(P)]
    ops, elems = pvar("coll_alltoallv_device_ops"), \
        pvar("coll_alltoallv_elems")
    saved = registry.get("trace_enable")
    registry.set("trace_enable", True)
    try:
        def fn(comm):
            r = comm.rank
            comm.alltoallv_arr(xs[r], counts[r], counts[:, r], capacity=cap)
            return [e for e in comm.state.tracer.snapshot()
                    if e.get("name") == "alltoallv_arr"]

        res = one_chip(P, fn)
    finally:
        registry.set("trace_enable", saved)
    assert pvar("coll_alltoallv_device_ops") - ops == P
    assert pvar("coll_alltoallv_elems") - elems == int(counts.sum())
    for r, spans in enumerate(res):
        assert len(spans) == 1
        assert spans[0]["args"]["elems"] == int(counts[r].sum())
        assert spans[0]["args"]["capacity"] == cap


def test_the_sdc_injector_flips_a_ragged_deposit():
    """The integrity plane does not sample the call (DESIGN.md section
    25), but its fault injector reaches every deposit: a ragged one
    flips its array and keeps its counts."""
    from ompi_tpu.obs import integrity
    meta = ragged.arguments(2, 8, [3, 5], [4, 4], None, None, 16)
    dep = ragged.Deposit(np.arange(8, dtype=np.int32), meta, 16)
    bad = integrity.flip_value(dep)
    assert bad.meta is meta and bad.capacity == 16
    assert int(np.sum(np.asarray(bad.x) != dep.x)) == 1


def test_the_chunk_of_a_pair():
    """Half a balanced block as a power of two, a whole number of
    tiles, at most CHUNK and at least one tile, never longer than
    either buffer; 0 where a buffer holds no whole tile."""
    t = ragged.TILE
    assert ragged.chunk_of(1 << 24, 3 << 23, 8, ragged.CHUNK, t) == 1 << 19
    assert ragged.chunk_of(1 << 24, 3 << 23, 8, 1 << 21, t) == 1 << 20
    assert ragged.chunk_of(8192, 12288, 8, ragged.CHUNK, t) == 1024
    assert ragged.chunk_of(100, 12288, 8, ragged.CHUNK, t) == 0
    assert ragged.chunk_of(8192, 1000, 8, ragged.CHUNK, t) == 0
    assert ragged.chunk_of(300000, 5000, 4, ragged.CHUNK, t) == 4096
    assert ragged.chunk_of(1 << 20, 1 << 20, 4, ragged.CHUNK, 2 * t) == 1 << 17


# -- the program, compiled here for a described v5e (no chip) -------------

@pytest.fixture(scope="module")
def one_v5e():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_program_compiles_for_the_chip_to_one_pass_a_chunk(one_v5e):
    """What the body's speed rests on (PERF.md section 5): the chip's
    compiler sees the chunk's destination offset as a multiple of the
    tile (1,023 known zero bits) and fuses the dynamic_slice into the
    in-place dynamic_update_slice, one pass a chunk; no whole buffer is
    copied; the program is named ompi_alltoallv."""
    import re
    P, n = 4, 1 << 22
    cap = 3 * n // 2
    meta = jax.ShapeDtypeStruct((3, P, P), jnp.int32, sharding=one_v5e)
    xs = [jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_v5e)] * P
    txt = jax.jit(ragged.body((cap,) * P)).lower(meta, *xs).compile() \
        .as_text()
    assert "jit_ompi_alltoallv" in txt
    assert len(re.findall(r" while\(", txt)) == P * P
    # every chunk loop's body is ONE fusion whose root is the update
    fused = re.findall(
        r"ROOT %dynamic_update_slice\S* = \S+ dynamic-update-slice\("
        r"[^\n]*?\"zeroes\":\"1023\"", txt)
    assert len(fused) >= P * P
    assert not re.search(r"s32\[(%d|%d)\]\S* copy" % (n, cap), txt)


def numpy_arguments(size, length, scounts, rcounts, sdispls, rdispls,
                    capacity):
    """The (4, size) int64 ``meta`` and the refusals of PR 40's
    arguments(), in numpy, as the reference of ragged.arguments."""
    if capacity is None:
        raise eh.MPIException(
            eh.ERR_ARG, "alltoallv_arr: capacity (the static length of "
            "the result, MPI's receive buffer) must be given "
            "(MPI_ERR_ARG)")
    meta = np.empty((4, size), np.int64)
    for row, (given, of) in enumerate(
            ((scounts, None), (sdispls, 0), (rcounts, None), (rdispls, 2))):
        if given is None and of is not None:
            meta[row, 0] = 0
            np.cumsum(meta[of, :-1], out=meta[row, 1:])
            continue
        v = np.asarray(given)
        if v.shape != (size,) or v.dtype.kind not in "iu":
            raise eh.MPIException(
                eh.ERR_COUNT, f"alltoallv_arr: {ragged._ROWS[row]} must be "
                f"{size} integers, one a rank (MPI_ERR_COUNT)")
        meta[row] = v
    if meta.min() < 0:
        raise eh.MPIException(
            eh.ERR_COUNT, "alltoallv_arr: a negative count or "
            "displacement (MPI_ERR_COUNT)")
    if (meta[1] + meta[0]).max() > length:
        raise eh.MPIException(
            eh.ERR_BUFFER, f"alltoallv_arr: a send block ends at "
            f"{int((meta[1] + meta[0]).max())}, past the {length} "
            "elements of the send buffer (MPI_ERR_BUFFER)")
    if (meta[3] + meta[2]).max() > capacity:
        raise eh.MPIException(
            eh.ERR_TRUNCATE, f"alltoallv_arr: a receive block ends at "
            f"{int((meta[3] + meta[2]).max())}, past the capacity of "
            f"{capacity} elements (MPI_ERR_TRUNCATE)")
    return meta


def numpy_operand(metas, longest):
    """PR 40's operand(): the (3, P, P) int32 from the P (4, P) int64
    metas, with its two refusals."""
    metas = np.stack(metas)                               # (P, 4, P)
    counts = metas[:, 0, :]
    if not np.array_equal(counts, metas[:, 2, :].T):
        i, j = np.argwhere(counts != metas[:, 2, :].T)[0]
        raise eh.MPIException(
            eh.ERR_COUNT, f"alltoallv_arr: rank {i} sends {counts[i, j]} "
            f"elements to rank {j}, which expects {metas[j, 2, i]} "
            "(MPI_ERR_COUNT)")
    if longest >= 1 << 31:
        raise eh.MPIException(
            eh.ERR_COUNT, "alltoallv_arr: a buffer of 2**31 elements or "
            "more (MPI_ERR_COUNT)")
    return np.stack([counts, metas[:, 1, :], metas[:, 3, :].T]).astype(
        np.int32)


def outcome(fn, *args):
    """(result, None) or (None, (error class, message))."""
    try:
        return fn(*args), None
    except eh.MPIException as e:
        return None, (e.error_class, str(e))


FORMS = {
    "numpy-int64": lambda v: np.asarray(v, np.int64),
    "numpy-int32": lambda v: np.asarray(v, np.int32),
    "numpy-uint32": lambda v: np.asarray(v, np.uint32),
    "list": lambda v: [int(c) for c in v],
    "tuple": lambda v: tuple(int(c) for c in v),
    "list-of-numpy-ints": lambda v: list(np.asarray(v, np.int64)),
}


@pytest.mark.parametrize("form", sorted(FORMS))
def test_every_form_of_the_counts_gives_one_meta_and_one_answer(form):
    """Counts and displacements given as numpy int64, int32 or uint32,
    as a list or a tuple: the same plain-integer ``meta`` as the numpy
    reference, and the same answers on the device."""
    P, n = 4, 300
    rng = np.random.default_rng(sorted(FORMS).index(form) + 420)
    counts = random_counts(rng, P, n, zeros=0.2)
    sd = packed(counts) + 3 * np.arange(P)
    rd = packed(counts.T)
    cap = int(rd[:, -1].max() + counts[-1].max()) + 5
    to = FORMS[form]
    for r in range(P):
        for sdispls, rdispls in ((None, None), (to(sd[r]), to(rd[r]))):
            meta = ragged.arguments(P, n + 3 * P, to(counts[r]),
                                    to(counts[:, r]), sdispls, rdispls, cap)
            want = numpy_arguments(P, n + 3 * P, counts[r], counts[:, r],
                                   None if sdispls is None else sd[r],
                                   None if rdispls is None else rd[r], cap)
            assert len(meta) == 5 and meta[ragged.SENT] == int(want[0].sum())
            assert all(type(v) is int for row in meta[:4] for v in row)
            assert np.array_equal(np.array(meta[:4]), want)
    xs = [rng.integers(0, 1 << 20, n + 3 * P).astype(np.int32)
          for _ in range(P)]

    def fn(comm):
        r = comm.rank
        out = comm.alltoallv_arr(
            jax.device_put(xs[r], comm.device), to(counts[r]),
            to(counts[:, r]), to(sd[r]), to(rd[r]), capacity=cap)
        return np.asarray(out), comm.coll.providers["alltoallv_arr"]

    for r, (out, prov) in enumerate(one_chip(P, fn)):
        want, live = expected(xs, counts, sd, rd, r, cap)
        assert prov == "hbm" and np.array_equal(out[live], want[live])


REFUSALS = {
    # (scounts, rcounts, sdispls, rdispls, capacity) of a 2-rank call
    # whose send buffer is 10 elements
    "wrong-length": ([5, 5, 0], [5, 5], None, None, 10),
    "wrong-length-rdispls": ([5, 5], [5, 5], None, [0, 5, 9], 10),
    "a-float-array": (np.array([5.0, 5.0]), [5, 5], None, None, 10),
    "bools": ([True, False], [5, 5], None, None, 10),
    "a-negative-count": ([5, -5], [5, 5], None, None, 10),
    "a-negative-displacement": ([5, 5], [5, 5], [-1, 5], None, 10),
    "past-the-buffer": ([5, 6], [5, 5], None, None, 10),
    "past-the-buffer-by-displacement": ([5, 5], [5, 5], [0, 6], None, 10),
    "past-the-capacity": ([5, 5], [5, 6], None, None, 10),
    "past-the-capacity-by-displacement": ([5, 5], [5, 5], None, [6, 0], 10),
    "no-capacity": ([5, 5], [5, 5], None, None, None),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_every_refusal_keeps_its_class_and_message(case):
    """Each refusal of the entry raises the numpy reference's MPI error
    class and message, on the caller's own rank, before any rank
    waits."""
    sc, rc, sd, rd, cap = REFUSALS[case]
    _, want = outcome(numpy_arguments, 2, 10, sc, rc, sd, rd, cap)
    assert want is not None
    assert outcome(ragged.arguments, 2, 10, sc, rc, sd, rd, cap)[1] == want
    x = np.arange(10, dtype=np.int32)

    def fn(comm):
        _, got = outcome(comm.alltoallv_arr, x, sc, rc, sd, rd, cap)
        return got

    assert one_chip(2, fn) == [want, want]


@pytest.mark.parametrize("seed", range(40))
def test_the_operand_is_the_numpy_formulas(seed):
    """40 random count matrices (zeros, gaps, any order, every third
    one a count that does not meet): the plain-integer operand equals
    the numpy formula it replaced, and a refusal is the same
    refusal."""
    rng = np.random.default_rng(4200 + seed)
    P = int(rng.integers(2, 9))
    n = int(rng.integers(0, 5000))
    counts = random_counts(rng, P, n, zeros=0.3 * (seed % 3))
    sd = packed(counts) + rng.integers(0, 50, (P, P)).cumsum(1)
    rd = np.stack([rng.permutation(P) for _ in range(P)]) * (n + 50)
    rc = counts.T.copy()
    if seed % 3 == 2 and n:
        i, j = rng.integers(0, P, 2)
        rc[j, i] += 1
    length = int((sd + counts).max()) + 1
    cap = int((rd + rc).max()) + 1
    metas = [ragged.arguments(P, length, counts[r], rc[r], sd[r], rd[r],
                              cap) for r in range(P)]
    deps = [ragged.Deposit(np.zeros(length, np.int32), m, cap)
            for m in metas]
    ref = [numpy_arguments(P, length, counts[r], rc[r], sd[r], rd[r], cap)
           for r in range(P)]
    for longest in (max(length, cap), 1 << 31):
        got, err = outcome(ragged.operand, deps, longest)
        want, werr = outcome(numpy_operand, ref, longest)
        assert err == werr
        if werr is None:
            assert got.dtype == np.int32 and got.shape == (3, P, P)
            assert np.array_equal(got, want)


# -- rows, on both providers and through the fallback -------------------------

def emulated(x, out, offsets, sizes, lands, takes):
    """``lax.ragged_all_to_all``'s semantics in operations XLA:CPU
    lowers (an all-gather and a masked gather a source), so that the
    mesh half's meeting, operand, keys and layout run here: rank i's
    rows ``[offsets[j], offsets[j] + sizes[j])`` land at ``lands[j]`` of
    rank j's result."""
    from jax import lax
    me = lax.axis_index("r")
    xs = lax.all_gather(x, "r")
    offs, szs, lds = (lax.all_gather(v, "r") for v in (offsets, sizes, lands))
    rows = lax.iota(jnp.int32, out.shape[0])
    for i in range(xs.shape[0]):
        o, c, at = offs[i, me], szs[i, me], lds[i, me]
        src = jnp.clip(o + rows - at, 0, x.shape[0] - 1)
        take = ((rows >= at) & (rows < at + c)).reshape(
            (-1,) + (1,) * (out.ndim - 1))
        out = jnp.where(take, xs[i][src], out)
    return out


@pytest.fixture
def mesh_emulated(monkeypatch):
    """coll/tpu's mesh half on this process's CPU devices, its one
    collective emulated; the compile cache cleared on both sides."""
    monkeypatch.setattr(ragged, "NO_LOWERING", ())
    monkeypatch.setattr(ragged, "_exchange", emulated)
    dev.compile_cache.clear()
    yield
    dev.compile_cache.clear()


def mesh_world(P, fn, **kw):
    """P ranks, each on its own device: coll/tpu's layout."""
    return run_ranks(P, fn, devices=True, **kw)


def rows_exchange(P, xs, counts, cap, sd=None, rd=None, world=one_chip):
    """Run one exchange of rows; assert every rank's answer, shape and
    device; return the providers."""
    counts = np.asarray(counts)
    sdp = packed(counts) if sd is None else np.asarray(sd)
    rdp = packed(counts.T) if rd is None else np.asarray(rd)

    def fn(comm):
        r = comm.rank
        out = comm.alltoallv_arr(
            jax.device_put(xs[r], comm.device), counts[r], counts[:, r],
            None if sd is None else sdp[r], None if rd is None else rdp[r],
            capacity=cap)
        assert isinstance(out, jax.Array) and comm.device in out.devices()
        return np.asarray(out), comm.coll.providers["alltoallv_arr"]

    res = world(P, fn)
    for r, (out, _prov) in enumerate(res):
        assert out.shape == (cap, *xs[0].shape[1:])
        assert out.dtype == xs[0].dtype
        want = np.zeros_like(out)
        live = np.zeros(cap, bool)
        for i in range(P):
            c = counts[i][r]
            want[rdp[r][i]:rdp[r][i] + c] = xs[i][sdp[i][r]:sdp[i][r] + c]
            live[rdp[r][i]:rdp[r][i] + c] = True
        assert np.array_equal(out[live].view(np.uint8),
                              want[live].view(np.uint8)), r
    return {prov for _o, prov in res}


ROWS = {
    # (row shape, dtype, how): one answer each; every row of 512 B or
    # more but the 1-D one, which a mesh serves through the host
    "1-D-int32": ((), "int32", "random"),
    "rows-bfloat16": ((256,), "bfloat16", "random"),
    "rows-uint32": ((2, 64), "uint32", "random"),
    "rows-zero-counts": ((130,), "uint32", "zeros"),
    "rows-with-gaps": ((160,), "float32", "gaps"),
}
PATHS = ("hbm", "mesh", "fallback")


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("case", sorted(ROWS))
def test_rows_on_every_path(case, path, monkeypatch):
    """A buffer of rows: counts, displacements and capacity in rows,
    the result ``(capacity, *row)``, every received row bit for bit, on
    coll/hbm (the flat view), coll/tpu's mesh program (its collective
    emulated on CPU devices; a row under ``MESH_ROW_BYTES`` goes to the
    host) and the host fallback (a CPU mesh, whose compiler cannot
    lower the collective)."""
    row, dtype, how = ROWS[case]
    P, n = 4, 90
    rng = np.random.default_rng(sorted(ROWS).index(case) + 4300)
    counts = random_counts(rng, P, n, zeros=0.5 if how == "zeros" else 0.0)
    if how == "zeros":
        counts[1] = 0
        counts[:, 2] = 0
    xs = [rng.integers(0, 1 << 15, (n, *row)) for _ in range(P)]
    xs = [np.asarray(jnp.asarray(x, jnp.float32).astype(dtype))
          if dtype in ("bfloat16", "float32") else x.astype(dtype)
          for x in xs]
    sd = rd = None
    cap = int(counts.sum(0).max()) + 5
    if how == "gaps":
        sd = packed(counts) + 2 * np.arange(P)
        slot = int(counts.max()) + 3
        rd = np.tile(slot * np.arange(P)[::-1], (P, 1))
        xs = [np.concatenate([x, x[:2 * P]]) for x in xs]
        cap = slot * P
    if path == "mesh":
        monkeypatch.setattr(ragged, "NO_LOWERING", ())
        monkeypatch.setattr(ragged, "_exchange", emulated)
        dev.compile_cache.clear()
    staged = pvar("coll_arr_host_staged_collectives")
    ops = pvar("coll_alltoallv_device_ops")
    try:
        provs = rows_exchange(P, xs, counts, cap, sd, rd,
                              one_chip if path == "hbm" else mesh_world)
    finally:
        if path == "mesh":
            dev.compile_cache.clear()
    assert provs == {"hbm" if path == "hbm" else "tpu"}
    on_device = path == "hbm" or (path == "mesh" and row)
    assert pvar("coll_alltoallv_device_ops") - ops == (P if on_device else 0)
    assert pvar("coll_arr_host_staged_collectives") - staged == (
        0 if on_device else P)


def test_the_counters_and_the_span_count_rows():
    """``coll_alltoallv_elems`` moves by the rows sent times the
    elements of a row, ``coll_alltoallv_bytes`` by the bytes (no padded
    bound); the ``coll`` span carries the rows, the elements, the bytes
    of a row and the capacity."""
    P, n, cap, row = 4, 60, 150, (2, 7)
    rng = np.random.default_rng(431)
    counts = random_counts(rng, P, n, zeros=0.3)
    xs = [rng.integers(0, 100, (n, *row)).astype(np.uint16)
          for _ in range(P)]
    ops, elems, nbytes = (pvar("coll_alltoallv_" + k)
                          for k in ("device_ops", "elems", "bytes"))
    saved = registry.get("trace_enable")
    registry.set("trace_enable", True)
    try:
        def fn(comm):
            r = comm.rank
            comm.alltoallv_arr(xs[r], counts[r], counts[:, r], capacity=cap)
            return [e for e in comm.state.tracer.snapshot()
                    if e.get("name") == "alltoallv_arr"]

        res = one_chip(P, fn)
    finally:
        registry.set("trace_enable", saved)
    sent = int(counts.sum())
    assert pvar("coll_alltoallv_device_ops") - ops == P
    assert pvar("coll_alltoallv_elems") - elems == sent * 14
    assert pvar("coll_alltoallv_bytes") - nbytes == sent * 28
    for r, spans in enumerate(res):
        args = spans[0]["args"]
        assert (args["rows"], args["elems"], args["row_bytes"],
                args["capacity"]) == (int(counts[r].sum()),
                                      14 * int(counts[r].sum()), 28, cap)


def test_rows_of_another_shape_are_refused_on_every_rank():
    """Rows of another shape on one rank break MPI's matching type
    signatures: only the meeting sees it, and every rank hears of it."""
    def fn(comm):
        x = np.zeros((8, 3 if comm.rank else 4), np.int32)
        with pytest.raises(RuntimeError, match="MPI_ERR_TYPE"):
            comm.alltoallv_arr(x, [4, 4], [4, 4], capacity=8)
        return True

    assert one_chip(2, fn) == [True, True]


# -- the mesh half's meeting ---------------------------------------------------

def numpy_mesh_operand(metas):
    """What ``lax.ragged_all_to_all`` asks of rank i, from the (4, P)
    numpy metas: its send displacements, its send counts, where in each
    receiver's result its block lands (the receiver's rdispls, column
    i), and its receive counts."""
    metas = np.stack(metas)                               # (P, 4, P)
    return np.stack([np.stack([m[1], m[0], metas[:, 3, i], m[2]])
                     for i, m in enumerate(metas)]).astype(np.int32)


@pytest.mark.parametrize("seed", range(20))
def test_the_mesh_operand_is_the_numpy_formulas(seed):
    """20 random count matrices (zeros, gaps, any order, every third
    one a count that does not meet): the mesh operand equals the numpy
    formula, and a refusal is the coll/hbm operand's refusal."""
    rng = np.random.default_rng(4310 + seed)
    P = int(rng.integers(2, 9))
    n = int(rng.integers(0, 3000))
    counts = random_counts(rng, P, n, zeros=0.3 * (seed % 3))
    sd = packed(counts) + rng.integers(0, 40, (P, P)).cumsum(1)
    rd = np.stack([rng.permutation(P) for _ in range(P)]) * (n + 40)
    rc = counts.T.copy()
    if seed % 3 == 2 and n:
        i, j = rng.integers(0, P, 2)
        rc[j, i] += 1
    length = int((sd + counts).max()) + 1
    cap = int((rd + rc).max()) + 1
    metas = [ragged.arguments(P, length, counts[r], rc[r], sd[r], rd[r],
                              cap) for r in range(P)]
    deps = [ragged.Deposit(np.zeros((length, 3), np.uint16), m, cap)
            for m in metas]
    got, err = outcome(ragged.mesh_operand, deps, max(length, cap))
    _, herr = outcome(ragged.operand, deps, max(length, cap))
    assert err == herr
    if err is None:
        want = numpy_mesh_operand(
            [numpy_arguments(P, length, counts[r], rc[r], sd[r], rd[r], cap)
             for r in range(P)])
        assert got.dtype == np.int32 and got.shape == (P, 4, P)
        assert np.array_equal(got, want)


def test_forty_count_matrices_build_one_mesh_program(mesh_emulated):
    """40 calls over the mesh, 40 different count matrices: ONE
    ompi_alltoallv_mesh program built and one meeting computation a
    comm; every answer right; nothing host-staged."""
    P, n, cap, calls, row = 4, 256, 1024, 40, (128,)
    rng = np.random.default_rng(4340)
    mats = [random_counts(rng, P, n, zeros=0.2 * (k % 3))
            for k in range(calls)]
    assert len({m.tobytes() for m in mats}) == calls
    xs = [rng.integers(0, 1 << 30, (n, *row)).astype(np.int32)
          for _ in range(P)]
    builds = dev.compile_cache.builds
    staged = pvar("coll_arr_host_staged_collectives")
    ops = pvar("coll_alltoallv_device_ops")

    def fn(comm):
        x = jax.device_put(xs[comm.rank], comm.device)
        outs = [np.asarray(comm.alltoallv_arr(
            x, m[comm.rank], m[:, comm.rank], capacity=cap)) for m in mats]
        return outs, comm.coll.providers["alltoallv_arr"]

    res = mesh_world(P, fn)
    assert dev.compile_cache.builds - builds == 1
    assert pvar("coll_arr_host_staged_collectives") == staged
    assert pvar("coll_alltoallv_device_ops") - ops == P * calls
    for r, (outs, prov) in enumerate(res):
        assert prov == "tpu"
        for m, out in zip(mats, outs):
            rd = packed(m.T)
            for i in range(P):
                c = m[i][r]
                assert np.array_equal(
                    out[rd[r][i]:rd[r][i] + c],
                    xs[i][packed(m)[i][r]:packed(m)[i][r] + c])


def test_deposits_of_other_lengths_meet_through_the_host(mesh_emulated):
    """Ranks that size their own send buffers and capacities cannot be
    the shards of one mesh program: the meeting serves them through the
    host, every answer right and on its rank's device, counted
    host-staged once a rank-call, the device counters at rest."""
    P = 4
    rng = np.random.default_rng(4350)
    lens = [100, 0, 257, 64]
    counts = np.zeros((P, P), np.int64)
    for i, n in enumerate(lens):
        counts[i] = random_counts(rng, P, n)[0] if n else 0
    caps = [int(c) + 3 * r for r, c in enumerate(counts.sum(0))]
    xs = [rng.integers(0, 1 << 20, (n, 128)).astype(np.int32) for n in lens]
    sd, rd = packed(counts), packed(counts.T)
    staged = pvar("coll_arr_host_staged_collectives")
    ops = pvar("coll_alltoallv_device_ops")

    def fn(comm):
        r = comm.rank
        out = comm.alltoallv_arr(
            jax.device_put(xs[r], comm.device), counts[r], counts[:, r],
            capacity=caps[r])
        assert comm.device in out.devices()
        return np.asarray(out)

    for r, out in enumerate(mesh_world(P, fn)):
        assert out.shape == (caps[r], 128)
        for i in range(P):
            c = counts[i][r]
            assert np.array_equal(out[rd[r][i]:rd[r][i] + c],
                                  xs[i][sd[i][r]:sd[i][r] + c])
    assert pvar("coll_arr_host_staged_collectives") - staged == P
    assert pvar("coll_alltoallv_device_ops") == ops


# -- the mesh program's slab body ----------------------------------------------

def row_major(t):
    """The layout the chip gives a row of whole 128-element lanes: rows
    major, ``(t, 128)`` tiles."""
    from jax.experimental.layout import Layout
    return Layout(major_to_minor=(0, 1), tiling=((t, 128),))


RULE = {
    # (layout, shape, itemsize, capacity): the rows of a slab, 0 for
    # the row body
    "combine-bfloat16": (
        "bf16", (16384, 7168), 2, 16384, 8),
    "float32-rows": ("rm8", (16384, 4096), 4, 16384, 8),
    "uint32-1920": ("rm8", (16384, 1920), 4, 16384, 8),
    "dispatch-column-major": ("cm8", (16384, 1864), 4, 16384, 0),
    "eight-byte-element": ("rm8", (4096, 128), 8, 4096, 0),
    "row-not-whole-lanes": ("rm8", (4096, 200), 4, 4096, 0),
    "rows-of-two-dims": ("rm8", (4096, 2, 128), 4, 4096, 0),
    "rows-not-a-slab-multiple": ("rm8", (4100, 128), 4, 4096, 0),
    "capacity-not-a-slab-multiple": ("rm8", (4096, 128), 4, 4100, 0),
    "no-tiles": ("flat", (4096, 128), 4, 4096, 0),
    "no-layout": (None, (4096, 128), 4, 4096, 0),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_the_slab_rule_reads_the_layout(case):
    """The slab body serves a 2-D buffer of 2- or 4-byte elements laid
    out rows major on ``(t, 128)`` tiles, ``t`` dividing its rows and
    the capacity; everything else is the row body's."""
    from jax.experimental.layout import Layout
    lay, shape, itemsize, cap, t = RULE[case]
    layout = {
        "bf16": Layout(major_to_minor=(0, 1), tiling=((8, 128), (2, 1))),
        "rm8": row_major(8),
        "cm8": Layout(major_to_minor=(1, 0), tiling=((8, 128),)),
        "flat": Layout(major_to_minor=(0, 1), tiling=()),
        None: None}[lay]
    assert ragged.slab_rows(layout, shape, itemsize, cap) == t


def slab_case(rng, P, t):
    """One exchange whose blocks start and end at every phase of a slab:
    counts with zeros and blocks shorter than a slab, send and receive
    blocks in a random order with gaps, one block that ends at the
    buffer's last row, a capacity beyond what arrives, and now and then
    a rank that receives nothing; the ``(P,)`` metas, the rows and the
    capacity."""
    counts = rng.integers(0, 4 * t, (P, P))
    counts[rng.random((P, P)) < 0.2] = 0
    counts[rng.random((P, P)) < 0.2] %= t
    sd = np.zeros((P, P), np.int64)
    rd = np.zeros((P, P), np.int64)
    for disp, c in ((sd, counts), (rd, counts.T)):
        for i in range(P):
            at = 0
            for j in rng.permutation(P):
                at += int(rng.integers(0, 2 * t))
                disp[i, j] = at
                at += c[i, j]
    end = sd + counts
    n = -(-int(end.max() + rng.integers(0, 2 * t)) // t) * t
    i = int(np.argmax(end.max(1)))
    j = int(np.argmax(end[i]))
    sd[i, j] += n - end[i, j]                  # the buffer's last row
    cap = -(-int((rd + counts.T).max() + rng.integers(0, 3 * t)) // t) * t
    if rng.random() < 0.2:
        counts[:, 0] = 0                        # a rank that gets nothing,
        rd[0] = cap                             # its blocks at the end
    metas = [ragged.arguments(P, n, counts[r], counts[:, r], sd[r], rd[r],
                              cap) for r in range(P)]
    return metas, n, cap


def slab_payload(rng, n, dtype):
    """``n`` rows of 128 seeded bit patterns: for bfloat16, subnormals
    and NaNs of both signs among them."""
    if dtype == "uint32":
        return rng.integers(0, 1 << 32, (n, 128), dtype=np.uint64) \
            .astype(np.uint32)
    bits = rng.integers(0, 1 << 16, (n, 128)).astype(np.uint16)
    odd = np.array([0x0001, 0x007F, 0x8001, 0x807F, 0x7FC1, 0xFFC1,
                    0x7F81, 0xFF81], np.uint16)
    bits.flat[::3] = odd[np.arange(bits.size)[::3] % len(odd)]
    return bits.view(jnp.bfloat16)


class _Count:
    def add(self, n):
        pass


@pytest.mark.parametrize("dtype", ["bfloat16", "uint32"])
@pytest.mark.parametrize("seed", range(40))
def test_the_slab_exchange_gives_the_host_answer(seed, dtype):
    """40 count matrices on 4 ranks, every phase of the displacements
    against slabs of 1 to 16 rows: the slab operand sends whole slabs
    of the sender's buffer into its receivers' staging buffers without
    overlap or overflow; the slab-granular exchange emulated here, then
    the program's gather (``ragged.arrival``, run on the CPU), gives
    every received row of ``ragged.through_host``'s answer bit for
    bit."""
    rng = np.random.default_rng(4500 + seed)
    P, t = 4, (8, 8, 16, 4, 2, 1)[seed % 6]
    metas, n, cap = slab_case(rng, P, t)
    xs = [slab_payload(rng, n, dtype) for _ in range(P)]
    deps = [ragged.Deposit(x, m, cap) for x, m in zip(xs, metas)]
    op = ragged.slab_operand(deps, max(n, cap) * 128, t)
    room = ragged.staging_slabs(cap, t, P, xs[0].dtype.itemsize)
    assert op.dtype == np.int32 and op.shape == (P, 7, P)
    start, slabs, lands, takes, first, rd, rc = op.transpose(1, 0, 2)
    assert np.array_equal(takes, slabs.T)
    assert (start + slabs <= n // t).all() and (lands + slabs <= room).all()
    assert (np.diff(rd, axis=1) >= 0).all()        # in result order
    devs = jax.devices()[:P]
    want = ragged.through_host(deps, devs, _Count())
    arrive = jax.jit(ragged.arrival, static_argnums=(2, 3))
    for j in range(P):
        staged = np.asarray(slab_payload(rng, room * t, dtype))
        for i in range(P):
            k, at, s = slabs[i, j], lands[i, j], start[i, j]
            staged[at * t:(at + k) * t] = xs[i][s * t:(s + k) * t]
        got = np.asarray(arrive(staged, op[j, 4:], cap, True))
        assert got.shape == (cap, 128) and got.dtype == xs[0].dtype
        live = np.zeros(cap, bool)
        for i in range(P):
            live[rd[j, i]:rd[j, i] + rc[j, i]] = True
        words = np.uint16 if dtype == "bfloat16" else np.uint32
        assert np.array_equal(got[live].view(words),
                              np.asarray(want[j])[live].view(words)), j


def test_receive_blocks_that_overlap_have_no_slab_operand():
    """Receive blocks that overlap (MPI forbids them) get no slab
    operand, and the meeting serves the call through the host; blocks
    that touch, in any order, get one."""
    P, t, n, cap = 4, 8, 72, 64
    for lands, fits in (([0, 16, 32, 48], True), ([48, 0, 32, 16], True),
                        ([0, 16, 31, 48], False), ([0] * P, False)):
        counts = np.full((P, P), 16)
        metas = [ragged.arguments(P, n, counts[r], counts[:, r],
                                  [1 + 17 * j for j in range(P)], lands,
                                  cap) for r in range(P)]
        deps = [ragged.Deposit(np.zeros((n, 128), np.uint32), m, cap)
                for m in metas]
        op = ragged.slab_operand(deps, n * 128, t)
        assert (op is not None) == fits, lands


SLAB_ROWS = {
    # (row, dtype, the slab body serves it)
    "combine-like-bfloat16": (256, "bfloat16", True),
    "float32-rows": (128, "float32", True),
    "dispatch-like-uint32": (200, "uint32", False),
}


@pytest.mark.parametrize("case", sorted(SLAB_ROWS))
def test_the_mesh_program_chooses_its_body_by_the_layout(
        case, mesh_emulated, monkeypatch):
    """coll/tpu's mesh half with every deposit laid out rows major on
    (8, 128) tiles, as a chip lays a row of whole lanes: 12 count
    matrices, ONE program built; the slab body serves a row of whole
    lanes (``coll_alltoallv_slab_ops`` moves with
    ``coll_alltoallv_device_ops``), the row body any other; every
    answer right; nothing host-staged."""
    row, dtype, slab = SLAB_ROWS[case]
    monkeypatch.setattr(ragged, "layout_of", lambda x: row_major(8))
    P, n, cap, calls = 4, 96, 160, 12
    rng = np.random.default_rng(4520 + sorted(SLAB_ROWS).index(case))
    mats = [random_counts(rng, P, n - 20, zeros=0.2 * (k % 3))
            for k in range(calls)]
    xs = [np.asarray(jnp.asarray(rng.integers(0, 1 << 15, (n, row)),
                                 jnp.float32).astype(dtype))
          for _ in range(P)]
    builds = dev.compile_cache.builds
    staged = pvar("coll_arr_host_staged_collectives")
    ops = pvar("coll_alltoallv_device_ops")
    slabs = pvar("coll_alltoallv_slab_ops")
    for m in mats:
        sd = packed(m) + rng.integers(0, 5, (P, P)).cumsum(1)
        assert rows_exchange(P, xs, m, cap, sd, None, mesh_world) == {"tpu"}
    assert dev.compile_cache.builds - builds == 1
    assert pvar("coll_arr_host_staged_collectives") == staged
    assert pvar("coll_alltoallv_device_ops") - ops == P * calls
    assert pvar("coll_alltoallv_slab_ops") - slabs == (
        P * calls if slab else 0)


def test_overlapping_receive_blocks_meet_through_the_host(
        mesh_emulated, monkeypatch):
    """Where receive blocks overlap, the slab body's meeting hands back
    the host's answer (the last sender's rows win, as through the
    host), counted host-staged."""
    monkeypatch.setattr(ragged, "layout_of", lambda x: row_major(8))
    P, n, cap = 4, 72, 16
    rng = np.random.default_rng(4530)
    xs = [rng.integers(0, 1 << 30, (n, 128)).astype(np.int32)
          for _ in range(P)]
    sd = [1 + 17 * j for j in range(P)]
    staged = pvar("coll_arr_host_staged_collectives")
    slabs = pvar("coll_alltoallv_slab_ops")

    def fn(comm):
        out = comm.alltoallv_arr(
            jax.device_put(xs[comm.rank], comm.device), [16] * P, [16] * P,
            sd, [0] * P, capacity=cap)
        assert comm.device in out.devices()
        return np.asarray(out)

    for r, out in enumerate(mesh_world(P, fn)):
        assert np.array_equal(out, xs[P - 1][sd[r]:sd[r] + 16])
    assert pvar("coll_arr_host_staged_collectives") - staged == P
    assert pvar("coll_alltoallv_slab_ops") == slabs


# -- the mesh operand: one transfer from the host a meeting -------------------

def spy_puts(monkeypatch):
    """Every host array the library puts on a device, with the device,
    in order: through runtime/x64.put, and the mesh meeting's operand
    through ``ragged.put_operand``."""
    from ompi_tpu.runtime import x64
    puts = []

    def spy(real):
        def put(x, d, *what):
            if not isinstance(x, jax.Array):
                puts.append((np.array(x), d))
            return real(x, d, *what)
        return put

    monkeypatch.setattr(x64, "put", spy(x64.put))
    monkeypatch.setattr(ragged, "put_operand", spy(ragged.put_operand))
    return puts


@pytest.mark.parametrize("body", ["row", "slab"])
def test_the_mesh_operand_crosses_once_a_meeting(body, mesh_emulated,
                                                 monkeypatch):
    """40 count matrices on 4 ranks through each body of the mesh
    program: every answer equals the host's; ONE program built, with
    its placeholders of zeros put once on every chip but the first;
    then exactly one host array a meeting, the ``(P, k, P)`` int32
    operand, put onto the first rank's chip, counted once a meeting by
    ``coll_alltoallv_operand_puts``; nothing host-staged."""
    if body == "slab":
        monkeypatch.setattr(ragged, "layout_of", lambda x: row_major(8))
    k = 7 if body == "slab" else 4
    P, n, cap, calls = 4, 96, 384, 40
    rng = np.random.default_rng(4600 + k)
    mats = [random_counts(rng, P, n - 20, zeros=0.2 * (c % 3))
            for c in range(calls)]
    sds = [packed(m) + rng.integers(0, 5, (P, P)).cumsum(1) for m in mats]
    xs = [rng.integers(0, 1 << 30, (n, 128)).astype(np.int32)
          for _ in range(P)]
    puts = spy_puts(monkeypatch)
    builds = dev.compile_cache.builds
    counted = pvar("coll_alltoallv_operand_puts")
    staged = pvar("coll_arr_host_staged_collectives")
    ops = pvar("coll_alltoallv_device_ops")
    slabs = pvar("coll_alltoallv_slab_ops")

    def fn(comm):
        r = comm.rank
        x = jax.device_put(xs[r], comm.device)
        outs = [np.asarray(comm.alltoallv_arr(
            x, m[r], m[:, r], sd[r], capacity=cap))
            for m, sd in zip(mats, sds)]
        return outs, comm.device

    res = mesh_world(P, fn)
    devices = [d for _outs, d in res]
    assert dev.compile_cache.builds - builds == 1
    assert pvar("coll_alltoallv_operand_puts") - counted == calls
    assert pvar("coll_arr_host_staged_collectives") == staged
    assert pvar("coll_alltoallv_device_ops") - ops == P * calls
    assert pvar("coll_alltoallv_slab_ops") - slabs == (
        P * calls if body == "slab" else 0)
    spare = [(a, d) for a, d in puts if d != devices[0]]
    assert [d for _a, d in spare] == devices[1:]
    assert all(a.shape == (P, k, P) and a.dtype == np.int32
               and not a.any() for a, _d in spare)
    fresh = [a for a, d in puts if d == devices[0]]
    assert len(fresh) == calls
    assert all(a.shape == (P, k, P) and a.dtype == np.int32 and a.any()
               for a in fresh)
    for r, (outs, _d) in enumerate(res):
        for m, sd, out in zip(mats, sds, outs):
            rd = packed(m.T)
            for i in range(P):
                c = m[i][r]
                assert np.array_equal(out[rd[r][i]:rd[r][i] + c],
                                      xs[i][sd[i][r]:sd[i][r] + c])


@pytest.mark.parametrize("why", ["overlapping-receive-blocks",
                                 "other-lengths"])
def test_a_meeting_through_the_host_puts_no_operand(why, mesh_emulated,
                                                    monkeypatch):
    """A meeting that the host serves (receive blocks that overlap, for
    the slab body; deposits of other lengths, for any body) puts no
    count operand on any chip: ``coll_alltoallv_operand_puts`` stays
    where it was and no ``(P, k, P)`` int32 array reaches the first
    rank's chip."""
    monkeypatch.setattr(ragged, "layout_of", lambda x: row_major(8))
    P, cap = 4, 16
    rng = np.random.default_rng(4610)
    lens = [72] * P if why == "overlapping-receive-blocks" \
        else [72, 80, 72, 88]
    xs = [rng.integers(0, 1 << 30, (n, 128)).astype(np.int32)
          for n in lens]
    sd = [1 + 17 * j for j in range(P)]
    puts = spy_puts(monkeypatch)
    counted = pvar("coll_alltoallv_operand_puts")
    staged = pvar("coll_arr_host_staged_collectives")

    def fn(comm):
        out = comm.alltoallv_arr(
            jax.device_put(xs[comm.rank], comm.device), [16] * P,
            [16] * P, sd, [0] * P, capacity=cap)
        assert comm.device in out.devices()
        return np.asarray(out), comm.device

    res = mesh_world(P, fn)
    for r, (out, _d) in enumerate(res):
        assert np.array_equal(out, xs[P - 1][sd[r]:sd[r] + 16])
    assert pvar("coll_alltoallv_operand_puts") == counted
    assert pvar("coll_arr_host_staged_collectives") - staged == P
    first = res[0][1]
    assert not [a for a, d in puts
                if d == first and a.ndim == 3 and a.dtype == np.int32]


# -- the mesh program, compiled here for a described 2x2 v5e (no chip) --------

@pytest.fixture(scope="module")
def v5e_mesh():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mesh = Mesh(np.array(topo.devices), ("r",))
    return mesh, NamedSharding(mesh, PartitionSpec("r"))


def chip_layout(sharding, shape, dtype):
    """The layout the described chip gives a buffer of ``shape`` a rank
    (one with no layout of its own: the default)."""
    c = jax.jit(lambda a: a).lower(jax.ShapeDtypeStruct(
        (4 * shape[0], *shape[1:]), dtype, sharding=sharding)).compile()
    return c.input_formats[0][0].layout


MESH_SHAPES = {
    # (rows a rank, row shape, dtype, the slab rows the chip's layout
    # gives, the words the collective moves): the expert-parallel
    # dispatch and combine rows at their published widths, the
    # narrowest row served, and 8-byte elements.  A row of whole lanes
    # travels in slabs of 8 rows (the slab body); the dispatch's
    # 1,864-word rows, which the chip lays out column-major, and 8-byte
    # elements travel a row a block (the row body)
    "dispatch-uint32-1864": (16384, (1864,), "uint32", 0,
                             "u32[16384,1,1864]"),
    "combine-bfloat16-7168": (16384, (7168,), "bfloat16", 8,
                              "bf16[2056,8,7168]"),
    "narrowest-int32-128": (1 << 16, (128,), "int32", 8, "s32[8199,8,128]"),
    "bit-patterns-uint64": (4096, (64,), "uint64", 0, "u32[4096,1,128]"),
}


@pytest.mark.parametrize("case", sorted(MESH_SHAPES))
def test_the_mesh_program_compiles_for_a_2x2_v5e(v5e_mesh, case):
    """The chip's compiler takes ``ompi_alltoallv_mesh`` for four chips:
    its operand lowered at ``(P * P, k, P)``, ONE all-reduce of the
    ``s32[4,k,4]`` operand (the spread of the first chip's counts) and
    ONE ragged-all-to-all over the four devices and no other collective,
    under the program's stable name, an 8-byte element as two 32-bit
    words.  The slab rule, read from the layout the chip gives the
    buffer, picks the body: the slab body moves whole 8-row slabs of a
    row-major buffer as it lies, with no copy, reshape, transpose or
    fusion over a whole buffer: the one pass on arrival, the Pallas
    kernel ompi_alltoallv_arrival, writes the result; the row body
    moves each row as one tile-shaped block."""
    mesh, sharding = v5e_mesh
    n, row, dtype, t, moved = MESH_SHAPES[case]
    with jax.enable_x64(dtype == "uint64"):
        dt = jnp.dtype(dtype)
        got = ragged.slab_rows(chip_layout(sharding, (n, *row), dt),
                               (n, *row), dt.itemsize, n)
        assert got == t
        prog = ragged.mesh_program(mesh, n, sharding, t)
        k = 7 if t else 4
        txt = prog.lower(
            jax.ShapeDtypeStruct((16, k, 4), jnp.int32,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((4 * n, *row), dt,
                                 sharding=sharding)).compile().as_text()
    assert "jit_ompi_alltoallv_mesh" in txt
    ops = re.findall(r"= (\S+) (ragged-all-to-all|all-to-all|all-gather|"
                     r"all-reduce|collective-permute)\(", txt)
    assert sorted(op for _shape, op in ops) == [
        "all-reduce", "ragged-all-to-all"], ops
    spread = next(shape for shape, op in ops if op == "all-reduce")
    assert spread.startswith("s32[4,%d,4]{" % k), spread
    assert "replica_groups={{0,1,2,3}}" in txt
    line = next(ln for ln in txt.splitlines() if " ragged-all-to-all(" in ln)
    assert line.split("=", 1)[1].strip().startswith(moved + "{"), line
    if t:
        entry = txt[txt.index("\nENTRY"):]
        entry = entry[:entry.index("\n}")]
        kind = moved.split("[")[0]
        passes = re.findall(r"= %s\[([\d,]+)\]\S* (copy|reshape|transpose|"
                            r"fusion|custom-call)\((?!\)[^\n]*AllocateBuffer)"
                            % kind, entry)
        assert [p for p in passes if p[0].endswith(",%d" % row[-1])] == [
            ("%d,%d" % (n, row[-1]), "custom-call")]
        root = next(ln for ln in entry.splitlines() if "ROOT" in ln)
        assert "ompi_alltoallv_arrival" in root and "tpu_custom_call" in root
