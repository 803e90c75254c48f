"""The deployment osu-hbm8-f64 (ISSUE 32): a typed device collective
(a derived datatype as the collective's own argument), MPI_DOUBLE on
the device, a reduction other than SUM, held to the benchmark's plain
reference on the CPU.

* typed ``reduce_scatter_arr`` / ``allreduce_arr`` on coll/hbm (8
  ranks, one device) and coll/tpu (4 virtual devices) equal
  cellbench/reference_typed.py bit for bit for MAX and MIN, within a
  stated float64 tolerance for SUM, on a float64 buffer and on the
  uint64 bit-pattern carrier a device without binary64 takes (there a
  SUM is the host's); for indexed, contiguous and tail-block types
  they equal the host convertor's pack followed by the untyped call;
* a typed call is one rendezvous and one program with the pack
  inside it (static slices of a regular layout, a gather of any
  other); the two counters move as PERF.md section 3 says; a
  datatype the device cannot pack is served by the host fallback and
  counted;
* answers computed through float32, the odd-indexed elements, a rank's
  own block handed back and the bf16 control are NOT correct;
* the stream is the same bits on device and host and is binary64's:
  53 significant bits, its exponent range; a device whose float64 is
  not binary64 is found out and refused; the integrity plane digests a
  typed call's packed stream; the required-bytes
  rule at the cell's size; the reader of ``typed_roofline``; the cell
  end to end in the development mode.

Every test here runs with ``mpi_device_x64`` on: the switch is
process-wide, so one module fixture flips it and flips it back, and the
tests that need it OFF live in tests/test_coll_device.py.
"""
import copy
import json
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from cellbench import bytes_typed, manifest, reference, \
    reference_typed  # noqa: E402
from cellbench.readers import typed_roofline  # noqa: E402
from cellbench.traffic import blocking_typed  # noqa: E402
from ompi_tpu.coll import device as coll_device  # noqa: E402
from ompi_tpu.datatype import convertor, engine as dtmod  # noqa: E402
from ompi_tpu.mca.params import registry  # noqa: E402
from ompi_tpu.obs import integrity as ig  # noqa: E402
from ompi_tpu.op import op as mpi_op  # noqa: E402
from ompi_tpu.runtime import x64 as x64mod  # noqa: E402
from ompi_tpu.testing import run_ranks  # noqa: E402

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

SEED = 3000000029            # the driver's seeds pass 2**31
CELL = "rsb-max-f64-vector-16MiB.hbm8"
VECTOR = {"count": 512, "blocklength": 1, "stride": 2}
# how the ranks sit: 8 on one device (coll/hbm), 4 on 4 (coll/tpu)
LAYOUTS = {"hbm": (8, lambda r: jax.devices()[0]), "tpu": (4, None)}
# a float64 SUM of P values rounds P - 1 times; against the block's
# largest answer
SUM_TOLERANCE = 8 * 2.0 ** -52
BITS = np.dtype(np.uint64)
CARRIERS = {"float64": np.dtype(np.float64), "bits": BITS}


@pytest.fixture(scope="module", autouse=True)
def x64():
    """mpi_device_x64 for this file: run_ranks applies it where a
    device world first touches JAX; the finaliser puts the process back
    into the 32-bit world the other files expect."""
    registry.set("mpi_device_x64", 1)
    yield
    registry.set("mpi_device_x64", 0)
    jax.config.update("jax_enable_x64", False)


def ranks(layout, fn):
    n, device_map = LAYOUTS[layout]
    return n, run_ranks(n, fn, devices=True, device_map=device_map)


def pvar(name):
    return next(int(p.read()) for p in registry.all_pvars()
                if p.full_name == name)


def buffer_of(comm, vector, seed=SEED, carrier=np.dtype(np.float64)):
    """The rank's buffer, made as the generator makes it."""
    return blocking_typed.make_input(jax, jnp, comm, seed, vector, None,
                                     carrier)


def vec_type(vector, base=dtmod.DOUBLE):
    return dtmod.vector(vector["count"], vector["blocklength"],
                        vector["stride"], base).commit()


# -- the library against the typed reference -----------------------------------

@pytest.mark.parametrize("carrier", ["float64", "bits"])
@pytest.mark.parametrize("layout", ["hbm", "tpu"])
@pytest.mark.parametrize("reduce", ["MPI_MAX", "MPI_MIN", "MPI_SUM"])
@pytest.mark.parametrize("op", ["reduce_scatter_block", "allreduce"])
def test_typed_collective_equals_the_reference(layout, reduce, op, carrier):
    red = getattr(mpi_op, reduce.replace("MPI_", ""))
    carrier = CARRIERS[carrier]
    # MPI_DOUBLE as bit patterns: MAX and MIN compare on the device, a
    # SUM is arithmetic and the host's (in binary64)
    on_device = carrier != BITS or reference_typed.exact(reduce)

    def fn(comm):
        assert comm.coll.providers[blocking_typed.OP_VTABLE[op]] == layout
        x = buffer_of(comm, VECTOR, carrier=carrier)
        assert x.dtype == carrier
        entry = getattr(comm, blocking_typed.OP_ENTRY[op])
        comm.Barrier()
        before = pvar("coll_typed_device_ops"), pvar("coll_typed_host_packs")
        comm.Barrier()
        out = entry(x, red, vec_type(VECTOR), 1)
        comm.Barrier()
        assert comm.device in out.devices()
        return np.asarray(out), (pvar("coll_typed_device_ops") - before[0],
                                 pvar("coll_typed_host_packs") - before[1])

    n, res = ranks(layout, fn)
    m = VECTOR["count"] // n if op == "reduce_scatter_block" \
        else VECTOR["count"]
    for r, (got, moved) in enumerate(res):
        assert moved == ((n, 0) if on_device else (0, n))
        assert got.dtype == carrier and got.shape == (m,)
        ref = reference_typed.expected(op, reduce, SEED, n, VECTOR, r, 0, m)
        g = reference_typed.gap(reduce, got, ref)
        if reference_typed.exact(reduce):
            assert g == 0.0 and got.tobytes() == ref.tobytes()
        else:
            assert g <= SUM_TOLERANCE


def _types():
    """(name, datatype, count, buffer elements): shapes the vector
    reference does not know, held to the host convertor instead."""
    return {
        "indexed": (dtmod.indexed([3, 1, 4, 8], [40, 2, 9, 20],
                                  dtmod.DOUBLE).commit(), 1, 64),
        "contiguous": (dtmod.contiguous(32, dtmod.DOUBLE).commit(), 1, 40),
        # 2 elements of vector(4, 2, 5): the second starts right behind
        # the first's last block (the extent ends with the tail block,
        # not with a whole stride)
        "tail": (dtmod.vector(4, 2, 5, dtmod.DOUBLE).commit(), 2, 40),
        # None: as many elements of the type as the buffer holds
        "whole_buffer": (dtmod.vector(4, 1, 2, dtmod.DOUBLE).commit(),
                         None, 28),
    }


@pytest.mark.parametrize("layout", ["hbm", "tpu"])
@pytest.mark.parametrize("shape", ["indexed", "contiguous", "tail",
                                   "whole_buffer"])
def test_typed_call_equals_host_pack_then_untyped_call(layout, shape):
    dt, count, elems = _types()[shape]

    def fn(comm):
        host = np.random.default_rng([SEED, comm.rank]).standard_normal(
            elems)
        x = jax.device_put(host, comm.device)
        n = count if count is not None else elems * 8 // dt.extent
        packed = np.frombuffer(convertor.pack(dt, n, host), np.float64)
        assert packed.size % comm.size == 0
        typed = (comm.allreduce_arr(x, mpi_op.MAX, dt, count),
                 comm.reduce_scatter_arr(x, mpi_op.SUM, datatype=dt,
                                         count=count))
        px = jax.device_put(packed, comm.device)
        plain = (comm.allreduce_arr(px, mpi_op.MAX),
                 comm.reduce_scatter_arr(px, mpi_op.SUM))
        return [np.asarray(a) for a in typed + plain]

    _, res = ranks(layout, fn)
    for t_all, t_rs, p_all, p_rs in res:
        assert t_all.dtype == np.float64
        assert t_all.tobytes() == p_all.tobytes()
        assert t_rs.tobytes() == p_rs.tobytes()


# -- one rendezvous, one program, the counters ---------------------------------

def test_a_typed_call_is_one_rendezvous_and_one_program():
    calls = 3
    launches = []

    def fn(comm):
        x = buffer_of(comm, VECTOR)
        vec = vec_type(VECTOR)
        rv = coll_device._get_rendezvous(comm)
        jax.block_until_ready(comm.reduce_scatter_arr(x, mpi_op.MAX, vec, 1))
        # the comm's resolved plan: one entry, keyed by the datatype
        (key, plan), = comm.__dict__["_hbm_plans"].items()
        assert isinstance(key[-1], coll_device._dtdev.Typed)

        def counted(shards, _plan=plan):
            launches.append(comm.rank)
            return _plan(shards)

        comm.__dict__["_hbm_plans"][key] = counted
        comm.Barrier()
        builds, gen = coll_device.compile_cache.builds, rv.gen
        before = {n: pvar(n) for n in ("coll_typed_device_ops",
                                       "coll_typed_folded_first",
                                       "coll_typed_host_packs",
                                       "coll_arr_host_staged_collectives")}
        comm.Barrier()
        for _ in range(calls):
            jax.block_until_ready(
                comm.reduce_scatter_arr(x, mpi_op.MAX, vec, 1))
        comm.Barrier()
        return (coll_device.compile_cache.builds - builds,
                rv.gen - gen - 2,       # less the two barriers
                {n: pvar(n) - v for n, v in before.items()})

    n, res = ranks("hbm", fn)
    assert len(launches) == calls            # one publisher a call
    for builds, meetings, moved in res:
        assert builds == 0 and meetings == calls
        # a MAX through the cell's vector folds first and packs once
        # (ISSUE 36): still one meeting and one program
        assert moved == {"coll_typed_device_ops": calls * n,
                         "coll_typed_folded_first": calls * n,
                         "coll_typed_host_packs": 0,
                         "coll_arr_host_staged_collectives": 0}


def test_the_pack_is_inside_the_named_program():
    typed = coll_device._dtdev.typed_operand(
        vec_type(VECTOR), 1,
        jax.ShapeDtypeStruct((reference_typed.span_elems(VECTOR),),
                             jnp.float64))
    jax.config.update("jax_enable_x64", True)
    jbody, _ = coll_device.HbmCollModule._build_stacked(
        "reduce_scatter", "MPI_MAX", typed)
    x = jnp.zeros(reference_typed.span_elems(VECTOR), jnp.float64)
    low = jbody.lower(*[x] * 8)
    assert "ompi_typed_reduce_scatter" in low.as_text()
    # the cell's layout is one regular run: a strided slice, no gather
    # and no index vector, in the program or on the host
    assert typed.sliced and typed.layout == ((0, VECTOR["count"], 1, 2),)
    assert "gather" not in low.as_text()
    assert "gather" not in low.compile().as_text()
    assert typed._idx is None
    # equal layouts described twice are one program
    again = coll_device._dtdev.typed_operand(vec_type(VECTOR), 1, x)
    assert again == typed and hash(again) == hash(typed)
    # a layout with no such form keeps the gather, inside the same program
    irregular = coll_device._dtdev.typed_operand(IRREGULAR(), 1, x)
    jbody, _ = coll_device.HbmCollModule._build_stacked(
        "reduce_scatter", "MPI_MAX", irregular)
    low = jbody.lower(*[x] * 8)
    assert not irregular.sliced
    assert "ompi_typed_reduce_scatter" in low.as_text()
    assert "gather" in low.compile().as_text()


def IRREGULAR(base=dtmod.DOUBLE):
    """8 blocks of unequal lengths at uneven displacements, 32 elements:
    more runs than a concatenation of slices takes."""
    return dtmod.indexed([3, 1, 4, 8, 2, 5, 6, 3],
                         [40, 2, 9, 20, 60, 70, 80, 90], base).commit()


def same_elements_as(vector, base=dtmod.DOUBLE):
    """The vector's elements, described by three other datatypes."""
    n, stride = vector["count"], vector["stride"]
    assert vector["blocklength"] == 1
    return {
        "vector": vec_type(vector, base),
        "hvector": dtmod.hvector(n, 1, stride * base.size, base).commit(),
        # column 0 of an (n, stride) array
        "subarray": dtmod.subarray([n, stride], [n, 1], [0, 0],
                                   dtmod.ORDER_C, base).commit(),
        "indexed": dtmod.indexed([1] * n, range(0, n * stride, stride),
                                 base).commit(),
    }


@pytest.mark.parametrize("layout", ["hbm", "tpu"])
def test_the_sliced_counter_moves_once_a_rank_call(layout):
    """coll_typed_sliced_packs: one a rank-call on both providers'
    typed paths when the call's layout packs as slices (the cell's
    vector), none for an irregular indexed, which still counts as a
    typed device operation."""
    names = ("coll_typed_device_ops", "coll_typed_sliced_packs",
             "coll_typed_host_packs")

    def fn(comm):
        x = buffer_of(comm, VECTOR)
        moved = []
        for dt in (vec_type(VECTOR), IRREGULAR()):
            comm.Barrier()
            before = [pvar(n) for n in names]
            comm.Barrier()
            jax.block_until_ready(
                comm.reduce_scatter_arr(x, mpi_op.MAX, dt, 1))
            jax.block_until_ready(comm.allreduce_arr(x, mpi_op.MIN, dt, 1))
            comm.Barrier()
            moved.append([pvar(n) - b for n, b in zip(names, before)])
        return moved

    n, res = ranks(layout, fn)
    for sliced, gathered in res:
        assert sliced == [2 * n, 2 * n, 0]
        assert gathered == [2 * n, 0, 0]


@pytest.mark.parametrize("layout", ["hbm", "tpu"])
def test_equal_layouts_share_one_program_and_different_ones_do_not(layout):
    """What keys a typed program is the layout, not the datatype
    object: a vector, an hvector, a subarray column and an indexed that
    address the same elements compile once; another regular layout of
    as many elements compiles its own."""
    other = {"count": VECTOR["count"], "blocklength": 1, "stride": 3}
    assert other["stride"] != VECTOR["stride"]

    def fn(comm):
        # long enough for either layout
        x = buffer_of(comm, other)
        cache = coll_device.compile_cache
        answers, builds = {}, {}
        for name, dt in list(same_elements_as(VECTOR).items()) + [
                ("other", vec_type(other))]:
            comm.Barrier()
            before = cache.builds
            comm.Barrier()
            answers[name] = np.asarray(
                comm.reduce_scatter_arr(x, mpi_op.MAX, dt, 1))
            comm.Barrier()
            builds[name] = cache.builds - before
        return answers, builds

    _, res = ranks(layout, fn)
    for answers, builds in res:
        # rank-threads that miss together each build (CompiledLRU runs
        # builders outside its lock), so a new layout is one or more
        assert builds.pop("vector") >= 1 and builds.pop("other") >= 1
        assert builds == {"hvector": 0, "subarray": 0, "indexed": 0}
        for name in ("hvector", "subarray", "indexed"):
            assert answers[name].tobytes() == answers["vector"].tobytes()
        assert answers["other"].tobytes() != answers["vector"].tobytes()


def test_a_datatype_the_device_cannot_pack_is_served_by_the_host():
    # two doubles, the second 12 bytes in: no whole-element displacement
    dt = dtmod.struct([1, 1], [0, 12], [dtmod.DOUBLE, dtmod.DOUBLE]).commit()
    assert not coll_device._dtdev.is_device_packable(dt, 1)

    def fn(comm):
        host = np.arange(4, dtype=np.float64) * (comm.rank + 1) + 0.25
        comm.Barrier()
        before = {n: pvar(n) for n in ("coll_typed_device_ops",
                                       "coll_typed_host_packs",
                                       "coll_arr_host_staged_collectives")}
        comm.Barrier()
        out = comm.allreduce_arr(jax.device_put(host, comm.device),
                                 mpi_op.MAX, dt, 1)
        comm.Barrier()
        moved = {n: pvar(n) - v for n, v in before.items()}
        return np.asarray(out), np.frombuffer(
            convertor.pack(dt, 1, host), np.float64), moved

    n, res = ranks("hbm", fn)
    want = np.max([packed for _, packed, _ in res], axis=0)
    for out, _, moved in res:
        assert out.dtype == np.float64 and np.array_equal(out, want)
        assert moved["coll_typed_device_ops"] == 0
        assert moved["coll_typed_host_packs"] == n
        assert moved["coll_arr_host_staged_collectives"] == n


def test_a_struct_of_two_element_types_is_a_type_error():
    from ompi_tpu import errhandler
    dt = dtmod.struct([1, 1], [0, 8], [dtmod.DOUBLE, dtmod.INT64_T]).commit()

    def fn(comm):
        with pytest.raises(errhandler.MPIException) as e:
            comm.allreduce_arr(jnp.zeros(2, jnp.float64), mpi_op.MAX, dt, 1)
        return e.value.code

    _, res = ranks("hbm", fn)
    assert set(res) == {errhandler.ERR_TYPE}


def test_the_coll_span_of_a_typed_call_names_its_datatype():
    def fn(comm):
        from ompi_tpu import trace
        comm.state.tracer = tr = trace.Tracer(comm.rank, 256)
        x = buffer_of(comm, VECTOR)
        comm.reduce_scatter_arr(x, mpi_op.MAX, vec_type(VECTOR), 1)
        comm.reduce_scatter_arr(
            jnp.zeros(8 * comm.size, jnp.float64), mpi_op.MAX)
        comm.state.tracer = None
        return [e["args"] for e in tr.snapshot()
                if e["name"] == "reduce_scatter_block_arr"]

    _, res = ranks("hbm", fn)
    for typed, plain in res:
        assert typed["datatype"] == "VECTOR" and typed["count"] == 1
        assert typed["packed_bytes"] == VECTOR["count"] * 8
        assert set(plain) == {"cid", "seq"}


# -- what is NOT correct -------------------------------------------------------

def _answers(wrong: str, ranks_: int = 8, rank: int = 3):
    m = VECTOR["count"] // ranks_
    ref = reference_typed.expected("reduce_scatter_block", "MPI_MAX", SEED,
                                   ranks_, VECTOR, rank, 0, m)
    streams = [reference_typed.values_at(
        SEED, s, np.arange(reference_typed.span_elems(VECTOR)))
        for s in range(ranks_)]
    if wrong == "through_float32":
        with np.errstate(over="ignore"):
            got = np.max([s[::2].astype(np.float32) for s in streams],
                         axis=0)[rank * m:(rank + 1) * m].astype(np.float64)
    elif wrong == "odd_indexed":
        got = np.max([s[1::2] for s in streams],
                     axis=0)[rank * m:(rank + 1) * m]
    elif wrong == "own_block":
        got = streams[rank][::2][rank * m:(rank + 1) * m]
    elif wrong == "other_ranks_block":
        got = reference_typed.expected(
            "reduce_scatter_block", "MPI_MAX", SEED, ranks_, VECTOR,
            rank + 1, 0, m)
    else:
        got = np.max([s[::2] for s in streams],
                     axis=0)[rank * m:(rank + 1) * m]
    return got, ref


@pytest.mark.parametrize("wrong", ["through_float32", "odd_indexed",
                                   "own_block", "other_ranks_block"])
def test_a_wrong_answer_is_not_correct(wrong):
    got, ref = _answers(wrong)
    assert reference_typed.gap("MPI_MAX", got, ref) > 0.0
    sound, _ = _answers("sound")
    assert reference_typed.gap("MPI_MAX", sound, ref) == 0.0
    assert reference_typed.gap("MPI_MAX", sound[:-1], ref) == float("inf")
    nan = sound.copy()
    nan[0] = np.nan
    assert reference_typed.gap("MPI_MAX", nan, ref) == float("inf")


@pytest.mark.parametrize("carrier", [np.dtype(np.float64), BITS],
                         ids=["float64", "bits"])
def test_bf16_control_is_not_correct(carrier):
    def fn(comm):
        x = blocking_typed.make_input(jax, jnp, comm, SEED, VECTOR, "bf16",
                                      carrier)
        assert x.dtype == carrier          # MPI_DOUBLE, values rounded
        return np.asarray(comm.reduce_scatter_arr(
            x, mpi_op.MAX, vec_type(VECTOR), 1))

    n, res = ranks("hbm", fn)
    m = VECTOR["count"] // n
    for r, got in enumerate(res):
        ref = reference_typed.expected("reduce_scatter_block", "MPI_MAX",
                                       SEED, n, VECTOR, r, 0, m)
        # most of the stream lies beyond bfloat16's 8 exponent bits
        assert reference_typed.gap("MPI_MAX", got, ref) == float("inf")
        vals = reference_typed.as_doubles(got)
        assert (vals != ref).mean() > 0.99


# -- a device whose float64 is not binary64 (a TPU v5e) -------------------------

@pytest.fixture
def no_binary64(monkeypatch):
    """What runtime/x64.native() finds on a v5e, said of this CPU."""
    monkeypatch.setattr(x64mod, "_native", False)


def test_the_probe_tells_binary64_from_two_float32_words(monkeypatch, capsys):
    assert x64mod.native() is True          # this CPU's float64
    monkeypatch.setattr(x64mod, "_native", None)

    def two_float32_words(x, device=None):
        with np.errstate(over="ignore", invalid="ignore"):
            hi = x.astype(np.float32)
            lo = (x - hi.astype(np.float64)).astype(np.float32)
            return hi.astype(np.float64) + lo.astype(np.float64)

    monkeypatch.setattr(jax, "device_put", two_float32_words)
    assert x64mod.native() is False
    # each probe value is lost in its own way
    back = two_float32_words(x64mod._PROBE)
    assert back[0] != x64mod._PROBE[0] and back[1] == 0.0 \
        and not np.isfinite(back[2])
    x64mod.apply()                          # the job is told, once a process
    assert "not IEEE binary64" in capsys.readouterr().err


def test_a_device_without_binary64_refuses_float64_and_carries_bits(
        no_binary64):
    from ompi_tpu import errhandler
    vec = vec_type(VECTOR)
    assert blocking_typed.carrier_of({"name": "c", "dtype": "float64"}) \
        == BITS

    def fn(comm):
        idx = np.arange(reference_typed.span_elems(VECTOR))
        host = reference_typed.values_at(SEED, comm.rank, idx)
        refused = []
        for buf, dt in ((host, vec),                        # would be rounded
                        (buffer_of(comm, VECTOR), vec),     # already was
                        (host, None)):                      # untyped
            with pytest.raises(errhandler.MPIException) as e:
                comm.reduce_scatter_arr(buf, mpi_op.MAX, dt, 1) if dt \
                    else comm.reduce_scatter_arr(buf, mpi_op.MAX)
            refused.append((e.value.code, "binary64" in str(e.value)))
        with pytest.raises(errhandler.MPIException):
            comm.send_arr(host, (comm.rank + 1) % comm.size)
        comm.Barrier()
        before = pvar("coll_typed_device_ops")
        comm.Barrier()
        out = comm.reduce_scatter_arr(x64mod.bits(host), mpi_op.MAX, vec, 1)
        comm.Barrier()
        assert comm.device in out.devices()
        return refused, np.asarray(out), pvar("coll_typed_device_ops") - before

    n, res = ranks("hbm", fn)
    m = VECTOR["count"] // n
    for r, (refused, got, served) in enumerate(res):
        assert refused == [(errhandler.ERR_TYPE, True)] * 3 and served == n
        ref = reference_typed.expected("reduce_scatter_block", "MPI_MAX",
                                       SEED, n, VECTOR, r, 0, m)
        assert got.dtype == BITS and got.tobytes() == ref.tobytes()


def test_order_key_is_ieee_total_order():
    """The integer compare the bit-pattern carrier rests on: every
    class of binary64 value, in order, and back bit for bit."""
    from ompi_tpu.datatype.device import from_order_key, order_key
    tiny = np.nextafter(0.0, 1.0)
    vals = np.array([-np.inf, -1e308, -1.0 - 2.0 ** -52, -1.0, -1e-308,
                     -tiny, -0.0, 0.0, tiny, 1e-308, 1.0, 1.0 + 2.0 ** -52,
                     1e308, np.inf])
    b = jnp.asarray(vals.view(np.uint64))
    k = np.asarray(order_key(b))
    assert (np.diff(k.astype(object)) > 0).all()
    assert np.asarray(from_order_key(order_key(b))).tobytes() \
        == vals.tobytes()


def drive(fault=None, control=None, said=None):
    """blocking_typed.run(), minus the harness's look for a chip, on
    eight thread-ranks of this process that share one device."""
    spec = copy.deepcopy(manifest.cell(CELL, REPO))

    def body(comm):
        opts = types.SimpleNamespace(
            seed=SEED, seconds=0.3, trace=0, tiny=True, control=control,
            t0_epoch=time.time(), rank_main_epoch=time.time(),
            say=(said.append if said is not None else lambda msg: None),
            peaks=None, out_dir=None, describe_trace=None,
            xla={"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0})
        return blocking_typed.run(comm, spec, opts, entry_wrap=fault)

    return run_ranks(spec["config"]["ranks"], body, devices=True,
                     device_map=lambda r: jax.devices()[0], timeout=240)[0]


def low_bit_flipped(comm, call):
    def flipped(x):
        out = call(x)
        return out.at[5].set(out[5] ^ jnp.uint64(1))
    return flipped


def through_the_devices_float64(comm, call):
    """What the parent's way did on a v5e: the doubles as two float32
    words."""
    def detour(x):
        v = np.asarray(call(x)).view(np.float64)
        with np.errstate(over="ignore", invalid="ignore"):
            hi = v.astype(np.float32)
            lo = (v - hi.astype(np.float64)).astype(np.float32)
            v = hi.astype(np.float64) + lo.astype(np.float64)
        return jax.device_put(v.view(np.uint64), comm.device)
    return detour


@pytest.mark.parametrize("fault", [None, low_bit_flipped,
                                   through_the_devices_float64],
                         ids=lambda f: getattr(f, "__name__", "sound"))
def test_the_cell_on_a_device_without_binary64(no_binary64, fault):
    said = []
    r = drive(fault, said=said)
    assert any("carried as uint64" in line for line in said)
    chk = r["checks"]
    assert chk["wrong_dtype"]["value"] == 0 and r["failed"] == 0
    assert chk["typed_device_ops"]["value"] == r["attempted"]
    if fault is None:
        assert r["correct"] is True and chk["gap"]["value"] == 0.0, chk
    else:
        assert r["correct"] is False and chk["gap"]["value"] > 0.0, chk


def test_the_control_on_a_device_without_binary64(no_binary64):
    r = drive(control="bf16")
    assert r["correct"] is False and r["checks"]["gap"]["value"] > 0.0


# -- the integrity plane reads a typed call's packed stream --------------------

@pytest.mark.parametrize("layout", ["hbm", "tpu"])
@pytest.mark.parametrize("carrier", [np.dtype(np.float64), BITS],
                         ids=["float64", "bits"])
def test_integrity_plane_covers_a_typed_call(layout, carrier):
    arm = {"integrity_enable": 1, "integrity_sample": 1,
           "integrity_sample_auto": 0}
    saved = {k: registry.get(k) for k in arm}
    for k, v in arm.items():
        registry.set(k, v)
    ig.refresh()
    names = ("integrity_checks", "integrity_mismatches")
    try:
        def fn(comm):
            x = buffer_of(comm, VECTOR, carrier=carrier)
            comm.Barrier()
            before = [pvar(n) for n in names]
            comm.Barrier()
            out = [comm.reduce_scatter_arr(x, mpi_op.MAX, vec_type(VECTOR),
                                           1),
                   comm.allreduce_arr(x, mpi_op.MIN, vec_type(VECTOR), 1)]
            comm.Barrier()
            return [np.asarray(o) for o in out], \
                [pvar(n) - b for n, b in zip(names, before)]

        n, res = ranks(layout, fn)
    finally:
        for k, v in saved.items():
            registry.set(k, v)
        ig.refresh()
    m = VECTOR["count"] // n
    for r, ((rs, ar), (checks, mismatches)) in enumerate(res):
        assert checks == 2 * n and mismatches == 0
        assert rs.tobytes() == reference_typed.expected(
            "reduce_scatter_block", "MPI_MAX", SEED, n, VECTOR, r, 0,
            m).tobytes()
        assert ar.tobytes() == reference_typed.expected(
            "allreduce", "MPI_MIN", SEED, n, VECTOR, r, 0,
            VECTOR["count"]).tobytes()


@pytest.mark.parametrize("carrier", [np.dtype(np.float64), BITS],
                         ids=["float64", "bits"])
def test_integrity_digest_is_of_the_packed_stream(carrier):
    idx = np.arange(reference_typed.span_elems(VECTOR))
    streams = [reference_typed.values_at(SEED, r, idx) for r in range(4)]
    x = streams[0].view(carrier)
    typed = coll_device._dtdev.typed_operand(vec_type(VECTOR), 1, x)
    ig.set_armed(True)
    try:
        ck = ig.spec_typed("allreduce", "MPI_MAX", typed)
    finally:
        ig.set_armed(False)
    assert ck[:3] == ("allreduce", ig.F_MAX, 8) and ck[4] is typed
    shards = [types.SimpleNamespace(
        v=s.view(carrier), d=ig._digest_for(ck, s.view(carrier)))
        for s in streams]
    # the claim is the extremum of what the datatype picks, as doubles:
    # not of the skipped elements, not of the bit patterns as integers
    assert [s.d for s in shards] == [float(s[::2].max()) for s in streams]
    good = np.max([s[::2] for s in streams], axis=0)
    assert ig._verify(ck, shards, [good.view(carrier)])
    bad = good.copy()
    bad[int(np.argmax(good))] = np.nextafter(good.max(), np.inf)
    assert not ig._verify(ck, shards, [bad.view(carrier)])
    assert ig._bisect(ck, shards) == -1
    shards[2].v = (streams[2] * 2).view(carrier)
    assert ig._bisect(ck, shards) == 2


# -- the reference, the bytes, the reader --------------------------------------

@pytest.mark.parametrize("carrier", [np.dtype(np.float64), BITS],
                         ids=["float64", "bits"])
def test_stream_is_the_same_bits_on_device_and_host(carrier):
    def fn(comm):
        return np.asarray(buffer_of(comm, VECTOR, carrier=carrier))

    _, res = ranks("tpu", fn)
    idx = np.arange(reference_typed.span_elems(VECTOR))
    for r, dev in enumerate(res):
        host = reference_typed.values_at(SEED, r, idx)
        assert dev.dtype == carrier and dev.tobytes() == host.tobytes()
    assert res[0].tobytes() != res[1].tobytes()


def test_stream_is_binary64s_own():
    """53 significant bits and binary64's exponent range: nothing a
    narrower format holds (reference_typed's docstring)."""
    n = 1 << 16
    v = reference_typed.values_at(SEED, 0, np.arange(n))
    w = reference_typed.values_at(SEED, 5, np.arange(n))
    assert np.isfinite(v).all() and (v != 0).all() \
        and 0.45 < (v < 0).mean() < 0.55
    bits = v.view(np.uint64)
    for b in range(52):         # every fraction bit, in half the values
        share = float(((bits >> np.uint64(b)) & np.uint64(1)).mean())
        assert 0.45 < share < 0.55, (b, share)
    expo = (bits >> np.uint64(52)) & np.uint64(0x7FF)
    assert expo.min() >= 1 and expo.max() <= 2042 \
        and expo.min() < 8 and expo.max() > 2034
    # a SUM over 8 ranks cannot overflow
    assert np.isfinite(8 * np.abs(v).max())
    # float32, and a v5e's two-float32 float64, lose nearly all of it
    with np.errstate(over="ignore", invalid="ignore"):
        hi = v.astype(np.float32)
        lo = (v - hi.astype(np.float64)).astype(np.float32)
        pair = hi.astype(np.float64) + lo.astype(np.float64)
    assert (pair != v).mean() > 0.85
    # a quarter of the indices: every rank holds the same high word
    # and its own low word, so the low 32 bits alone decide
    same_hi = (bits >> np.uint64(32)) == (w.view(np.uint64) >> np.uint64(32))
    assert 0.24 < same_hi.mean() < 0.26 and (v != w).all()
    # a MAX that compares high words only (the first of a tie wins) is
    # wrong there, on either side of zero
    streams = np.stack([reference_typed.values_at(SEED, r, np.arange(n))
                        for r in range(8)])
    ref = streams.max(axis=0)
    blind = (streams.view(np.uint64) & ~np.uint64(0xFFFFFFFF)).view(
        np.float64)
    got = streams[blind.argmax(axis=0), np.arange(n)]
    bad = got != ref
    assert bad[same_hi].mean() > 0.5 and not bad[~same_hi].any()
    assert (ref[bad] < 0).any() and (ref[bad] > 0).any()
    assert reference_typed.gap("MPI_MAX", got, ref) > 0.0


def test_reference_is_the_max_of_the_even_elements():
    ranks_, m = 8, VECTOR["count"] // 8
    streams = [reference_typed.values_at(
        SEED, s, np.arange(reference_typed.span_elems(VECTOR)))
        for s in range(ranks_)]
    whole = np.max([s[::2] for s in streams], axis=0)
    for r in (0, 5, 7):
        assert np.array_equal(reference_typed.expected(
            "reduce_scatter_block", "MPI_MAX", SEED, ranks_, VECTOR, r, 3,
            m - 1), whole[r * m + 3:(r + 1) * m - 1])
    assert np.array_equal(reference_typed.expected(
        "allreduce", "MPI_MAX", SEED, ranks_, VECTOR, 2, 0,
        VECTOR["count"]), whole)
    wide = {"count": 8, "blocklength": 3, "stride": 5}
    assert reference_typed.packed_index(wide, 0, 7).tolist() \
        == [0, 1, 2, 5, 6, 7, 10]
    assert reference_typed.span_elems(wide) == 38
    with pytest.raises(KeyError):
        reference_typed.expected("alltoall", "MPI_MAX", SEED, 8, VECTOR, 0,
                                 0, 1)


def test_required_bytes_at_the_cells_size():
    spec = manifest.cell(CELL, REPO)
    vector = spec["traffic"]["vector"]
    packed = reference_typed.packed_elems(vector) * 8
    assert packed == spec["traffic"]["bytes_per_rank"] == 16777216
    assert reference_typed.span_elems(vector) * 8 == 33554432 - 8
    need = bytes_typed.required("reduce_scatter_block", 8, packed, 1)
    assert need == {"hbm": 150994944, "ici": 0}
    peaks = manifest.load_json(os.path.join(REPO, "cellbench",
                                            "peaks.json"))["TPU v5 lite"]
    least, bound = bytes_typed.least_seconds("reduce_scatter_block", 8,
                                             packed, 1, peaks)
    assert bound == "hbm" and round(least * 1e6, 1) == 184.4
    with pytest.raises(KeyError):
        bytes_typed.required("reduce_scatter_block", 4, packed, 4)
    with pytest.raises(KeyError):
        bytes_typed.required("alltoall", 8, packed, 1)


def test_typed_roofline_reader():
    peaks = manifest.load_json(os.path.join(REPO, "cellbench",
                                            "peaks.json"))["TPU v5 lite"]
    facts = {"op": "reduce_scatter_block", "ranks": 8, "chips": 1,
             "bytes_per_rank": 16777216, "platform": "tpu", "peaks": peaks,
             "trace": {"kernel_events_matched": True,
                       "kernel_s_per_iter": 0.2427}}
    said = []
    share = typed_roofline.read({}, facts, said.append)
    assert round(share, 4) == round(100 * 150994944 / 819e9 / 0.2427, 4)
    assert 0.07 < share < 0.08 and "184.3" in said[0]
    # a program that is not there (the parent's library) gives nothing
    facts["trace"]["kernel_events_matched"] = False
    assert typed_roofline.read({}, facts, said.append) is None
    assert typed_roofline.read({}, {**facts, "trace": {}},
                               said.append) is None
    assert typed_roofline.read({}, {**facts, "platform": "cpu"},
                               said.append) is None


@pytest.mark.parametrize("name,pvar_name", [
    ("typed_folded_per_iter", "coll_typed_folded_first"),
    ("typed_sliced_per_iter", "coll_typed_sliced_packs")])
def test_typed_counter_metrics_read_through_pvar_sum(name, pvar_name):
    """A data-only metric: the counter's delta over the window per
    rank-iteration; 1.0 where it moved once a rank-call, nothing (never
    0) on a program that has no such variable (the parent's)."""
    from cellbench.readers import pvar_sum
    spec = manifest.metric_spec(name)
    assert spec["reader"] == "pvar_sum" and spec["pvars"] == [pvar_name]
    assert spec["per"] == "rank_iteration" and spec["moves"] == "iter_us"
    assert spec["workloads"] == [CELL]
    entry = next(m for m in manifest.manifest(REPO)["per_layer"]
                 if m["name"] == name)
    for k in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert spec[k] == entry[k], k
    assert name in {m["name"] for m in manifest.cell(CELL, REPO)["per_layer"]}
    facts = {"iters": 499, "ranks": 8,
             "pvars_before": {pvar_name: 24, "coll_typed_device_ops": 24},
             "pvars_after": {pvar_name: 24 + 499 * 8,
                             "coll_typed_device_ops": 24 + 499 * 8}}
    said = []
    assert pvar_sum.read(spec, facts, said.append) == 1.0
    assert pvar_name in said[0]
    older = {k: {"coll_typed_device_ops": v["coll_typed_device_ops"]}
             for k, v in facts.items() if k.startswith("pvars_")}
    assert pvar_sum.read(spec, {**facts, **older}, said.append) is None
    # a library that has the variable and served pack-first reads 0.0
    still = {**facts, "pvars_after": {**facts["pvars_after"],
                                      pvar_name: 24}}
    assert pvar_sum.read(spec, still, said.append) == 0.0


def test_the_cell_in_the_manifest():
    spec = manifest.cell(CELL, REPO)
    assert [m["name"] for m in spec["end_to_end"]] == [
        "setup_s", "iter_us", "iter_p95_us"]
    due = {m["name"] for m in spec["per_layer"]}
    # the one share of a roofline (PR 38) is due here too; the five
    # metrics PR 38 took out are due nowhere
    assert {"collective_roofline", "typed_ops_per_iter",
            "typed_folded_per_iter", "kernel_us", "rdv_per_iter",
            "pack_unpack_per_iter_us", "assemble_scatter_us",
            "device_idle_pct"} <= due
    assert not due & {"typed_roofline", "move_roofline",
                      "segments_per_iter", "inflight_segments",
                      "pack_unpack_us"}
    cfg = spec["config"]
    assert cfg["dtype"] == "float64" and cfg["chips"] == 1
    assert cfg["launch"][-3:] == ["--mca", "mpi_device_x64", "1"]
    assert "bit for bit (limit 0)" in cfg["guarantees"]["arithmetic"]
    assert "53-bit significands" in cfg["guarantees"]["arithmetic"]
    assert "significand" not in cfg["assumed"]      # nothing is cut
    assert spec["pairing"]["kernel_events"] == [
        "^jit_ompi_typed_reduce_scatter\\("]


# -- the cell end to end, in the development mode ------------------------------

def _dev_run(*extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "cellbench", "run.py"),
         "--workload", CELL, "--seed", str(SEED), "--seconds", "0.5",
         "--allow-cpu", "--tiny", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_dev_mode_runs_the_cell_and_labels_it():
    res = _dev_run("--trace", "1")
    assert res["correct"] is True and res["failed"] == 0
    assert res["metrics"] == {} and "DEV MODE" in res["dev_mode"]
    dev = res["cpu_rehearsal"]
    assert dev["dev_rdv_per_iter"]["value"] == 1.0
    assert dev["dev_typed_ops_per_iter"]["value"] == 1.0
    assert dev["dev_pack_unpack_per_iter_us"]["value"] == 0
    chk = res["checks"]
    assert chk["gap"] == {"value": 0.0, "limit": 0.0}
    assert chk["typed_device_ops"]["value"] == res["attempted"]
    assert chk["typed_host_packs"]["value"] == 0
    assert chk["wrong_dtype"]["value"] == 0


def test_dev_mode_control_reads_not_correct():
    res = _dev_run("--trace", "0", "--control", "bf16")
    assert res["correct"] is False and res["checks"]["gap"]["value"] > 0
