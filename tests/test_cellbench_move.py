"""The deployment osu-tpu4-move (ISSUE 28): four ranks on four devices
moving data through the large-message tier, held to the benchmark's
plain references on the CPU.

* ``bcast_arr`` through the tier's compiled plans equals
  cellbench/reference_rooted.py bit for bit, for every root and for a
  count that leaves a tail; ``alltoall_arr`` equals
  cellbench/reference.py on every rank; both equal the fused
  single-dispatch path byte for byte;
* every bit pattern is delivered as sent, a call is one rendezvous,
  the segment counter stays at rest, and the programs are named for
  their algorithms and free of arithmetic;
* the lower-precision control (inputs handed over in bfloat16) and an
  answer that is the rank's own input are NOT correct;
* the rooted reference against a two-line numpy statement of itself,
  the required-bytes rules of cellbench/bytes_mesh.py at the cells'
  sizes, the reader of ``move_roofline``, the choice of compared ranks;
* BENCHMARK.json is valid with the six cells.
"""
import functools
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from cellbench import (bytes_mesh, manifest, reference,  # noqa: E402
                       reference_rooted, validate)
from cellbench.readers import mesh_roofline  # noqa: E402
from cellbench.traffic import blocking_rooted  # noqa: E402
from cellbench.traffic.blocking_collective import make_input  # noqa: E402
from ompi_tpu.mca.params import registry  # noqa: E402
from ompi_tpu.testing import run_ranks  # noqa: E402

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

# register the knobs before any snapshot of them
import ompi_tpu.coll.pipeline as pipeline  # noqa: E402
import ompi_tpu.coll.plan as plan  # noqa: E402

P = 4
SEED = 3000000019            # the driver's seeds pass 2**31
SEG_ELEMS = 1024             # coll_seg_size 4096 B of float32
# the default knobs but for the test-sized crossover and segment: the
# compiled plans (coll/plan.mesh_move) serve both operations
PLANNED = {"coll_pipeline_min_bytes": 2048, "coll_seg_size": 4 * SEG_ELEMS}
FUSED = {"coll_pipeline_enable": False, "coll_hier_enable": False}
TIERS = {"planned": PLANNED, "fused": FUSED}
# elements per rank: (bcast, alltoall); the tail case leaves 3 elements
# of a bcast segment and 7 columns of an alltoall's blocks over
COUNTS = {"whole": (4 * SEG_ELEMS, 4 * SEG_ELEMS),
          "tail": (4 * SEG_ELEMS + 3, P * (SEG_ELEMS + 7))}


def knobs_set(vals):
    saved = {k: registry.get(k) for k in vals}
    for k, v in vals.items():
        registry.set(k, v)
    return saved


@functools.lru_cache(maxsize=None)
def answers(tier: str, count: str, control=None):
    """One world of four rank-threads on four devices: every root's
    bcast and one alltoall of the seed's inputs, as host arrays, and
    what the tier's counters moved by; per rank."""
    nb, na = COUNTS[count]

    def body(comm):
        before = [v.read() for v in (pipeline.pv_ops, plan.pv_segments)]
        xb = make_input(jax, jnp, comm, SEED, nb, control)
        xa = make_input(jax, jnp, comm, SEED, na, control)
        out = {("bcast", root): np.asarray(comm.bcast_arr(xb, root))
               for root in range(P)}
        out["alltoall"] = np.asarray(comm.alltoall_arr(xa))
        comm.Barrier()
        out["moved"] = [v.read() - b for v, b in zip(
            (pipeline.pv_ops, plan.pv_segments), before)]
        out["provider"] = comm.coll.providers.get("bcast_arr")
        return out

    saved = knobs_set(TIERS[tier])
    try:
        return run_ranks(P, body, devices=True, timeout=240)
    finally:
        knobs_set(saved)


def bcast_gap(got, rank, root, n):
    ref = reference_rooted.expected("bcast", SEED, P, n, rank, root, 0, n)
    return reference_rooted.gap(got, ref)


def alltoall_gap(got, rank, n):
    ref = reference.expected("alltoall", SEED, P, n, rank, 0, n)
    return reference.gap("alltoall", got, ref)


# -- the library against the references --------------------------------------

@pytest.mark.parametrize("count", ["whole", "tail"])
@pytest.mark.parametrize("root", range(P))
def test_planned_bcast_equals_the_reference_and_the_fused_tier(root, count):
    n = COUNTS[count][0]
    planned, fused = answers("planned", count), answers("fused", count)
    for rank in range(P):
        got = planned[rank]["bcast", root]
        assert got.dtype == np.float32 and got.shape == (n,)
        assert bcast_gap(got, rank, root, n) == 0.0
        assert got.tobytes() == reference.values(SEED, root, 0, n).tobytes()
        assert got.tobytes() == fused[rank]["bcast", root].tobytes()
    assert planned[0]["provider"] == "tpu"
    # the tier counted every call, and a mover adds no segments
    assert planned[0]["moved"] == [P * (P + 1), 0]
    assert fused[0]["moved"] == [0, 0]           # the other did not engage


@pytest.mark.parametrize("count", ["whole", "tail"])
def test_planned_alltoall_equals_the_reference_and_the_fused_tier(count):
    n = COUNTS[count][1]
    planned, fused = answers("planned", count), answers("fused", count)
    for rank in range(P):
        got = planned[rank]["alltoall"]
        assert got.dtype == np.float32 and got.shape == (n,)
        assert alltoall_gap(got, rank, n) == 0.0
        assert got.tobytes() == fused[rank]["alltoall"].tobytes()


@pytest.mark.parametrize("op", ["bcast", "alltoall"])
def test_bf16_control_is_not_correct(op):
    """Inputs rounded to bfloat16 before the library sees them: the
    answer is 2**-9 off somewhere, far above the limit of 0."""
    nb, na = COUNTS["tail"]
    res = answers("planned", "tail", "bf16")
    for rank in range(P):
        if op == "bcast":
            g = min(bcast_gap(res[rank]["bcast", root], rank, root, nb)
                    for root in range(P))
        else:
            g = alltoall_gap(res[rank]["alltoall"], rank, na)
        assert 1e-4 < g < 2.0 ** -8


def test_own_input_handed_back_is_not_correct():
    """Every rank's input is its own stream: handed back as the answer
    it is wrong on every rank but the root, in (nearly) every element;
    so is another non-root's input."""
    n = COUNTS["tail"][0]
    for root in range(P):
        for rank in range(P):
            own = reference.values(SEED, rank, 0, n)
            g = bcast_gap(own, rank, root, n)
            assert (g == 0.0) == (rank == root)
            if rank != root:
                ref = reference_rooted.expected("bcast", SEED, P, n, rank,
                                                root, 0, n)
                assert np.mean(own != ref) > 0.999
    # the generator's own comparison says the same of a kept answer
    def body(comm):
        x = make_input(jax, jnp, comm, SEED, n, None)
        chk = {"block_elems": 65536, "blocks": 64}
        return blocking_rooted.compare(jax, jnp, {0: x}, "bcast", SEED, P, n,
                                       comm.rank, 1, chk)
    res = run_ranks(P, body, devices=True)
    assert [g == 0.0 for g, _ in res] == [False, True, False, False]
    assert all(c == n for _, c in res)


# -- the references and rules themselves ---------------------------------------

@pytest.mark.parametrize("root", [0, 2])
def test_rooted_reference_is_the_roots_stream(root):
    n, lo, hi = 5000, 1234, 4321
    whole = reference.values_from_key(
        np.uint32(reference.stream_key(SEED, root)), 0, n)
    for rank in range(P):
        got = reference_rooted.expected("bcast", SEED, P, n, rank, root,
                                        lo, hi)
        assert got.dtype == np.float32
        assert got.tobytes() == whole[lo:hi].tobytes()
    with pytest.raises(KeyError):
        reference_rooted.expected("scatter", SEED, P, n, 0, root, lo, hi)
    with pytest.raises(ValueError):
        reference_rooted.expected("bcast", SEED, P, n, 0, P, lo, hi)


@pytest.mark.parametrize("op,per_rank,ici,hbm,least_us", [
    ("bcast", 67108864, 67108864, 67108864, 335.54),
    ("alltoall", 4 * 4194304, 12582912, 33554432, 62.91)])
def test_required_bytes_across_chips(op, per_rank, ici, hbm, least_us):
    assert bytes_mesh.required(op, P, per_rank) == {"hbm": hbm, "ici": ici}
    peaks = manifest.load_json(os.path.join(
        REPO, "cellbench", "peaks.json"))["TPU v5 lite"]
    least, bound = bytes_mesh.least_seconds(op, P, per_rank, peaks)
    assert bound == "ici" and least * 1e6 == pytest.approx(least_us, abs=0.01)


def test_no_required_bytes_rule_is_an_error():
    with pytest.raises(KeyError):
        bytes_mesh.required("allreduce", P, 1 << 20)


def test_move_roofline_reader():
    peaks = {"hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 200e9}
    facts = {"op": "bcast", "ranks": P, "bytes_per_rank": 67108864,
             "platform": "tpu", "peaks": peaks,
             "trace": {"kernel_events_matched": True,
                       "kernel_s_per_iter": 0.008}}
    said = []
    v = mesh_roofline.read({"name": "move_roofline"}, facts, said.append)
    assert v == pytest.approx(100 * 335.54432e-6 / 0.008)
    assert "bound by ici" in said[0]
    # nothing to read gives nothing, never a 0
    facts["trace"]["kernel_events_matched"] = False
    assert mesh_roofline.read({}, facts, said.append) is None
    assert mesh_roofline.read({}, dict(facts, trace={}), said.append) is None
    assert mesh_roofline.read({}, dict(facts, platform="cpu"),
                              said.append) is None


@pytest.mark.parametrize("root", range(P))
def test_compared_ranks_hold_two_that_are_not_the_root(root):
    for seed in range(50):
        pick = blocking_rooted.picked_ranks(np.random.default_rng(seed), P,
                                            root, 3)
        assert root in pick and len(pick - {root}) >= 2
        assert (root - 1) % P in pick and pick <= set(range(P))
    assert blocking_rooted.picked_ranks(
        np.random.default_rng(0), 2, root % 2, 3) == {0, 1}


# -- the compiled plans of the two operations (ISSUE 29) ------------------------

def odd_bits(n: int) -> np.ndarray:
    """float32 patterns arithmetic would not keep: both zeros, NaNs
    with payloads (quiet and signalling, either sign), denormals,
    infinities; repeated to n elements."""
    bits = np.array([0x80000000, 0x00000000, 0x7FC12345, 0xFFA00001,
                     0x7F800001, 0x00000001, 0x807FFFFF, 0x7F800000,
                     0xFF800000, 0x3F800000], np.uint32)
    return np.resize(bits, n).view(np.float32)


@pytest.mark.parametrize("root", [0, P - 1])
def test_planned_bcast_delivers_every_bit_pattern(root):
    n = COUNTS["tail"][0]

    def body(comm):
        mine = odd_bits(n) if comm.rank == root else np.full(
            n, comm.rank + 1.0, np.float32)
        out = comm.bcast_arr(jax.device_put(mine, comm.device), root)
        return np.asarray(out).view(np.uint32).tobytes()

    saved = knobs_set(PLANNED)
    try:
        res = run_ranks(P, body, devices=True, timeout=240)
    finally:
        knobs_set(saved)
    assert set(res) == {odd_bits(n).view(np.uint32).tobytes()}


@pytest.mark.parametrize("op", ["bcast", "alltoall"])
def test_a_planned_call_is_one_rendezvous(op):
    """One call is one meeting on every rank, a mover adds nothing to
    the segment counter, and the second call of a shape is served by
    the plan the first resolved."""
    n = COUNTS["tail"][op == "alltoall"]

    def body(comm):
        tr = comm.state.tracer
        x = make_input(jax, jnp, comm, SEED, n, None)
        call = (lambda: comm.bcast_arr(x, 1)) if op == "bcast" else (
            lambda: comm.alltoall_arr(x))

        def counted():
            return [tr.layer_totals()["rendezvous"]] + [v.read() for v in (
                pipeline.pv_ops, plan.pv_segments,
                plan.pv_builds, plan.pv_hits)]

        jax.block_until_ready(call())
        comm.Barrier()
        before = counted()
        comm.Barrier()     # nobody counts before everybody has read
        jax.block_until_ready(call())
        comm.Barrier()
        return [a - b for a, b in zip(counted(), before)]

    saved = knobs_set(dict(PLANNED, trace_enable=True,
                           trace_phase_enable=True, trace_dump_path=""))
    try:
        res = run_ranks(P, body, devices=True, timeout=240)
    finally:
        knobs_set(saved)
    # the rank's own meetings; the process-wide counters, all four ranks'
    assert res == [[1, P, 0, 0, P]] * P


@pytest.mark.parametrize("alg,root", [("segbcast", 1), ("sega2a", None)])
def test_planned_programs_are_named_and_move_data_only(alg, root):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(jax.devices()[:P]), ("r",))
    total = 5 * SEG_ELEMS
    jfn = plan._compile_mesh_move(alg, mesh, P, total, root)
    x = jax.ShapeDtypeStruct((P * total,), jnp.float32,
                             sharding=NamedSharding(mesh,
                                                    PartitionSpec("r")))
    lowered = jfn.lower(x)
    text = lowered.as_text()
    assert f"@jit_ompi_{alg} " in text
    # nothing is added, multiplied or reduced: a select keeps what a
    # rank had, so -0.0 and a NaN's payload arrive as they were sent
    for arithmetic in ("stablehlo.add", "stablehlo.multiply",
                       "stablehlo.all_reduce", "stablehlo.reduce"):
        assert arithmetic not in text
    assert ("stablehlo.all_gather" if alg == "segbcast"
            else "stablehlo.all_to_all") in text
    assert "all-reduce" not in lowered.compile().as_text()


# -- the manifest ---------------------------------------------------------------

def test_manifest_is_valid_with_six_cells():
    assert validate.check(REPO) == []
    man = manifest.manifest(REPO)
    # what the name means, not a count that the next cell breaks: the
    # two cells of PR 28 are defined, in their order, on their
    # configuration, and at most half the cells ask for four chips
    names = [w["name"] for w in man["workloads"]]
    assert len(names) >= 6 and len(man["configs"]) >= 3
    assert names.index("alltoall-4MiB.tpu4") \
        == names.index("bcast-64MiB.tpu4") + 1 == 5
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert 3 <= four <= max(1, len(names) // 2)
    for name in ("bcast-64MiB.tpu4", "alltoall-4MiB.tpu4"):
        entry = man["workloads"][names.index(name)]
        assert (entry["config"], entry["chips"]) == ("osu-tpu4-move", 4)
        spec = manifest.cell(name, REPO)
        assert [m["name"] for m in spec["end_to_end"]] == ["setup_s",
                                                           "iter_us"]
        due = {m["name"] for m in spec["per_layer"]}
        # the one share of a roofline (PR 38), due here as in every
        # collective cell; the five metrics PR 38 took out due nowhere
        assert {"collective_roofline", "kernel_us"} <= due
        assert not due & GONE_SINCE_PR38
        assert spec["config"]["guarantees"]["delivery"].startswith(
            "bit-exact")
    assert not {m["name"] for m in man["per_layer"]} & GONE_SINCE_PR38


GONE_SINCE_PR38 = {"move_roofline", "typed_roofline", "inflight_segments",
                   "segments_per_iter", "pack_unpack_us"}


def test_the_ragged_cell_in_the_manifest():
    """The cell of ISSUE 39: an allreduce off the segment grid on the
    one-chip deployment, data files alone, due exactly the per-layer
    metrics of the 256 MiB cell and the pack's own counter."""
    name = "allreduce-ragged-24000012B.hbm8"
    man = manifest.manifest(REPO)
    entry = next(w for w in man["workloads"] if w["name"] == name)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "osu-hbm8", "allreduce-sum-24000012B", 1)
    spec = manifest.cell(name, REPO)
    # not on iter_p95_us's list: the parent's pooled p95 spread 9.8%
    assert [m["name"] for m in spec["end_to_end"]] == ["setup_s", "iter_us"]
    due = {m["name"] for m in spec["per_layer"]}
    big = {m["name"] for m in manifest.cell("allreduce-256MiB.hbm8",
                                            REPO)["per_layer"]}
    assert due == big | {"pack_unpack_per_iter_us"} and len(due) == 17
    assert {"collective_roofline", "kernel_us", "rdv_per_iter",
            "device_idle_pct", "traced_iter_us"} <= due
    mix, pairing = spec["traffic"], spec["pairing"]
    assert mix["bytes_per_rank"] == 24000012 and mix["bytes_per_rank"] % (
        1 << 20) != 0
    big_spec = manifest.cell("allreduce-256MiB.hbm8", REPO)
    assert {k: v for k, v in mix.items()
            if k not in ("name", "bytes_per_rank")} == {
        k: v for k, v in big_spec["traffic"].items()
        if k not in ("name", "bytes_per_rank")}
    assert (pairing["check"], pairing["kernel_events"]) == (
        big_spec["pairing"]["check"], big_spec["pairing"]["kernel_events"])
