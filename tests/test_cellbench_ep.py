"""The deployment deepep-ds3-ep4: one MoE layer's token exchange of
DeepSeek-V3 at expert-parallel degree 4, dispatch and combine through
``comm.alltoallv_arr`` of token rows, held to the benchmark's plain
reference (cellbench/reference_ep.py) on the CPU; and the queue's cell
iallreduce-x4-4KiB.hbm8 (cellbench/traffic/nonblocking_batch.py).

* the reference's routing holds DeepSeek-V3's properties at a small
  size, and equals a token-by-token statement of the rule;
* the EP generator in this process, on four CPU devices with the mesh
  program's collective emulated (XLA:CPU cannot lower
  ``ragged-all-to-all``): a sound run is correct, traced the layer
  account closes; the control, the answers of the iteration before and
  a path through the host are NOT correct; a library that refuses rows
  is refused at once;
* both cells end to end in the development mode;
* BENCHMARK.json is valid with thirteen cells, five of them on four
  chips, and the two cells are on the lists of the metrics they report.
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

from cellbench import manifest, reference_ep, validate  # noqa: E402
from cellbench.traffic import blocking_ep, nonblocking_batch  # noqa: E402
from ompi_tpu.coll import device as coll_device  # noqa: E402
from ompi_tpu.coll import ragged  # noqa: E402
from ompi_tpu.mca.params import registry  # noqa: E402
from ompi_tpu.testing import run_ranks  # noqa: E402

jax = pytest.importorskip("jax")

SEED = 4300000013            # a seed past 2**31, as benchmark seeds are
EP = "ep-ragged-alltoall.tpu4"
Q = "iallreduce-x4-4KiB.hbm8"
CATALOG_CONFIG = {
    # DeepSeek-V3's config.json, every number of it (the catalog's row)
    "ep_size": 1, "first_k_dense_replace": 3, "hidden_size": 7168,
    "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "topk_group": 4, "v_head_dim": 128,
    "vocab_size": 129280}


def small(tokens=64, ranks=4):
    spec = manifest.cell(EP, REPO)
    st = reference_ep.setting(spec["config"], spec["traffic"])
    return dict(st, tokens=tokens, ranks=ranks)


# -- the reference itself ------------------------------------------------------

@pytest.mark.parametrize("parity", [0, 1])
def test_the_routing_holds_deepseek_v3s_properties(parity):
    """8 distinct experts a token, all inside at most 4 of the 8 groups,
    weights summing to 2.5; a token sent once to every rank that holds
    one of its experts, one row per (token, rank) hit, tokens ascending
    in a rank's block; the two routing sets differ."""
    st = small()
    ex = reference_ep.exchange(SEED, parity, st)
    for i, r in enumerate(ex["routes"]):
        ids = r["ids"]
        assert ids.shape == (64, 8) and ids.dtype == np.int32
        assert all(len(set(t)) == 8 for t in ids.tolist())
        assert (np.array([len(set(t)) for t in (ids // 32).tolist()])
                <= 4).all()
        np.testing.assert_allclose(r["w"].sum(1), 2.5, rtol=1e-6)
        assert (r["w"] > 0).all()
        owners = np.zeros((64, 4), bool)
        for t, row in enumerate(ids.tolist()):
            owners[t, sorted({e // 64 for e in row})] = True
        assert np.array_equal(owners, r["owners"])
        for j, block in enumerate(ex["order"][i]):
            assert block.tolist() == [t for t in range(64) if owners[t, j]]
        assert ex["counts"][i].sum() == owners.sum()
    other = reference_ep.exchange(SEED, parity ^ 1, st)
    assert not np.array_equal(other["counts"], ex["counts"])


def test_the_routing_against_a_token_by_token_statement():
    """The rule of the reference's head, one token at a time in plain
    Python: sigmoid scores, group score the sum of a group's two best,
    the 4 best groups, the 8 best experts in them, weights normalised
    and scaled; ties to the lower index."""
    st = small(tokens=16)
    E, per = st["experts"], st["experts"] // st["groups"]
    off = np.random.default_rng([SEED, 1, 0]).normal(
        0.0, st["sigma"], E).astype(np.float32)
    z = np.random.default_rng([SEED, 1, 1, 2]).standard_normal(
        (16, E), np.float32) + off
    r = reference_ep.route(SEED, 2, 1, st)
    for t in range(16):
        s = [float(1.0 / (1.0 + np.exp(-np.float32(v)))) for v in z[t]]
        gs = [sum(sorted(s[g * per:(g + 1) * per])[-2:])
              for g in range(st["groups"])]
        keep = sorted(range(st["groups"]), key=lambda g: (-gs[g], g))[:4]
        cand = [e for e in range(E) if e // per in keep]
        top = sorted(cand, key=lambda e: (-s[e], e))[:8]
        assert r["ids"][t].tolist() == top
        w = [s[e] / sum(s[e] for e in top) * 2.5 for e in top]
        np.testing.assert_allclose(r["w"][t], w, rtol=1e-5)


def test_the_owed_rows_are_the_sources_rows():
    """What a rank is owed after the dispatch is every source's records
    for it, sources ascending; after the combine, its own send order
    with the stand-in its destination made for each row; the reference
    imports nothing of the library."""
    st = small()
    ex = reference_ep.exchange(SEED, 0, st)
    words = reference_ep.record_words(st)
    assert words == 1864
    got = reference_ep.dispatch_owed(SEED, ex, 1, st, 0, 10 ** 6)
    src, tok = reference_ep.received(ex, 1)
    assert got.shape == (int(ex["counts"][:, 1].sum()), words)
    for k in (0, len(tok) // 2, len(tok) - 1):
        r = ex["routes"][src[k]]
        assert got[k, -16:-8].view(np.int32).tolist() == \
            r["ids"][tok[k]].tolist()
        assert np.array_equal(got[k, -8:].view(np.float32), r["w"][tok[k]])
    back = reference_ep.combine_owed(SEED, ex, 3, st, 5, 25)
    assert back.shape == (20, 7168) and back.dtype == np.uint16
    assert ((back & 0x4000) == 0).all()     # finite bfloat16 everywhere
    assert ((back & 0x7F80) != 0).all()     # and normal: no subnormal
    src = open(os.path.join(REPO, "cellbench", "reference_ep.py")).read()
    assert "ompi_tpu" not in src.split('"""', 2)[2]


# -- the EP generator, in this process -----------------------------------------

def mesh_emulated(monkeypatch):
    """coll/tpu's mesh half on this process's CPU devices with its one
    collective emulated (tests/test_alltoallv_arr.py's)."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from test_alltoallv_arr import emulated
    monkeypatch.setattr(ragged, "NO_LOWERING", ())
    monkeypatch.setattr(ragged, "_exchange", emulated)
    coll_device.compile_cache.clear()


def drive(cell, gen, fault=None, control=None, said=None, trace=0,
          out_dir=None, **world):
    """``gen.run()``, minus the harness's look for a chip, on the
    configuration's thread-ranks of this process."""
    spec = copy.deepcopy(manifest.cell(cell, REPO))

    def body(comm):
        opts = types.SimpleNamespace(
            seed=SEED, seconds=0.3, trace=trace, tiny=True, control=control,
            t0_epoch=time.time(), rank_main_epoch=time.time(),
            say=(said.append if said is not None else lambda msg: None),
            peaks=None, out_dir=out_dir, describe_trace=None,
            xla={"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0})
        return gen.run(comm, spec, opts, entry_wrap=fault)

    return run_ranks(spec["config"]["ranks"], body, timeout=300,
                     **(world or {"devices": True}))[0]


def test_the_ep_cell_is_correct(monkeypatch):
    mesh_emulated(monkeypatch)
    said = []
    r = drive(EP, blocking_ep, said=said)
    chk = r["checks"]
    assert r["correct"] is True and r["failed"] == 0, chk
    assert chk["gap"] == {"value": 0.0, "limit": 0.0}
    assert chk["device_ops"]["value"] == 2 * r["attempted"] > 0
    assert chk["device_bytes"]["value"] == chk["device_bytes"]["equals"]
    assert chk["parities_compared"]["value"] == 2
    assert chk["ranks_compared"]["value"] == 3
    assert all(chk[k]["value"] == 0 for k in (
        "recv_rows_off", "host_staged", "compiled_in_window", "off_device",
        "incomplete", "wrong_provider"))
    assert any("provider=tpu," in line for line in said)
    # on iter_p95_us's list: its pooled p95 spread 0.55% over 6 seeds
    assert {"setup_s", "iter_us", "iter_p95_us"} == set(r["metrics"])


def test_the_traced_ep_cell_closes_the_layer_account(monkeypatch, tmp_path):
    """Traced, the two calls of an iteration are two rendezvous and two
    device programs a rank; the mesh program's traced twin banks
    assemble, launch and scatter, and the account closes."""
    mesh_emulated(monkeypatch)
    saved = {k: registry.get(k) for k in (
        "trace_enable", "trace_phase_enable", "trace_buffer_events")}
    for k, v in (("trace_enable", True), ("trace_phase_enable", True),
                 ("trace_buffer_events", 65536)):
        registry.set(k, v)
    try:
        r = drive(EP, blocking_ep, trace=1, out_dir=str(tmp_path))
    finally:
        for k, v in saved.items():
            registry.set(k, v)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert r["correct"] is True, r["checks"]
    assert m["rdv_per_iter"] == 2.0 and m["ragged_ops_per_iter"] == 2.0
    assert m["ragged_bytes_per_iter"] == \
        r["checks"]["device_bytes"]["value"] / r["attempted"]
    assert m["launch_us"] > 0 and m["assemble_scatter_us"] > 0
    assert abs(m["unaccounted_us"]) < 0.05 * m["traced_iter_us"]


def chip_like_layout(x):
    """The layout a v5e gives a 2-D buffer: rows major on (8, 128) tiles
    where a row is a whole number of 128-element lanes, columns major
    otherwise (tests/test_alltoallv_arr.py reads it from the described
    chip)."""
    from jax.experimental.layout import Layout
    return Layout(major_to_minor=(0, 1) if x.shape[-1] % 128 == 0
                  else (1, 0), tiling=((8, 128),))


def test_the_combine_alone_takes_the_slab_body(monkeypatch, tmp_path):
    """With the chip's layouts, the combine's bfloat16 rows of 7,168
    lanes travel in the mesh program's slab body and the dispatch's
    1,864-word rows in its row body: ``ragged_slab_ops_per_iter`` reads
    exactly 1, ``ragged_ops_per_iter`` 2, and the run is correct with
    the bytes it was asked to send."""
    mesh_emulated(monkeypatch)
    monkeypatch.setattr(ragged, "layout_of", chip_like_layout)
    saved = {k: registry.get(k) for k in (
        "trace_enable", "trace_phase_enable", "trace_buffer_events")}
    for k, v in (("trace_enable", True), ("trace_phase_enable", True),
                 ("trace_buffer_events", 65536)):
        registry.set(k, v)
    try:
        r = drive(EP, blocking_ep, trace=1, out_dir=str(tmp_path))
    finally:
        for k, v in saved.items():
            registry.set(k, v)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert r["correct"] is True and r["failed"] == 0, r["checks"]
    assert m["ragged_slab_ops_per_iter"] == 1.0
    assert m["ragged_ops_per_iter"] == 2.0
    assert m["ragged_bytes_per_iter"] == \
        r["checks"]["device_bytes"]["value"] / r["attempted"]


def one_iteration_late(comm, call):
    """Every answer is the exchange of the iteration before: the other
    routing set's rows."""
    held = []

    def late(parity):
        held.append(call(parity))
        del held[:-2]
        return held[0]
    return late


def test_the_exchange_of_the_iteration_before_is_not_correct(monkeypatch):
    mesh_emulated(monkeypatch)
    r = drive(EP, blocking_ep, one_iteration_late)
    chk = r["checks"]
    assert r["correct"] is False and chk["gap"]["value"] > 0.0, chk
    assert chk["device_ops"]["value"] == 2 * r["attempted"]


def test_the_ep_control_is_not_correct_by_the_gap_alone(monkeypatch):
    """The low byte of every element cleared on the host: the same
    counts and bytes, other bits than the reference owes."""
    mesh_emulated(monkeypatch)
    r = drive(EP, blocking_ep, control="bf16")
    chk = r["checks"]
    assert r["correct"] is False and 0 < chk["gap"]["value"] <= 255
    assert chk["recv_rows_off"]["value"] == 0
    assert chk["device_ops"]["value"] == 2 * r["attempted"]
    assert chk["device_bytes"]["value"] == chk["device_bytes"]["equals"]
    assert chk["host_staged"]["value"] == 0


def test_a_path_through_the_host_is_not_correct():
    """On XLA:CPU the mesh cannot lower the collective and every call
    goes through the host: the answers are right and on the device,
    the counters say how they travelled."""
    r = drive(EP, blocking_ep)
    chk = r["checks"]
    assert chk["gap"]["value"] == 0.0 and chk["off_device"]["value"] == 0
    assert r["correct"] is False
    assert chk["host_staged"]["value"] == 2 * r["attempted"]
    assert r["failed"] == r["attempted"]
    assert chk["device_ops"]["value"] == 0


def test_a_library_that_refuses_rows_is_refused_at_once(monkeypatch):
    """The entry of a library whose alltoallv_arr takes 1-D buffers
    only: every rank raises at its first call, before any window."""
    from ompi_tpu import errhandler as eh
    mesh_emulated(monkeypatch)
    entry = ragged.alltoallv_arr

    def one_d_only(comm, e, x, *args):
        if len(x.shape) != 1:
            raise eh.MPIException(eh.ERR_BUFFER, "1-D only")
        return entry(comm, e, x, *args)

    from ompi_tpu.comm import communicator
    monkeypatch.setattr(communicator, "_alltoallv_arr", one_d_only)
    t0 = time.monotonic()
    with pytest.raises(Exception, match="1-D only"):
        drive(EP, blocking_ep)
    assert time.monotonic() - t0 < 60


# -- the queue's cell, in this process -----------------------------------------

def test_the_queue_cell_is_correct_and_fused():
    r = drive(Q, nonblocking_batch,
              device_map=lambda rank: jax.devices()[0])
    chk = r["checks"]
    assert r["correct"] is True and r["failed"] == 0, chk
    assert chk["fused_collectives"]["value"] == 4 * r["attempted"] > 0
    assert chk["fused_batches"]["value"] == r["attempted"]
    assert chk["host_staged"]["value"] == 0
    assert chk["gap"]["value"] <= 1e-05


def test_the_queue_control_is_not_correct():
    r = drive(Q, nonblocking_batch, control="bf16",
              device_map=lambda rank: jax.devices()[0])
    assert r["correct"] is False and r["checks"]["gap"]["value"] > 1e-05


def test_the_fused_program_has_its_stable_name():
    from ompi_tpu.coll import fusion
    sig = (("allreduce", (8,), "<f4", "MPI_SUM"),)
    hbm = fusion._build_fused_hbm(2, sig)
    assert "ompi_fused_hbm" in hbm.lower(
        *[jax.ShapeDtypeStruct((8,), np.float32)] * 2).as_text()
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]), ("r",))
    assert "ompi_fused_mesh" in fusion._build_fused_mesh(
        mesh, sig).lower(jax.ShapeDtypeStruct((16,), np.float32)).as_text()


# -- the cells end to end, in the development mode -----------------------------

def _dev_run(cell, *extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "cellbench", "run.py"),
         "--workload", cell, "--seed", str(SEED), "--seconds", "0.5",
         "--allow-cpu", "--tiny", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_dev_mode_runs_the_queue_cell():
    res = _dev_run(Q, "--trace", "1")
    assert res["correct"] is True and res["failed"] == 0
    assert res["metrics"] == {} and "DEV MODE" in res["dev_mode"]
    assert res["cpu_rehearsal"]["dev_fused_ops_per_iter"]["value"] == 4.0


def test_dev_mode_runs_the_ep_cell_through_the_host():
    """On XLA:CPU every call of the cell is host-staged (the collective
    has no CPU lowering), so the run is not correct by the path alone:
    the answers are exact."""
    res = _dev_run(EP, "--trace", "0")
    chk = res["checks"]
    assert res["correct"] is False and chk["gap"]["value"] == 0.0
    assert chk["host_staged"]["value"] == 2 * res["attempted"]
    assert chk["recv_rows_off"]["value"] == 0


# -- the manifest --------------------------------------------------------------

def test_manifest_is_valid_with_thirteen_cells_five_on_four_chips():
    assert validate.check(REPO) == []
    man = manifest.manifest(REPO)
    names = [w["name"] for w in man["workloads"]]
    assert names[11:13] == [Q, EP]
    # what the name means, not a count that the next cell breaks
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert 5 <= four <= max(1, len(names) // 2)
    cfg = next(c for c in man["configs"] if c["name"] == "deepep-ds3-ep4")
    assert cfg["source"] == ("https://huggingface.co/deepseek-ai/"
                             "DeepSeek-V3/blob/main/config.json")
    assert cfg["reduced"] == ["chips", "ep_ranks"]
    body = manifest.cell(EP, REPO)["config"]
    for k, v in CATALOG_CONFIG.items():
        assert body[k] == v, k
    assert (body["architecture"], body["ep_ranks"], body["chips"],
            body["provider"]) == ("DeepSeek-V3", 4, 4, "tpu")
    assert {"routing_offset", "two_routing_sets", "records",
            "expert_computation", "grouping"} <= set(body["assumed"])
    assert {"blocking_completion", "placement", "delivery", "counts",
            "path"} == set(body["guarantees"])


def test_the_two_cells_are_on_the_lists_of_their_metrics():
    man = manifest.manifest(REPO)
    lists = {m["name"]: m.get("workloads") for m in man["per_layer"]}
    ep = {"launch_s", "compile_or_load_s", "rdv_wait_us", "dispatch_us",
          "kernel_us", "collective_roofline", "device_idle_pct",
          "entry_exit_us", "rdv_skew_us", "rdv_wake_us", "serve_us",
          "launch_us", "assemble_scatter_us", "caller_us", "rdv_per_iter",
          "unaccounted_us", "traced_iter_us", "ragged_ops_per_iter",
          "ragged_bytes_per_iter", "ragged_slab_ops_per_iter",
          "ragged_operand_puts_per_iter"}
    q = {"launch_s", "compile_or_load_s", "kernel_us", "device_idle_pct",
         "traced_iter_us", "collective_roofline", "fused_ops_per_iter"}
    assert {n for n, ws in lists.items() if EP in ws} == ep
    assert {n for n, ws in lists.items() if Q in ws} == q
    for name, pv in (("ragged_bytes_per_iter", "coll_alltoallv_bytes"),
                     ("ragged_slab_ops_per_iter", "coll_alltoallv_slab_ops"),
                     ("ragged_operand_puts_per_iter",
                      "coll_alltoallv_operand_puts"),
                     ("fused_ops_per_iter", "coll_device_fused_collectives")):
        spec = manifest.metric_spec(name, REPO)
        assert (spec["reader"], spec["pvars"]) == ("pvar_sum", [pv])
    assert manifest.cell(EP, REPO)["pairing"]["kernel_events"] == [
        "^jit_ompi_alltoallv_mesh\\("]
    assert manifest.cell(Q, REPO)["pairing"]["kernel_events"] == [
        "^jit_ompi_fused_hbm\\("]
