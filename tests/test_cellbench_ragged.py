"""The deployment npb-is-hbm8 (ISSUE 40): the key exchange of NAS
Parallel Benchmarks IS on 8 ranks of one chip through
``comm.alltoallv_arr``, held to the benchmark's plain reference on the
CPU; and the queue's first cell, allreduce-256KiB.hbm8.

* cellbench/reference_ragged.py against a brute-force statement of
  IS's rule at class S: the keys, the bucketing, the distribution rule,
  the counts and what every rank is owed;
* the generator in this process: a sound run is correct; the control,
  an entry that hands back the exchange of the iteration before (the
  parity guard) and a library path that stages through the host are
  NOT; a library without ``alltoallv_arr`` is refused at once;
* the two cells end to end in the development mode;
* BENCHMARK.json is valid with eleven cells, four of them on four
  chips, and the new cells are on the lists the issue names.
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

from cellbench import (manifest, reference,  # noqa: E402
                       reference_ragged, validate)
from cellbench.traffic import blocking_ragged  # noqa: E402
from ompi_tpu.coll import device as coll_device  # noqa: E402
from ompi_tpu.testing import run_ranks  # noqa: E402

jax = pytest.importorskip("jax")

P = 8
SEED = 4000000019            # the driver's seeds pass 2**31
IS = "is-alltoallv-classC.hbm8"
AR = "allreduce-256KiB.hbm8"
S = reference_ragged.CLASSES["S"]


# -- the reference itself -------------------------------------

def brute_keys(seed, rank, parity, n, max_key_log2):
    """key = floor(MAX_KEY / 4 * (u1 + u2 + u3 + u4)), one key at a
    time, the uniforms 24 bits of the harness's hash each."""
    key = reference.stream_key(seed, 2 * rank + parity)
    out = []
    for i in range(n):
        total = 0
        for k in range(4):
            x = ((4 * i + k) ^ key) & 0xFFFFFFFF
            x = ((x ^ (x >> 16)) * 0x7FEB352D) & 0xFFFFFFFF
            x = ((x ^ (x >> 15)) * 0x846CA68B) & 0xFFFFFFFF
            x ^= x >> 16
            total += x >> 8
        # total / 2**24 is the sum of the four uniforms
        out.append((total << max_key_log2) >> 26)
    return out


@pytest.mark.parametrize("parity", [0, 1])
def test_reference_against_a_brute_force_loop_at_class_s(parity):
    n = reference_ragged.num_keys(S, P)
    shift, nb = reference_ragged.shift_of(S), 1 << S["num_buckets_log2"]
    assert (n, shift, reference_ragged.size_of_buffers(S, P)) == (
        8192, 2, 12288)
    ks = [brute_keys(SEED, r, parity, n, S["max_key_log2"])
          for r in range(P)]
    # the rule of is.c's rank(), loop by loop
    sizes = [[sum(1 for k in keys if k >> shift == b) for b in range(nb)]
             for keys in ks]
    totals = [sum(s[b] for s in sizes) for b in range(nb)]
    buff1 = [[k for b in range(nb) for k in keys if k >> shift == b]
             for keys in ks]
    counts, last = [[0] * P for _ in range(P)], [-1] * P
    for r in range(P):
        acc = loc = j = 0
        for b in range(nb):
            acc += totals[b]
            loc += sizes[r][b]
            if j < P and acc >= (j + 1) * n:
                counts[r][j], last[j] = loc, b
                loc = 0
                j += 1
    ex = reference_ragged.exchange(SEED, parity, S, P)
    for r in range(P):
        assert reference_ragged.keys(SEED, r, parity, S, P).tolist() == ks[r]
        assert ex["buff1"][r].tolist() == buff1[r]
    assert ex["counts"].tolist() == counts and ex["last"].tolist() == last
    assert max(max(k) for k in ks) < 1 << S["max_key_log2"]
    for r in range(P):
        owed = []
        for i in range(P):
            at = sum(counts[i][:r])
            owed += buff1[i][at:at + counts[i][r]]
        assert reference_ragged.owed(ex, r).tolist() == owed
        lo, hi = reference_ragged.owned(ex["last"], r)
        assert all(lo <= k >> shift <= hi for k in owed)
    # the two parities are other keys and other counts
    other = reference_ragged.exchange(SEED, parity ^ 1, S, P)
    assert not np.array_equal(other["counts"], ex["counts"])


def test_a_rank_no_bucket_is_left_for_sends_and_receives_nothing():
    """Two buckets, four ranks: the rule moves to the next owner at
    most once a bucket, so two owners get a bucket each and the rest
    nothing."""
    sizes = np.array([5, 3], np.int64)
    totals = np.array([20, 12], np.int64)
    send, last = reference_ragged.distribute(sizes, totals, 8, 4)
    assert send.tolist() == [5, 3, 0, 0] and last.tolist() == [0, 1, -1, -1]
    assert reference_ragged.owned(last, 1) == (1, 1)
    assert reference_ragged.owned(last, 3) == (0, -1)


def test_gap_is_exact_and_a_wrong_length_is_infinitely_far():
    a = np.array([1, 2, 3], np.int32)
    assert reference_ragged.gap(a, a.copy()) == 0.0
    assert reference_ragged.gap(a, a + np.int32(2)) == 2.0
    assert reference_ragged.gap(a[:2], a) == float("inf")
    src = open(os.path.join(REPO, "cellbench",
                            "reference_ragged.py")).read()
    assert "ompi_tpu" not in src.split('"""', 2)[2]


# -- the generator, in this process --------------------------------------------

def drive(fault=None, control=None, said=None):
    """blocking_ragged.run(), minus the harness's look for a chip, on
    eight thread-ranks of this process sharing one device."""
    spec = copy.deepcopy(manifest.cell(IS, REPO))

    def body(comm):
        opts = types.SimpleNamespace(
            seed=SEED, seconds=0.3, trace=0, tiny=True, control=control,
            t0_epoch=time.time(), rank_main_epoch=time.time(),
            say=(said.append if said is not None else lambda msg: None),
            peaks=None, out_dir=None, describe_trace=None,
            xla={"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0})
        return blocking_ragged.run(comm, spec, opts, entry_wrap=fault)

    return run_ranks(spec["config"]["ranks"], body, timeout=240,
                     device_map=lambda r: jax.devices()[0])[0]


def one_iteration_late(comm, call):
    """Every answer is the exchange of the iteration before: the other
    parity's keys."""
    held = []

    def late(parity):
        held.append(call(parity))
        del held[:-2]
        return held[0]
    return late


def test_the_is_cell_is_correct():
    said = []
    r = drive(said=said)
    chk = r["checks"]
    assert r["correct"] is True and r["failed"] == 0, chk
    assert chk["gap"] == {"value": 0.0, "limit": 0.0}
    assert chk["device_ops"]["value"] == r["attempted"] > 0
    assert chk["device_elems"]["value"] == r["attempted"] * 8192
    assert chk["parities_compared"]["value"] == 2
    assert chk["ranks_compared"]["value"] == 3
    assert all(chk[k]["value"] == 0 for k in (
        "recv_elems_off", "stray_keys", "host_staged", "compiled_in_window",
        "off_device", "incomplete", "wrong_provider"))
    assert any("provider=hbm," in line for line in said)
    assert {"setup_s", "iter_us", "iter_p95_us"} == set(r["metrics"])


def test_the_exchange_of_the_iteration_before_is_not_correct():
    r = drive(one_iteration_late)
    chk = r["checks"]
    assert r["correct"] is False and chk["gap"]["value"] > 0.0, chk
    # the path itself was sound: only the comparison says so
    assert chk["device_ops"]["value"] == r["attempted"]


def test_the_control_is_not_correct_by_the_gap_alone():
    """The keys' low bits cleared on the host: the same buckets, counts
    and bytes, other keys than the reference owes."""
    r = drive(control="bf16")
    chk = r["checks"]
    assert r["correct"] is False and 0 < chk["gap"]["value"] <= 3
    assert chk["stray_keys"]["value"] == 0
    assert chk["recv_elems_off"]["value"] == 0
    assert chk["device_ops"]["value"] == r["attempted"]


def test_a_path_through_the_host_is_not_correct(monkeypatch):
    """The rule that says what the device serves made to refuse: the
    answers are right and on the device, the counters say how they
    travelled."""
    monkeypatch.setattr(coll_device.HbmCollModule, "_ragged_eligible",
                        lambda self, comm, x: False)
    r = drive()
    chk = r["checks"]
    assert chk["gap"]["value"] == 0.0 and chk["off_device"]["value"] == 0
    assert r["correct"] is False
    assert chk["host_staged"]["value"] == r["attempted"] == r["failed"]
    assert chk["device_ops"]["value"] == 0


def test_a_library_without_the_call_is_refused_at_once(monkeypatch):
    from ompi_tpu.comm import communicator
    monkeypatch.delattr(communicator.Communicator, "alltoallv_arr")
    t0 = time.monotonic()
    with pytest.raises(Exception, match="no alltoallv_arr"):
        drive()
    assert time.monotonic() - t0 < 30


# -- the cells end to end, in the development mode ------------------------

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


def test_dev_mode_runs_the_is_cell_and_labels_it():
    res = _dev_run(IS, "--trace", "1")
    assert res["correct"] is True and res["failed"] == 0
    assert res["metrics"] == {} and "DEV MODE" in res["dev_mode"]
    dev = res["cpu_rehearsal"]
    assert dev["dev_ragged_ops_per_iter"]["value"] == 1.0
    assert dev["dev_ragged_elems_per_iter"]["value"] == 8192.0
    assert dev["dev_rdv_per_iter"]["value"] == 1.0
    assert dev["dev_pack_unpack_per_iter_us"]["value"] == 0.0
    # the layer account covers the call with no new accumulator
    assert abs(dev["dev_unaccounted_us"]["value"]) \
        < 0.05 * dev["dev_traced_iter_us"]["value"]


def test_dev_mode_is_control_reads_not_correct():
    res = _dev_run(IS, "--trace", "0", "--control", "bf16")
    assert res["correct"] is False and res["checks"]["gap"]["value"] > 0


def test_dev_mode_runs_the_256kib_cell():
    res = _dev_run(AR, "--trace", "1")
    assert res["correct"] is True and res["failed"] == 0
    assert res["cpu_rehearsal"]["dev_rdv_per_iter"]["value"] == 1.0


# -- the manifest ---------------------------------------------------------------

def test_manifest_is_valid_with_eleven_cells_four_on_four_chips():
    assert validate.check(REPO) == []
    man = manifest.manifest(REPO)
    names = [w["name"] for w in man["workloads"]]
    assert names[9:11] == [AR, IS] and len(man["configs"]) >= 6
    # what the name means, not a count that the next cell breaks: the
    # four four-chip cells of then are there, at most half of them all
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert 4 <= four <= max(1, len(names) // 2)
    cell = manifest.cell(IS, REPO)
    cfg = cell["config"]
    assert (cell["entry"]["config"], cell["entry"]["chips"]) == (
        "npb-is-hbm8", 1)
    assert cfg["architecture"] is None and cfg["provider"] == "hbm"
    assert list(cfg["reduced"]) == ["chips"]
    assert cfg["class_table"]["num_keys_per_process"] == 1 << 24 \
        == reference_ragged.num_keys(reference_ragged.CLASSES["C"], 8)
    assert cfg["class_table"]["size_of_buffers"] == 25165824 \
        == reference_ragged.size_of_buffers(reference_ragged.CLASSES["C"], 8)
    assert {"blocking_completion", "placement", "delivery", "counts",
            "path"} == set(cfg["guarantees"])
    assert cell["traffic"]["generator"] == "blocking_ragged"
    assert cell["pairing"]["check"]["limit"] == 0.0
    assert cell["pairing"]["kernel_events"] == ["^jit_ompi_alltoallv\\("]
    # on iter_p95_us's list: its pooled p95 spread 1.1% over 6 seeds of
    # one call (PERF.md section 2); the 256 KiB cell's was not measured
    assert {m["name"] for m in cell["end_to_end"]} == {
        "setup_s", "iter_us", "iter_p95_us"}
    assert {m["name"] for m in manifest.cell(AR, REPO)["end_to_end"]} == {
        "setup_s", "iter_us"}


def test_the_new_cells_are_on_the_lists_of_their_siblings():
    man = manifest.manifest(REPO)
    for new, sibling in ((IS, "alltoall-4MiB.hbm8"),
                         (AR, "allreduce-4KiB.hbm8")):
        for m in man["per_layer"]:
            ws = m.get("workloads")
            if ws is not None and sibling in ws:
                assert new in ws, (m["name"], new)
    due = {m["name"]: m for m in manifest.cell(IS, REPO)["per_layer"]}
    assert {"collective_roofline", "kernel_us", "rdv_per_iter",
            "unaccounted_us", "ragged_ops_per_iter",
            "ragged_elems_per_iter"} <= set(due)
    for name, pv in (("ragged_ops_per_iter", "coll_alltoallv_device_ops"),
                     ("ragged_elems_per_iter", "coll_alltoallv_elems")):
        # the IS cell first; a later cell of the same call may follow
        assert due[name]["workloads"][0] == IS
        spec = manifest.metric_spec(name, REPO)
        assert (spec["reader"], spec["pvars"]) == ("pvar_sum", [pv])
    ar = manifest.cell(AR, REPO)
    assert ar["traffic"]["bytes_per_rank"] == 262144
    assert ar["pairing"]["check"] == manifest.cell(
        "allreduce-4KiB.hbm8", REPO)["pairing"]["check"]
