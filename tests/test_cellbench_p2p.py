"""The deployment osu-tpu4-p2p (ISSUE 34): four ranks on four devices
exchanging device arrays point to point through pml/ob1 and btl/tpu,
held to the benchmark's plain reference on the CPU.

* cellbench/reference_p2p.py is the left neighbour's stream of the
  iteration's parity, against a two-line numpy statement of itself;
* the ring cell's generator in this process: a sound run is correct;
  the lower-precision control, an entry that hands back the rank's own
  input, one that delivers every message one iteration late (the
  parity guard) and a library path that stages through the host are
  NOT; a library without ``btl_tpu_d2d_sends`` is refused at once;
* a traced run cuts the compared blocks on the device while the
  profiler is on (the exchanges show on no device plane; the only
  programs of a traced window are the comparison's), and what the
  trace says of the device reaches the result's line;
* the cell end to end in the development mode;
* BENCHMARK.json is valid with eight cells, four of them on four chips,
  and no metric of the cell claims the device's time.
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
                       reference_p2p, validate)
from cellbench.traffic import blocking_p2p  # noqa: E402
from ompi_tpu.btl import tpu as btl_tpu  # noqa: E402
from ompi_tpu.mca.params import registry  # noqa: E402
from ompi_tpu.testing import run_ranks  # noqa: E402

jax = pytest.importorskip("jax")

P = 4
SEED = 3400000019            # the driver's seeds pass 2**31
RING = "sendrecv-ring-32MiB.tpu4"


# -- the reference itself -------------------------------------

@pytest.mark.parametrize("iteration", [0, 1, 6, 7])
def test_reference_is_the_left_neighbours_stream_of_the_parity(iteration):
    n, lo, hi = 5000, 1234, 4321
    for rank in range(P):
        left, parity = (rank - 1) % P, iteration % 2
        whole = reference.values_from_key(np.uint32(reference.stream_key(
            SEED, 2 * left + parity)), 0, n)
        got = reference_p2p.expected("ring", SEED, P, n, rank, iteration,
                                     lo, hi)
        assert got.dtype == np.float32
        assert got.tobytes() == whole[lo:hi].tobytes()
        # the other parity, the rank's own input and the right
        # neighbour's are all another stream
        for other in (reference_p2p.expected("ring", SEED, P, n, rank,
                                             iteration + 1, lo, hi),
                      reference.values_from_key(np.uint32(
                          reference_p2p.stream_key(SEED, rank, parity)),
                          lo, hi)):
            assert np.mean(other != got) > 0.999
    with pytest.raises(KeyError):
        reference_p2p.expected("star", SEED, P, n, 0, 0, lo, hi)
    with pytest.raises(ValueError):
        reference_p2p.expected("ring", SEED, P, n, P, 0, lo, hi)


def test_kept_iterations_hold_both_parities():
    for seed in range(40):
        for n in (4, 5, 6, 7, 100, 101):
            keep = blocking_p2p.kept_iterations(
                np.random.default_rng([seed, n]), n, 3)
            assert {0, n - 1} <= keep <= set(range(n))
            assert {k & 1 for k in keep} == {0, 1}


# -- the generator, in this process --------------------------------------------

def drive(fault=None, control=None, said=None, trace=0, out_dir=None):
    """blocking_p2p.run(), minus the harness's look for a chip, on four
    thread-ranks of this process, each on its own device."""
    spec = copy.deepcopy(manifest.cell(RING, REPO))

    def body(comm):
        opts = types.SimpleNamespace(
            seed=SEED, seconds=0.3, trace=trace, tiny=True, control=control,
            t0_epoch=time.time(), rank_main_epoch=time.time(),
            say=(said.append if said is not None else lambda msg: None),
            peaks=None, out_dir=out_dir, describe_trace=None,
            xla={"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0})
        return blocking_p2p.run(comm, spec, opts, entry_wrap=fault)

    return run_ranks(spec["config"]["ranks"], body, devices=True,
                     timeout=240)[0]


def own_input_back(comm, call):
    def own(x):
        call(x)
        return x
    return own


def one_iteration_late(comm, call):
    """Every answer is the message of the iteration before."""
    held = []

    def late(x):
        held.append(call(x))
        del held[:-2]
        return held[0]
    return late


def test_the_ring_cell_is_correct():
    said = []
    r = drive(said=said)
    chk = r["checks"]
    assert r["correct"] is True and r["failed"] == 0, chk
    assert chk["gap"] == {"value": 0.0, "limit": 0.0}
    assert chk["d2d_sends"]["value"] == r["attempted"] > 0
    assert chk["parities_compared"]["value"] == 2
    assert chk["ranks_compared"]["value"] == 3
    assert all(chk[k]["value"] == 0 for k in (
        "staged_sends", "staged_bytes", "recv_moves", "host_staged",
        "off_device", "incomplete", "wrong_provider"))
    assert any("provider=btl/tpu," in line for line in said)
    assert any(line.startswith("layout: ranks on device ids [0, 1, 2, 3]")
               for line in said)
    assert {"setup_s", "iter_us"} <= set(r["metrics"])


@pytest.mark.parametrize("fault", [own_input_back, one_iteration_late],
                         ids=lambda f: f.__name__)
def test_a_wrong_answer_is_not_correct(fault):
    r = drive(fault)
    chk = r["checks"]
    assert r["correct"] is False and chk["gap"]["value"] > 0.0, chk
    # the path itself was sound: only the comparison says so
    assert chk["d2d_sends"]["value"] == r["attempted"]


def test_bf16_control_is_not_correct():
    r = drive(control="bf16")
    assert r["correct"] is False
    assert 1e-4 < r["checks"]["gap"]["value"] < 2.0 ** -8


def test_a_path_through_the_host_is_not_correct(monkeypatch):
    """The peers made to look as if across a process boundary, with a
    chunk smaller than the message: every message is parked, pulled and
    staged chunk by chunk through host memory.  The answers are right
    and on the device; the counters say how they travelled."""
    monkeypatch.setattr(btl_tpu, "_peer_local_device",
                        lambda comm, dst: (False, None))
    saved = registry.get("btl_tpu_chunk_bytes")
    registry.set("btl_tpu_chunk_bytes", 16384)
    try:
        said = []
        r = drive(said=said)
    finally:
        registry.set("btl_tpu_chunk_bytes", saved)
    chk = r["checks"]
    assert chk["gap"]["value"] == 0.0 and chk["off_device"]["value"] == 0
    assert r["correct"] is False and r["failed"] == r["attempted"]
    assert chk["d2d_sends"]["value"] == 0
    assert chk["staged_sends"]["value"] == r["attempted"]
    assert chk["staged_bytes"]["value"] == r["attempted"] * 131072
    assert chk["wrong_provider"]["value"] == 1
    assert any("provider=host-staged" in line for line in said)


def test_a_library_without_the_counter_is_refused_at_once(monkeypatch):
    real = blocking_p2p.pvars
    monkeypatch.setattr(blocking_p2p, "pvars", lambda: {
        k: v for k, v in real().items() if not k.startswith("btl_tpu_d2d")})
    t0 = time.monotonic()
    with pytest.raises(Exception, match="btl_tpu_d2d_sends"):
        drive()
    assert time.monotonic() - t0 < 30


# -- the cell end to end, in the development mode -------------------------

def _dev_run(cell, *extra, rc=0):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "cellbench", "run.py"),
         "--workload", cell, "--seed", str(SEED), "--seconds", "0.5",
         "--allow-cpu", "--tiny", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == rc, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1]) if rc == 0 else r


def test_dev_mode_runs_the_ring_cell_and_labels_it():
    res = _dev_run(RING, "--trace", "1")
    assert res["correct"] is True and res["failed"] == 0
    assert res["metrics"] == {} and "DEV MODE" in res["dev_mode"]
    dev = res["cpu_rehearsal"]
    for name in ("p2p_send_us", "p2p_match_us", "p2p_deliver_us",
                 "caller_us", "traced_iter_us"):
        assert dev["dev_" + name]["value"] > 0
    # the four accumulators are the traced iteration, to the tracer's
    # own cost and the ranks' skew at the two edges of a window of a
    # hundred or so iterations
    assert abs(dev["dev_p2p_unaccounted_us"]["value"]) \
        < 0.05 * dev["dev_traced_iter_us"]["value"]
    chk = res["checks"]
    assert chk["gap"] == {"value": 0.0, "limit": 0.0}
    assert chk["d2d_sends"]["value"] == res["attempted"]


def test_a_traced_run_cuts_its_blocks_inside_the_trace(monkeypatch,
                                                       tmp_path):
    """The exchanges show on no device plane, so the only programs of a
    traced window are the comparison's slices: they run while the
    profiler is on (after the warm-up's, which compile them), and what
    the trace then says of the device reaches the result's line."""
    on, seen = [], []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: on.append(True))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: on.clear())
    real = blocking_p2p.pull
    monkeypatch.setattr(
        blocking_p2p, "pull",
        lambda *a: (seen.append((bool(on), len(a[2]))), real(*a))[1])
    heard = {"window_s": 0.3, "busy_s": 0.001, "device_ops": [["take", 1]],
             "idle_gaps": [["recv_arr_inplace", 0.299]]}
    monkeypatch.setattr(blocking_p2p.tracered, "reduce_dir",
                        lambda *a: dict(heard))
    said = []
    r = drive(said=said, trace=1, out_dir=str(tmp_path))
    assert r["correct"] is True and r["checks"]["gap"]["value"] == 0.0
    assert r["device"]["busy_s"] == 0.001 and r["device"]["window_s"] == 0.3
    assert r["breakdown"]["device_ops"] == [["take", 1]]
    # three compared ranks: one warming slice each with the profiler off,
    # then their three kept answers each with it on
    assert sorted(seen) == [(False, 1)] * 3 + [(True, 3)] * 3
    assert any("busy_s is the comparison's own slices" in line
               for line in said)
    # a trace without a device plane leaves the two keys out
    heard.clear()
    r = drive(trace=1, out_dir=str(tmp_path))
    assert r["correct"] is True and "busy_s" not in r["device"]


def test_dev_mode_ring_control_reads_not_correct():
    res = _dev_run(RING, "--trace", "0", "--control", "bf16")
    assert res["correct"] is False and res["checks"]["gap"]["value"] > 0


# -- the manifest ---------------------------------------------------------------

def test_manifest_is_valid_with_eight_cells_four_on_four_chips():
    assert validate.check(REPO) == []
    man = manifest.manifest(REPO)
    names = [w["name"] for w in man["workloads"]]
    assert len(names) >= 8 and len(man["configs"]) >= 5
    assert names.index(RING) == 7
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert 4 <= four <= max(1, len(names) // 2)
    ring = manifest.cell(RING, REPO)
    assert (ring["entry"]["config"], ring["entry"]["chips"]) == (
        "osu-tpu4-p2p", 4)
    assert ring["traffic"]["bytes_per_rank"] == 33554432
    # some hundreds of chip-to-chip transfers cut a chip's trace short,
    # and the window's only programs come last (PERF.md section 7)
    assert ring["traffic"]["trace_seconds"] <= 0.5
    assert ring["config"]["provider"] == "btl/tpu"
    assert {"blocking_completion", "placement", "delivery", "ordering",
            "send_buffer_reuse", "path"} == set(ring["config"]["guarantees"])
    assert {m["name"] for m in ring["end_to_end"]} == {
        "setup_s", "iter_us", "iter_p95_us"}
    due = {m["name"]: m for m in ring["per_layer"]}
    assert set(due) == {"p2p_send_us", "p2p_match_us", "p2p_deliver_us",
                        "p2p_unaccounted_us", "caller_us", "traced_iter_us",
                        "launch_s", "compile_or_load_s"}
    # the copy's device time has no source yet (PERF.md section 7): no
    # metric of the cell says it reads the device
    assert all(m["source"] != "device_trace" for m in due.values())
    # the reference imports nothing of the library
    src = open(os.path.join(REPO, "cellbench", "reference_p2p.py")).read()
    assert "ompi_tpu" not in src.split('"""', 2)[2]
