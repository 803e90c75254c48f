"""The benchmark's own tests (CPU; ``python -m pytest cellbench/tests``).

* the manifest validator accepts BENCHMARK.json and catches what a
  manifest is refused over (PR 22's non-ASCII ``source`` first);
* the development mode runs a cell end to end, labels every line and
  puts no number under a device metric's name; without ``--allow-cpu``
  the CPU gets no result;
* the lower-precision control (inputs handed to the library in
  bfloat16) comes out NOT correct, at a size a test run can hold;
* the rest of a run, driven with the timed path broken underneath,
  sees ``correct`` come out false for each fault a cell can have: the
  exchange left out (which is also "the state returned unchanged"),
  half of the ranks left out with the rest scaled up, one element of
  an answer altered where it is produced, and a host-staged call;
* the trace reduction reads the numbers expected from a small trace
  recorded on the chip.
"""
import copy
import json
import os
import shutil
import subprocess
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from cellbench import manifest, tracered, validate  # noqa: E402

RUN = os.path.join(REPO, "cellbench", "run.py")


# -- validator --------------------------------------------------------------

def test_manifest_is_valid():
    assert validate.check(REPO) == []
    man = manifest.manifest(REPO)
    for c in man["configs"]:
        assert len(c["source"]) <= 200 and c["source"].isascii()
    assert all("workloads" in m for m in man["per_layer"])


def broken(tmp_path, edit):
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "cellbench"),
                    os.path.join(root, "cellbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = os.path.join(root, "BENCHMARK.json")
    man = json.load(open(path))
    edit(man)
    json.dump(man, open(path, "w"))
    return validate.check(root)


def ed(path, value):
    def edit(man):
        node = man
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = value
    return edit


def four_chips_everywhere(man):
    for w in man["workloads"]:
        w["chips"] = 4


@pytest.mark.parametrize("edit,needle", [
    (ed(["configs", 0, "source"], "OSU × 8 ranks"), "printable ASCII"),
    (ed(["configs", 0, "source"], "x" * 201), "printable ASCII"),
    (ed(["configs", 0, "source"], "two\nlines"), "printable ASCII"),
    (ed(["workloads", 0, "name"], "has space"), "name must match"),
    (ed(["end_to_end", 1, "unit"], "us per op"), "unit must match"),
    (ed(["per_layer", 0, "moves"], "no_such_metric"), "no end-to-end"),
    (ed(["per_layer", 6, "moves"], "iter_p95_us"), "does not report"),
    (ed(["per_layer", 0, "why"], "x"), "keys must be"),
    (ed(["workloads", 0, "traffic"], "no-such-mix"), "does not exist"),
    (ed(["end_to_end", 1, "bound"], 0.5), "bound must lie"),
    (ed(["run_seconds"], 52), "run_seconds"),
    (ed(["configs", 0, "reduced"], ["hidden_size"]), "width"),
    (four_chips_everywhere, "ask for 4 chips"),
    (lambda man: man["per_layer"][0].pop("workloads"), "no workloads"),
])
def test_validator_catches(tmp_path, edit, needle):
    problems = broken(tmp_path, edit)
    assert any(needle in p for p in problems), problems


# -- the development mode ---------------------------------------------------

def bench(*args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, RUN, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("cell,trace", [
    ("allreduce-4KiB.hbm8", 0), ("allreduce-128MiB.tpu4", 1),
    ("allreduce-256MiB.hbm8", 0), ("alltoall-4MiB.hbm8", 1)])
def test_dev_mode_runs_a_cell_and_labels_it(cell, trace):
    p = bench("--workload", cell, "--seed", "3000000019", "--seconds", "1",
              "--trace", str(trace), "--allow-cpu", "--tiny")
    assert p.returncode == 0, p.stdout + p.stderr
    lines = p.stdout.splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True, p.stderr
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"] == {} and "dev_mode" in last
    assert all(k.startswith("dev_") for k in last["cpu_rehearsal"])
    assert last["device"]["platform"] == "cpu"
    assert all("DEV MODE" in ln for ln in lines[:-1]), p.stdout
    assert list(last)[-1] == "checks"
    assert "check gap:" in p.stderr and "correct=True" in p.stderr
    if trace:
        # no device plane on the CPU: the device metrics are left out,
        # never reported as 0
        assert not {"dev_kernel_us", "dev_collective_roofline",
                    "dev_device_idle_pct"} & set(last["cpu_rehearsal"])
        assert "dev_rdv_wait_us" in last["cpu_rehearsal"]


def test_no_result_off_the_chip():
    p = bench("--workload", "allreduce-4KiB.hbm8", "--seed", "1",
              "--seconds", "1")
    assert p.returncode != 0
    assert "CELLBENCH_RESULT" not in p.stdout
    assert not p.stdout.strip().splitlines()[-1].startswith("{")
    assert "not 'tpu'" in p.stderr


def test_control_bf16_is_not_correct():
    p = bench("--workload", "allreduce-256MiB.hbm8", "--seed", "77",
              "--seconds", "1", "--allow-cpu", "--tiny", "--control", "bf16")
    assert p.returncode == 0, p.stdout + p.stderr
    last = json.loads(p.stdout.splitlines()[-1])
    assert last["correct"] is False
    gap = last["checks"]["gap"]
    assert gap["value"] > 3 * gap["limit"]


# -- the timed path broken underneath ---------------------------------------

def drive(cell, fault, seed=11, ranks_on_one_device=True):
    """The generator's run(), minus the harness's look for a chip, on
    thread-ranks of this process."""
    import jax

    from ompi_tpu.testing import run_ranks

    from cellbench.traffic import blocking_collective as gen

    spec = copy.deepcopy(manifest.cell(cell, REPO))
    P = spec["config"]["ranks"]

    def body(comm):
        opts = types.SimpleNamespace(
            seed=seed, seconds=0.3, trace=0, tiny=True, control=None,
            t0_epoch=time.time(), rank_main_epoch=time.time(),
            say=lambda msg: None, peaks=None, out_dir=None,
            describe_trace=None,
            xla={"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0})
        return gen.run(comm, spec, opts, entry_wrap=fault)

    dmap = (lambda r: jax.devices()[0]) if ranks_on_one_device else None
    return run_ranks(P, body, devices=True, device_map=dmap,
                     timeout=240)[0]


def no_exchange(comm, call):
    return lambda x: x                       # state returned unchanged


def half_left_out(comm, call):
    import jax.numpy as jnp
    mine = 1.0 if comm.rank < comm.size // 2 else 0.0
    return lambda x: call(x * jnp.float32(mine)) * jnp.float32(2.0)


def one_element_altered(comm, call):
    return lambda x: call(x).at[0].add(1e-3)


def host_staged(comm, call):
    """The right answer from the wrong system: coll/hbm's own fallback,
    which stages every call through host memory."""
    from ompi_tpu.op import op as mpi_op
    fallback = comm.coll.allreduce_arr._coll_inner.__self__.fallback
    return lambda x: fallback.allreduce_arr(comm, x, mpi_op.SUM)


def test_sound_run_is_correct():
    r = drive("allreduce-256MiB.hbm8", None)
    assert r["correct"] is True and r["failed"] == 0, r["checks"]


@pytest.mark.parametrize("cell,fault", [
    ("allreduce-256MiB.hbm8", no_exchange),
    ("allreduce-256MiB.hbm8", half_left_out),
    ("allreduce-256MiB.hbm8", one_element_altered),
    ("alltoall-4MiB.hbm8", no_exchange),
    ("alltoall-4MiB.hbm8", one_element_altered),
], ids=lambda v: getattr(v, "__name__", v))
def test_broken_path_is_not_correct(cell, fault):
    r = drive(cell, fault)
    assert r["correct"] is False, r["checks"]
    gap = r["checks"]["gap"]
    assert gap["value"] > gap["limit"]


def test_host_staged_calls_count_as_failed():
    r = drive("allreduce-256MiB.hbm8", host_staged)
    assert r["checks"]["gap"]["value"] <= r["checks"]["gap"]["limit"]
    assert r["checks"]["host_staged"]["value"] >= r["attempted"]
    assert r["correct"] is False and r["failed"] == r["attempted"]


# -- trace reduction on a trace recorded on the chip --------------------------

def test_trace_reduction_on_recorded_trace():
    path = os.path.join(REPO, "cellbench", "fixtures",
                        "trace_allreduce-256MiB.hbm8.json")
    planes = tracered.load(path)
    expect = manifest.load_json(path.replace(".json", ".expect.json"))
    pairing = manifest.load_json(os.path.join(
        REPO, "cellbench", "workloads", "allreduce-256MiB.hbm8.json"))
    out = tracered.reduce(planes, expect["iters"],
                          pairing["kernel_events"])
    assert out["devices"] == 1
    for k in ("window_s", "busy_s", "kernel_s_per_iter"):
        assert out[k] == pytest.approx(expect[k], rel=1e-9), k
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["device_ops"][0][0] == expect["top_op"]
    # no device plane: nothing, never a 0
    assert tracered.reduce({"/host:CPU": planes.get("/host:CPU", {})},
                           1, pairing["kernel_events"]) == {}


def test_union_and_gap_naming():
    assert tracered.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == \
        [[0, 3], [5, 8]]
    planes = {
        "/host:CPU": {"t": [[tracered.WINDOW, 0, 100]]},
        "/device:TPU:0": {
            "XLA Ops": [["fusion", 10, 20], ["copy", 50, 10]],
            "XLA Modules": [["jit_k(1)", 10, 20], ["jit_other(2)", 50, 10]]},
    }
    out = tracered.reduce(planes, 2, ["^jit_k"],
                          host_spans=[("ph_rdv_wait", 35, 45)])
    assert out["busy_s"] == pytest.approx(30e-9)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["kernel_s_per_iter"] == pytest.approx(10e-9)
    gaps = dict(map(tuple, out["idle_gaps"]))
    assert gaps["ph_rdv_wait"] == pytest.approx(20e-9)      # gap 30..50
    assert gaps["host:no_kept_span"] == pytest.approx(50e-9)
