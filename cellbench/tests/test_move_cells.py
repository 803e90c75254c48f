"""The two cells of osu-tpu4-move in the development mode (CPU;
``python -m pytest cellbench/tests``).

* ``--allow-cpu --tiny`` runs each new cell end to end, timed and
  traced, on four virtual devices through the large-message tier, labels
  every line and puts no number under a device metric's name;
* the lower-precision control comes out NOT correct in both;
* ``blocking_rooted``, driven with the timed path broken underneath,
  sees ``correct`` come out false when every rank is handed its own
  input back, and when one element of an answer is altered.
"""
import copy
import json
import os
import subprocess
import sys
import time
import types

import pytest

# the in-process drive below needs four devices: said before anything
# of this process touches jax (tests/conftest.py does the same)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") \
        + " --xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from cellbench import manifest  # noqa: E402

RUN = os.path.join(REPO, "cellbench", "run.py")
CELLS = ("bcast-64MiB.tpu4", "alltoall-4MiB.tpu4")


def bench(*args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, RUN, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_dev_mode_runs_a_new_cell_and_labels_it(cell, trace):
    p = bench("--workload", cell, "--seed", "3000000019", "--seconds", "1",
              "--trace", str(trace), "--allow-cpu", "--tiny")
    assert p.returncode == 0, p.stdout + p.stderr
    lines = p.stdout.splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True, p.stderr
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"] == {} and "dev_mode" in last
    assert all(k.startswith("dev_") for k in last["cpu_rehearsal"])
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                              "memory_peak_bytes": 0}
    assert all("DEV MODE" in ln for ln in lines[:-1]), p.stdout
    assert list(last)[-1] == "checks"
    assert last["checks"]["gap"] == {"value": 0.0, "limit": 0.0}
    assert "provider=tpu" in p.stdout
    assert "check gap:" in p.stderr and "correct=True" in p.stderr
    got = last["cpu_rehearsal"]
    if not trace:
        assert set(got) == {"dev_setup_s", "dev_iter_us"}
        return
    # no device plane on the CPU: the device metrics are left out,
    # never reported as 0; the program's counters are all there.  One
    # rendezvous and one whole-payload program a call since PR 29, and
    # nothing packs on the host (a whole number of segments)
    assert not {"dev_kernel_us", "dev_collective_roofline",
                "dev_device_idle_pct"} & set(got)
    assert got["dev_rdv_per_iter"]["value"] == 1
    assert got["dev_pack_unpack_per_iter_us"]["value"] == 0
    assert got["dev_serve_us"]["value"] > got["dev_launch_us"]["value"] > 0
    assert abs(got["dev_unaccounted_us"]["value"]) \
        < 0.03 * got["dev_traced_iter_us"]["value"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_bf16_is_not_correct_in_a_new_cell(cell):
    p = bench("--workload", cell, "--seed", "77", "--seconds", "1",
              "--allow-cpu", "--tiny", "--control", "bf16")
    assert p.returncode == 0, p.stdout + p.stderr
    last = json.loads(p.stdout.splitlines()[-1])
    assert last["correct"] is False
    assert last["checks"]["gap"]["limit"] == 0.0
    assert last["checks"]["gap"]["value"] > 1e-4
    assert "correct=False" in p.stderr


# -- the rooted generator with the timed path broken underneath ----------------

def drive(fault, seed=11):
    """blocking_rooted.run(), minus the harness's look for a chip, on
    four thread-ranks of this process, one per virtual device."""
    from ompi_tpu.testing import run_ranks

    from cellbench.traffic import blocking_rooted as gen

    spec = copy.deepcopy(manifest.cell("bcast-64MiB.tpu4", REPO))

    def body(comm):
        opts = types.SimpleNamespace(
            seed=seed, seconds=0.3, trace=0, tiny=True, control=None,
            t0_epoch=time.time(), rank_main_epoch=time.time(),
            say=lambda msg: None, peaks=None, out_dir=None,
            describe_trace=None,
            xla={"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0})
        return gen.run(comm, spec, opts, entry_wrap=fault)

    return run_ranks(spec["config"]["ranks"], body, devices=True,
                     timeout=240)[0]


def own_input_back(comm, call):
    return lambda x: x                       # nothing was broadcast


def one_element_altered(comm, call):
    return lambda x: call(x).at[0].add(1e-3)


def test_sound_rooted_run_is_correct():
    r = drive(None)
    assert r["correct"] is True and r["failed"] == 0, r["checks"]
    assert r["checks"]["ranks_compared"]["value"] == 3


@pytest.mark.parametrize("fault", [own_input_back, one_element_altered],
                         ids=lambda f: f.__name__)
def test_broken_rooted_path_is_not_correct(fault):
    r = drive(fault)
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["gap"]["value"] > r["checks"]["gap"]["limit"] == 0.0
