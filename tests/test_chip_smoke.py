"""chip_smoke.py in its development mode, and the rules it rests on.

The chip itself is checked by ``python3 chip_smoke.py`` through the
chip tool; here the same script runs ``--allow-cpu --tiny`` on the
virtual CPU mesh and on one device, refuses the CPU without the flag,
and fails when its child does.  Also: the compile-cache helper's
directory rule, mpirun's one-shell-per-chip-host refusal, the launcher
staying off jax, and the copying-runtime branches (coll/plan's
``_pack``, osc/device's non-aliasing put/get/accumulate) that the CPU
backend never selects on its own.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def smoke(*args, devices=None, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}"
        if devices else "")
    return subprocess.run([sys.executable, SMOKE, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def op_lines(stdout):
    """{op name: [provider, counters]} from the smoke's per-op lines."""
    out = {}
    for ln in stdout.splitlines():
        if " op=" not in ln:
            continue
        f = dict(kv.split("=", 1) for kv in ln.split() if "=" in kv)
        counters = json.loads(f["counters"]) if "counters" in f else {}
        out.setdefault(f["op"], []).append((f["provider"], counters))
    return out


@pytest.mark.parametrize("devices,module", [(8, "tpu"), (None, "hbm")])
def test_dev_mode_every_operation_served_by_a_device_module(devices,
                                                            module):
    p = smoke("--allow-cpu", "--tiny", devices=devices)
    assert p.returncode == 0, p.stdout + p.stderr
    lines = p.stdout.splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": devices or 1}
    # the explicit development mode is labelled in every line
    assert "dev_mode" in last
    assert all("DEV MODE" in ln for ln in lines[:-1]
               if not ln.startswith("SMOKE_PROBE")), p.stdout
    ops = op_lines(p.stdout)
    assert len(ops["allreduce_sum"]) == 5
    for name in ("bcast", "alltoall", "reduce_scatter_block_sum",
                 "config5_reduce_scatter_block_max_vector", "allgather",
                 "ppermute_ring"):
        assert name in ops, (name, sorted(ops))
    # the ragged exchange runs where a device serves it: one chip
    assert ("alltoallv_is_class_s" in ops) == (module == "hbm")
    if module == "hbm":
        assert ops["alltoallv_is_class_s"][0][1]["ragged"] == 8 * 4
    for name, calls in ops.items():
        for provider, counters in calls:
            assert provider != "arr_host", (name, provider)
            if counters:
                assert counters["host"] == 0, (name, counters)
            if provider in ("tpu", "hbm"):
                assert provider == module
                assert counters[module] > 0, (name, counters)
    # the large-message tier, compiled plans and the fused batch ran
    assert any(c["pipe_ops"] and c["plan_builds"]
               for _, c in ops["allreduce_sum"])
    assert ops["iallreduce_sum_x4_fused"][0][1]["fused"] > 0
    assert ops["send_arr_recv_arr_ring"][0][0] == "btl/tpu"
    # ... as btl/tpu's own counters say: every send of every rank placed
    # on the peer's device, none through the host, none placed again
    ring = next(ln for ln in lines if "op=send_arr_recv_arr_ring" in ln)
    p2p = json.loads(ring.split("p2p_counters=")[1].split()[0])
    assert p2p == {"d2d": 4 * (devices or 8), "staged": 0, "moved": 0}
    assert ops["win_fence_put_get_accumulate"][0][0] == "DeviceWindow"
    assert "block_until_ready_waits:" in p.stdout
    assert "native_loaded=True" in p.stdout


def test_no_cpu_fallback_without_the_flag():
    p = smoke()
    assert p.returncode != 0
    assert "platform is 'cpu', not 'tpu'" in p.stderr
    assert '"ok"' not in p.stdout  # no result line


def test_failing_child_fails_the_parent():
    # the job cannot finish inside one second: mpirun kills it (124)
    p = smoke("--allow-cpu", "--tiny", "--timeout", "1")
    assert p.returncode != 0
    assert "the mpirun job exited" in p.stderr
    assert '"ok"' not in p.stdout


def test_compile_cache_dir_rule(monkeypatch):
    from ompi_tpu.runtime import jaxcache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jaxcache.cache_dir() == os.path.join(REPO, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert jaxcache.cache_dir() == "/somewhere/else"
    # CPU backend: left alone, and no directory is set in code
    import jax
    assert jaxcache.enable() is None
    assert jax.config.jax_compilation_cache_dir in (None, "/somewhere/else")


def test_mpirun_refuses_second_device_shell_on_one_host():
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("JAX_PLATFORMS", None)  # as on a chip host
    cmd = [sys.executable, "-m", "ompi_tpu.tools.mpirun", "-np", "4",
           "--ranks-per-proc", "2",
           os.path.join(REPO, "examples", "ring.py")]
    p = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=60)
    assert p.returncode == 2
    assert "one process at a time" in p.stderr
    p = subprocess.run(cmd[:-1] + ["--hosts", "localhost:4", cmd[-1]],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=60)
    assert p.returncode == 2
    assert "one process at a time" in p.stderr


def test_launcher_side_stays_off_jax():
    code = ("import sys\n"
            "import ompi_tpu.tools.mpirun, ompi_tpu.tools.tpud\n"
            "import ompi_tpu.tools.plm, ompi_tpu.runtime.kvstore\n"
            "import ompi_tpu.runtime.jaxcache, benchmarks.osu_sweep\n"
            "assert 'jax' not in sys.modules, 'launcher imported jax'\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env=dict(os.environ, PYTHONPATH=REPO),
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr


def test_copying_runtime_branches(monkeypatch):
    """A runtime whose device_put copies (every accelerator) takes
    coll/plan's composing ``_pack`` and osc/device's compose-and-upload
    put, read-back get and host read-modify-write accumulate.  The CPU
    runtime aliases, so force the probe's verdict."""
    from ompi_tpu import osc
    from ompi_tpu.mca.params import registry
    from ompi_tpu.op import op as mpi_op
    from ompi_tpu.runtime import staging
    from ompi_tpu.testing import run_ranks
    import ompi_tpu.coll.pipeline  # noqa: F401 — registers the knobs

    monkeypatch.setattr(staging, "_zero_copy", False)
    n = 23_439  # ragged against the 1024-element segment below
    knobs = {"coll_pipeline_min_bytes": 16384, "coll_seg_size": 4096}
    saved = {k: registry.get(k) for k in knobs}
    for k, v in knobs.items():
        registry.set(k, v)

    def fn(comm):
        import jax
        rank, size = comm.rank, comm.size
        host = (np.arange(n) % 7 + rank).astype(np.float32)
        r = comm.allreduce_arr(jax.device_put(host, comm.device),
                               mpi_op.SUM)
        ref = sum((np.arange(n) % 7 + s).astype(np.float32)
                  for s in range(size))
        assert np.array_equal(np.asarray(r), ref)

        win = osc.allocate(comm, 2 * 256, disp_unit=1)
        assert type(win).__name__ == "DeviceWindow"
        tgt = (rank + 1) % size
        a = np.full(64, rank + 1, np.float32)
        win.fence()
        win.put(a, tgt, disp=0)
        win.accumulate(a, 0, disp=256, op=mpi_op.SUM)
        win.fence()
        back = np.empty(64, np.float32)
        win.get(back, tgt, disp=0)
        win.fence()
        mem = win.memory.view(np.float32)
        assert np.array_equal(back, a)
        assert np.array_equal(mem[:64],
                              np.full(64, (rank - 1) % size + 1))
        if rank == 0:
            assert np.array_equal(
                mem[64:], np.full(64, sum(range(1, size + 1))))
        win.free()
        return True

    try:
        assert all(run_ranks(4, fn, devices=True))
    finally:
        for k, v in saved.items():
            registry.set(k, v)
