"""The generator for a batch of NONBLOCKING collectives on device arrays:
OSU Micro-Benchmarks' ``osu_iallreduce`` post-then-wait, with several
posted before the wait.

Every rank-thread holds ``posted`` inputs (input k is its own stream of
the seed, ``k * ranks + rank``), and in every iteration posts

    reqs = [comm.iallreduce_arr(x_k, MPI_SUM) for k in range(posted)]

then waits on every request and calls ``jax.block_until_ready`` on the
results: one batch outstanding per rank, no think time.  The library
may coalesce what is posted (coll/fusion: one rendezvous and one
program a batch); the loop neither asks for nor forbids it.  The
operation, the sizes and the loop's lengths are data
(cellbench/traffic/<mix>.json); ranks, layout and provider are the
configuration's; nothing here names a cell.

Required bytes.  The readers are handed ``op`` "allreduce" and
``bytes_per_rank`` = posted x the bytes of an input: the batch is that
much allreduce work (cellbench/bytes.py's one-chip row), whatever
fuses it.

The window, the whole-window arithmetic and the warm-up are
blocking_collective's; ``finish``, the result line's metrics, is shared
with blocking_ep.py.

``correct``.  Every input k's answer of the first, the last and one
seeded iteration between, on 3 ranks, against cellbench/reference.py's
allreduce of input k (the sum over ranks in float64), by the pairing's
limit; ``coll_device_fused_collectives`` = posted x iterations x ranks
and ``coll_device_fused_batches`` = iterations x ranks (every posted
call rode one fused batch a rank-iteration); nothing host-staged; the
configuration's provider; results on the rank's own device.  The
control (``--control bf16``) hands the library the inputs rounded to
bfloat16.
"""
from __future__ import annotations

import contextlib
import importlib
import os
import shutil
import time

import numpy as np

from cellbench import reference, tracered
from cellbench.manifest import metric_spec
from cellbench.traffic.blocking_collective import (
    PHASES, RANK_FACTS, gather, pvars, span_rows, timed_loop, warm_up)

COLLS, BATCHES = ("coll_device_fused_collectives",
                  "coll_device_fused_batches")
STAGED = "coll_arr_host_staged_collectives"


def make_inputs(jax, jnp, comm, seed: int, n: int, posted: int, control):
    """This rank's ``posted`` inputs on its device, input k the stream
    ``k * size + rank`` of the seed, in one jitted call whose key is an
    argument."""
    make = jax.jit(lambda key: reference.values_from_key(key, 0, n, jnp))
    xs = []
    for k in range(posted):
        x = make(jax.device_put(np.uint32(reference.stream_key(
            seed, k * comm.size + comm.rank)), comm.device))
        if control == "bf16":
            # the lower-precision control: applied to what the library
            # is handed, never by an option of the library
            x = x.astype(jnp.bfloat16)
        xs.append(jax.block_until_ready(x))
    return xs


def owed(seed: int, ranks: int, k: int, n: int) -> np.ndarray:
    """cellbench/reference.py's allreduce of input k: the float64 sum
    over ranks of their streams ``k * ranks + s``."""
    acc = np.zeros(n, np.float64)
    for s in range(ranks):
        acc += reference.values(seed, k * ranks + s, 0, n)
    return acc


def compare(kept: dict, seed: int, ranks: int, n: int):
    """(worst gap, elements compared) of this rank's kept answers, each
    input's whole."""
    refs = {}
    worst, compared = 0.0, 0
    for _, outs in sorted(kept.items()):
        for k, out in enumerate(outs):
            if out.shape != (n,):
                return float("inf"), compared
            if k not in refs:
                refs[k] = owed(seed, ranks, k, n)
            g = reference.gap("allreduce", np.asarray(out), refs[k])
            if not g <= worst:
                worst = g if g == g else float("inf")   # a NaN fails
            compared += n
    return worst, compared


def run(comm, spec: dict, opts, entry_wrap=None):
    """Drive one cell; the result dict on rank 0, None elsewhere.
    ``entry_wrap(comm, call) -> call`` lets a test break the timed path
    underneath (tests/test_cellbench_ep.py); the benchmark never passes
    it."""
    import jax
    import jax.numpy as jnp
    from ompi_tpu.op import op as mpi_op

    rank, P = comm.rank, comm.size
    cfg, traffic, pairing = spec["config"], spec["traffic"], spec["pairing"]
    posted = traffic["posted"]
    nbytes = traffic["bytes_per_input"]
    if opts.tiny:
        nbytes = max(4 * P, nbytes // traffic["tiny_divisor"])
    n = nbytes // 4
    red = getattr(mpi_op, traffic["reduce"].replace("MPI_", ""))
    bur = jax.block_until_ready
    say = opts.say if rank == 0 else (lambda msg: None)

    t_in = time.perf_counter()
    xs = make_inputs(jax, jnp, comm, opts.seed, n, posted, opts.control)
    inputs_s = time.perf_counter() - t_in
    post = getattr(comm, "i" + traffic["op"] + "_arr")

    def call(batch):
        reqs = [post(x, red) for x in batch]
        for q in reqs:
            q.wait()
        return [q.result for q in reqs]

    if entry_wrap is not None:
        call = entry_wrap(comm, call)
    t_w = time.perf_counter()
    N = warm_up(comm, call, xs, traffic,
                min(opts.seconds, traffic["trace_seconds"]) if opts.trace
                else opts.seconds, bur)
    warm_s = time.perf_counter() - t_w

    # which answers of the window are compared: the first, the last and
    # some between, on the first rank, the last and some between, all
    # drawn from the seed (the same on every rank)
    chk = pairing["check"]
    rng = np.random.default_rng([opts.seed & 0xFFFFFFFF, N, P])
    keep = frozenset({0, N - 1, *(int(i) for i in rng.integers(
        1, max(2, N - 1), size=max(0, chk["answers"] - 2)))})
    pick = {0, P - 1, *(int(r) for r in rng.integers(
        0, P, size=max(0, chk["ranks"] - 2)))}

    tracer = comm.state.tracer if opts.trace else None
    trace_dir = None
    comm.Barrier()
    before = pvars()
    comm.Barrier()   # nobody counts before everybody has read
    if opts.trace and rank == 0:
        trace_dir = os.path.join(opts.out_dir, "trace",
                                 spec["entry"]["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        po = jax.profiler.ProfileOptions()
        po.python_tracer_level = 0
        po.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=po)

    # -- the window --------------------------------------------------------
    comm.Barrier()
    wall_open, pc_open = time.time(), time.perf_counter()
    with (jax.profiler.TraceAnnotation(tracered.WINDOW)
          if trace_dir is not None else contextlib.nullcontext()):
        lat, kept, t_open, t_end = timed_loop(
            call, xs, N, bur, keep if rank in pick else ())
    comm.Barrier()
    if trace_dir is not None:
        t_st = time.perf_counter()
        jax.profiler.stop_trace()
        say(f"trace: stop_trace took {time.perf_counter() - t_st:.2f} s")
    after = pvars()

    # -- after the window: memory first, then free, then the reference ----
    provider = comm.coll.providers.get(traffic["op"] + "_arr", "none")
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.devices()), default=0) if rank == 0 else 0
    on_dev = all(isinstance(o, jax.Array) and comm.device in o.devices()
                 for outs in kept.values() for o in outs)
    del xs
    worst, compared = compare(kept, opts.seed, P, n) \
        if rank in pick else (0.0, 0)
    kept.clear()
    check_s = time.perf_counter() - t_end

    per_rank = gather(comm, [
        worst if np.isfinite(worst) else 1e300, compared,
        0 if on_dev else 1, len(lat), t_open, t_end, inputs_s, warm_s,
        comm.device.id])
    pooled = gather(comm, lat)
    spans = gather(comm, span_rows(tracer, wall_open,
                                   wall_open + (t_end - pc_open) + 1.0),
                   np.int64)
    if rank != 0:
        return None

    # -- rank 0 reduces ----------------------------------------------------
    col = dict(zip(RANK_FACTS, per_rank.T))
    window = col["t_end"].max() - col["t_open"].min()

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    staged = delta(STAGED)
    wrong_provider = provider != cfg["provider"]
    attempted = N * P
    incomplete = int(attempted - col["iters"].sum())
    failed = attempted if wrong_provider else min(
        attempted, staged + incomplete)
    limit = chk["limit"]
    checks = {
        "gap": {"value": float(col["gap"].max()), "limit": limit},
        "answers_elems": {"value": int(col["compared"].sum()),
                          "at_least": 1},
        "fused_collectives": {"value": int(delta(COLLS)),
                              "equals": posted * attempted},
        "fused_batches": {"value": int(delta(BATCHES)),
                          "equals": attempted},
        "host_staged": {"value": int(staged), "limit": 0},
        "wrong_provider": {"value": int(wrong_provider), "limit": 0},
        "off_device": {"value": int(col["off_device"].sum()), "limit": 0},
        "incomplete": {"value": incomplete, "limit": 0},
    }
    correct = bool(
        checks["gap"]["value"] <= limit
        and checks["answers_elems"]["value"] >= 1
        and delta(COLLS) == posted * attempted
        and delta(BATCHES) == attempted
        and not (staged or wrong_provider or incomplete
                 or checks["off_device"]["value"]))
    iter_us = window / N * 1e6
    devs = jax.devices()
    facts = {
        "op": traffic["op"], "ranks": P, "chips": cfg["chips"],
        "bytes_per_rank": posted * n * 4, "iters": N, "iter_us": iter_us,
        "iter_p95_us": float(np.percentile(pooled, 95)) * 1e6,
        "pvars_before": before, "pvars_after": after,
        "spans": spans, "phases": PHASES, "wall_open": wall_open,
        "platform": devs[0].platform,
        "device_ids": sorted({int(i) for i in col["device_id"]}),
        "kernel_events": pairing.get("kernel_events", []),
        "t0_epoch": opts.t0_epoch, "rank_main_epoch": opts.rank_main_epoch,
        "compile_or_load_s": opts.xla["compile_s"],
        "setup_s": wall_open - opts.t0_epoch,
        "peaks": opts.peaks, "describe_to": opts.describe_trace,
    }
    say(f"window: iters={N} per rank x {P} ranks, window_s={window:.6f}, "
        f"provider={provider}, posted={posted} of {n * 4} B, counters="
        + str({k: after[k] - before.get(k, 0) for k in after
               if k.startswith("coll_") and after[k] != before.get(k, 0)}))
    say(f"setup parts: inputs_s={col['inputs_s'].max():.3f} "
        f"warmup_s={col['warm_s'].max():.3f} "
        f"xla_compile_or_load_s={opts.xla['compile_s']:.3f} "
        f"persistent_cache_hits={opts.xla['cache_hits']} "
        f"misses={opts.xla['cache_misses']}")
    med = float(np.median(pooled))
    say(f"iterations: p50={med * 1e6:.1f} p95="
        f"{float(np.percentile(pooled, 95)) * 1e6:.1f} p99="
        f"{float(np.percentile(pooled, 99)) * 1e6:.1f} max="
        f"{float(pooled.max()) * 1e6:.1f} us; "
        f"peak_bytes_in_use={peak} reference_check_s={check_s:.2f}")

    return finish(spec, opts, facts, trace_dir, say, checks,
                  {"correct": correct, "attempted": attempted,
                   "failed": int(failed)}, int(peak))


def finish(spec: dict, opts, facts: dict, trace_dir, say, checks: dict,
           result: dict, peak: int) -> dict:
    """The result line's rest, as every generator of this benchmark
    makes it: the device, then the end-to-end metrics of a timed run or
    the per-layer metrics of a traced one (its trace reduced and its
    readers run), then the numbers compared."""
    import jax

    devs = jax.devices()
    metrics = result["metrics"] = {}
    device = result["device"] = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "memory_peak_bytes": peak}
    if not opts.trace:
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": facts[m["name"]],
                                  "unit": m["unit"]}
    else:
        t_rd = time.perf_counter()
        tr = facts["trace"] = tracered.reduce_dir(trace_dir, facts, say)
        say(f"trace: read and reduced in {time.perf_counter() - t_rd:.2f} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
        for m in spec["per_layer"]:
            ms = metric_spec(m["name"])
            reader = importlib.import_module(
                "cellbench.readers." + ms["reader"])
            v = reader.read(ms, facts, say)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tr.get("busy_s"):
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            result["breakdown"] = {"device_ops": tr["device_ops"],
                                   "idle_gaps": tr["idle_gaps"]}
    result["checks"] = checks
    return result
