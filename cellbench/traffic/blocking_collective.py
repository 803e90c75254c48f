"""The one general generator of this benchmark: a closed loop of blocking
collectives, as OSU's collective tests are.

Every rank-thread calls ``comm.<op>_arr(x)`` on a device-resident array
and then ``jax.block_until_ready`` on what it returned; one outstanding
operation per rank, no think time.  One *iteration* is one such
call-and-complete.  The operation, its size and the loop's lengths are
data (cellbench/traffic/<mix>.json); ranks, layout and provider are the
configuration's; nothing here names a cell.

Whole-window arithmetic.  The window opens at the barrier and closes
when the last rank completes its last iteration.  ``iter_us`` is that
time over the iterations each rank ran; ``iter_p95_us`` is the 95th
percentile of every iteration's entry-to-completion time pooled over
all ranks.  No number here is a median of chunks or of repeats.

The iteration count is fixed before the window opens, from the rate the
settle phase measured and ``--seconds``, so no host-side agreement sits
between timed iterations.
"""
from __future__ import annotations

import importlib
import os
import shutil
import time

import numpy as np

from cellbench import reference, tracered
from cellbench.manifest import metric_spec

SPAN_ROWS = 4096          # phase spans kept per rank for the reduction
PHASES = ("ph_rdv_wait", "ph_pack", "ph_dispatch", "ph_execute",
          "ph_unpack")


def sizes(traffic: dict, ranks: int, tiny: bool) -> int:
    """float32 elements per rank."""
    nbytes = traffic.get("bytes_per_rank") or \
        traffic["bytes_per_pair"] * ranks
    if tiny:
        nbytes = max(4 * ranks, nbytes // traffic["tiny_divisor"])
    return nbytes // 4


def pvars() -> dict:
    from ompi_tpu.mca.params import registry
    out = {}
    for p in registry.all_pvars():
        try:
            out[p.full_name] = int(p.read())
        except (TypeError, ValueError):
            pass
    return out


def gather(comm, vals, dtype=np.float64):
    """(size, n) on rank 0, None elsewhere."""
    s = np.ascontiguousarray(vals, dtype=dtype).reshape(-1)
    r = np.empty((comm.size, s.size), dtype) if comm.rank == 0 else None
    comm.Gather(s, r, root=0)
    return r


def bcast_int(comm, value: int) -> int:
    buf = np.array([value], np.int64)
    comm.Bcast(buf, root=0)
    return int(buf[0])


def timed_loop(call, x, n, bur, keep=()):
    """n iterations; returns (per-iteration seconds, kept results,
    time of entry, time of last completion).  One clock read per
    iteration: an iteration is entered when the one before completes."""
    pc = time.perf_counter
    lat = np.empty(n)
    kept = {}
    t0 = t = pc()
    for i in range(n):
        out = bur(call(x))
        t1 = pc()
        lat[i] = t1 - t
        t = t1
        if i in keep:
            kept[i] = out
    return lat, kept, t0, t


def span_rows(tracer, wall_lo: float, wall_hi: float) -> np.ndarray:
    """This rank's phase spans inside the window as rows of
    (phase index, start in wall-clock ns, duration ns, op sequence)."""
    rows = np.full((SPAN_ROWS, 4), -1, np.int64)
    if tracer is None:
        return rows
    k = 0
    for e in tracer.snapshot():
        if e.get("ph") != "X" or e["name"] not in PHASES:
            continue
        if not wall_lo <= e["ts"] <= wall_hi or k == SPAN_ROWS:
            continue
        rows[k] = (PHASES.index(e["name"]), int(e["ts"] * 1e9),
                   int(e["dur"] * 1e9), int(e["args"].get("seq", 0)))
        k += 1
    return rows


# what every rank hands rank 0 after the window, one float each
RANK_FACTS = ("gap", "compared", "off_device", "iters", "t_open", "t_end",
              "inputs_s", "warm_s", "device_id")


def make_input(jax, jnp, comm, seed: int, n: int, control):
    """This rank's input: on its device, from the seed, in one jitted
    call whose key is an argument (one program for every seed)."""
    make = jax.jit(lambda key: reference.values_from_key(key, 0, n, jnp))
    key = jax.device_put(np.uint32(reference.stream_key(seed, comm.rank)),
                         comm.device)
    x = make(key)
    if control == "bf16":
        # the lower-precision control: applied to what the library is
        # handed, never by an option of the library
        x = jax.jit(lambda a: a.astype(jnp.bfloat16))(x)
    return jax.block_until_ready(x)


def entry_of(comm, traffic: dict):
    """The entry a user calls, ``comm.<op>_arr``, with the mix's
    reduction bound where it has one."""
    from ompi_tpu.op import op as mpi_op
    entry = getattr(comm, traffic["op"] + "_arr")
    if not traffic.get("reduce"):
        return entry
    red = getattr(mpi_op, traffic["reduce"].replace("MPI_", ""))
    return lambda a: entry(a, red)


def warm_up(comm, call, x, traffic: dict, seconds: float, bur) -> int:
    """Warm this cell's one shape and fix the window's iteration count:
    a first call (compiles or loads), ``probe_iters`` to size the
    settle phase, ``settle_seconds`` of iterations whose rate, on the
    slowest rank, sets the count.  The same number on every rank."""
    bur(call(x))
    k = traffic["probe_iters"]
    n = 0
    for target in (traffic["settle_seconds"], seconds):
        comm.Barrier()
        _, _, t0, t1 = timed_loop(call, x, k, bur)
        per = gather(comm, [t1 - t0])
        n = bcast_int(comm, 0 if comm.rank else max(
            4, int(round(target * k / per.max()))))
        k = n
    return n


def compare(jax, jnp, kept: dict, op: str, seed: int, ranks: int, n: int,
            rank: int, chk: dict):
    """(worst gap, elements compared) of this rank's kept answers
    against the reference, in blocks drawn from the seed."""
    starts, blk = reference.block_starts(seed, n, chk["block_elems"],
                                         chk["blocks"])
    take = jax.jit(lambda a, s: jax.vmap(
        lambda st: jax.lax.dynamic_slice(a.reshape(-1), (st,), (blk,)))(s))
    worst, compared = 0.0, 0
    for _, out in sorted(kept.items()):
        if out.shape != (n,):
            return float("inf"), compared
        got = np.asarray(take(out, jnp.asarray(starts, jnp.int32)))
        for row, lo in zip(got, starts):
            ref = reference.expected(op, seed, ranks, n, rank, int(lo),
                                     int(lo) + blk)
            g = reference.gap(op, row, ref)
            if not g <= worst:
                worst = g if g == g else float("inf")   # a NaN fails
            compared += blk
    return worst, compared


def run(comm, spec: dict, opts, entry_wrap=None):
    """Drive one cell; the result dict on rank 0, None elsewhere.
    ``entry_wrap(comm, call) -> call`` lets a test break the timed path
    underneath (cellbench/tests); the benchmark never passes it."""
    import jax
    import jax.numpy as jnp

    rank, P = comm.rank, comm.size
    cfg, traffic, pairing = spec["config"], spec["traffic"], spec["pairing"]
    op, fname = traffic["op"], traffic["op"] + "_arr"
    n = sizes(traffic, P, opts.tiny)
    bur = jax.block_until_ready
    say = opts.say if rank == 0 else (lambda msg: None)

    t_in = time.perf_counter()
    x = make_input(jax, jnp, comm, opts.seed, n, opts.control)
    inputs_s = time.perf_counter() - t_in
    call = entry_of(comm, traffic)
    if entry_wrap is not None:
        call = entry_wrap(comm, call)
    t_w = time.perf_counter()
    N = warm_up(comm, call, x, traffic,
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
    if trace_dir is not None:
        with jax.profiler.TraceAnnotation(tracered.WINDOW):
            lat, kept, t_open, t_end = timed_loop(call, x, N, bur, keep)
    else:
        lat, kept, t_open, t_end = timed_loop(call, x, N, bur, keep)
    comm.Barrier()
    if trace_dir is not None:
        t_st = time.perf_counter()
        jax.profiler.stop_trace()
        say(f"trace: stop_trace took {time.perf_counter() - t_st:.2f} s")
    after = pvars()

    # -- after the window: memory first, then free, then the reference ----
    provider = comm.coll.providers.get(fname, "none")
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.devices()), default=0) if rank == 0 else 0
    on_dev = all(isinstance(o, jax.Array) and comm.device in o.devices()
                 for o in kept.values())
    del x
    worst, compared = compare(jax, jnp, kept, op, opts.seed, P, n, rank,
                              chk) if rank in pick else (0.0, 0)
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
    staged = after.get("coll_arr_host_staged_collectives", 0) \
        - before.get("coll_arr_host_staged_collectives", 0)
    wrong_provider = provider != cfg["provider"]
    attempted = N * P
    incomplete = int(attempted - col["iters"].sum())
    failed = attempted if wrong_provider else min(
        attempted, staged + incomplete)
    limit = 0.0 if reference.exact(op) else chk["limit"]
    checks = {
        "gap": {"value": float(col["gap"].max()), "limit": limit},
        "answers_elems": {"value": int(col["compared"].sum()),
                          "at_least": 1},
        "host_staged": {"value": int(staged), "limit": 0},
        "wrong_provider": {"value": int(wrong_provider), "limit": 0},
        "off_device": {"value": int(col["off_device"].sum()), "limit": 0},
        "incomplete": {"value": incomplete, "limit": 0},
    }
    correct = bool(
        limit is not None and checks["gap"]["value"] <= limit
        and checks["answers_elems"]["value"] >= 1
        and not (staged or wrong_provider or incomplete
                 or checks["off_device"]["value"]))
    iter_us = window / N * 1e6
    devs = jax.devices()
    facts = {
        "op": op, "ranks": P, "chips": cfg["chips"],
        "bytes_per_rank": n * 4, "iters": N, "iter_us": iter_us,
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
        f"provider={provider}, counters="
        + str({k: after[k] - before.get(k, 0) for k in after
               if k.startswith("coll_") and after[k] != before.get(k, 0)}))
    say(f"setup parts: inputs_s={col['inputs_s'].max():.3f} "
        f"warmup_s={col['warm_s'].max():.3f} "
        f"xla_compile_or_load_s={opts.xla['compile_s']:.3f} "
        f"persistent_cache_hits={opts.xla['cache_hits']} "
        f"misses={opts.xla['cache_misses']}")
    busbw = {"allreduce": 2 * (P - 1) / P, "alltoall": (P - 1) / P}[op] \
        * n * 4 / (iter_us * 1e-6) / 1e9
    say(f"busbw_GBs={busbw:.3f} (OSU convention) "
        f"peak_bytes_in_use={peak} reference_check_s={check_s:.2f}")

    metrics = {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": attempted,
              "failed": int(failed), "metrics": metrics, "device": device}
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
