"""The generator for ROOTED blocking collectives: the closed loop of
cellbench/traffic/blocking_collective.py, for an operation that has a
root and so another entry, another reference and another choice of the
ranks compared.

Every rank-thread calls ``comm.<op>_arr(x, root)`` on a device-resident
array and then ``jax.block_until_ready`` on what it returned; one
outstanding operation per rank, no think time.  The operation, its
root, its size and the loop's lengths are data
(cellbench/traffic/<mix>.json); ranks, layout and provider are the
configuration's; nothing here names a cell.

The window, the whole-window arithmetic (``iter_us``), the warm-up that
fixes the iteration count, the inputs (every rank its own stream of the
seed), the ``checks``, the ``failed`` rule and the result line are
blocking_collective's: its helpers are imported, not copied.  What
differs: the entry takes the root; the answers are compared with
cellbench/reference_rooted.py, bit for bit (limit 0); and the ranks
compared are the root and at least two others, so an answer that is a
non-root's own input cannot pass unseen.
"""
from __future__ import annotations

import importlib
import os
import shutil
import time

import numpy as np

from cellbench import reference, reference_rooted, tracered
from cellbench.manifest import metric_spec
from cellbench.traffic.blocking_collective import (
    PHASES, RANK_FACTS, gather, make_input, pvars, sizes, span_rows,
    timed_loop, warm_up)


def entry_of(comm, traffic: dict):
    """The entry a user calls, ``comm.<op>_arr(x, root)``."""
    entry, root = getattr(comm, traffic["op"] + "_arr"), traffic["root"]
    return lambda a: entry(a, root)


def picked_ranks(rng, ranks: int, root: int, want: int) -> set:
    """The root, the rank before it on the ring (the last a circulation
    reaches) and others drawn from the seed: at least two are not the
    root wherever the communicator has them."""
    others = [(root + d) % ranks for d in range(1, ranks)]
    pick = {root, *others[-1:]}
    rest = others[:-1]
    for i in rng.permutation(len(rest))[:max(1, want - 2)]:
        pick.add(rest[int(i)])
    return pick


def compare(jax, jnp, kept: dict, op: str, seed: int, ranks: int, n: int,
            rank: int, root: int, chk: dict):
    """(worst gap, elements compared) of this rank's kept answers
    against the rooted reference, in blocks drawn from the seed."""
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
            ref = reference_rooted.expected(op, seed, ranks, n, rank, root,
                                            int(lo), int(lo) + blk)
            g = reference_rooted.gap(row, ref)
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
    op, fname, root = traffic["op"], traffic["op"] + "_arr", traffic["root"]
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
    # some between, on the root and on at least two other ranks, all
    # drawn from the seed (the same on every rank)
    chk = pairing["check"]
    rng = np.random.default_rng([opts.seed & 0xFFFFFFFF, N, P])
    keep = frozenset({0, N - 1, *(int(i) for i in rng.integers(
        1, max(2, N - 1), size=max(0, chk["answers"] - 2)))})
    pick = picked_ranks(rng, P, root, chk["ranks"])

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
                              root, chk) if rank in pick else (0.0, 0)
    kept.clear()
    check_s = time.perf_counter() - t_end

    per_rank = gather(comm, [
        worst if np.isfinite(worst) else 1e300, compared,
        0 if on_dev else 1, len(lat), t_open, t_end, inputs_s, warm_s,
        comm.device.id])
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
    compared_ranks = int((col["compared"] > 0).sum())
    checks = {
        "gap": {"value": float(col["gap"].max()), "limit": 0.0},
        "answers_elems": {"value": int(col["compared"].sum()),
                          "at_least": 1},
        "ranks_compared": {"value": compared_ranks,
                           "at_least": min(P, 3)},
        "host_staged": {"value": int(staged), "limit": 0},
        "wrong_provider": {"value": int(wrong_provider), "limit": 0},
        "off_device": {"value": int(col["off_device"].sum()), "limit": 0},
        "incomplete": {"value": incomplete, "limit": 0},
    }
    correct = bool(
        checks["gap"]["value"] <= 0.0
        and checks["answers_elems"]["value"] >= 1
        and compared_ranks >= min(P, 3)
        and not (staged or wrong_provider or incomplete
                 or checks["off_device"]["value"]))
    iter_us = window / N * 1e6
    devs = jax.devices()
    facts = {
        "op": op, "ranks": P, "chips": cfg["chips"],
        "bytes_per_rank": n * 4, "iters": N, "iter_us": iter_us,
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
        f"provider={provider}, root={root}, compared ranks="
        f"{sorted(int(r) for r in np.flatnonzero(col['compared'] > 0))}, "
        "counters="
        + str({k: after[k] - before.get(k, 0) for k in after
               if k.startswith("coll_") and after[k] != before.get(k, 0)}))
    say(f"setup parts: inputs_s={col['inputs_s'].max():.3f} "
        f"warmup_s={col['warm_s'].max():.3f} "
        f"xla_compile_or_load_s={opts.xla['compile_s']:.3f} "
        f"persistent_cache_hits={opts.xla['cache_hits']} "
        f"misses={opts.xla['cache_misses']}")
    say(f"bw_GBs={n * 4 / (iter_us * 1e-6) / 1e9:.3f} (message bytes over "
        f"iter_us, OSU's bcast convention) peak_bytes_in_use={peak} "
        f"reference_check_s={check_s:.2f}")

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
