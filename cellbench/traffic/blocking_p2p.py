"""The generator for blocking device-array POINT-TO-POINT: the closed
loop of cellbench/traffic/blocking_collective.py, for messages.

Every rank-thread calls ``comm.sendrecv_arr(x[k % 2], dst, src, tag)``
on a device-resident array and then ``jax.block_until_ready`` on what
it returned; one outstanding exchange per rank, no think time.  The
pattern (a ring in rank order: to rank + 1, from rank - 1), the size,
the tag and the loop's lengths are data (cellbench/traffic/<mix>.json);
ranks, layout and provider are the configuration's; nothing here names
a cell.

Two inputs per rank, alternating by the iteration's parity, each its
own stream of the seed, the rank and the parity
(cellbench/reference_p2p.py): every iteration would otherwise carry the
same bytes, and a message delivered one iteration late, or twice, could
not be seen.

The whole-window arithmetic (``iter_us``, ``iter_p95_us``), the warm-up
that fixes the iteration count, the gathers and the result line are
blocking_collective's: its helpers are imported, not copied.  The loop
is this file's, because of the parity.  What ``correct`` holds besides
the comparison (bit for bit, limit 0, both parities among the compared
answers, at least three ranks): every exchange was placed on the peer's
own device (``btl_tpu_d2d_sends`` = iterations x ranks), nothing went
through host memory (``btl_tpu_staged_sends``, ``btl_tpu_staged_bytes``,
``coll_arr_host_staged_collectives`` 0), nothing had to be placed again
on arrival (``btl_tpu_recv_moves`` 0), every answer is on the rank's own
device and every rank ran every iteration.

A library whose btl/tpu does not account for itself (no
``btl_tpu_d2d_sends``) is refused during set-up, on every rank alike and
before any rank waits for another: the run exits non-zero in seconds, it
does not hang.

The copy from chip to chip is a transfer the runtime issues, not an XLA
program: the profiler shows it on no device plane, so no metric of this
generator reads the device's time (PERF.md section 7).  The only
programs a rank runs between the window's barriers are the comparison's
own: once its last exchange has completed (the clock has stopped), a
compared rank cuts the compared blocks out of its kept answers on its
device (``pull``), and the closing barrier follows.  In a traced run the
result's ``busy_s`` is therefore those few slices and nothing of the
exchanges, and says so on stderr.  The transfers cut the chip's trace
short all the same (after some hundreds of them a chip records no later
program, PERF.md section 7), so the mix keeps its traced window to a
hundred or so exchanges a rank (``trace_seconds``).
"""
from __future__ import annotations

import contextlib
import importlib
import os
import shutil
import time

import numpy as np

from cellbench import reference, reference_p2p, tracered
from cellbench.manifest import metric_spec
from cellbench.traffic.blocking_collective import (
    RANK_FACTS, SPAN_ROWS, gather, pvars, sizes, warm_up)

# the library's p2p spans, by the way that served the call (the idle
# gaps of the device are named after them)
SPANS = ("send_arr_d2d", "send_arr_byref", "send_arr_staged",
         "send_arr_chunked", "recv_arr_inplace", "recv_arr_moved",
         "recv_arr_chunked")
# what has to stay at rest over the window, and what has to move once
# an exchange
AT_REST = ("btl_tpu_staged_sends", "btl_tpu_staged_bytes",
           "btl_tpu_recv_moves", "coll_arr_host_staged_collectives")
PLACED = "btl_tpu_d2d_sends"


def make_inputs(jax, jnp, comm, seed: int, n: int, control) -> tuple:
    """This rank's two inputs, one per parity: on its device, from the
    seed, by one jitted program whose key is an argument."""
    make = jax.jit(lambda key: reference.values_from_key(key, 0, n, jnp))
    xs = []
    for parity in (0, 1):
        key = jax.device_put(np.uint32(reference_p2p.stream_key(
            seed, comm.rank, parity)), comm.device)
        x = make(key)
        if control == "bf16":
            # the lower-precision control: what the library is handed is
            # rounded to bfloat16 on the host (and stays float32, so the
            # same bytes travel); never an option of the library
            x = jax.device_put(np.asarray(x).astype(jnp.bfloat16).astype(
                np.float32), comm.device)
        xs.append(jax.block_until_ready(x))
    return tuple(xs)


def entry_of(comm, traffic: dict):
    """The entry a user calls, ``comm.sendrecv_arr(x, dst, src, tag)``,
    with the pattern's neighbours bound."""
    src = reference_p2p.source(traffic["pattern"], comm.size, comm.rank)
    dst = reference_p2p.destination(traffic["pattern"], comm.size,
                                    comm.rank)
    tag = traffic["tag"]
    return lambda a: comm.sendrecv_arr(a, dst, src, tag)


def timed_ring(call, xs, n, bur, keep=()):
    """n iterations, input k % 2 in iteration k; returns (per-iteration
    seconds, kept results, time of entry, time of last completion).
    One clock read per iteration, as blocking_collective.timed_loop."""
    pc = time.perf_counter
    lat = np.empty(n)
    kept = {}
    t0 = t = pc()
    for i in range(n):
        out = bur(call(xs[i & 1]))
        t1 = pc()
        lat[i] = t1 - t
        t = t1
        if i in keep:
            kept[i] = out
    return lat, kept, t0, t


def kept_iterations(rng, n: int, answers: int) -> frozenset:
    """The first, the last and some between, drawn from the seed; both
    parities are always among them."""
    mid = [int(i) for i in rng.integers(1, max(2, n - 1),
                                        size=max(1, answers - 2))]
    if n > 2 and not (n - 1) & 1 and not mid[0] & 1:
        mid[0] += 1      # iteration 0 and the last are even: an odd one
    return frozenset({0, n - 1, *mid})


def span_rows(tracer, wall_lo: float, wall_hi: float) -> np.ndarray:
    """This rank's p2p spans inside the window as rows of (index into
    SPANS, start in wall-clock ns, duration ns, 0)."""
    rows = np.full((SPAN_ROWS, 4), -1, np.int64)
    if tracer is None:
        return rows
    k = 0
    for e in tracer.snapshot():
        if k == SPAN_ROWS:
            break
        if e.get("ph") == "X" and e["name"] in SPANS \
                and wall_lo <= e["ts"] <= wall_hi:
            rows[k] = (SPANS.index(e["name"]), int(e["ts"] * 1e9),
                       int(e["dur"] * 1e9), 0)
            k += 1
    return rows


def slicer(jax, seed: int, n: int, chk: dict):
    """(take, starts, block): the jitted program that cuts the compared
    blocks, drawn from the seed, out of an answer on its device."""
    starts, blk = reference.block_starts(seed, n, chk["block_elems"],
                                         chk["blocks"])
    take = jax.jit(lambda a, s: jax.vmap(
        lambda st: jax.lax.dynamic_slice(a.reshape(-1), (st,), (blk,)))(s))
    return take, starts, blk


def pull(take, jnp, kept: dict, starts, n: int) -> dict:
    """The compared blocks of this rank's kept answers, cut on the
    device and brought to the host; None for an answer that is not n
    float32."""
    at = jnp.asarray(starts, jnp.int32)
    return {it: np.asarray(take(out, at))
            if getattr(out, "shape", None) == (n,)
            and np.dtype(out.dtype) == np.float32 else None
            for it, out in sorted(kept.items())}


def compare(got: dict, starts, blk: int, pattern: str, seed: int,
            ranks: int, n: int, rank: int):
    """(worst gap, elements compared) of this rank's pulled blocks
    against the reference; host only."""
    worst, compared = 0.0, 0
    for it, rows in got.items():
        if rows is None:
            return float("inf"), compared
        for row, lo in zip(rows, starts):
            ref = reference_p2p.expected(pattern, seed, ranks, n, rank, it,
                                         int(lo), int(lo) + blk)
            g = reference_p2p.gap(row, ref)
            if not g <= worst:
                worst = g if g == g else float("inf")   # a NaN fails
            compared += blk
    return worst, compared


def layout_line(jax, comm, pattern: str, ids) -> str:
    """Where the ranks sit and what each hop of the pattern crosses, from
    the devices' own coordinates (a chip of a 2x2 host has a link to each
    chip that differs in one coordinate)."""
    by_id = {d.id: d for d in jax.devices()}
    at = [tuple(getattr(by_id[int(i)], "coords", ()) or ())
          for i in ids]
    hops = []
    for r in range(comm.size):
        s = reference_p2p.source(pattern, comm.size, r)
        d = sum(a != b for a, b in zip(at[s], at[r])) if at[r] else -1
        hops.append(f"{s}->{r}:{d if d >= 0 else '?'}")
    return (f"ranks on device ids {[int(i) for i in ids]} at coords "
            f"{at}; hops (from->to:coordinates that differ) "
            + " ".join(hops))


def run(comm, spec: dict, opts, entry_wrap=None):
    """Drive one cell; the result dict on rank 0, None elsewhere.
    ``entry_wrap(comm, call) -> call`` lets a test break the timed path
    underneath (tests/test_cellbench_p2p.py); the benchmark never passes
    it."""
    import jax
    import jax.numpy as jnp

    rank, P = comm.rank, comm.size
    cfg, traffic, pairing = spec["config"], spec["traffic"], spec["pairing"]
    pattern = traffic["pattern"]
    n = sizes(traffic, P, opts.tiny)
    bur = jax.block_until_ready
    say = opts.say if rank == 0 else (lambda msg: None)

    import ompi_tpu.btl.tpu  # noqa: F401  (registers its variables)
    if PLACED not in pvars():
        # the same on every rank-thread, and before any of them waits
        raise RuntimeError(
            f"this library's btl/tpu has no {PLACED}: a device-array "
            "message cannot say how it travelled, so the cell's "
            "guarantees (placed chip to chip, never host-staged) cannot "
            "be held; refusing before the window")

    t_in = time.perf_counter()
    xs = make_inputs(jax, jnp, comm, opts.seed, n, opts.control)
    inputs_s = time.perf_counter() - t_in
    call = entry_of(comm, traffic)
    if entry_wrap is not None:
        call = entry_wrap(comm, call)
    turn = [0]

    def in_turn(_x):
        # blocking_collective's warm-up hands every iteration the same
        # argument: send the two inputs in turn there too
        turn[0] ^= 1
        return call(xs[turn[0]])

    t_w = time.perf_counter()
    N = warm_up(comm, in_turn, None, traffic,
                min(opts.seconds, traffic["trace_seconds"]) if opts.trace
                else opts.seconds, bur)
    warm_s = time.perf_counter() - t_w

    # which answers of the window are compared: the first, the last and
    # one between (both parities), on at least three ranks, all drawn
    # from the seed (the same on every rank)
    chk = pairing["check"]
    rng = np.random.default_rng([opts.seed & 0xFFFFFFFF, N, P])
    keep = kept_iterations(rng, N, chk["answers"])
    pick = {int(r) for r in rng.permutation(P)[:max(3, chk["ranks"])]}
    # the comparison's one program, compiled before the window on every
    # rank that will run it
    take, starts, blk = slicer(jax, opts.seed, n, chk)
    if rank in pick:
        pull(take, jnp, {0: xs[0]}, starts, n)

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

    # -- the window: the exchanges (the clock stops at t_end) and the
    # barrier that closes them, which also closes the tracer's last
    # caller interval; then the compared ranks cut their blocks on the
    # device, in no layer's account, and a barrier holds the trace open
    # until the last of them has ----------------------------------------
    comm.Barrier()
    wall_open, pc_open = time.time(), time.perf_counter()
    with (jax.profiler.TraceAnnotation(tracered.WINDOW)
          if trace_dir is not None else contextlib.nullcontext()):
        lat, kept, t_open, t_end = timed_ring(call, xs, N, bur, keep)
        comm.Barrier()
        on_dev = all(isinstance(o, jax.Array) and comm.device in o.devices()
                     for o in kept.values())
        got = pull(take, jnp, kept, starts, n) if rank in pick else {}
        comm.Barrier()
    if trace_dir is not None:
        t_st = time.perf_counter()
        jax.profiler.stop_trace()
        say(f"trace: stop_trace took {time.perf_counter() - t_st:.2f} s")
    after = pvars()

    # -- after the window: memory first, then free, then the reference ----
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.devices()), default=0) if rank == 0 else 0
    del xs
    kept.clear()
    worst, compared = compare(got, starts, blk, pattern, opts.seed, P, n,
                              rank)
    parities = len({it & 1 for it in got}) if compared else 0
    got.clear()
    check_s = time.perf_counter() - t_end

    per_rank = gather(comm, [
        worst if np.isfinite(worst) else 1e300, compared,
        0 if on_dev else 1, len(lat), t_open, t_end, inputs_s, warm_s,
        comm.device.id, parities])
    pooled = gather(comm, lat)
    spans = gather(comm, span_rows(tracer, wall_open,
                                   wall_open + (t_end - pc_open) + 1.0),
                   np.int64)
    if rank != 0:
        return None

    # -- rank 0 reduces ----------------------------------------------------
    col = dict(zip(RANK_FACTS + ("parities",), per_rank.T))
    window = col["t_end"].max() - col["t_open"].min()

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    attempted = N * P
    placed = delta(PLACED)
    rest = {k: int(delta(k)) for k in AT_REST}
    # the provider, from what the counters say served the window
    if placed == attempted and not any(rest.values()):
        provider = "btl/tpu"
    elif delta("btl_tpu_byref_sends"):
        provider = "btl/tpu by reference (a peer without a device)"
    else:
        provider = "host-staged"
    wrong_provider = provider != cfg["provider"]
    incomplete = int(attempted - col["iters"].sum())
    failed = attempted if wrong_provider else min(
        attempted, sum(rest.values()) + incomplete)
    compared_ranks = int((col["compared"] > 0).sum())
    both = int(col["parities"][col["compared"] > 0].min()) \
        if compared_ranks else 0
    checks = {
        "gap": {"value": float(col["gap"].max()), "limit": 0.0},
        "answers_elems": {"value": int(col["compared"].sum()),
                          "at_least": 1},
        "ranks_compared": {"value": compared_ranks,
                           "at_least": min(P, 3)},
        "parities_compared": {"value": both, "at_least": 2},
        "d2d_sends": {"value": int(placed), "equals": attempted},
        "staged_sends": {"value": rest["btl_tpu_staged_sends"], "limit": 0},
        "staged_bytes": {"value": rest["btl_tpu_staged_bytes"], "limit": 0},
        "recv_moves": {"value": rest["btl_tpu_recv_moves"], "limit": 0},
        "host_staged": {"value": rest["coll_arr_host_staged_collectives"],
                        "limit": 0},
        "wrong_provider": {"value": int(wrong_provider), "limit": 0},
        "off_device": {"value": int(col["off_device"].sum()), "limit": 0},
        "incomplete": {"value": incomplete, "limit": 0},
    }
    correct = bool(
        checks["gap"]["value"] <= 0.0
        and checks["answers_elems"]["value"] >= 1
        and compared_ranks >= min(P, 3) and both >= 2
        and placed == attempted
        and not (any(rest.values()) or wrong_provider or incomplete
                 or checks["off_device"]["value"]))
    iter_us = window / N * 1e6
    devs = jax.devices()
    facts = {
        "op": traffic["op"], "ranks": P, "chips": cfg["chips"],
        "bytes_per_rank": n * 4, "iters": N, "iter_us": iter_us,
        "iter_p95_us": float(np.percentile(pooled, 95)) * 1e6,
        "pvars_before": before, "pvars_after": after,
        "spans": spans, "phases": SPANS, "wall_open": wall_open,
        "platform": devs[0].platform,
        "device_ids": sorted({int(i) for i in col["device_id"]}),
        "kernel_events": pairing.get("kernel_events", []),
        "t0_epoch": opts.t0_epoch, "rank_main_epoch": opts.rank_main_epoch,
        "compile_or_load_s": opts.xla["compile_s"],
        "setup_s": wall_open - opts.t0_epoch,
        "describe_to": opts.describe_trace,
    }
    say("layout: " + layout_line(jax, comm, pattern, col["device_id"]))
    say(f"window: iters={N} per rank x {P} ranks, window_s={window:.6f}, "
        f"provider={provider}, pattern={pattern}, compared iterations="
        f"{sorted(keep)} on ranks="
        f"{sorted(int(r) for r in np.flatnonzero(col['compared'] > 0))}, "
        "counters="
        + str({k: after[k] - before.get(k, 0) for k in after
               if k.startswith(("btl_tpu_", "coll_"))
               and after[k] != before.get(k, 0)}))
    say(f"setup parts: inputs_s={col['inputs_s'].max():.3f} "
        f"warmup_s={col['warm_s'].max():.3f} "
        f"xla_compile_or_load_s={opts.xla['compile_s']:.3f} "
        f"persistent_cache_hits={opts.xla['cache_hits']} "
        f"misses={opts.xla['cache_misses']}")
    say(f"bw_GBs={n * 4 / (iter_us * 1e-6) / 1e9:.3f} (message bytes a "
        f"rank sends over iter_us; it receives as many) "
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
            say("trace: the exchanges are transfers of the runtime, on no "
                "device plane; busy_s is the comparison's own slices of the "
                f"kept answers ({len(keep)} a compared rank, after the "
                "clock stopped): " + str(tr["device_ops"]))
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            result["breakdown"] = {"device_ops": tr["device_ops"],
                                   "idle_gaps": tr["idle_gaps"]}
    result["checks"] = checks
    return result
