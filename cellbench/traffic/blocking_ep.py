"""The generator for expert-parallel dispatch and combine on device
arrays: the closed loop of cellbench/traffic/blocking_ragged.py around
TWO ``comm.alltoallv_arr`` calls of token rows, with DeepSeek-V3's
routing as DeepEP's normal kernels move it.

Every rank-thread holds two routing sets (one per parity of the
iteration; cellbench/reference_ep.py states the rule), and for each a
dispatch buffer (its tokens' records grouped by destination rank,
tokens ascending in a block) and a combine buffer (a stand-in expert
output for every row it receives, in the order it receives them).  In
iteration k it calls

    y = comm.alltoallv_arr(dispatch[k % 2], send[k % 2], recv[k % 2],
                           capacity=C)
    z = comm.alltoallv_arr(combine[k % 2], recv[k % 2], send[k % 2],
                           capacity=C)

then ``jax.block_until_ready`` on both: the blocking form of DeepEP's
``dispatch`` then ``combine``, one micro-batch of one MoE layer, no
think time.  Widths, routing and tokens are the configuration's and the
mix's (cellbench/configs/<config>.json, cellbench/traffic/<mix>.json);
nothing here names a cell.  Every send buffer and result has
C = ranks x tokens rows, the most a rank can send or receive, so that
every rank's buffers have one shape.

Set-up, outside the window: the routing on the host (the reference's
own rule), the count exchange through the library's HOST ``Alltoall``
(DeepEP's layout notification), the buffers made on the device from the
seed in one jitted call each.

Required bytes.  The readers are handed ``op`` "alltoall" and
``bytes_per_rank`` = the largest over ranks of the mean bytes a rank
SENDS an iteration, dispatch plus combine, from its own counts:
cellbench/bytes.py's four-chip alltoall row (every sent byte read and
written once, (P - 1) / P of them over ICI).  The capacity is not
work.

The window, the whole-window arithmetic, the warm-up and the gathers
are blocking_collective's, the loop that alternates by parity and the
choice of the kept iterations blocking_p2p's, the result line's metrics
nonblocking_batch's (``finish``).

``correct``.  Every tolerance is 0, because the operation moves bits:
rows of the first, the last and one seeded iteration between (both
parities among them), of BOTH calls, on 3 ranks, in blocks of rows drawn
from the seed with the block that ends the received rows among them,
equal reference_ep.py's word for word (``gap`` 0); the rows a rank
receives in the dispatch are what the reference says (the count
exchange); ``coll_alltoallv_device_ops`` = 2 x iterations x ranks and
``coll_alltoallv_bytes`` the reference's (the counts and no padded
bound); nothing host-staged; nothing compiled inside the window; the
configuration's provider; results on the rank's own device.

The control (``--control bf16``'s slot): the low byte of every element
of both buffers cleared on the host before they are handed over: the
same counts and bytes, other bits than the reference owes.  It must
read ``correct`` false.
"""
from __future__ import annotations

import contextlib
import functools
import os
import shutil
import threading
import time

import numpy as np

from cellbench import reference, reference_ep, tracered
from cellbench.traffic.blocking_collective import (
    PHASES, RANK_FACTS, gather, pvars, span_rows, warm_up)
from cellbench.traffic.blocking_p2p import kept_iterations, timed_ring
from cellbench.traffic.nonblocking_batch import finish

# what has to stay at rest over the window
AT_REST = ("coll_arr_host_staged_collectives", "coll_device_cache_misses",
           "coll_plan_builds")
OPS, BYTES = "coll_alltoallv_device_ops", "coll_alltoallv_bytes"

# the reference's routing sets, shared by the rank-threads of the one
# app shell: (seed, parity, setting) -> reference_ep.exchange
_EXCHANGES: dict = {}
_EXCHANGES_LOCK = threading.Lock()


def exchange_of(seed: int, parity: int, st: dict) -> dict:
    """One routing set of every rank (reference_ep.exchange), made once
    a process."""
    k = (seed, parity, tuple(sorted(st.items())))
    with _EXCHANGES_LOCK:
        got = _EXCHANGES.get(k)
    if got is None:
        got = reference_ep.exchange(seed, parity, st)
        with _EXCHANGES_LOCK:
            got = _EXCHANGES.setdefault(k, got)
    return got


@functools.lru_cache(maxsize=None)
def makers(words: int, hidden: int, tokens: int):
    """The set-up's two programs, shared by the rank-threads: a rank's
    dispatch rows (``records``) and combine rows (``standins``, as
    bfloat16) for row identities given as arrays."""
    import jax
    import jax.numpy as jnp

    st = {"hidden": hidden, "tokens": tokens}

    def dispatch(key, tok, ids, w):
        return reference_ep.records(key, tok, ids, w, words, jnp)

    def combine(key, src, tok):
        return jax.lax.bitcast_convert_type(
            reference_ep.standins(key, src, tok, st, jnp), jnp.bfloat16)

    return jax.jit(dispatch), jax.jit(combine)


def padded(a: np.ndarray, n: int) -> np.ndarray:
    """``a`` as int32 with zeros to ``n`` entries."""
    out = np.zeros(n, np.int32)
    out[:a.size] = a
    return out


def make_buffers(jax, comm, seed: int, st: dict, control) -> list:
    """This rank's two iterations' worth, one per parity: (dispatch
    buffer, combine buffer, dispatch send counts, dispatch receive
    counts), the buffers on the device."""
    cap, words = reference_ep.capacity(st), reference_ep.record_words(st)
    dispatch, combine = makers(words, st["hidden"], st["tokens"])
    me, dev = comm.rank, comm.device
    sets = []
    for parity in (0, 1):
        ex = exchange_of(seed, parity, st)
        route, order = ex["routes"][me], ex["order"][me]
        send = np.array([len(o) for o in order], np.int64)
        recv = np.empty_like(send)
        comm.Alltoall(send, recv)
        put = functools.partial(jax.device_put, device=dev)
        x = dispatch(put(np.uint32(reference_ep.key(
            seed, reference_ep.RECORD, me, parity))),
            put(padded(np.concatenate(order), cap)), put(route["ids"]),
            put(route["w"]))
        src, tok = reference_ep.received(ex, me)
        c = combine(put(np.uint32(reference_ep.key(
            seed, reference_ep.STANDIN, me, parity))),
            put(padded(src, cap)), put(padded(tok, cap)))
        if control == "bf16":
            # the control: applied to what the library is handed, never
            # by an option of the library; counts and bytes unchanged
            x = put(np.asarray(x) & np.uint32(0xFFFFFF00))
            c = put((np.ascontiguousarray(c).view(np.uint16)
                     & np.uint16(0xFF00)).view(c.dtype))
        sets.append((jax.block_until_ready(x), jax.block_until_ready(c),
                     send, recv))
    return sets


def compare(jax, kept: dict, rank: int, seed: int, st: dict, chk: dict):
    """(worst gap, rows compared) of this rank's kept answers of both
    calls against reference_ep.py, in blocks of rows drawn from the
    seed; an answer of the wrong shape or type is infinitely far."""
    cap, words = reference_ep.capacity(st), reference_ep.record_words(st)
    takes = {}

    def take(a, starts, blk):
        fn = takes.get(blk)
        if fn is None:
            fn = takes[blk] = jax.jit(lambda a, s: jax.vmap(
                lambda at: jax.lax.dynamic_slice_in_dim(a, at, blk))(s))
        return np.ascontiguousarray(fn(a, np.asarray(starts, np.int32)))

    worst, compared = 0.0, 0
    for it, (y, z) in sorted(kept.items()):
        ex = exchange_of(seed, it & 1, st)
        for out, shape, dtype, rows, owed in (
                (y, (cap, words), np.uint32,
                 int(ex["counts"][:, rank].sum()), reference_ep.dispatch_owed),
                (z, (cap, st["hidden"]), jax.numpy.bfloat16,
                 int(ex["counts"][rank].sum()), reference_ep.combine_owed)):
            if out.shape != shape or np.dtype(out.dtype) != np.dtype(dtype):
                return float("inf"), compared
            if not rows:
                continue
            starts, blk = reference.block_starts(
                seed + it, rows, chk["block_rows"], chk["blocks"])
            got = take(out, starts, blk)
            if got.dtype != np.uint32:
                got = got.view(np.uint16)
            for row, lo in zip(got, starts):
                g = reference_ep.gap(
                    row, owed(seed, ex, rank, st, int(lo), int(lo) + blk))
                if not g <= worst:
                    worst = g if g == g else float("inf")
                compared += blk
    return worst, compared


def run(comm, spec: dict, opts, entry_wrap=None):
    """Drive one cell; the result dict on rank 0, None elsewhere.
    ``entry_wrap(comm, call) -> call`` lets a test break the timed path
    underneath (tests/test_cellbench_ep.py); the benchmark never passes
    it."""
    import jax

    rank, P = comm.rank, comm.size
    cfg, traffic, pairing = spec["config"], spec["traffic"], spec["pairing"]
    st = reference_ep.setting(cfg, traffic, opts.tiny)
    cap, words = reference_ep.capacity(st), reference_ep.record_words(st)
    row_bytes = (words * 4, st["hidden"] * 2)
    bur = jax.block_until_ready
    say = opts.say if rank == 0 else (lambda msg: None)

    t_in = time.perf_counter()
    sets = make_buffers(jax, comm, opts.seed, st, opts.control)
    inputs_s = time.perf_counter() - t_in
    entry = comm.alltoallv_arr

    def call(parity):
        x, c, send, recv = sets[parity]
        y = entry(x, send, recv, capacity=cap)
        return y, entry(c, recv, send, capacity=cap)

    if entry_wrap is not None:
        call = entry_wrap(comm, call)
    turn = [0]

    def in_turn(_x):
        # blocking_collective's warm-up hands every iteration the same
        # argument: the two routing sets in turn there too
        turn[0] ^= 1
        return call(turn[0])

    t_w = time.perf_counter()
    N = warm_up(comm, in_turn, None, traffic,
                min(opts.seconds, traffic["trace_seconds"]) if opts.trace
                else opts.seconds, bur)
    warm_s = time.perf_counter() - t_w

    # which answers of the window are compared: the first, the last and
    # one between (both parities), on three ranks, all drawn from the
    # seed (the same on every rank); only those ranks keep answers
    chk = pairing["check"]
    rng = np.random.default_rng([opts.seed & 0xFFFFFFFF, N, P])
    keep = kept_iterations(rng, N, chk["answers"])
    pick = {int(r) for r in rng.permutation(P)[:max(3, chk["ranks"])]}

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
        # blocking_p2p's loop: routing set k % 2 in iteration k
        lat, kept, t_open, t_end = timed_ring(
            call, (0, 1), N, bur, keep if rank in pick else ())
    comm.Barrier()
    if trace_dir is not None:
        t_st = time.perf_counter()
        jax.profiler.stop_trace()
        say(f"trace: stop_trace took {time.perf_counter() - t_st:.2f} s")
    after = pvars()

    # -- after the window: memory first, then free, then the reference ----
    provider = comm.coll.providers.get("alltoallv_arr", "none")
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.devices()), default=0) if rank == 0 else 0
    on_dev = all(isinstance(o, jax.Array) and comm.device in o.devices()
                 for pair in kept.values() for o in pair)
    sends = [s[2] for s in sets]
    recvs = [s[3] for s in sets]
    del sets
    worst, compared = 0.0, 0
    if rank in pick:
        worst, compared = compare(jax, kept, rank, opts.seed, st, chk)
    parities = len({it & 1 for it in kept}) if compared else 0
    kept.clear()
    owed = [int(exchange_of(opts.seed, p, st)["counts"][:, rank].sum())
            for p in (0, 1)]
    recv_off = sum(abs(int(r.sum()) - o) for r, o in zip(recvs, owed))
    # what this rank sends an iteration, dispatch plus combine, the
    # mean of the two routing sets (its own counts)
    sent_bytes = sum(int(s.sum()) * row_bytes[0] + int(r.sum())
                     * row_bytes[1] for s, r in zip(sends, recvs)) / 2
    check_s = time.perf_counter() - t_end

    per_rank = gather(comm, [
        worst if np.isfinite(worst) else 1e300, compared,
        0 if on_dev else 1, len(lat), t_open, t_end, inputs_s, warm_s,
        comm.device.id, parities, recv_off, sent_bytes])
    pooled = gather(comm, lat)
    spans = gather(comm, span_rows(tracer, wall_open,
                                   wall_open + (t_end - pc_open) + 1.0),
                   np.int64)
    if rank != 0:
        return None

    # -- rank 0 reduces ----------------------------------------------------
    col = dict(zip(RANK_FACTS + ("parities", "recv_off", "sent_bytes"),
                   per_rank.T))
    window = col["t_end"].max() - col["t_open"].min()

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    attempted = N * P
    rest = {k: int(delta(k)) for k in AT_REST}
    staged = rest["coll_arr_host_staged_collectives"]
    wrong_provider = provider != cfg["provider"]
    incomplete = int(attempted - col["iters"].sum())
    failed = attempted if wrong_provider else min(
        attempted, staged + incomplete)
    compared_ranks = int((col["compared"] > 0).sum())
    both = int(col["parities"][col["compared"] > 0].min()) \
        if compared_ranks else 0
    # every dispatched row is sent once in the dispatch and once back in
    # the combine; iterations 0, 2, ... use routing set 0
    rows = [int(exchange_of(opts.seed, p, st)["counts"].sum())
            for p in (0, 1)]
    bytes_owed = ((N + 1) // 2 * rows[0] + N // 2 * rows[1]) \
        * sum(row_bytes)
    checks = {
        "gap": {"value": float(col["gap"].max()), "limit": 0.0},
        "answers_rows": {"value": int(col["compared"].sum()),
                         "at_least": 1},
        "ranks_compared": {"value": compared_ranks,
                           "at_least": min(P, 3)},
        "parities_compared": {"value": both, "at_least": 2},
        "recv_rows_off": {"value": int(col["recv_off"].sum()), "limit": 0},
        "device_ops": {"value": int(delta(OPS)), "equals": 2 * attempted},
        "device_bytes": {"value": int(delta(BYTES)), "equals": bytes_owed},
        "host_staged": {"value": staged, "limit": 0},
        "compiled_in_window": {
            "value": rest["coll_device_cache_misses"]
            + rest["coll_plan_builds"], "limit": 0},
        "wrong_provider": {"value": int(wrong_provider), "limit": 0},
        "off_device": {"value": int(col["off_device"].sum()), "limit": 0},
        "incomplete": {"value": incomplete, "limit": 0},
    }
    correct = bool(
        checks["gap"]["value"] <= 0.0
        and checks["answers_rows"]["value"] >= 1
        and compared_ranks >= min(P, 3) and both >= 2
        and delta(OPS) == 2 * attempted and delta(BYTES) == bytes_owed
        and not (any(rest.values()) or wrong_provider or incomplete
                 or checks["off_device"]["value"]
                 or checks["recv_rows_off"]["value"]))
    iter_us = window / N * 1e6
    devs = jax.devices()
    bytes_per_rank = int(col["sent_bytes"].max())
    facts = {
        # the required-bytes row: an alltoall of S = the bytes a rank
        # sends an iteration (this file's head says why)
        "op": "alltoall", "ranks": P, "chips": cfg["chips"],
        "bytes_per_rank": bytes_per_rank, "iters": N, "iter_us": iter_us,
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
    ex0 = exchange_of(opts.seed, 0, st)
    say(f"window: iters={N} per rank x {P} ranks, window_s={window:.6f}, "
        f"provider={provider}, tokens a rank={st['tokens']}, capacity="
        f"{cap} rows, dispatch counts (routing set 0) "
        f"{ex0['counts'].tolist()}, rows dispatched {rows}, ranks a token "
        f"{np.mean([r['owners'].sum(1).mean() for r in ex0['routes']]):.3f}"
        f", compared iterations={sorted(keep)} on ranks="
        f"{sorted(int(r) for r in np.flatnonzero(col['compared'] > 0))}, "
        "counters="
        + str({k: after[k] - before.get(k, 0) for k in after
               if k.startswith("coll_") and after[k] != before.get(k, 0)}))
    say(f"setup parts: inputs_s={col['inputs_s'].max():.3f} "
        f"warmup_s={col['warm_s'].max():.3f} "
        f"xla_compile_or_load_s={opts.xla['compile_s']:.3f} "
        f"persistent_cache_hits={opts.xla['cache_hits']} "
        f"misses={opts.xla['cache_misses']}")
    med = float(np.median(pooled))
    slow = np.argwhere(pooled > 10 * med)
    say(f"iterations: p50={med * 1e6:.1f} p95="
        f"{float(np.percentile(pooled, 95)) * 1e6:.1f} p99="
        f"{float(np.percentile(pooled, 99)) * 1e6:.1f} max="
        f"{float(pooled.max()) * 1e6:.1f} us; over 10 x p50: {len(slow)} "
        f"of {pooled.size}, {float(pooled[pooled > 10 * med].sum()):.3f} s "
        f"in all, (rank, iteration, s) of the longest: "
        + str([(int(r), int(i), round(float(pooled[r, i]), 3))
               for r, i in slow[np.argsort(-pooled[tuple(slow.T)])[:6]]]))
    say(f"bytes_per_rank={bytes_per_rank} (largest mean bytes a rank sends "
        f"an iteration, dispatch plus combine) bw_GBs="
        f"{bytes_per_rank / (iter_us * 1e-6) / 1e9:.3f} "
        f"peak_bytes_in_use={peak} reference_check_s={check_s:.2f}")

    with _EXCHANGES_LOCK:
        _EXCHANGES.clear()
    return finish(spec, opts, facts, trace_dir, say, checks,
                  {"correct": correct, "attempted": attempted,
                   "failed": int(failed)}, int(peak))
