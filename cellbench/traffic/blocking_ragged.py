"""The generator for a blocking RAGGED exchange on device arrays: the
closed loop of cellbench/traffic/blocking_collective.py around
``comm.alltoallv_arr``, with the counts of NAS Parallel Benchmarks IS.

Every rank-thread holds two key sets (one per parity of the iteration,
each its own stream of the seed, the rank and the parity), buckets them
as ``IS/is.c``'s ``rank()`` does (cellbench/reference_ragged.py states
the rule), and in iteration k calls

    comm.alltoallv_arr(key_buff1[k % 2], send_count[k % 2],
                       recv_count[k % 2], capacity=SIZE_OF_BUFFERS)

then ``jax.block_until_ready`` on what it returned; one outstanding
exchange per rank, no think time.  The class (IS's table row), the
element type and the loop's lengths are data
(cellbench/traffic/<mix>.json); ranks, layout and provider are the
configuration's; nothing here names a cell.

Set-up, outside the window: the keys are made and grouped by bucket on
the device (a stable sort by bucket); the bucket totals and the count
exchange go through the library's HOST ``Allreduce`` and ``Alltoall``,
as IS's do.  The two parities' counts differ, so two calls in a row
never move the same split, and a program keyed on a count would compile
inside the window (``correct`` holds the build counters to 0 there).

Required bytes.  The readers are handed ``op`` "alltoall" and
``bytes_per_rank`` = the bytes a rank SENDS (NUM_KEYS x 4): an
alltoallv in which every rank sends S bytes reads every sent byte once
and writes it once, whatever the split, which is cellbench/bytes.py's
one-chip alltoall row (2 x P x S).  The capacity is not work.

The window, the whole-window arithmetic (``iter_us``), the warm-up that
fixes the iteration count, the gathers and the result line are
blocking_collective's, the loop that alternates by parity and the choice
of the kept iterations blocking_p2p's: their helpers are imported, not
copied.

``correct``.  Every tolerance is 0, because the operation moves bits:
elements ``[0, sum(rcounts))`` of the first, the last and one seeded
iteration between (both parities among them) on 3 ranks equal
reference_ragged.py's, in blocks drawn from the seed with the block
that ends the received data among them (``gap`` 0); ``sum(rcounts)`` is
what the reference says; every received key's bucket lies in the
receiver's owned range (IS's own partial verification, over all of the
received keys, on the device); ``coll_alltoallv_device_ops`` =
iterations x ranks and ``coll_alltoallv_elems`` = iterations x ranks x
NUM_KEYS (the program is asked to move the counts and no padded
bound); nothing host-staged; nothing compiled inside the window
(``coll_device_cache_misses`` and ``coll_plan_builds`` at rest); the
configuration's provider; results on the rank's own device.

The control (``--control bf16``'s slot): the keys' low bits (8, or the
bucket shift where that is less) cleared on the host before they are
handed over: the same buckets, the same counts, the same bytes, and
other keys than the reference owes.  It must read ``correct`` false.
"""
from __future__ import annotations

import contextlib
import functools
import importlib
import os
import shutil
import threading
import time

import numpy as np

from cellbench import reference, reference_ragged, tracered
from cellbench.manifest import metric_spec
from cellbench.traffic.blocking_collective import (
    PHASES, RANK_FACTS, gather, pvars, span_rows, warm_up)
from cellbench.traffic.blocking_p2p import kept_iterations, timed_ring

# what has to stay at rest over the window
AT_REST = ("coll_arr_host_staged_collectives", "coll_device_cache_misses",
           "coll_plan_builds")
OPS, ELEMS = "coll_alltoallv_device_ops", "coll_alltoallv_elems"

# the host reference's parts, shared by the rank-threads of the one app
# shell: (seed, class, ranks, rank, parity) -> reference_ragged.part
_PARTS: dict = {}
_PARTS_LOCK = threading.Lock()


def klass(traffic: dict, tiny: bool) -> dict:
    """IS's table row: the mix's class, or its ``tiny_class`` in the
    development mode."""
    return reference_ragged.CLASSES[
        traffic["tiny_class"] if tiny else traffic["class"]]


@functools.lru_cache(maxsize=None)
def grouper(n: int, max_key_log2: int, shift: int, nb: int):
    """The set-up's one program, shared by the rank-threads: the ``n``
    keys of a stream key, grouped by bucket (a stable sort), and the
    bucket sizes."""
    import jax
    import jax.numpy as jnp

    def group(key):
        ks = reference_ragged.keys_from_key(key, n, max_key_log2, jnp)
        b, buff1 = jax.lax.sort((ks >> shift, ks), num_keys=1,
                                is_stable=True)
        edges = jnp.searchsorted(b, jnp.arange(nb + 1, dtype=b.dtype))
        return buff1, edges[1:] - edges[:-1]

    return jax.jit(group)


def make_keysets(jax, comm, seed: int, cls: dict, control):
    """This rank's two exchanges, one per parity: (key_buff1 on the
    device, its bucket sizes on the host) each, from the seed."""
    shift = reference_ragged.shift_of(cls)
    group = grouper(reference_ragged.num_keys(cls, comm.size),
                    cls["max_key_log2"], shift,
                    1 << cls["num_buckets_log2"])
    sets = []
    for parity in (0, 1):
        key = jax.device_put(np.uint32(reference_ragged.stream_key(
            seed, comm.rank, parity)), comm.device)
        buff1, sizes = group(key)
        if control == "bf16":
            # the control: applied to what the library is handed, never
            # by an option of the library; the buckets stay as they are
            low = (1 << min(8, shift)) - 1
            buff1 = jax.device_put(np.asarray(buff1) & np.int32(~low),
                                   comm.device)
        sets.append((jax.block_until_ready(buff1),
                     np.asarray(sizes, np.int64)))
    return sets


def counts_of(comm, sizes: np.ndarray, nkeys: int):
    """(send_count, recv_count, last bucket of every owner): the bucket
    totals and the count exchange through the library's host
    collectives, the rule between them."""
    totals = np.empty_like(sizes)
    from ompi_tpu.op import op as mpi_op
    comm.Allreduce(sizes, totals, mpi_op.SUM)
    send, last = reference_ragged.distribute(sizes, totals, nkeys, comm.size)
    recv = np.empty_like(send)
    comm.Alltoall(send, recv)
    return send, recv, last


def host_part(seed: int, cls: dict, ranks: int, rank: int,
              parity: int) -> dict:
    """A rank's part of the reference (reference_ragged.part), made
    once a process: the rank-threads of the app shell share what the
    reference is built from."""
    k = (seed, cls["total_keys_log2"], cls["max_key_log2"], ranks, rank,
         parity)
    with _PARTS_LOCK:
        got = _PARTS.get(k)
    if got is None:
        got = reference_ragged.part(seed, rank, parity, cls, ranks)
        with _PARTS_LOCK:
            got = _PARTS.setdefault(k, got)
    return got


def checker(jax, jnp, shift: int, blk: int):
    """The comparison's two programs: ``take`` cuts blocks at the given
    starts out of an answer; ``strays`` counts the keys among an
    answer's first ``total`` whose bucket is outside [lo, hi]."""
    take = jax.jit(lambda a, s: jax.vmap(
        lambda st: jax.lax.dynamic_slice(a, (st,), (blk,)))(s))

    def strays(a, total, lo, hi):
        b = a >> shift
        live = jax.lax.iota(jnp.int32, a.shape[0]) < total
        return jnp.sum(live & ((b < lo) | (b > hi)))

    return take, jax.jit(strays)


def compare(jax, jnp, kept: dict, exchanges: dict, rank: int, seed: int,
            chk: dict, shift: int, cap: int):
    """(worst gap, elements compared, stray keys, iterations whose
    length the reference does not own) of this rank's kept answers."""
    worst, compared, stray, short = 0.0, 0, 0, 0
    for it, out in sorted(kept.items()):
        ex = exchanges[it & 1]
        ref = reference_ragged.owed(ex, rank)
        total = ref.size
        if getattr(out, "shape", None) != (cap,) \
                or np.dtype(out.dtype) != np.int32:
            return float("inf"), compared, stray, short + 1
        starts, blk = reference.block_starts(seed + it, total,
                                             chk["block_elems"],
                                             chk["blocks"])
        if not total:
            continue
        take, strays = checker(jax, jnp, shift, blk)
        got = np.asarray(take(out, jnp.asarray(starts, jnp.int32)))
        for row, lo in zip(got, starts):
            g = reference_ragged.gap(row, ref[int(lo):int(lo) + blk])
            if not g <= worst:
                worst = g if g == g else float("inf")
            compared += blk
        lo_b, hi_b = reference_ragged.owned(ex["last"], rank)
        stray += int(strays(out, total, lo_b, hi_b))
    return worst, compared, stray, short


def run(comm, spec: dict, opts, entry_wrap=None):
    """Drive one cell; the result dict on rank 0, None elsewhere.
    ``entry_wrap(comm, call) -> call`` lets a test break the timed path
    underneath (tests/test_cellbench_ragged.py); the benchmark never
    passes it."""
    import jax
    import jax.numpy as jnp

    rank, P = comm.rank, comm.size
    cfg, traffic, pairing = spec["config"], spec["traffic"], spec["pairing"]
    fname = traffic["op"] + "_arr"
    cls = klass(traffic, opts.tiny)
    nkeys = reference_ragged.num_keys(cls, P)
    cap = reference_ragged.size_of_buffers(cls, P)
    shift = reference_ragged.shift_of(cls)
    bur = jax.block_until_ready
    say = opts.say if rank == 0 else (lambda msg: None)

    if not hasattr(comm, fname):
        # the same on every rank-thread, and before any of them waits
        raise RuntimeError(
            f"this library's communicator has no {fname}: the cell's "
            "operation does not exist here; refusing before the window")

    t_in = time.perf_counter()
    sets = make_keysets(jax, comm, opts.seed, cls, opts.control)
    xs = [s[0] for s in sets]
    send, recv, last = zip(*(counts_of(comm, s[1], nkeys) for s in sets))
    inputs_s = time.perf_counter() - t_in
    entry = getattr(comm, fname)

    def call(parity):
        return entry(xs[parity], send[parity], recv[parity], capacity=cap)

    if entry_wrap is not None:
        call = entry_wrap(comm, call)
    turn = [0]

    def in_turn(_x):
        # blocking_collective's warm-up hands every iteration the same
        # argument: the two exchanges in turn there too
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
        # blocking_p2p's loop: argument k % 2 in iteration k
        lat, kept, t_open, t_end = timed_ring(
            call, (0, 1), N, bur, keep if rank in pick else ())
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
    del xs, sets
    # every rank-thread makes its own part of both parities' reference
    # on the host; the compared ranks then assemble the exchanges
    for parity in (0, 1):
        host_part(opts.seed, cls, P, rank, parity)
    comm.Barrier()
    worst, compared, stray, short = 0.0, 0, 0, 0
    mine = [int(np.sum(r)) for r in recv]
    owed_elems = list(mine)
    if rank in pick:
        exchanges = {parity: reference_ragged.assemble(
            [host_part(opts.seed, cls, P, r, parity) for r in range(P)])
            for parity in (0, 1)}
        owed_elems = [int(exchanges[p]["counts"][:, rank].sum())
                      for p in (0, 1)]
        worst, compared, stray, short = compare(
            jax, jnp, kept, exchanges, rank, opts.seed, chk, shift, cap)
        del exchanges
    parities = len({it & 1 for it in kept}) if compared else 0
    kept.clear()
    comm.Barrier()
    with _PARTS_LOCK:
        _PARTS.clear()
    check_s = time.perf_counter() - t_end

    per_rank = gather(comm, [
        worst if np.isfinite(worst) else 1e300, compared,
        0 if on_dev else 1, len(lat), t_open, t_end, inputs_s, warm_s,
        comm.device.id, parities, stray, short,
        sum(abs(a - b) for a, b in zip(mine, owed_elems)),
        sum(int(np.sum(s)) for s in send)])
    pooled = gather(comm, lat)
    spans = gather(comm, span_rows(tracer, wall_open,
                                   wall_open + (t_end - pc_open) + 1.0),
                   np.int64)
    if rank != 0:
        return None

    # -- rank 0 reduces ----------------------------------------------------
    col = dict(zip(RANK_FACTS + ("parities", "stray", "short", "recv_off",
                                 "sent"), per_rank.T))
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
    # every rank sends NUM_KEYS a call, in either parity
    elems_owed = attempted * nkeys
    checks = {
        "gap": {"value": float(col["gap"].max()), "limit": 0.0},
        "answers_elems": {"value": int(col["compared"].sum()),
                          "at_least": 1},
        "ranks_compared": {"value": compared_ranks,
                           "at_least": min(P, 3)},
        "parities_compared": {"value": both, "at_least": 2},
        "recv_elems_off": {"value": int(col["recv_off"].sum()
                                        + col["short"].sum()), "limit": 0},
        "stray_keys": {"value": int(col["stray"].sum()), "limit": 0},
        "sent_elems": {"value": int(col["sent"].sum()),
                       "equals": 2 * P * nkeys},
        "device_ops": {"value": int(delta(OPS)), "equals": attempted},
        "device_elems": {"value": int(delta(ELEMS)), "equals": elems_owed},
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
        and checks["answers_elems"]["value"] >= 1
        and compared_ranks >= min(P, 3) and both >= 2
        and checks["sent_elems"]["value"] == 2 * P * nkeys
        and delta(OPS) == attempted and delta(ELEMS) == elems_owed
        and not (any(rest.values()) or wrong_provider or incomplete
                 or checks["off_device"]["value"]
                 or checks["recv_elems_off"]["value"]
                 or checks["stray_keys"]["value"]))
    iter_us = window / N * 1e6
    devs = jax.devices()
    facts = {
        # the required-bytes row: an alltoall of S = the bytes a rank
        # sends (this file's head says why)
        "op": "alltoall", "ranks": P, "chips": cfg["chips"],
        "bytes_per_rank": nkeys * 4, "iters": N, "iter_us": iter_us,
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
        f"provider={provider}, keys a rank={nkeys}, capacity={cap}, "
        f"rank 0 sends {[int(c) for c in send[0]]} / "
        f"{[int(c) for c in send[1]]}, owners end at buckets "
        f"{[int(b) for b in last[0]]}, compared iterations="
        f"{sorted(keep)} on ranks="
        f"{sorted(int(r) for r in np.flatnonzero(col['compared'] > 0))}, "
        "counters="
        + str({k: after[k] - before.get(k, 0) for k in after
               if k.startswith("coll_") and after[k] != before.get(k, 0)}))
    say(f"setup parts: inputs_s={col['inputs_s'].max():.3f} "
        f"warmup_s={col['warm_s'].max():.3f} "
        f"xla_compile_or_load_s={opts.xla['compile_s']:.3f} "
        f"persistent_cache_hits={opts.xla['cache_hits']} "
        f"misses={opts.xla['cache_misses']}")
    # where a window's time went when it is not all steady iterations:
    # the pooled per-iteration times, and the longest with their place
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
    say(f"bw_GBs={nkeys * 4 / (iter_us * 1e-6) / 1e9:.3f} (bytes a rank "
        f"sends over iter_us; it receives about as many) "
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
