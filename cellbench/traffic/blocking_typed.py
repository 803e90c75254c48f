"""The generator for TYPED blocking reductions: the closed loop of
cellbench/traffic/blocking_collective.py, for a collective that takes
a derived datatype, a reduction other than SUM and MPI_DOUBLE.

Every rank-thread calls ``comm.<op>_arr(x, op, datatype, count)`` on a
device-resident buffer of MPI_DOUBLE (the flat buffer the datatype
addresses) and then ``jax.block_until_ready`` on what it returned; one
outstanding operation per rank, no think time.  The buffer is what the
library says MPI_DOUBLE is on this device (ompi_tpu/runtime/x64.py): a
float64 array where the device's float64 is IEEE binary64, else the
same 8 bytes an element as uint64 bit patterns (a TPU v5e holds no
binary64; a float64 array there has already lost 5 of its 53 bits and
most of its exponent range before any collective sees it).  The datatype is the
LIBRARY's argument: this file builds an ``MPI_Type_vector`` from the
mix's three integers, commits it and passes it; it jits nothing of its
own in the timed loop.  The operation, the reduction, the vector and
the loop's lengths are data (cellbench/traffic/<mix>.json);
ranks, layout, provider and the launch line (which carries
``--mca mpi_device_x64 1``) are the configuration's; nothing here names
a cell.

The window, the whole-window arithmetic (``iter_us``), the warm-up that
fixes the iteration count, the gathers and the result line are
blocking_collective's: its helpers are imported, not copied.  What
differs: the inputs (cellbench/reference_typed.py's float64 stream with
53-bit significands and binary64's exponent range, made on the device
bit for bit as the host makes them), the entry (a datatype and a
count), the comparison (exact for MAX / MIN, limit 0) and four more
things ``correct`` holds: the answers come in the carrier the inputs
went in (8 bytes an element), the host convertor packed nothing
(``coll_typed_host_packs`` 0), every rank-call was served with the pack
inside the device program (``coll_typed_device_ops`` = iterations x
ranks), and at least three ranks were compared.

A library without typed collectives or without runtime/x64, or a job
without ``mpi_device_x64``, fails here during set-up, on every rank
and before any rank waits for another: the run exits non-zero, it does
not hang.
"""
from __future__ import annotations

import importlib
import os
import shutil
import time

import numpy as np

from cellbench import reference, reference_typed, tracered
from cellbench.manifest import metric_spec
from cellbench.traffic.blocking_collective import (
    PHASES, RANK_FACTS, gather, pvars, span_rows, timed_loop, warm_up)

OP_ENTRY = {"reduce_scatter_block": "reduce_scatter_arr",
            "allreduce": "allreduce_arr"}
OP_VTABLE = {"reduce_scatter_block": "reduce_scatter_block_arr",
             "allreduce": "allreduce_arr"}


def vector_of(traffic: dict, ranks: int, tiny: bool) -> dict:
    """The mix's ``MPI_Type_vector(count, blocklength, stride)``; in
    the development mode its count is divided (and kept a multiple of
    the ranks), its shape is not."""
    v = dict(traffic["vector"])
    if tiny:
        v["count"] = max(ranks, v["count"] // traffic["tiny_divisor"]
                         // ranks * ranks)
    return v


def carrier_of(cfg: dict) -> np.dtype:
    """What the library carries the configuration's dtype in on this
    device; raises where the job was launched without 8-byte types or
    the library has no such statement."""
    import jax
    from ompi_tpu.runtime import x64
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            f"{cfg['name']} states dtype {cfg['dtype']}, and this job "
            "runs without 8-byte types on the device: its launch line "
            "carries --mca mpi_device_x64 1, which this library did not "
            "apply")
    return np.dtype(cfg["dtype"] if x64.native() else np.uint64)


def make_input(jax, jnp, comm, seed: int, vector: dict, control, carrier):
    """This rank's buffer: the binary64 elements the datatype addresses
    (and the ones it skips), on its device, from the seed, in one
    jitted call whose keys are arguments.  The two 32-bit words of
    every element are put together with integer operations (and, for a
    float64 carrier, one bitcast): no conversion, so the bits are the
    host's."""
    n = reference_typed.span_elems(vector)
    if control == "bf16":
        # the lower-precision control: the values the library is handed
        # are rounded to bfloat16 (and stay MPI_DOUBLE, as the datatype
        # says); never an option of the library.  Made on the host: the
        # rounding is then numpy's, whatever the device's float64 is
        with np.errstate(over="ignore"):
            v = reference_typed.values_at(
                seed, comm.rank, np.arange(n)).astype(jnp.bfloat16)
        x = jax.device_put(v.astype(np.float64).view(carrier), comm.device)
        return jax.block_until_ready(x)

    def words(key, shared):
        lo, hi = reference_typed.words_from_key(
            key, shared, jnp.arange(n, dtype=jnp.uint32), jnp)
        bits = (hi.astype(jnp.uint64) << jnp.uint64(32)) \
            | lo.astype(jnp.uint64)
        return bits if carrier == np.uint64 else \
            jax.lax.bitcast_convert_type(bits, jnp.float64)

    key, shared = (jax.device_put(np.uint32(k), comm.device)
                   for k in reference_typed.keys(seed, comm.rank))
    return jax.block_until_ready(jax.jit(words)(key, shared))


def entry_of(comm, traffic: dict, vector: dict, dtype: str):
    """The entry a user calls, ``comm.<op>_arr(x, op, datatype,
    count)``, with the mix's reduction, its committed datatype and a
    count of 1 (the vector is the whole message)."""
    from ompi_tpu.datatype import engine as dtmod
    from ompi_tpu.op import op as mpi_op
    vec = dtmod.vector(vector["count"], vector["blocklength"],
                       vector["stride"],
                       dtmod.from_numpy_dtype(np.dtype(dtype))).commit()
    entry = getattr(comm, OP_ENTRY[traffic["op"]])
    red = getattr(mpi_op, traffic["reduce"].replace("MPI_", ""))
    return lambda a: entry(a, red, vec, 1)


def compare(kept: dict, traffic: dict, seed: int, ranks: int,
            vector: dict, rank: int, m: int, chk: dict, carrier):
    """(worst gap, elements compared, answers not in the carrier) of
    this rank's kept answers against the typed reference, in blocks
    drawn from the seed."""
    starts, blk = reference.block_starts(seed, m, chk["block_elems"],
                                         chk["blocks"])
    worst, compared, wrong_dtype = 0.0, 0, 0
    for _, out in sorted(kept.items()):
        if np.dtype(out.dtype) != carrier:
            wrong_dtype += 1
        if out.shape != (m,):
            return float("inf"), compared, wrong_dtype
        got = np.asarray(out)
        for lo in starts:
            lo = int(lo)
            ref = reference_typed.expected(
                traffic["op"], traffic["reduce"], seed, ranks, vector,
                rank, lo, lo + blk)
            g = reference_typed.gap(traffic["reduce"], got[lo:lo + blk],
                                    ref)
            if not g <= worst:
                worst = g
            compared += blk
    return worst, compared, wrong_dtype


def run(comm, spec: dict, opts, entry_wrap=None):
    """Drive one cell; the result dict on rank 0, None elsewhere.
    ``entry_wrap(comm, call) -> call`` lets a test break the timed path
    underneath; the benchmark never passes it."""
    import jax
    import jax.numpy as jnp

    rank, P = comm.rank, comm.size
    cfg, traffic, pairing = spec["config"], spec["traffic"], spec["pairing"]
    op, fname = traffic["op"], OP_VTABLE[traffic["op"]]
    carrier = carrier_of(cfg)
    vector = vector_of(traffic, P, opts.tiny)
    n = reference_typed.packed_elems(vector)
    m = n // P if op == "reduce_scatter_block" else n
    bur = jax.block_until_ready
    say = opts.say if rank == 0 else (lambda msg: None)

    t_in = time.perf_counter()
    x = make_input(jax, jnp, comm, opts.seed, vector, opts.control, carrier)
    inputs_s = time.perf_counter() - t_in
    call = entry_of(comm, traffic, vector, cfg["dtype"])
    if entry_wrap is not None:
        call = entry_wrap(comm, call)
    t_w = time.perf_counter()
    N = warm_up(comm, call, x, traffic,
                min(opts.seconds, traffic["trace_seconds"]) if opts.trace
                else opts.seconds, bur)
    warm_s = time.perf_counter() - t_w

    # which answers of the window are compared: the first, the last and
    # some between, on the first rank, the last and at least one more,
    # all drawn from the seed (the same on every rank)
    chk = pairing["check"]
    rng = np.random.default_rng([opts.seed & 0xFFFFFFFF, N, P])
    keep = frozenset({0, N - 1, *(int(i) for i in rng.integers(
        1, max(2, N - 1), size=max(0, chk["answers"] - 2)))})
    between = [int(r) for r in rng.permutation(np.arange(1, max(1, P - 1)))]
    pick = {0, P - 1, *between[:max(1, chk["ranks"] - 2)]}

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
    worst, compared, wrong_dtype = compare(
        kept, traffic, opts.seed, P, vector, rank, m, chk, carrier) \
        if rank in pick else (0.0, 0, sum(
            np.dtype(o.dtype) != carrier for o in kept.values()))
    kept.clear()
    check_s = time.perf_counter() - t_end

    per_rank = gather(comm, [
        worst if np.isfinite(worst) else 1e300, compared,
        0 if on_dev else 1, len(lat), t_open, t_end, inputs_s, warm_s,
        comm.device.id, wrong_dtype])
    pooled = gather(comm, lat)
    spans = gather(comm, span_rows(tracer, wall_open,
                                   wall_open + (t_end - pc_open) + 1.0),
                   np.int64)
    if rank != 0:
        return None

    # -- rank 0 reduces ----------------------------------------------------
    col = dict(zip(RANK_FACTS + ("wrong_dtype",), per_rank.T))
    window = col["t_end"].max() - col["t_open"].min()

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    staged = delta("coll_arr_host_staged_collectives")
    host_packs = delta("coll_typed_host_packs")
    typed_ops = delta("coll_typed_device_ops")
    wrong_provider = provider != cfg["provider"]
    attempted = N * P
    incomplete = int(attempted - col["iters"].sum())
    failed = attempted if wrong_provider else min(
        attempted, staged + host_packs + incomplete)
    compared_ranks = int((col["compared"] > 0).sum())
    limit = 0.0 if reference_typed.exact(traffic["reduce"]) \
        else chk["limit"]
    checks = {
        "gap": {"value": float(col["gap"].max()), "limit": limit},
        "answers_elems": {"value": int(col["compared"].sum()),
                          "at_least": 1},
        "ranks_compared": {"value": compared_ranks,
                           "at_least": min(P, 3)},
        "wrong_dtype": {"value": int(col["wrong_dtype"].sum()),
                        "limit": 0},
        "host_staged": {"value": int(staged), "limit": 0},
        "typed_host_packs": {"value": int(host_packs), "limit": 0},
        "typed_device_ops": {"value": int(typed_ops),
                             "equals": attempted},
        "wrong_provider": {"value": int(wrong_provider), "limit": 0},
        "off_device": {"value": int(col["off_device"].sum()), "limit": 0},
        "incomplete": {"value": incomplete, "limit": 0},
    }
    correct = bool(
        limit is not None and checks["gap"]["value"] <= limit
        and checks["answers_elems"]["value"] >= 1
        and compared_ranks >= min(P, 3)
        and typed_ops == attempted
        and not (staged or host_packs or wrong_provider or incomplete
                 or checks["off_device"]["value"]
                 or checks["wrong_dtype"]["value"]))
    iter_us = window / N * 1e6
    devs = jax.devices()
    itemsize = np.dtype(cfg["dtype"]).itemsize
    facts = {
        "op": op, "ranks": P, "chips": cfg["chips"],
        # the packed stream: what the operation is defined on
        "bytes_per_rank": n * itemsize, "iters": N, "iter_us": iter_us,
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
        f"provider={provider}, {traffic['reduce']} on {cfg['dtype']} "
        f"carried as {carrier.name} "
        f"through vector({vector['count']}, {vector['blocklength']}, "
        f"{vector['stride']}), compared ranks="
        f"{sorted(int(r) for r in np.flatnonzero(col['compared'] > 0))}, "
        "counters="
        + str({k: after[k] - before.get(k, 0) for k in after
               if k.startswith("coll_") and after[k] != before.get(k, 0)}))
    say(f"setup parts: inputs_s={col['inputs_s'].max():.3f} "
        f"warmup_s={col['warm_s'].max():.3f} "
        f"xla_compile_or_load_s={opts.xla['compile_s']:.3f} "
        f"persistent_cache_hits={opts.xla['cache_hits']} "
        f"misses={opts.xla['cache_misses']}")
    say(f"packed_GBs={n * itemsize / (iter_us * 1e-6) / 1e9:.3f} (packed "
        f"bytes per rank over iter_us) peak_bytes_in_use={peak} "
        f"reference_check_s={check_s:.2f}")

    metrics = {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": attempted,
              "failed": int(failed), "metrics": metrics, "device": device}
    if not opts.trace:
        for mt in spec["end_to_end"]:
            metrics[mt["name"]] = {"value": facts[mt["name"]],
                                   "unit": mt["unit"]}
    else:
        t_rd = time.perf_counter()
        tr = facts["trace"] = tracered.reduce_dir(trace_dir, facts, say)
        say(f"trace: read and reduced in {time.perf_counter() - t_rd:.2f} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
        for mt in spec["per_layer"]:
            ms = metric_spec(mt["name"])
            reader = importlib.import_module(
                "cellbench.readers." + ms["reader"])
            v = reader.read(ms, facts, say)
            if v is not None:
                metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}
        if tr.get("busy_s"):
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            result["breakdown"] = {"device_ops": tr["device_ops"],
                                   "idle_gaps": tr["idle_gaps"]}
    result["checks"] = checks
    return result
