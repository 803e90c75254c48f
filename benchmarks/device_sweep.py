"""Device-path sweep: the coll/tpu / coll/hbm side of BASELINE.md.

Runs thread-ranks in-process (the TPU-host execution model) and times
allreduce/bcast/alltoall/reduce_scatter on device-resident arrays
through the XLA collective path.  Used by bench.py; also runnable
directly:  python benchmarks/device_sweep.py --max-ar 1048576

Timing methodology (forced completion + chained dependency — r4;
quiet-gated reads + dual-mode allreduce — r5).  It was built for the
backend the r02–r05 chip records were taken on, where
``jax.Array.block_until_ready()`` returned WITHOUT awaiting execution
(measured there: 10 dispatched 8-MiB 8-way sums "complete" in
0.37 ms), so any timing that relied on it reported the dispatch floor,
not the op.  On a directly attached v5e block_until_ready does await
execution (chip_smoke.py prints ``block_until_ready_waits``; PERF.md
has the reading), so the forced read and its subtraction are no
longer needed there — ROADMAP S0(d) replaces the method; it is kept
unchanged here.  And N dispatches of the same op on the
SAME input carry no data dependency, so XLA/the runtime may alias or
elide them (r3's failure: a stacked bcast is near-free metadata).
Every timed point here instead:

  1. warms up the op AND a tiny per-shape probe read (first read
     compiles), verifying the numeric result;
  2. measures the device-to-host read constant (min of several
     4-byte d2h reads);
  3. runs N CHAINED iterations where each op's input depends on the
     previous op's output (the device must fully execute op k before
     op k+1 can start, and no op can be aliased out), then forces
     completion with ONE 4-byte d2h read of the LAST result
     (in-order device execution awaits the whole chain);
  4. reports (elapsed - read_const) / N, rank 0 as the timekeeper.

Quiet gate (r5): every d2h read — the read-constant probes, warmup
verification, and each timed round's completion read — runs while
the other rank-threads SLEEP on a threading.Event instead of polling
inside a software collective.  The r4 sweep's reads ran against 7
polling peers and cost ~20x the idle read constant; the excess was
charged to the ops, putting a false ~1 ms floor on every device
point.  The subtraction in (4) is only honest when the measured
constant and the in-loop read share a context — now both are quiet.

Allreduce runs in two modes (single-chip):

  * latency mode (< 1 MiB): every rank deposits the SHARED previous
    output; the op->op feedback is the data dependency.  No per-rank
    chain step — the r4 chain cost 8 extra cross-thread dispatches
    per iteration, ~0.5-1 ms in the r04 record (what a cross-thread
    dependency chain costs on a directly attached chip is not
    measured; see coll/device._DeviceDispatcher).  Values stay finite via an EXACT
    power-of-two rescale (one extra dispatch per rank every 32 ops:
    x * 2^-96 after 32 sums of 8 == x, bit-exact in f32).  Inputs
    alias at these sizes, so the HBM-gate traffic factor drops to 2
    (read n + write n) — immaterial: these points are latency-bound
    by the per-op dispatch cost (~300 us in the r05 record), three
    orders of magnitude above the HBM time of the payload.
  * bandwidth mode (>= 1 MiB, and always on real meshes): the r4
    methodology — each rank's own chain step (multiply by a runtime
    device scalar) produces P DISTINCT input buffers per iteration,
    so the op must move the full P*n bytes and the reported busbw is
    honest at sizes where traffic, not dispatch, dominates.

A physical sanity gate then checks each point's implied bandwidth
against the chip's HBM peak, using a PER-COLLECTIVE minimal-traffic
model (a LOWER bound, so the gate can only catch physically-
impossible timings).  A violating point is recorded as null with the
violation in ``gated``.

Budget (r5): the wall-clock budget is SPLIT per collective up front
(allreduce 45% — it carries the north-star verdict and sweeps every
power of two >= 4 KiB; bcast/alltoall 15% each and reduce_scatter 25%
on SPARSE size sets — 4/8/16 B tell one story, so non-gating
collectives keep a handful of representative sizes and always reach
their caps).  Leftover budget rolls forward.  The r4 failure mode —
27 allreduce sizes starving reduce_scatter to a 2 KiB toy table —
cannot recur: each collective owns its window.
"""

from __future__ import annotations

import argparse
import json
import threading
import time

import numpy as np

MIB = 1024 * 1024

# published HBM peak bytes/s by jax device_kind (the sanity gate's
# ceiling, not a claim)
_HBM_PEAK = {
    "TPU v5 lite": 0.82e12,
    "TPU v5e": 0.82e12,
    "TPU v4": 1.23e12,
    "TPU v5p": 2.77e12,
    "TPU v6 lite": 1.64e12,
    "TPU v6e": 1.64e12,
}


def hbm_peak(device_kind: str) -> float:
    """Published HBM peak of ``device_kind``; a kind the table does
    not hold is an error, never a default."""
    try:
        return _HBM_PEAK[device_kind]
    except KeyError:
        raise ValueError(
            f"no published HBM peak for device_kind {device_kind!r} "
            f"(known: {sorted(_HBM_PEAK)}); add it to "
            f"benchmarks/device_sweep.py with its source") from None


# latency-mode/bandwidth-mode crossover (single-chip allreduce)
_LAT_MAX = 1 * MIB
_RESCALE_EVERY = 32  # 8^32 = 2^96: rescale by 2^-96 is bit-exact f32

# sparse size sets for the non-north-star collectives (each reaches
# its BASELINE cap; intermediate powers of two tell the same story
# as their neighbors and starved the r4 sweep)
_BCAST_SIZES = (4, 4096, 65536, MIB, 8 * MIB, 64 * MIB)
_A2A_SIZES = (4, 4096, 65536, MIB, 4 * MIB)
_RSB_SIZES = (64, 4096, 65536, MIB, 16 * MIB)
# allreduce: three latency points below the verdict cut, then EVERY
# power of two >= 4 KiB (the north star is per-size there)
_AR_SMALL = (4, 256, 2048)


class _QuietGate:
    """Sleep-parked meeting for the measurement harness itself: the
    reading rank works while every other rank waits on an Event (a
    real futex sleep — no progress sweeps, no GIL churn against the
    reading thread).  Two cyclic-barrier phases bound each round."""

    def __init__(self, n: int) -> None:
        self.barrier = threading.Barrier(n)
        self.ev = threading.Event()
        self.box: dict = {}

    def run(self, rank: int, who: int, fn):
        """All ranks call; ``fn`` runs on rank ``who`` alone while the
        rest sleep.  Returns fn()'s value on every rank."""
        self.barrier.wait()
        if rank == who:
            try:
                self.box["out"] = ("ok", fn())
            except BaseException as e:  # noqa: BLE001
                self.box["out"] = ("err", e)
            self.ev.set()
        else:
            self.ev.wait()
        self.barrier.wait()
        kind, val = self.box["out"]
        self.barrier.wait()
        if rank == who:
            self.ev = threading.Event()  # fresh before the next round
        self.barrier.wait()
        if kind == "err":
            raise RuntimeError(f"quiet-gated read failed: {val}") \
                from (val if rank == who else None)
        return val


def _rank_devices(nranks: int):
    import jax

    ndev = len(jax.devices())
    if ndev >= nranks:
        return None, True
    return (lambda r: jax.devices()[r % ndev]), False


def sizes_upto(max_bytes: int, start: int = 4):
    s = start
    while s <= max_bytes:
        yield s
        s *= 2


def _ar_sizes(max_ar: int):
    for s in _AR_SMALL:
        if s <= max_ar:
            yield s
    for s in sizes_upto(max_ar, start=4096):
        yield s


def _measure_read_const(probe) -> float:
    """Constant of one tiny d2h read (min of 5)."""
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        probe()
        best = min(best, time.perf_counter() - t0)
    return best


def _forced_time(comm, gate: _QuietGate, x0, make_op, chain,
                 read_token, read_const: float, deadline: float) -> float:
    """One timed point: N chained op+chain iterations + ONE forced read.

    All ranks iterate (the collective requires it); rank 0 is the
    timekeeper.  Its completion-forcing read runs under the quiet
    gate — peers sleep, so the read costs the same constant the
    harness measured and subtracts.
    """
    target = max(0.3, 4.0 * read_const)
    max_iters = 1_000_000
    iters = 64 if read_const > 1e-3 else 30  # fast local backends: small N
    me = comm.rank
    while True:
        gate.barrier.wait()
        t0 = time.perf_counter()
        x = x0
        for _ in range(iters):
            x = chain(make_op(x))

        def finish():
            read_token(x)
            work = time.perf_counter() - t0 - read_const
            over_deadline = (deadline > 0
                             and time.perf_counter() >= deadline)
            if work >= target or iters >= max_iters or over_deadline:
                # deadline-forced acceptance of a jitter-dominated
                # point is reported as unmeasurable, never as a number
                per_op = (work / iters
                          if work > max(0.0, 0.2 * read_const)
                          else -1.0)
                return (1.0, per_op)
            # project N from the measured round (clamped growth)
            grow = target / max(work, 0.01)
            return (0.0, float(int(min(max_iters,
                                       max(iters * 2, iters * grow)))))

        done, val = gate.run(me, 0, finish)
        if done == 1.0:
            return float(val)
        iters = int(val)


def _min_traffic_factor(kind: str, nranks: int, single_chip: bool,
                        latency_mode: bool = False) -> float:
    """Bytes the device MUST move per iteration, as a multiple of the
    point's size key — a LOWER bound per collective, so the gate can
    only catch physically-impossible timings, never flag honest ones.

    Single chip (stacked coll/hbm; every rank's shard lives in the
    one HBM), bandwidth mode: an allreduce/reduce_scatter must READ
    all P distinct input shards (they are distinct buffers — each
    rank's chain step produced its own).  A bcast's outputs may
    legally alias the root shard (zero-copy is a correct win of the
    shared-HBM model), but each of the P ranks' mandatory chain step
    still reads+writes its n bytes, so >= P*n moves.  An alltoall's
    size key is the per-pair block; each rank holds P blocks, so the
    chain alone moves >= P*(P*b).  Latency-mode allreduce deposits
    alias (see module docstring): the op still must read its input
    and write a fresh output — >= 2n.  On a real mesh the OSU busbw
    factors apply."""
    if single_chip:
        if kind == "allreduce" and latency_mode:
            return 2.0
        return {"allreduce": float(nranks),
                "bcast": float(nranks),
                "alltoall": float(nranks * nranks),
                "reduce_scatter": float(nranks)}[kind]
    return {"allreduce": 2.0 * (nranks - 1) / nranks,
            "bcast": 1.0,
            "alltoall": float(nranks - 1),
            "reduce_scatter": (nranks - 1) / nranks}[kind]


def run_device_sweep(nranks: int, max_ar: int, max_bcast: int,
                     max_a2a: int, max_rsb: int,
                     budget_s: float = 0.0,
                     allow_cpu: bool = False) -> dict:
    """The sweep's result names the device it ran on (``device``).  Off
    a TPU it raises unless ``allow_cpu`` says the caller wants the CPU
    dry run — whose numbers are not device metrics and carry no
    physical gate."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.op import op as mpi_op
    from ompi_tpu.runtime import jaxcache
    from ompi_tpu.testing import run_ranks

    jaxcache.enable()
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    if dev0.platform != "tpu" and not allow_cpu:
        raise RuntimeError(
            f"device sweep found platform {dev0.platform!r}, not 'tpu'; "
            f"a CPU run is not a device measurement (pass allow_cpu / "
            f"--allow-cpu for an explicit dry run)")
    device_map, devices = _rank_devices(nranks)
    single_chip = not devices
    gate = _QuietGate(nranks)
    t_start = time.perf_counter()

    # per-collective budget windows (fractions of the total); unused
    # time rolls forward because each deadline is computed when its
    # collective STARTS from the time actually remaining
    shares = {"allreduce": 0.45, "bcast": 0.15, "alltoall": 0.15,
              "reduce_scatter": 0.25}

    # explicit CPU dry run: no physical model to gate against
    peak = hbm_peak(dev0.device_kind) if dev0.platform == "tpu" else None

    def fn(comm):
        out = {"allreduce": {}, "bcast": {}, "alltoall": {},
               "reduce_scatter": {}, "truncated": False,
               "read_const_us": None, "gated": [],
               "latency_mode_below": _LAT_MAX if single_chip else 0}

        # per-shape probe reads (compiled at warmup); the token is the
        # first element of the flattened result
        token_fns = {}

        def read_token(arr) -> float:
            key = (arr.shape, str(arr.dtype))
            f = token_fns.get(key)
            if f is None:
                f = jax.jit(lambda a: a.reshape(-1)[:1])
                token_fns[key] = f
            return float(np.asarray(f(arr))[0])

        # d2h read constant, measured on a warmed tiny read
        # UNDER THE QUIET GATE — the same context as every in-loop
        # completion read it will be subtracted from
        tiny = jnp.zeros((1,), jnp.float32)

        def warm_and_measure():
            read_token(tiny)  # compile the probe
            return _measure_read_const(lambda: read_token(tiny))

        read_const = gate.run(comm.rank, 0, warm_and_measure)
        if comm.rank == 0:
            out["read_const_us"] = round(read_const * 1e6, 1)

        def budget_deadline(kind: str) -> float:
            if not budget_s:
                return 0.0
            remaining = budget_s - (time.perf_counter() - t_start)
            later = {"allreduce": ("bcast", "alltoall",
                                   "reduce_scatter"),
                     "bcast": ("alltoall", "reduce_scatter"),
                     "alltoall": ("reduce_scatter",),
                     "reduce_scatter": ()}[kind]
            frac = shares[kind] / (shares[kind]
                                   + sum(shares[k] for k in later))
            return time.perf_counter() + max(5.0, remaining * frac)

        def should_continue(deadline: float,
                            projected_s: float = 0.0) -> bool:
            # rank 0 decides; the gate distributes — ranks must never
            # diverge on whether the next size's collectives run.
            # ``projected_s`` gates ENTRY into a size whose warmup +
            # rounds alone would blow the window (the r2 starvation
            # pattern: an unbudgeted 128 MiB probe ate the budget)
            return gate.run(
                comm.rank, 0,
                lambda: deadline <= 0
                or time.perf_counter() + projected_s < deadline)

        def trace(msg: str) -> None:
            if comm.rank == 0:
                import sys as _sys
                print(f"[sweep +{time.perf_counter() - t_start:6.1f}s] "
                      f"{msg}", file=_sys.stderr, flush=True)

        def one(kind, size_key, x0, make_op, chain, expect0,
                deadline, latency_mode=False, min_of=2):
            # warmup: compile op + chain + probe, verify the numeric
            # result on BOTH the first and the last rank (a collective
            # broken only on its final ring/tree step passes a
            # rank-0-only check); all reads quiet-gated
            r = make_op(x0)
            got = gate.run(comm.rank, 0, lambda: read_token(r))
            if comm.rank == 0:
                assert abs(got - expect0) < 1e-3, \
                    (kind, size_key, got, expect0)
            got = gate.run(comm.rank, nranks - 1,
                           lambda: read_token(r))
            if comm.rank == nranks - 1:
                assert abs(got - expect0) < 1e-3, \
                    (kind, size_key, got, expect0)
            c = chain(r)  # compile the chain step outside the timed loop
            # also compile the probe for the CHAIN output's shape: the
            # timed loop's completion read is on a chain result, which
            # for reduce_scatter has a different shape than the op
            # result — an unwarmed probe would put its ~1 s compile
            # inside the measured window
            gate.run(comm.rank, 0, lambda: read_token(c))
            ts = []
            for _ in range(min_of):
                t = _forced_time(comm, gate, x0, make_op, chain,
                                 read_token, read_const, deadline)
                if t > 0:
                    ts.append(t)
                if not should_continue(deadline):
                    break
            if not ts:
                # deadline hit before the point could be amortized
                # past the read-constant jitter: unmeasurable, not a
                # number
                out[kind][size_key] = None
                return
            t = min(ts)
            # physical sanity gate, PER POINT: a time implying more
            # HBM traffic than the chip can move is a measurement
            # artifact — null THIS point with the violation recorded,
            # keep the rest of the sweep (r3 raised away everything)
            if peak is not None:
                factor = _min_traffic_factor(kind, nranks, single_chip,
                                             latency_mode)
                implied = factor * int(size_key) / t
                if implied > 1.05 * peak:
                    out["gated"].append({
                        "kind": kind, "bytes": int(size_key),
                        "us": round(t * 1e6, 2),
                        "implied_GBs": round(implied / 1e9, 1),
                        "peak_GBs": round(peak / 1e9, 1),
                        "reason": "implied bandwidth exceeds HBM peak "
                                  "(timing artifact)"})
                    out[kind][size_key] = None
                    return
            out[kind][size_key] = round(t * 1e6, 2)

        # runtime device scalars for the chain steps: values XLA only
        # sees at execution time, so the dependency can never be
        # constant-folded into an identity
        inv_p = jax.device_put(jnp.asarray(1.0 / nranks, jnp.float32),
                               comm.device)
        eps32 = jax.device_put(jnp.asarray(0.0, jnp.float32),
                               comm.device)
        # 8 ranks x 32 feedback sums multiply values by 8^32 = 2^96
        # exactly; the rescale restores them bit-for-bit (powers of
        # two are exact in f32 and 36*2^96 ~ 2.9e30 < f32 max)
        descale = jax.device_put(
            jnp.asarray(float(nranks) ** -_RESCALE_EVERY, jnp.float32),
            comm.device)
        scale_f = jax.jit(lambda a, s: a * s)
        shift_f = jax.jit(lambda a, e: a + e)

        expect_sum = float(sum(range(1, nranks + 1)))
        ar_deadline = budget_deadline("allreduce")
        last_cost = [0.0]
        for nbytes in _ar_sizes(max_ar):
            # a size costs ~2x its predecessor (warmup + rounds scale
            # with the payload); entry is gated on that projection
            if not should_continue(ar_deadline, 2.0 * last_cost[0]):
                out["allreduce"]["truncated"] = True
                break
            t_size = time.perf_counter()
            trace(f"allreduce {nbytes}B start")
            n = max(1, nbytes // 4)
            x = jax.device_put(
                jnp.full((n,), comm.rank + 1.0, jnp.float32), comm.device)
            latency_mode = single_chip and nbytes < _LAT_MAX
            if latency_mode:
                # feedback chain: the op's own output is the next
                # input (shared across ranks); exact rescale every
                # _RESCALE_EVERY ops keeps values finite.  The chain
                # closure carries the op counter — per-rank state,
                # advanced identically on every rank.
                ctr = [0]

                def chain_lat(r):
                    ctr[0] += 1
                    if ctr[0] % _RESCALE_EVERY == 0:
                        return scale_f(r, descale)
                    return r

                one("allreduce", str(n * 4), x,
                    lambda v: comm.allreduce_arr(v, mpi_op.SUM),
                    chain_lat, expect_sum, ar_deadline,
                    latency_mode=True, min_of=2)
            else:
                # steady state: sum(1..P) -> *1/P -> mean -> sum
                one("allreduce", str(n * 4), x,
                    lambda v: comm.allreduce_arr(v, mpi_op.SUM),
                    lambda r: scale_f(r, inv_p), expect_sum,
                    ar_deadline)
            last_cost[0] = time.perf_counter() - t_size
            trace(f"allreduce {nbytes}B done in {last_cost[0]:.1f}s -> "
                  f"{out['allreduce'].get(str(max(1, nbytes // 4) * 4))}")
        bc_deadline = budget_deadline("bcast")
        for nbytes in _BCAST_SIZES:
            if nbytes > max_bcast:
                break
            if not should_continue(bc_deadline):
                out["bcast"]["truncated"] = True
                break
            n = max(1, nbytes // 4)
            x = jax.device_put(
                jnp.full((n,), 7.0 if comm.rank == 0 else 0.0,
                         jnp.float32), comm.device)
            one("bcast", str(n * 4), x,
                lambda v: comm.bcast_arr(v, root=0),
                lambda r: shift_f(r, eps32), 7.0, bc_deadline)
            trace(f"bcast {nbytes}B -> "
                  f"{out['bcast'].get(str(max(1, nbytes // 4) * 4))}")
        a2a_deadline = budget_deadline("alltoall")
        for nbytes in _A2A_SIZES:
            if nbytes > max_a2a:
                break
            if not should_continue(a2a_deadline):
                out["alltoall"]["truncated"] = True
                break
            per = max(1, nbytes // 4)
            x = jax.device_put(
                jnp.full((per * nranks,), comm.rank + 1.0,
                         jnp.float32), comm.device)
            one("alltoall", str(per * 4), x,
                lambda v: comm.alltoall_arr(v),
                lambda r: shift_f(r, eps32), 1.0, a2a_deadline)
            trace(f"alltoall {nbytes}B -> "
                  f"{out['alltoall'].get(str(max(1, nbytes // 4) * 4))}")
        if max_rsb:
            # BASELINE config 5 as specified: MPI_MAX on MPI_DOUBLE
            # sourced through a derived VECTOR datatype, with the
            # datatype pack running ON DEVICE (datatype/device.py: the
            # run descriptors become static slices, here a strided
            # read, fused into the collective).  float64 needs jax x64; when the backend
            # cannot compile f64 (some TPU generations) the sweep
            # falls back to float32 and RECORDS the substitution
            # instead of silently benching a different config.
            from ompi_tpu.datatype import engine as dtmod
            from ompi_tpu.datatype.device import device_pack
            rs_dtype = jnp.float64
            x64_before = bool(jax.config.jax_enable_x64)
            try:
                jax.config.update("jax_enable_x64", True)
                probe = jax.device_put(jnp.zeros((2,), jnp.float64),
                                       comm.device)
                _ = (probe + 1).dtype
                if np.dtype(probe.dtype) != np.dtype("float64"):
                    rs_dtype = jnp.float32  # x64 unavailable: silent
            except Exception:
                rs_dtype = jnp.float32
            if rs_dtype is jnp.float32:
                # process-global switch: never leave it flipped when
                # the section runs f32 anyway
                jax.config.update("jax_enable_x64", x64_before)
            out["config5_dtype"] = str(np.dtype(rs_dtype))
            itemsize = np.dtype(rs_dtype).itemsize
            base_dt = dtmod.from_numpy_dtype(np.dtype(rs_dtype))
            neg1 = jax.device_put(jnp.asarray(-1.0, rs_dtype),
                                  comm.device)
            rsb_deadline = budget_deadline("reduce_scatter")
            for nbytes in _RSB_SIZES:
                if nbytes > max_rsb:
                    break
                if not should_continue(rsb_deadline):
                    out["reduce_scatter"]["truncated"] = True
                    break
                per = max(1, nbytes // itemsize // nranks)
                n = per * nranks
                # vector: n blocks of 1 element, stride 2 elements —
                # the packed stream is the even-indexed elements
                vec = dtmod.vector(n, 1, 2, base_dt).commit()
                raw = jax.device_put(
                    jnp.stack([jnp.full((n,), comm.rank + 1.0,
                                        rs_dtype),
                               jnp.full((n,), -1.0, rs_dtype)],
                              axis=1).reshape(-1), comm.device)
                packed_fn = jax.jit(
                    lambda a: device_pack(vec, 1, a))
                packed_fn(raw)  # warm the pack

                # chain: re-interleave the (n/P)-element result back
                # into the strided raw layout — the device_pack slice
                # stays INSIDE the timed loop (it is part of config 5)
                # and every iteration's raw input depends on the
                # previous collective's output
                def reinterleave(prev, filler, _n=n, _p=nranks,
                                 _dt=rs_dtype):
                    main = jnp.tile(prev, _p)[:_n]
                    pad = jnp.broadcast_to(filler, (_n,))
                    return jnp.stack([main, pad], axis=1).reshape(-1)

                chain_fn = jax.jit(reinterleave)
                one("reduce_scatter", str(n * itemsize), raw,
                    lambda v: comm.reduce_scatter_arr(
                        packed_fn(v), mpi_op.MAX),
                    lambda r: chain_fn(r, neg1),
                    float(nranks), rsb_deadline)
                trace(f"reduce_scatter {nbytes}B -> "
                      f"{out['reduce_scatter'].get(str(n * itemsize))}")
            if "config5_dtype" in out:
                jax.config.update("jax_enable_x64", x64_before)
        out["truncated"] = any(
            isinstance(v, dict) and v.get("truncated")
            for v in out.values())
        comm.Barrier()
        return out

    res = run_ranks(nranks, fn, devices=devices, device_map=device_map,
                    timeout=3600)
    return {**res[0], "device": device}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nranks", type=int, default=8)
    ap.add_argument("--max-ar", type=int, default=256 * 1024 * 1024)
    ap.add_argument("--max-bcast", type=int, default=64 * 1024 * 1024)
    ap.add_argument("--max-a2a", type=int, default=4 * 1024 * 1024)
    ap.add_argument("--max-rsb", type=int, default=16 * 1024 * 1024)
    ap.add_argument("--budget", type=float, default=0.0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="explicit CPU dry run (not a device "
                         "measurement)")
    opts = ap.parse_args()
    print(json.dumps(run_device_sweep(
        opts.nranks, opts.max_ar, opts.max_bcast, opts.max_a2a,
        opts.max_rsb, budget_s=opts.budget,
        allow_cpu=opts.allow_cpu)), flush=True)


if __name__ == "__main__":
    main()
