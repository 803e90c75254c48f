"""Device data-plane smoke: the rank program chip_smoke.py launches.

    python -m ompi_tpu.tools.mpirun -np N --ranks-per-proc all \
        examples/device_smoke.py --seed 0

Every rank-thread drives the public device API once at BASELINE.md's
real sizes — ``comm.*_arr`` collectives, a fused ``iallreduce_arr``
batch, a ``send_arr``/``recv_arr`` ring and one ``Win`` fence epoch —
and the job fails unless each result equals an independent numpy
computation on the first and the last rank, each call was served by a
device module (``hbm`` or ``tpu``, never the host-staged ``arr_host``)
and each shard sits on the device the layout says.

Layout is whatever the launch gives: N ranks on one device select
coll/hbm, N ranks on N devices select coll/tpu.  Inputs are
small-integer-valued and made from ``--seed``, so every reduction is
exact in any order.  Timings are host-clock around
``jax.block_until_ready`` and are printed as smoke timings: they show
the path runs, they are not a benchmark.

``--tiny`` divides every size by 256 for CPU debugging; chip_smoke.py
divides the pipeline tier's crossover and segment size by the same
factor so the same code paths run.
"""
import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

import ompi_tpu
from ompi_tpu import osc
from ompi_tpu.coll import ragged
from ompi_tpu.mca.params import registry
from ompi_tpu.op import op as mpi_op

KIB, MIB = 1024, 1024 * 1024

# per-rank bytes; float32 unless the entry says otherwise
REAL = {
    "allreduce": (4 * KIB, MIB, 24_000_012, 128 * MIB, 256 * MIB),
    "fused": 4 * KIB, "bcast": 64 * MIB, "alltoall_pair": 4 * MIB,
    "reduce_scatter": 16 * MIB, "config5": 16 * MIB,
    "allgather": 16 * MIB, "ppermute": 32 * MIB, "sendrecv": 32 * MIB,
    "win": MIB, "bur": 128 * MIB,
}
TINY = {
    "allreduce": (16, 4 * KIB, 93_756, 512 * KIB, MIB),
    "fused": 16, "bcast": 256 * KIB, "alltoall_pair": 16 * KIB,
    "reduce_scatter": 64 * KIB, "config5": 64 * KIB,
    "allgather": 64 * KIB, "ppermute": 128 * KIB, "sendrecv": 128 * KIB,
    "win": 4 * KIB, "bur": 512 * KIB,
}

COUNTERS = {
    "tpu": "coll_tpu_offloaded_collectives",
    "hbm": "coll_hbm_offloaded_collectives",
    "host": "coll_arr_host_staged_collectives",
    "pipe_ops": "coll_pipeline_ops",
    "pipe_segs": "coll_pipeline_segments",
    "plan_builds": "coll_plan_builds",
    "plan_hits": "coll_plan_hits",
    "fused": "coll_device_fused_collectives",
    "typed": "coll_typed_device_ops",
    "ragged": "coll_alltoallv_device_ops",
    "d2d": "btl_tpu_d2d_sends",
    "staged": "btl_tpu_staged_sends",
    "moved": "btl_tpu_recv_moves",
}
CALLS = 3  # after the compiling one


class SmokeFailure(RuntimeError):
    pass


def counters():
    pv = {p.full_name: p for p in registry.all_pvars()}
    return {k: int(pv[n].read()) if n in pv else 0
            for k, n in COUNTERS.items()}


def gen(seed, op_id, rank, n, dtype):
    """Rank ``rank``'s input for operation ``op_id``: n small integers
    in [-8, 8).  Any rank can regenerate any other rank's input, which
    is what makes the reference independent of the library."""
    rng = np.random.default_rng([seed, op_id, rank])
    return rng.integers(-8, 8, size=n, dtype=np.int8).astype(dtype)


class Smoke:
    def __init__(self, comm, opts):
        import jax

        self.jax = jax
        self.comm = comm
        self.rank, self.size = comm.rank, comm.size
        self.seed = opts.seed
        self.sizes = TINY if opts.tiny else REAL
        self.label = opts.label
        self.ops = []       # rank 0: one record per operation
        self.op_id = 0
        self.layout_ids = []  # rank 0: distinct device ids of the ranks

    # -- plumbing ---------------------------------------------------------
    def say(self, msg):
        if self.rank == 0:
            # one atomic write per line: ranks share the shell's stdout
            sys.stdout.write(f"{self.label}{msg}\n")
            sys.stdout.flush()

    def gather(self, vals):
        """(size, len(vals)) int64 on rank 0, None elsewhere."""
        s = np.asarray(vals, dtype=np.int64)
        r = np.empty((self.size, s.size), np.int64) \
            if self.rank == 0 else None
        self.comm.Gather(s, r, root=0)
        return r

    def put(self, host):
        return self.jax.device_put(host, self.comm.device)

    def on_my_device(self, arr):
        """The result is a device array this rank's device holds (a
        replicated output sits on every device of the mesh)."""
        return isinstance(arr, self.jax.Array) \
            and self.comm.device in arr.devices()

    # -- one collective ---------------------------------------------------
    def collective(self, name, fname, nbytes, dtype, make_host, call,
                   reference, check_memory=False, counter=None):
        """Run ``call`` once (compiling) and CALLS more times on this
        rank's seeded input, then check result, provider, counters and
        placement.  ``make_host(rank)`` is numpy in, ``reference(rank)``
        the numpy answer for that rank.  ``check_memory`` also requires
        at least this rank-input's bytes in use on every device of the
        layout while all ranks still hold their inputs.  ``counter`` names the engagement
        counter that must move when it is not the provider's own."""
        jax, comm = self.jax, self.comm
        self.op_id += 1
        x = self.put(make_host(self.rank))
        comm.Barrier()
        before = counters()
        comm.Barrier()  # nobody counts before everybody has read
        t0 = time.perf_counter()
        out = jax.block_until_ready(call(x))
        first = time.perf_counter() - t0
        steady = []
        for _ in range(CALLS):
            t0 = time.perf_counter()
            out = jax.block_until_ready(call(x))
            steady.append(time.perf_counter() - t0)
        if check_memory and self.rank == 0:
            # no peer passes the barrier below before this returns, so
            # every rank's input is still resident
            self.memory_in_use(nbytes)
        comm.Barrier()
        after = counters()

        ok = 1
        if self.rank in (0, self.size - 1):
            got, ref = np.asarray(out), reference(self.rank)
            ok = int(got.shape == ref.shape and got.dtype == ref.dtype
                     and np.array_equal(got, ref))
        med = statistics.median(steady)
        facts = self.gather([ok, int(self.on_my_device(out)),
                             int(first * 1e6), int(med * 1e6)])
        if self.rank != 0:
            return out
        delta = {k: after[k] - before[k] for k in COUNTERS}
        provider = comm.coll.providers.get(fname, "none")
        tier = "pipeline" if delta["pipe_ops"] else "single-dispatch"
        rec = {"op": name, "bytes": nbytes, "dtype": np.dtype(dtype).name,
               "provider": provider, "tier": tier, "counters": delta,
               "first_call_s": round(facts[:, 2].max() / 1e6, 4),
               "smoke_timing_s": round(facts[:, 3].max() / 1e6, 6)}
        self.ops.append(rec)
        self.say(f"op={name} bytes={nbytes} dtype={rec['dtype']} "
                 f"provider={provider} tier={tier} "
                 f"counters={json.dumps(delta, separators=(',', ':'))} "
                 f"first_call_s={rec['first_call_s']} "
                 f"smoke_timing_s={rec['smoke_timing_s']} "
                 f"(host clock, slowest rank, median of {CALLS})")
        if (counter is None and provider not in ("hbm", "tpu")) \
                or delta["host"] or not delta[counter or provider]:
            raise SmokeFailure(
                f"{name} {nbytes} B was not served by a device module: "
                f"provider={provider} counters={delta}")
        if not facts[:, 0].all():
            raise SmokeFailure(
                f"{name} {nbytes} B differs from the numpy reference on "
                f"rank(s) {np.flatnonzero(facts[:, 0] == 0).tolist()}")
        if not facts[:, 1].all():
            raise SmokeFailure(
                f"{name} {nbytes} B: result is off its rank's device on "
                f"rank(s) {np.flatnonzero(facts[:, 1] == 0).tolist()}")
        return out

    def layout_devices(self):
        return [d for d in self.jax.devices() if d.id in self.layout_ids]

    def memory_in_use(self, at_least):
        """Every device of the layout holds a rank's input (the CPU
        backend keeps no such statistics and is skipped)."""
        for d in self.layout_devices():
            st = d.memory_stats()
            if st is None and d.platform == "cpu":
                self.say(f"memory: device {d.id} keeps no memory_stats")
                continue
            used = st["bytes_in_use"]
            self.say(f"memory: device {d.id} bytes_in_use={used}")
            if used < at_least:
                raise SmokeFailure(
                    f"device {d.id} of the layout holds {used} B, less "
                    f"than one rank's {at_least} B input")

    # -- the operations ---------------------------------------------------
    def allreduce(self, nbytes, check_memory=False):
        n, oid = nbytes // 4, self.op_id + 1
        self.collective(
            "allreduce_sum", "allreduce_arr", n * 4, np.float32,
            lambda r: gen(self.seed, oid, r, n, np.float32),
            lambda x: self.comm.allreduce_arr(x, mpi_op.SUM),
            lambda r: sum(gen(self.seed, oid, s, n, np.float32)
                          for s in range(self.size)),
            check_memory=check_memory)

    def fused_batch(self):
        """Four small iallreduce_arr coalesced into one dispatch by
        coll/fusion, flushed by the first wait()."""
        n, oid, k = self.sizes["fused"] // 4, self.op_id + 1, 4

        def host(r):
            return gen(self.seed, oid, r, k * n, np.float32)

        def call(x):
            reqs = [self.comm.iallreduce_arr(x[i * n:(i + 1) * n],
                                             mpi_op.SUM)
                    for i in range(k)]
            for q in reqs:
                q.wait()
            return self.jax.numpy.concatenate([q.result for q in reqs])

        self.collective(
            "iallreduce_sum_x4_fused", "iallreduce_arr", n * 4,
            np.float32, host, call,
            lambda r: sum(host(s) for s in range(self.size)),
            counter="fused")

    def bcast(self):
        n, oid = self.sizes["bcast"] // 4, self.op_id + 1
        root = 1 % self.size
        self.collective(
            "bcast", "bcast_arr", n * 4, np.float32,
            lambda r: gen(self.seed, oid, r, n, np.float32),
            lambda x: self.comm.bcast_arr(x, root=root),
            lambda r: gen(self.seed, oid, root, n, np.float32))

    def alltoall(self):
        m, oid, p = self.sizes["alltoall_pair"] // 4, self.op_id + 1, \
            self.size
        self.collective(
            "alltoall", "alltoall_arr", m * 4, np.float32,
            lambda r: gen(self.seed, oid, r, p * m, np.float32),
            lambda x: self.comm.alltoall_arr(x),
            lambda r: np.concatenate(
                [gen(self.seed, oid, s, p * m, np.float32)
                 [r * m:(r + 1) * m] for s in range(p)]))

    def alltoallv(self):
        """The key exchange of NAS Parallel Benchmarks IS at class S
        (2**16 keys, MAX_KEY 2**11, 2**9 buckets): every rank buckets
        its keys, the ranks split the buckets so that each owns about
        NUM_KEYS keys, and one ``alltoallv_arr`` moves every key to its
        owner.  The counts are host integers and differ for every pair;
        coll/hbm on one chip, and coll/tpu over a mesh of chips, serve
        the call with one program whose counts are operands
        (``coll_alltoallv_device_ops`` moves once a rank-call).
        The receive buffer is IS's SIZE_OF_BUFFERS; what lies past the
        received keys is not part of the result, so the comparison
        takes the received keys alone.  Over a mesh a key travels as a
        row of 128 int32 (key x 128 + column): the narrowest row the
        mesh program moves (coll/ragged.MESH_ROW_BYTES)."""
        p, oid = self.size, self.op_id + 1
        nkeys, shift, nb = (1 << 16) // p, 2, 1 << 9
        cap = 3 * nkeys // 2
        ks = [np.random.default_rng([self.seed, oid, r]).integers(
            0, 1 << 9, (4, nkeys)).sum(0).astype(np.int32)
            for r in range(p)]
        cum = np.cumsum([np.bincount(k >> shift, minlength=nb)
                         for k in ks], axis=1)
        # owner j ends at the first bucket where the running total of
        # all ranks' keys reaches (j + 1) * NUM_KEYS
        last = np.searchsorted(cum.sum(0), (np.arange(p) + 1) * nkeys)
        upto = np.concatenate(
            [np.zeros((p, 1), np.int64), cum[:, np.minimum(last, nb - 1)]],
            axis=1)
        counts = np.diff(upto, axis=1)        # [source, destination]
        buff1 = [k[np.argsort(k >> shift, kind="stable")] for k in ks]
        at = np.cumsum(counts, axis=1) - counts
        if self.comm.coll.providers.get("alltoallv_arr") != "hbm":
            cols = np.arange(128, dtype=np.int32)
            buff1 = [b[:, None] * 128 + cols for b in buff1]
        me = self.rank
        self.collective(
            "alltoallv_is_class_s", "alltoallv_arr", buff1[0].nbytes,
            np.int32, lambda r: buff1[r],
            lambda x: self.comm.alltoallv_arr(
                x, counts[me], counts[:, me],
                capacity=cap)[:int(counts[:, me].sum())],
            lambda r: np.concatenate(
                [buff1[s][at[s, r]:at[s, r] + counts[s, r]]
                 for s in range(p)]),
            counter="ragged")

    def reduce_scatter(self):
        p, oid = self.size, self.op_id + 1
        m = self.sizes["reduce_scatter"] // 4 // p
        self.collective(
            "reduce_scatter_block_sum", "reduce_scatter_block_arr",
            p * m * 4, np.float32,
            lambda r: gen(self.seed, oid, r, p * m, np.float32),
            lambda x: self.comm.reduce_scatter_arr(x, mpi_op.SUM),
            lambda r: sum(gen(self.seed, oid, s, p * m, np.float32)
                          [r * m:(r + 1) * m] for s in range(p)))

    def config5(self):
        """BASELINE config 5: Reduce_scatter_block MPI_MAX on
        MPI_DOUBLE through a derived vector datatype.  The datatype is
        the collective's own argument (the library packs on the device,
        inside the collective's program) and MPI_DOUBLE on the device is
        the job's ``--mca mpi_device_x64 1``, which chip_smoke.py's
        launch line carries.  Where the device's float64 is not IEEE
        binary64 (a TPU v5e) the doubles travel as the library says
        they do there, as uint64 bit patterns (runtime/x64)."""
        from ompi_tpu.datatype import engine as dtmod
        from ompi_tpu.runtime import x64

        comm, p, oid = self.comm, self.size, self.op_id + 1
        if not self.jax.config.jax_enable_x64:
            raise SmokeFailure(
                "config5 needs MPI_DOUBLE on the device: launch with "
                "--mca mpi_device_x64 1")
        m = self.sizes["config5"] // 8 // p
        n = m * p
        # n blocks of 1 element, stride 2: the packed stream is the
        # even-indexed elements of a 2n-element buffer
        vec = dtmod.vector(n, 1, 2, dtmod.DOUBLE).commit()
        carry = (lambda a: a) if x64.native() else x64.bits
        self.collective(
            "config5_reduce_scatter_block_max_vector",
            "reduce_scatter_block_arr", n * 8, np.float64,
            lambda r: carry(gen(self.seed, oid, r, 2 * n, np.float64)),
            lambda x: comm.reduce_scatter_arr(x, mpi_op.MAX, vec, 1),
            lambda r: carry(np.max(
                [gen(self.seed, oid, s, 2 * n, np.float64)[::2]
                 [r * m:(r + 1) * m] for s in range(p)], axis=0)),
            counter="typed")
        return "float64" if x64.native() else "float64 as uint64 bits"

    def allgather(self):
        n, oid = self.sizes["allgather"] // 4, self.op_id + 1
        self.collective(
            "allgather", "allgather_arr", n * 4, np.float32,
            lambda r: gen(self.seed, oid, r, n, np.float32),
            lambda x: self.comm.allgather_arr(x),
            lambda r: np.concatenate(
                [gen(self.seed, oid, s, n, np.float32)
                 for s in range(self.size)]))

    def ppermute(self):
        n, oid, p = self.sizes["ppermute"] // 4, self.op_id + 1, self.size
        ring = [(i, (i + 1) % p) for i in range(p)]
        self.collective(
            "ppermute_ring", "ppermute_arr", n * 4, np.float32,
            lambda r: gen(self.seed, oid, r, n, np.float32),
            lambda x: self.comm.ppermute_arr(x, ring),
            lambda r: gen(self.seed, oid, (r - 1) % p, n, np.float32))

    def sendrecv_ring(self):
        """send_arr/recv_arr ring through btl/tpu: co-resident peers
        get the array placed on the receiver's device and delivered by
        reference."""
        jax, comm, p = self.jax, self.comm, self.size
        self.op_id += 1
        n, oid = self.sizes["sendrecv"] // 4, self.op_id
        dst, src = (self.rank + 1) % p, (self.rank - 1) % p
        x = self.put(gen(self.seed, oid, self.rank, n, np.float32))
        comm.Barrier()
        before = counters()
        comm.Barrier()   # nobody counts before everybody has read
        times = []
        for _ in range(1 + CALLS):
            t0 = time.perf_counter()
            comm.send_arr(x, dst, tag=7)
            out = jax.block_until_ready(comm.recv_arr(src, tag=7))
            times.append(time.perf_counter() - t0)
        comm.Barrier()
        after = counters()
        ok = int(np.array_equal(
            np.asarray(out), gen(self.seed, oid, src, n, np.float32)))
        facts = self.gather([ok, int(self.on_my_device(out)),
                             int(statistics.median(times[1:]) * 1e6)])
        if self.rank != 0:
            return
        # the provider is what the library's own counters say served
        # the calls: every send placed on the peer's device, none
        # through host memory, none placed again on arrival
        delta = {k: after[k] - before[k] for k in ("d2d", "staged", "moved")}
        placed = delta == {"d2d": p * (1 + CALLS), "staged": 0, "moved": 0}
        provider = "btl/tpu" if placed else "btl/tpu-not-placed"
        rec = {"op": "send_arr_recv_arr_ring", "bytes": n * 4,
               "dtype": "float32", "provider": provider,
               "smoke_timing_s": round(facts[:, 2].max() / 1e6, 6)}
        self.ops.append(rec)
        self.say(f"op={rec['op']} bytes={n * 4} dtype=float32 "
                 f"provider={provider} "
                 f"p2p_counters={json.dumps(delta, separators=(',', ':'))} "
                 f"smoke_timing_s={rec['smoke_timing_s']} "
                 f"(host clock, slowest rank, median of {CALLS})")
        if not facts[:, :2].all():
            raise SmokeFailure(
                "send_arr/recv_arr ring: [matches numpy, on my device] "
                f"per rank = {facts[:, :2].tolist()}")
        if not placed:
            raise SmokeFailure(
                f"send_arr/recv_arr ring: {p * (1 + CALLS)} sends, and "
                f"btl/tpu's counters moved by {delta}: not every one was "
                "placed on the peer's device, or one went through the host")

    def win_epoch(self):
        """One Win fence epoch with put, get and accumulate through
        osc/device (default lowering).  The device window needs one
        rank per device: that is the world comm on a mesh, and each
        rank's own single-member comm when the ranks share a chip."""
        comm = self.comm
        self.op_id += 1
        oid, nb = self.op_id, self.sizes["win"]
        n = nb // 4
        wcomm = comm if comm.mesh() is not None else comm.split(self.rank)
        me, p = wcomm.rank, wcomm.size
        tgt, frm = (me + 1) % p, (me - 1) % p
        # world ranks of my window peers, for regenerating their data
        wr = {r: wcomm.group[r] for r in (me, tgt, frm)}
        a = gen(self.seed, oid, wr[me], n, np.float32)
        b = gen(self.seed, oid + 1000, wr[me], n, np.float32)

        t0 = time.perf_counter()
        win = osc.allocate(wcomm, 3 * nb, disp_unit=1, name="smoke")
        win.fence()
        win.put(a, tgt, disp=0)
        win.accumulate(b, tgt, disp=nb, op=mpi_op.SUM)
        win.accumulate(b, 0, disp=2 * nb, op=mpi_op.SUM)  # contended
        win.fence()
        back = np.empty(n, np.float32)
        win.get(back, tgt, disp=0)
        win.fence()
        mem = win.memory.view(np.float32)
        elapsed = time.perf_counter() - t0
        exp_acc0 = sum(gen(self.seed, oid + 1000, wcomm.group[s], n,
                           np.float32) for s in range(p))
        ok = int(
            np.array_equal(back, a)
            and np.array_equal(
                mem[:n], gen(self.seed, oid, wr[frm], n, np.float32))
            and np.array_equal(
                mem[n:2 * n],
                gen(self.seed, oid + 1000, wr[frm], n, np.float32))
            and (me != 0 or np.array_equal(mem[2 * n:], exp_acc0)))
        kind = type(win).__name__
        win.free()
        facts = self.gather([ok, int(kind == "DeviceWindow"),
                             int(elapsed * 1e6)])
        if self.rank != 0:
            return
        where = "world mesh" if wcomm is comm else \
            "per-rank single-member comm (ranks share one chip)"
        rec = {"op": "win_fence_put_get_accumulate", "bytes": nb,
               "dtype": "float32", "provider": kind,
               "smoke_timing_s": round(facts[:, 2].max() / 1e6, 6)}
        self.ops.append(rec)
        self.say(f"op={rec['op']} bytes={nb} dtype=float32 "
                 f"provider={kind} window_comm={where!r} "
                 f"smoke_timing_s={rec['smoke_timing_s']} "
                 "(host clock, slowest rank, whole epoch incl. compile)")
        if not facts[:, :2].all():
            raise SmokeFailure(
                "Win epoch: [matches numpy, served by DeviceWindow] per "
                f"rank = {facts[:, :2].tolist()}")

    # -- does block_until_ready await execution here? ---------------------
    def block_until_ready_check(self):
        """A chained op on one rank, peers parked at a barrier: time to
        block_until_ready, then a 4-byte read, against the constant of
        that read on an idle array.  If block_until_ready returned
        early the read pays the chain's remaining device time."""
        jax, comm = self.jax, self.comm
        res = None
        if self.rank == 0:
            # each dispatch allocates its output ahead of execution:
            # 32 x 128 MiB is the most this check can hold at once
            n, iters = self.sizes["bur"] // 4, 32
            step = jax.jit(lambda a, s: a * s + 1.0)
            peek = jax.jit(lambda a: a[:1])
            s = self.put(np.float32(0.5))
            x = self.put(np.zeros(n, np.float32))
            y = jax.block_until_ready(step(x, s))
            np.asarray(peek(y))
            reads = []
            for _ in range(7):
                t0 = time.perf_counter()
                np.asarray(peek(y))
                reads.append(time.perf_counter() - t0)
            read_const = min(reads)
            t0 = time.perf_counter()
            y = x
            for _ in range(iters):
                y = step(y, s)
            t_dispatch = time.perf_counter() - t0
            jax.block_until_ready(y)
            t_block = time.perf_counter() - t0
            t1 = time.perf_counter()
            np.asarray(peek(y))
            t_read = time.perf_counter() - t1
            waits = bool(t_read <= 3 * read_const + 0.1 * t_block)
            res = {"block_until_ready_waits": waits,
                   "chain": f"{iters} x {n * 4} B",
                   "dispatch_s": round(t_dispatch, 6),
                   "to_block_until_ready_s": round(t_block, 6),
                   "read_after_s": round(t_read, 6),
                   "read_const_s": round(read_const, 6)}
            self.say(f"block_until_ready_waits: "
                     f"{'true' if waits else 'false'} "
                     f"{json.dumps(res, separators=(',', ':'))}")
        comm.Barrier()
        return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--label", default="")
    ap.add_argument("--expect-devices", type=int, default=0)
    opts = ap.parse_args()

    t_init = time.perf_counter()
    comm = ompi_tpu.init()
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not opts.allow_cpu:
        raise SmokeFailure(
            f"platform is {platform!r}, not 'tpu' (no CPU fallback; "
            "--allow-cpu --tiny is the development mode)")
    sm = Smoke(comm, opts)
    xla = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}
    if comm.rank == 0:
        # process-wide listeners: every rank-thread's compiles land here
        def on_duration(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                xla["compile_s"] += secs

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                xla["cache_hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                xla["cache_misses"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
    comm.Barrier()

    # the layout the launch gave: every rank's device id
    ids = sm.gather([comm.device.id])
    layout = None
    if comm.rank == 0:
        distinct = sm.layout_ids = sorted(set(ids[:, 0].tolist()))
        layout = "hbm" if len(distinct) == 1 else "tpu"
        if len(distinct) not in (1, comm.size) or (
                opts.expect_devices
                and len(distinct) != opts.expect_devices):
            raise SmokeFailure(
                f"{comm.size} ranks sit on device ids {ids[:, 0].tolist()}"
                f": expected one shared device or one each"
                + (f" ({opts.expect_devices} distinct)"
                   if opts.expect_devices else ""))
        from ompi_tpu import native
        import jaxlib

        from importlib import metadata
        try:
            libtpu = metadata.version("libtpu")
        except metadata.PackageNotFoundError:
            libtpu = "none"
        sm.say(f"device: platform={platform} kind={devs[0].device_kind} "
               f"count={len(devs)} jax={jax.__version__} "
               f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
        sm.say(f"layout: {comm.size} ranks on device ids {distinct} -> "
               f"coll/{layout}; native_loaded={native.available()}; "
               f"compile_cache="
               f"{jax.config.jax_compilation_cache_dir or 'off'} "
               f"(JAX_COMPILATION_CACHE_DIR "
               f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
        if not native.available():
            raise SmokeFailure(
                "ompi_tpu.native could not be built from native/*.cpp")

    for nbytes in sm.sizes["allreduce"]:
        sm.allreduce(nbytes,
                     check_memory=nbytes == sm.sizes["allreduce"][-1])
    sm.fused_batch()
    sm.bcast()
    sm.alltoall()
    if comm.coll.providers.get("alltoallv_arr") == "hbm" \
            or platform not in ragged.NO_LOWERING:
        sm.alltoallv()
    else:
        sm.say("alltoallv_is_class_s: skipped, one rank a device of "
               "XLA:CPU, which cannot lower coll/tpu's ragged-all-to-all")
    sm.reduce_scatter()
    c5 = sm.config5()
    sm.allgather()
    sm.ppermute()
    sm.sendrecv_ring()
    sm.win_epoch()
    peak = None
    if comm.rank == 0:  # before the check below queues its own chain
        peak = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in sm.layout_devices()}
    bur = sm.block_until_ready_check()

    if comm.rank == 0:
        summary = {
            "platform": platform, "kind": devs[0].device_kind,
            "count": len(devs), "ranks": comm.size, "layout": layout,
            "config5_dtype": c5,
            "block_until_ready_waits": bur["block_until_ready_waits"],
            "xla_compile_or_load_s": round(xla["compile_s"], 3),
            "persistent_cache_hits": xla["cache_hits"],
            "persistent_cache_misses": xla["cache_misses"],
            "leg_s": round(time.perf_counter() - t_init, 1),
            "peak_bytes_in_use": peak, "ops": sm.ops,
        }
        sm.say("SMOKE_LEG " + json.dumps(summary, separators=(",", ":")))
    ompi_tpu.finalize()


if __name__ == "__main__":
    main()
