"""Device-memory one-sided RMA: the osc/device component.

Re-design of ompi/mca/osc/rdma for the thread-rank TPU world: window
memory lives in device HBM (one uint8 shard per rank on the comm's
mesh) and the DATA PLANE never touches the host AM path.  The
single-controller property that powers the coll reroute powers true
one-sided semantics here: the ORIGIN thread alone launches a
whole-mesh jitted program that moves its payload onto the target's
shard with ``ppermute`` + masked dynamic-slice merge — the target
thread does not participate, exactly as osc/rdma's btl put/get
bypasses the target CPU (ref: osc_rdma_comm.c put/get paths).

Lowering table (DESIGN.md §19):

    put/rput      direct DMA: compose the target shard on the origin's
                  host staging buffer (64-byte aligned so device_put
                  aliases instead of copying) and swap it in; a
                  wholesale aligned overwrite skips even the compose
                  and borrows the origin buffer until the local
                  completion point, exactly like zero-copy RDMA —
                  MPI already forbids mutating an origin buffer
                  before flush/unlock/fence.  ``--mca
                  osc_device_dma 0`` selects the mesh-collective
                  lowering instead: ppermute row origin→target +
                  masked merge, donated, chunked by the pipeline
                  tier's segment size
    get/rget      direct DMA: device→host read of the target shard +
                  memcpy of the requested span (kernel mode: masked
                  slice on target row + ppermute target→origin)
    accumulate    whole-mesh bucket kernel with bitcast u8→dtype→u8
                  and the op mapped through coll/plan's jnp binop
                  table (read-modify-write stays on device)
    get_accumulate / fetch_and_op   accumulate kernel variant that
                  ppermutes the pre-op bytes back to the origin
    compare_and_swap   single-element kernel (cmp, new) pair

Every kernel is cached in coll/device's CompiledLRU under keys that
embed the mesh's dev_key top-level, so ULFM's ``drop_mesh`` purge
covers RMA kernels exactly as it covers collectives.  Transfers
larger than the pipeline tier's calibrated segment are chunked into
segment-sized bucket kernels so a size sweep stays bounded.

Synchronization: ops apply synchronously inside the origin's call
(the DMA or mesh program IS remote completion), so ``fence``
degenerates to a liveness check + Barrier and ``flush`` to the
local-completion work of decoupling any zero-copy put — no AM
round-trip, because a device window never has ops outstanding at the
target.  lock/unlock/PSCW are inherited unchanged from the host AM
window — control stays on the host, payloads stay on device — and a
target parked in ``wait`` still serves grants because the AM handler
rides the progress sweep.

Typed atomics: in DMA mode every accumulate/CAS dtype takes the
host-side read-modify-write of the target's write-through mirror
under the window's table lock — one lock, every op serialized, so
atomicity holds across mixed dtypes and paths.  In kernel mode the
wire dtypes jax can bitcast run the jitted bucket kernels and the
rest (int64/float64/complex/bool/pair) take the same host fallback,
with ``mpi_device_x64`` on as with it off: the bucket kernels are
built for the 32-bit wire dtypes only.  put/get are byte-level (the
window is uint8 on the device), so a window never narrows a double.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ompi_tpu import obs as _obs
from ompi_tpu import trace as _trace
from ompi_tpu.mca.params import registry
from ompi_tpu.op import op as opmod
from ompi_tpu.osc import window as _host
from ompi_tpu.osc.window import _DT_CODE, _WIRE_DTYPES, Window

_CAT_RMA = _trace.CAT_RMA
_NAME_RMA_PUT = _trace.NAME_RMA_PUT
_NAME_RMA_GET = _trace.NAME_RMA_GET
_NAME_RMA_ACC = _trace.NAME_RMA_ACC

_seg_var = registry.register(
    "osc", "device", "seg_bytes", 0, int,
    help="Chunk size (bytes) for device RMA transfers larger than one "
         "bucket kernel; 0 = reuse the pipeline tier's calibrated "
         "segment size (coll_seg_size / measured rules)")

_dma_var = registry.register(
    "osc", "device", "dma", 1, int,
    help="1 = lower contiguous put/get to direct host<->device DMA "
         "(aligned staging swap, zero-copy where the runtime allows); "
         "0 = whole-mesh ppermute bucket kernels for every transfer — "
         "the mesh-collective lowering, kept for topologies where an "
         "origin-driven host DMA is the slow path")

# staging discipline (alignment, aliasing probe, mirror pool, the
# donated-buffers warning filter) lives in the shared runtime module
# since the coll plan tier packs through the same bypass; the local
# names survive because the DMA path below predates the hoist
from ompi_tpu.runtime import staging as _staging

_STAGE_ALIGN = _staging.STAGE_ALIGN
_aligned_empty = _staging.aligned_empty
_runtime_zero_copy = _staging.runtime_zero_copy

#: window capacity / bucket alignment: max wire itemsize (complex128)
_ALIGN = 16
#: smallest bucket kernel — below this the fixed dispatch cost
#: dominates and one shape serves every tiny op
_BUCKET_MIN = 256

#: dtypes whose accumulate/CAS kernels run on device (8-byte and
#: complex dtypes take the host fallback, whatever mpi_device_x64 says)
_JIT_ACC_DTYPES = frozenset(
    np.dtype(t).str for t in (np.uint8, np.int8, np.int16, np.uint16,
                              np.int32, np.uint32, np.float32))


def _pow2ceil(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


def _pow2floor(n: int) -> int:
    b = 1
    while b * 2 <= n:
        b <<= 1
    return b


def _bucket(nbytes: int, cap: int) -> int:
    """Static kernel width for an nbytes transfer into a cap-byte
    shard: pow2-quantized so the compile-cache key set stays bounded,
    clamped to the shard so the slice math can always clamp left."""
    b = _pow2ceil(max(nbytes, min(_BUCKET_MIN, cap)))
    return min(b, cap)


def _binop(opname: str):
    if opname == "MPI_REPLACE":
        return lambda s, w: s
    if opname == "MPI_NO_OP":
        return lambda s, w: w
    from ompi_tpu.coll.plan import _binop as _plan_binop
    return _plan_binop(opname)


class _ShardTable:
    """The per-window cross-rank state in world.shared: every rank's
    device shard, one lock serializing all data-plane ops (which is
    what makes accumulate atomic), per-bucket zero rows for assembling
    source globals, and the DMA path's write-through mirrors — the
    aligned host staging buffer each shard aliases (None when a shard
    is borrowed from an origin buffer or is a kernel output).
    ``alias_tok`` identifies the zero-copy put that borrowed a shard,
    so only the borrowing origin's completion point decouples it."""

    __slots__ = ("arrs", "lock", "zeros", "mirrors", "alias_tok",
                 "pool")

    def __init__(self, size: int) -> None:
        self.arrs: List[Any] = [None] * size
        self.lock = threading.RLock()
        self.zeros: Dict[int, List[Any]] = {}
        self.mirrors: List[Optional[np.ndarray]] = [None] * size
        self.alias_tok: List[Any] = [None] * size
        #: displaced mirrors parked for reuse, so the decoupling copy
        #: at a completion point never pays fresh-page faults
        self.pool = _staging.MirrorPool(max_buffers=size)


# -- kernel builders --------------------------------------------------------


def _shmap(body, mesh, in_specs, out_specs):
    import jax
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _build_put(mesh, cap: int, b: int, o: int, t: int):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def body(w, s, st, cnt):
        moved = lax.ppermute(s, "r", perm=[(o, t)])
        i = lax.axis_index("r")
        s0 = jnp.minimum(st[0], cap - b)
        off = st[0] - s0
        winv = lax.dynamic_slice(w, (s0,), (b,))
        idx = lax.iota(jnp.int32, b)
        src = jnp.roll(moved, off)
        sel = (idx >= off) & (idx < off + cnt[0]) & (i == t)
        merged = jnp.where(sel, src, winv)
        return lax.dynamic_update_slice(w, merged, (s0,))

    fn = _shmap(body, mesh, (P("r"), P("r"), P(None), P(None)), P("r"))
    return jax.jit(fn, donate_argnums=(0,))


def _build_get(mesh, cap: int, b: int, t: int, o: int):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def body(w, st):
        s0 = jnp.minimum(st[0], cap - b)
        off = st[0] - s0
        winv = lax.dynamic_slice(w, (s0,), (b,))
        winv = jnp.roll(winv, -off)
        return lax.ppermute(winv, "r", perm=[(t, o)])

    fn = _shmap(body, mesh, (P("r"), P(None)), P("r"))
    return jax.jit(fn)


def _build_acc(mesh, cap: int, b: int, o: int, t: int, dtstr: str,
               opname: str, fetch: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    dt = np.dtype(dtstr)
    isz = dt.itemsize
    n = b // isz
    binop = _binop(opname)

    def body(w, s, st, cnt):
        moved = lax.ppermute(s, "r", perm=[(o, t)])
        i = lax.axis_index("r")
        s0 = jnp.minimum(st[0], cap - b)
        off = st[0] - s0
        winv = lax.dynamic_slice(w, (s0,), (b,))
        wt = lax.bitcast_convert_type(winv.reshape(n, isz), dt)
        srcb = jnp.roll(moved, off)
        stt = lax.bitcast_convert_type(srcb.reshape(n, isz), dt)
        idx = lax.iota(jnp.int32, n)
        oe = off // isz
        ce = cnt[0] // isz
        sel = (idx >= oe) & (idx < oe + ce) & (i == t)
        new = jnp.where(sel, binop(stt, wt), wt)
        outb = lax.bitcast_convert_type(new, jnp.uint8).reshape(b)
        neww = lax.dynamic_update_slice(w, outb, (s0,))
        if fetch:
            fetched = lax.ppermute(jnp.roll(winv, -off), "r",
                                   perm=[(t, o)])
            return neww, fetched
        return neww

    out_specs = (P("r"), P("r")) if fetch else P("r")
    fn = _shmap(body, mesh, (P("r"), P("r"), P(None), P(None)), out_specs)
    return jax.jit(fn, donate_argnums=(0,))


def _build_cas(mesh, cap: int, o: int, t: int, dtstr: str):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    dt = np.dtype(dtstr)
    isz = dt.itemsize
    b = 2 * isz  # source row carries [compare, new]

    def body(w, s, st):
        moved = lax.ppermute(s, "r", perm=[(o, t)])
        pair = lax.bitcast_convert_type(moved.reshape(2, isz), dt)
        i = lax.axis_index("r")
        winv = lax.dynamic_slice(w, (st[0],), (isz,))
        old = lax.bitcast_convert_type(winv.reshape(1, isz), dt)
        hit = (old[0] == pair[0]) & (i == t)
        newv = jnp.where(hit, pair[1], old[0]).reshape(1)
        newb = lax.bitcast_convert_type(newv, jnp.uint8).reshape(isz)
        neww = lax.dynamic_update_slice(w, newb, (st[0],))
        fetched = lax.ppermute(winv, "r", perm=[(t, o)])
        return neww, fetched

    fn = _shmap(body, mesh, (P("r"), P("r"), P(None)), (P("r"), P("r")))
    return jax.jit(fn, donate_argnums=(0,))


def _build_lslice(cap: int, b: int):
    """Single-device local read: dynamic slice out of one shard
    without pulling the whole capacity to the host."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def body(w, st):
        s0 = jnp.minimum(st[0], cap - b)
        off = st[0] - s0
        return jnp.roll(lax.dynamic_slice(w, (s0,), (b,)), -off)

    return jax.jit(body)


class DeviceWindow(Window):
    """MPI_Win whose memory is a device shard on the comm's mesh."""

    def __init__(self, comm, memory=None, disp_unit: int = 1,
                 name: str = "", info=None) -> None:
        import jax

        mesh = comm.mesh()
        if mesh is None:
            raise ValueError(
                "osc/device window needs a comm whose ranks own "
                "distinct devices (comm.mesh() is None)")
        self._mesh = mesh
        self._devs = list(mesh.devices.reshape(-1))
        self._dev = self._devs[comm.rank]
        self._dev_key = tuple(d.id for d in self._devs)

        if memory is None:
            memory = np.zeros(0, dtype=np.uint8)
        host = np.asarray(memory)  # device arrays copy to host once
        self._shape = host.shape
        self._view_dtype = host.dtype
        flat = np.ascontiguousarray(host).reshape(-1).view(np.uint8)
        self._win_bytes = flat.nbytes
        self._cap = max(_ALIGN, -(-flat.nbytes // _ALIGN) * _ALIGN)
        pad = _aligned_empty(self._cap)
        pad[:] = 0
        pad[: flat.nbytes] = flat
        #: target -> alias token for shards this window's zero-copy
        #: puts left aliasing an origin buffer; decoupled at the
        #: local-completion points (_materialize)
        self._borrowed: Dict[int, Any] = {}

        # cross-rank shard table: windows are created collectively in
        # the same order on every rank, so a per-comm sequence number
        # names this window uniquely; the parent constructor's closing
        # Barrier publishes every rank's deposit
        seq = comm.__dict__.get("_osc_win_seq", 0)
        comm.__dict__["_osc_win_seq"] = seq + 1
        self._world = comm.state.rte.world
        self._table_key = ("osc_devwin", comm.cid, tuple(comm.group), seq)
        with self._world.shared_lock:
            tab = self._world.shared.get(self._table_key)
            if tab is None:
                tab = _ShardTable(comm.size)
                self._world.shared[self._table_key] = tab
        tab.arrs[comm.rank] = jax.device_put(pad, self._dev)
        if _runtime_zero_copy():
            tab.mirrors[comm.rank] = pad  # device_put aliased it
        self._tab = tab

        super().__init__(comm, np.zeros(0, dtype=np.uint8), disp_unit,
                         name, info=info)

    # the parent constructor assigns ``self.memory``; the device
    # window serves it as a fresh host copy of the live shard instead
    @property
    def memory(self) -> np.ndarray:
        with self._tab.lock:
            host = np.asarray(self._tab.arrs[self.rank])[: self._win_bytes]
        if self._view_dtype == np.uint8 and len(self._shape) == 1:
            return host
        return host.view(self._view_dtype).reshape(self._shape)

    @memory.setter
    def memory(self, value) -> None:
        pass  # parent __init__ writes its placeholder; shard is truth

    # -- shard plumbing ---------------------------------------------------

    def _cache(self):
        from ompi_tpu.coll import device as _dc
        return _dc.compile_cache

    def _assemble_win(self):
        from ompi_tpu.coll import device as _dc
        return _dc._assemble(self._mesh, self._tab.arrs)

    def _assemble_src(self, row: np.ndarray):
        import jax
        from ompi_tpu.coll import device as _dc
        b = row.nbytes
        zeros = self._tab.zeros.get(b)
        if zeros is None:
            import jax.numpy as jnp
            zeros = [jax.device_put(jnp.zeros(b, jnp.uint8), d)
                     for d in self._devs]
            self._tab.zeros[b] = zeros
        rows = list(zeros)
        rows[self.rank] = jax.device_put(row, self._dev)
        return _dc._assemble(self._mesh, rows)

    def _replace_shards(self, out) -> None:
        from ompi_tpu.coll import device as _dc
        parts = _dc._scatter_out(out, self._mesh, self.size)
        for i in range(self.size):
            self._tab.arrs[i] = parts[i]
            self._tab.mirrors[i] = None  # kernel outputs own themselves
            self._tab.alias_tok[i] = None

    def _seg_bytes(self) -> int:
        v = _seg_var.value
        if v > 0:
            return _pow2floor(max(_ALIGN, v))
        try:
            from ompi_tpu.coll import plan
            s = plan.segment_elems(self.comm, 1)
        except Exception:  # noqa: BLE001 — calibrate profile optional
            s = 1 << 20
        return _pow2floor(max(s, 1 << 16))

    def _span(self, arr) -> Tuple[np.ndarray, int]:
        a = np.ascontiguousarray(arr)
        if _DT_CODE.get(a.dtype) is None:
            raise TypeError(f"dtype {a.dtype} not supported on windows")
        return a, a.nbytes

    def _range_check(self, start: int, nbytes: int) -> None:
        if start < 0 or start + nbytes > self._win_bytes:
            raise ValueError(
                f"RMA range [{start}, {start + nbytes}) outside the "
                f"{self._win_bytes}-byte window (MPI_ERR_RMA_RANGE)")

    # -- data plane: put / get -------------------------------------------

    def put(self, arr, target: int, disp: int = 0) -> None:
        tr = self.state.tracer
        if tr is None:
            nbytes = self._put_impl(arr, target, disp)
        else:
            t0 = tr.start_sampled(_CAT_RMA)
            nbytes = self._put_impl(arr, target, disp)
            if t0:
                tr.end(t0, _NAME_RMA_PUT, _CAT_RMA, self.comm.cid,
                       target, nbytes)
        band = _obs.current_band()
        _host.pv_puts.add(1, band)
        _host.pv_bytes_put.add(nbytes, band)

    def get(self, arr, target: int, disp: int = 0) -> None:
        tr = self.state.tracer
        if tr is None:
            nbytes = self._get_impl(arr, target, disp)
        else:
            t0 = tr.start_sampled(_CAT_RMA)
            nbytes = self._get_impl(arr, target, disp)
            if t0:
                tr.end(t0, _NAME_RMA_GET, _CAT_RMA, self.comm.cid,
                       target, nbytes)
        band = _obs.current_band()
        _host.pv_gets.add(1, band)
        _host.pv_bytes_got.add(nbytes, band)

    def _put_impl(self, arr, target: int, disp: int) -> int:
        self._check_target(target)
        a, nbytes = self._span(arr)
        start = disp * self.disp_unit
        self._range_check(start, nbytes)
        if nbytes == 0:
            return 0
        src = a.reshape(-1).view(np.uint8)
        if _dma_var.value:
            self._put_dma(src, target, start)
            return nbytes
        seg = self._seg_bytes()
        off = 0
        with self._tab.lock:
            while off < nbytes:
                chunk = min(seg, nbytes - off)
                self._put_chunk(src[off: off + chunk], target, start + off)
                off += chunk
        return nbytes

    def _ensure_mirror(self, target: int) -> np.ndarray:
        """Put the target shard into write-through-mirror state (the
        shard aliases an owned aligned host buffer) and return the
        mirror.  Caller holds the table lock; zero-copy runtime only."""
        import jax

        tab = self._tab
        mir = tab.mirrors[target]
        if mir is None:
            mir = tab.pool.take(self._cap)
            np.copyto(mir, np.asarray(tab.arrs[target]))
            tab.arrs[target] = jax.device_put(mir, self._devs[target])
            tab.mirrors[target] = mir
            tab.alias_tok[target] = None
        return mir

    def _put_dma(self, src: np.ndarray, target: int, start: int) -> None:
        """Direct-DMA put, never a whole-mesh program.

        Zero-copy runtime: a wholesale aligned overwrite aliases the
        origin buffer outright (O(1) device_put) and defers the
        decoupling copy to the local-completion point — MPI forbids
        the origin mutating the buffer before then, the same contract
        zero-copy RDMA rides.  Anything else is one memcpy into the
        target's write-through mirror, which the device shard aliases.

        Copying runtime: compose into an aligned staging buffer and
        upload — the device_put IS the host→HBM DMA then."""
        import jax

        n = src.nbytes
        tab = self._tab
        with tab.lock:
            if not _runtime_zero_copy():
                stage = _aligned_empty(self._cap)
                if n < self._cap:
                    stage[:] = np.asarray(tab.arrs[target])
                stage[start: start + n] = src
                tab.arrs[target] = jax.device_put(
                    stage, self._devs[target])
                return
            if (n == self._cap and start == 0
                    and src.ctypes.data % _STAGE_ALIGN == 0):
                tok = object()
                tab.arrs[target] = jax.device_put(
                    src, self._devs[target])
                tab.pool.park(tab.mirrors[target])
                tab.mirrors[target] = None
                tab.alias_tok[target] = tok
                self._borrowed[target] = tok
                return
            mir = self._ensure_mirror(target)
            np.copyto(mir[start: start + n], src)
            self._borrowed.pop(target, None)

    def _put_chunk(self, src: np.ndarray, target: int, start: int) -> None:
        n = src.nbytes
        b = _bucket(n, self._cap)
        pad = np.zeros(b, dtype=np.uint8)
        pad[:n] = src
        key = ("osc_pput", self._dev_key, self._cap, b, self.rank, target)
        fn = self._cache().get(
            key, lambda: _build_put(self._mesh, self._cap, b,
                                    self.rank, target))
        w = self._assemble_win()
        s = self._assemble_src(pad)
        out = fn(w, s, np.array([start], np.int32), np.array([n], np.int32))
        self._replace_shards(out)

    def _get_impl(self, arr, target: int, disp: int) -> int:
        self._check_target(target)
        if not (isinstance(arr, np.ndarray) and arr.flags.c_contiguous
                and arr.flags.writeable):
            raise ValueError("get target must be a writable contiguous "
                             "ndarray")
        nbytes = arr.nbytes
        start = disp * self.disp_unit
        self._range_check(start, nbytes)
        if nbytes == 0:
            return 0
        dst = arr.view(np.uint8).reshape(-1)
        if _dma_var.value:
            # direct DMA: device→host read of the target shard (a
            # zero-copy view on the CPU runtime) + one memcpy of the
            # requested span
            with self._tab.lock:
                view = np.asarray(self._tab.arrs[target])
                np.copyto(dst, view[start: start + nbytes])
            return nbytes
        seg = self._seg_bytes()
        off = 0
        with self._tab.lock:
            while off < nbytes:
                chunk = min(seg, nbytes - off)
                dst[off: off + chunk] = \
                    self._get_chunk(chunk, target, start + off)
                off += chunk
        return nbytes

    def _get_chunk(self, n: int, target: int, start: int) -> np.ndarray:
        b = _bucket(n, self._cap)
        key = ("osc_pget", self._dev_key, self._cap, b, target, self.rank)
        fn = self._cache().get(
            key, lambda: _build_get(self._mesh, self._cap, b,
                                    target, self.rank))
        w = self._assemble_win()
        out = fn(w, np.array([start], np.int32))
        from ompi_tpu.coll import device as _dc
        parts = _dc._scatter_out(out, self._mesh, self.size)
        return np.asarray(parts[self.rank])[:n]

    def rput(self, arr, target: int, disp: int = 0):
        from ompi_tpu.pml.request import CompletedRequest
        self.put(arr, target, disp)
        return CompletedRequest(self._progress)

    def rget(self, arr, target: int, disp: int = 0):
        from ompi_tpu.pml.request import CompletedRequest
        self.get(arr, target, disp)
        return CompletedRequest(self._progress)

    # -- data plane: accumulate family -----------------------------------

    def accumulate(self, arr, target: int, disp: int = 0,
                   op: opmod.Op = opmod.SUM) -> None:
        self._acc_entry(arr, None, target, disp, op)

    def raccumulate(self, arr, target: int, disp: int = 0,
                    op: opmod.Op = opmod.SUM):
        from ompi_tpu.pml.request import CompletedRequest
        self.accumulate(arr, target, disp, op)
        return CompletedRequest(self._progress)

    def get_accumulate(self, arr, result: np.ndarray, target: int,
                       disp: int = 0, op: opmod.Op = opmod.SUM) -> None:
        self._acc_entry(arr, result, target, disp, op)

    def rget_accumulate(self, arr, result: np.ndarray, target: int,
                        disp: int = 0, op: opmod.Op = opmod.SUM):
        from ompi_tpu.pml.request import CompletedRequest
        self.get_accumulate(arr, result, target, disp, op)
        return CompletedRequest(self._progress)

    def fetch_and_op(self, value, result: np.ndarray, target: int,
                     disp: int = 0, op: opmod.Op = opmod.SUM) -> None:
        self.get_accumulate(np.atleast_1d(np.asarray(
            value, dtype=result.dtype)), result, target, disp, op)

    def _acc_entry(self, arr, result, target, disp, op) -> None:
        tr = self.state.tracer
        if tr is None:
            nbytes = self._acc_impl(arr, result, target, disp, op)
        else:
            t0 = tr.start_sampled(_CAT_RMA)
            nbytes = self._acc_impl(arr, result, target, disp, op)
            if t0:
                tr.end(t0, _NAME_RMA_ACC, _CAT_RMA, self.comm.cid,
                       target, nbytes)
        _host.pv_accs.add(1, _obs.current_band())

    def _acc_impl(self, arr, result, target: int, disp: int,
                  op: opmod.Op) -> int:
        self._check_target(target)
        a, nbytes = self._span(arr)
        if result is not None and result.dtype != a.dtype:
            raise TypeError("get_accumulate origin/result dtype mismatch")
        start = disp * self.disp_unit
        self._range_check(start, nbytes)
        if nbytes == 0:
            return 0
        dtstr = a.dtype.str
        isz = a.dtype.itemsize
        jitted = (not _dma_var.value
                  and dtstr in _JIT_ACC_DTYPES and op.name != "MPI_MAXLOC"
                  and op.name != "MPI_MINLOC" and start % isz == 0)
        with self._tab.lock:
            if not jitted:
                old = self._acc_host(a, target, start, op)
            else:
                old = self._acc_dev(a, target, start, op,
                                    fetch=result is not None)
        if result is not None:
            res = result.view(np.uint8).reshape(-1)
            res[:] = old[: res.nbytes]
        return nbytes

    def _acc_dev(self, a: np.ndarray, target: int, start: int,
                 op: opmod.Op, fetch: bool) -> Optional[np.ndarray]:
        src = a.reshape(-1).view(np.uint8)
        nbytes = src.nbytes
        seg = self._seg_bytes()
        out_bytes = np.empty(nbytes, np.uint8) if fetch else None
        off = 0
        while off < nbytes:
            chunk = min(seg, nbytes - off)
            got = self._acc_chunk(src[off: off + chunk], target,
                                  start + off, a.dtype, op, fetch)
            if fetch:
                out_bytes[off: off + chunk] = got
            off += chunk
        return out_bytes

    def _acc_chunk(self, src: np.ndarray, target: int, start: int,
                   dt: np.dtype, op: opmod.Op,
                   fetch: bool) -> Optional[np.ndarray]:
        n = src.nbytes
        b = _bucket(n, self._cap)
        # bucket and clamp math stay dtype-aligned: cap and b are
        # multiples of _ALIGN >= itemsize and start % itemsize == 0
        pad = np.zeros(b, dtype=np.uint8)
        pad[:n] = src
        key = ("osc_pacc", self._dev_key, self._cap, b, dt.str,
               op.name, bool(fetch), self.rank, target)
        fn = self._cache().get(
            key, lambda: _build_acc(self._mesh, self._cap, b, self.rank,
                                    target, dt.str, op.name, fetch))
        w = self._assemble_win()
        s = self._assemble_src(pad)
        out = fn(w, s, np.array([start], np.int32), np.array([n], np.int32))
        from ompi_tpu.coll import device as _dc
        if fetch:
            neww, fetched = out
            self._replace_shards(neww)
            parts = _dc._scatter_out(fetched, self._mesh, self.size)
            return np.asarray(parts[self.rank])[:n]
        self._replace_shards(out)
        return None

    def _acc_host(self, a: np.ndarray, target: int, start: int,
                  op: opmod.Op) -> np.ndarray:
        """Atomic host-side read-modify-write: the DMA mode's typed
        path for every dtype, and the kernel mode's fallback for
        dtypes the 32-bit jax world cannot bitcast (int64/float64/
        complex/bool/pair).  Holds the table lock (caller), so it
        interleaves atomically with every device kernel."""
        flat = a.reshape(-1)
        if _runtime_zero_copy():
            mir = self._ensure_mirror(target)
            region = mir[start: start + a.nbytes].view(a.dtype)
            old = region.copy()
            region[:] = op.reduce(flat, region.copy())
            return old.view(np.uint8).reshape(-1)
        import jax

        cur = _aligned_empty(self._cap)
        cur[:] = np.asarray(self._tab.arrs[target])
        region = cur[start: start + a.nbytes].view(a.dtype)
        old = region.copy()
        region[:] = op.reduce(flat, region.copy())
        self._tab.arrs[target] = jax.device_put(cur, self._devs[target])
        return old.view(np.uint8).reshape(-1)

    def compare_and_swap(self, compare, new, result: np.ndarray,
                         target: int, disp: int = 0) -> None:
        self._check_target(target)
        dt = np.dtype(result.dtype)
        if _DT_CODE.get(dt) is None:
            raise TypeError(f"dtype {dt} not supported on windows")
        start = disp * self.disp_unit
        self._range_check(start, dt.itemsize)
        cmp_v = np.atleast_1d(np.asarray(compare, dtype=dt))
        new_v = np.atleast_1d(np.asarray(new, dtype=dt))
        with self._tab.lock:
            if (not _dma_var.value and dt.str in _JIT_ACC_DTYPES
                    and start % dt.itemsize == 0):
                old = self._cas_dev(cmp_v, new_v, target, start, dt)
            else:
                old = self._cas_host(cmp_v, new_v, target, start, dt)
        res = result.view(np.uint8).reshape(-1)
        res[:] = old[: res.nbytes]
        _host.pv_cas.add(1, _obs.current_band())

    def _cas_dev(self, cmp_v, new_v, target: int, start: int,
                 dt: np.dtype) -> np.ndarray:
        pair = np.concatenate([cmp_v, new_v]).view(np.uint8)
        key = ("osc_pcas", self._dev_key, self._cap, dt.str,
               self.rank, target)
        fn = self._cache().get(
            key, lambda: _build_cas(self._mesh, self._cap, self.rank,
                                    target, dt.str))
        w = self._assemble_win()
        s = self._assemble_src(np.ascontiguousarray(pair))
        neww, fetched = fn(w, s, np.array([start], np.int32))
        self._replace_shards(neww)
        from ompi_tpu.coll import device as _dc
        parts = _dc._scatter_out(fetched, self._mesh, self.size)
        return np.asarray(parts[self.rank])[: dt.itemsize]

    def _cas_host(self, cmp_v, new_v, target: int, start: int,
                  dt: np.dtype) -> np.ndarray:
        if _runtime_zero_copy():
            mir = self._ensure_mirror(target)
            region = mir[start: start + dt.itemsize].view(dt)
            old = region.copy()
            if old[0] == cmp_v[0]:
                region[0] = new_v[0]
            return old.view(np.uint8).reshape(-1)
        import jax

        cur = _aligned_empty(self._cap)
        cur[:] = np.asarray(self._tab.arrs[target])
        region = cur[start: start + dt.itemsize].view(dt)
        old = region.copy()
        if old[0] == cmp_v[0]:
            region[0] = new_v[0]
        self._tab.arrs[target] = jax.device_put(cur, self._devs[target])
        return old.view(np.uint8).reshape(-1)

    # -- local access (oshmem heap reads ride this) ----------------------

    def read_local(self, start: int, nbytes: int) -> np.ndarray:
        """Host copy of [start, start+nbytes) of the local shard — a
        direct device→host span read in DMA mode (the oshmem
        wait_until poll path), a jitted dynamic slice (O(bucket), not
        O(capacity)) in kernel mode."""
        self._range_check(start, nbytes)
        if nbytes == 0:
            return np.empty(0, np.uint8)
        if _dma_var.value:
            with self._tab.lock:
                view = np.asarray(self._tab.arrs[self.rank])
                return view[start: start + nbytes].copy()
        b = _bucket(nbytes, self._cap)
        key = ("osc_lslice", self._dev_key, self._cap, b)
        fn = self._cache().get(key, lambda: _build_lslice(self._cap, b))
        with self._tab.lock:
            out = fn(self._tab.arrs[self.rank], np.array([start], np.int32))
            return np.asarray(out)[:nbytes].copy()

    # -- synchronization --------------------------------------------------

    def _materialize(self) -> None:
        """Decouple shards still aliasing an origin buffer from a
        zero-copy put: copy them into an owned write-through mirror
        and swap that in.  This is the DMA path's local-completion
        work, so every sync entry point (fence / flush / flush_local /
        unlock / complete) runs it first.  The alias token skips
        shards some later op already rewrote."""
        if not self._borrowed:
            return
        import jax

        tab = self._tab
        with tab.lock:
            for t, tok in self._borrowed.items():
                if tab.alias_tok[t] is not tok:
                    continue
                mir = tab.pool.take(self._cap)
                np.copyto(mir, np.asarray(tab.arrs[t]))
                tab.arrs[t] = jax.device_put(mir, self._devs[t])
                tab.mirrors[t] = mir
                tab.alias_tok[t] = None
            self._borrowed.clear()

    def fence(self) -> None:
        """Active-target epoch boundary: device ops complete inside
        the origin's call, so the fence is a liveness check plus the
        collective Barrier (which rides the coll fence/rendezvous
        primitives and raises instead of hanging on a dead comm)."""
        self._check_alive()
        self._materialize()
        self._drain_out()
        self._ops_sent[:] = 0
        self.comm.Barrier()

    def flush(self, target: int) -> None:
        # device ops complete inside the origin's call and never ride
        # the AM path, so there is nothing outstanding at the target:
        # flush is the liveness check plus decoupling any zero-copy
        # put (the host component's FLUSH round-trip waits for applied
        # AMs, of which a device window has none)
        self._check_alive()
        self._materialize()
        self._drain_out()

    def flush_all(self) -> None:
        self._check_alive()
        self._materialize()
        self._drain_out()

    def flush_local(self, target: int) -> None:
        self._materialize()
        self._drain_out()

    def unlock(self, target: int) -> None:
        self._materialize()
        super().unlock(target)

    def unlock_all(self) -> None:
        self._materialize()
        super().unlock_all()

    def complete(self) -> None:
        self._materialize()
        super().complete()

    # -- lifecycle --------------------------------------------------------

    def _drop_table(self) -> None:
        with self._world.shared_lock:
            self._world.shared.pop(self._table_key, None)

    def free(self) -> None:
        if self._freed:
            return
        super().free()
        self._drop_table()

    def abandon(self) -> None:
        if self._freed:
            return
        super().abandon()
        self._drop_table()

    def __repr__(self) -> str:
        return (f"DeviceWindow({self.comm.name}, "
                f"rank={self.rank}/{self.size}, {self._win_bytes}B@"
                f"{getattr(self._dev, 'id', '?')}, "
                f"disp_unit={self.disp_unit})")


def create(comm, memory, disp_unit: Optional[int] = None,
           name: str = "", info=None) -> DeviceWindow:
    if disp_unit is None:
        itemsize = getattr(getattr(memory, "dtype", None), "itemsize", 1)
        disp_unit = itemsize if getattr(memory, "size", 0) else 1
    return DeviceWindow(comm, memory, disp_unit, name, info=info)


def allocate(comm, nbytes: int, disp_unit: int = 1,
             name: str = "") -> DeviceWindow:
    return DeviceWindow(comm, np.zeros(nbytes, dtype=np.uint8),
                        disp_unit, name)
