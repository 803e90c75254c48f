"""Small-message collective fusion/coalescing: the device fast path.

Every device collective pays a size-independent per-op cost —
rendezvous plus one jit dispatch — before any byte moves (~150-600 us
of dispatch alone in the r05 chip record; PERF.md has the smoke
timings of a directly attached v5e), so the 4-64 KiB band can lose to
the host seg path even though the op itself is nearly free there.
The fix is the reference's message-coalescing idea applied at the XLA
layer: when a rank has several small collectives
pending (surfaced through the nonblocking coll surface, coll/nbc),
pack their payloads into ONE flattened buffer per (reducer, dtype)
group — offset table from datatype/device.py — and issue a SINGLE
fused XLA call (one psum over the concatenation, bcasts joining the
SUM group as masked summands), then slice results back out.  One
dispatch amortized over N collectives.

Surface: ``comm.iallreduce_arr`` / ``comm.ibcast_arr`` return a
``FusedRequest``; pending ops coalesce until an explicit
``comm.flush_arr()``, a ``wait()``/``test()`` on any request of the
batch, the ``coll_device_fusion_max_ops`` bound, or MPI_Finalize
(the hook ``device.track_state`` registers) flushes them.
Ineligible ops (big payloads, host-only comms, exotic ops) execute
immediately through the blocking vtable and return an
already-complete request — callers never branch.

Batch symmetry: the flush is one rendezvous per batch, so every member
rank must enqueue the SAME sequence of collectives between flushes
(the usual SPMD discipline MPI already requires for collective
ordering).  The fused signature is validated at the meeting point —
a divergent batch raises a clear error on every rank instead of
deadlocking.
"""

from __future__ import annotations

import numpy as np

from ompi_tpu import obs as _obs
from ompi_tpu import trace as _trace
from ompi_tpu.obs import integrity as _ig
from ompi_tpu.mca.params import registry
from ompi_tpu.op.op import Op
from ompi_tpu.pml.request import Request

_fusion_var = registry.register(
    "coll", "device", "fusion", True, bool,
    help="Coalesce pending small nonblocking device collectives "
         "(iallreduce_arr/ibcast_arr) into one fused XLA call per "
         "batch, amortizing the per-op dispatch constant")
_threshold_var = registry.register(
    "coll", "device", "fusion_threshold", 65536, int,
    help="Per-op payload bound (bytes) for fusion eligibility; larger "
         "payloads are bandwidth-dominated and run unfused "
         "immediately")
_max_ops_var = registry.register(
    "coll", "device", "fusion_max_ops", 32, int,
    help="Auto-flush a pending fusion batch at this many collectives "
         "(bounds result latency and fused-executable arity)")

# session-banded (ompi_tpu/obs): on a resident pool each flush
# belongs to exactly one session (the engine is per-comm, the comm's
# state carries cid_band), so attribution is a band index away.
# Global reads through the registry are untouched.
_pv_batches = _obs.scoped_pvar(
    "coll", "device", "fused_batches",
    help="Fused device-collective batches dispatched")
_pv_colls = _obs.scoped_pvar(
    "coll", "device", "fused_collectives",
    help="Individual collectives that rode in a fused batch")
_pv_bytes = _obs.scoped_pvar(
    "coll", "device", "fused_bytes",
    help="Payload bytes carried by fused batches")

# -- cross-session batching (the DVM serve plane, tools/dvm) ---------------
# Concurrently-resident sessions are independent worlds multiplexed
# over the SAME device mesh, so their fused batches — each already one
# dispatch — can share a single XLA call when they land within a short
# window of each other.  The window only opens while the pool reports
# >1 resident session (set_xsession_hint), so solo jobs never pay it.
_xwin_var = registry.register(
    "dvm", "", "batch_window_us", 0, int,
    help="Cross-session fusion window (microseconds): a fused batch "
         "dispatched from a DVM-resident session waits this long for "
         "compatible batches from OTHER resident sessions and rides "
         "one combined XLA dispatch with them.  0 disables.  Only "
         "consulted while more than one session is resident "
         "(tpu-dvm --batch-window-us sets it pool-wide)")
_pv_xbatches = registry.register_pvar(
    "dvm", "", "xsession_batches",
    help="Combined dispatches that carried fused batches from 2+ "
         "concurrently-resident DVM sessions")
_pv_xcolls = registry.register_pvar(
    "dvm", "", "xsession_collectives",
    help="Individual collectives that rode a cross-session combined "
         "dispatch")

_xsession_hint = 0  # resident-session count, maintained by tools/dvm


def set_xsession_hint(n: int) -> None:
    """The DVM pool reports its resident-session count here on every
    attach/detach; the cross-session window opens only above 1."""
    global _xsession_hint
    _xsession_hint = n


class FusedRequest(Request):
    """Request handle for a (possibly) coalesced device collective.

    ``result`` is the output array once complete.  Completion requires
    running the fused batch — a bare progress sweep cannot do that, so
    ``wait()`` AND ``test()`` both flush the owning engine's pending
    batch (the batch rendezvous blocks on peers; under the SPMD batch
    discipline they are flushing too)."""

    def __init__(self, progress, engine) -> None:
        super().__init__(progress)
        self._engine = engine
        self._error = None
        self.result = None

    def _deliver(self, value) -> None:
        self.result = value
        self._complete()

    def _fail(self, exc: BaseException) -> None:
        self._error = exc
        self._complete()

    def test(self) -> bool:
        if not self.complete and self._engine is not None:
            self._engine.flush()
        return self.complete

    def wait(self, timeout=None):
        if not self.complete and self._engine is not None:
            self._engine.flush()
        st = super().wait(timeout)
        if self._error is not None:
            from ompi_tpu.errhandler import MPIException
            if isinstance(self._error, MPIException):
                # ULFM classes (PROC_FAILED/REVOKED) must surface
                # unchanged so the app's recovery logic can match on
                # the error class
                raise self._error
            raise RuntimeError(
                f"fused device collective failed: {self._error}"
            ) from self._error
        return st


class _Pending:
    __slots__ = ("kind", "x", "extra", "was_scalar", "nbytes", "req")

    def __init__(self, kind, x, extra, was_scalar, nbytes, req) -> None:
        self.kind = kind            # "allreduce" | "bcast"
        self.x = x                  # normalized payload (ndim >= 1)
        self.extra = extra          # opname (allreduce) or root (bcast)
        self.was_scalar = was_scalar
        self.nbytes = nbytes
        self.req = req


def _nbytes_of(x) -> int:
    """Payload bytes from shape x itemsize — the ``.nbytes`` property
    on device arrays walks the aval and costs microseconds; this runs
    on every nonblocking enqueue."""
    n = 1
    for s in getattr(x, "shape", ()):
        n *= s
    return n * x.dtype.itemsize


_RED_OPS = ("MPI_SUM", "MPI_MAX", "MPI_MIN")


def _group_plan(sig):
    """Static fusion plan, a pure function of the batch signature (so
    every rank and every cache layer derives the same plan): slots
    grouped by (reducer opname, dtype) — bcast joins the SUM group of
    its dtype as a root-masked summand — plus the gather-fold slots
    that keep per-slot all_gathers inside the same dispatch."""
    groups = {}
    folds = []
    for i, (kind, _shape, dt, extra) in enumerate(sig):
        if kind == "bcast":
            groups.setdefault(("MPI_SUM", dt), []).append(i)
        elif extra in _RED_OPS:
            groups.setdefault((extra, dt), []).append(i)
        else:
            folds.append(i)
    return (tuple((opname, dt, tuple(slots))
                  for (opname, dt), slots in groups.items()),
            tuple(folds))


def _fused_ck(mode, sig):
    """Integrity spec for one fused batch (DESIGN.md §25): one claim
    per deposit buffer — mesh mode digests each packed group buffer
    (claim index = group index), hbm mode digests each slot array.
    Returns None when any slot falls outside the checkable algebra
    (gather folds, non-native reducers, unsupported dtypes): a partly
    checked batch could not attribute a mismatch to one rank, so the
    whole batch runs unchecked instead."""
    ents = []
    if mode == "hbm":
        for i, (kind, _shape, dt, extra) in enumerate(sig):
            if kind == "bcast":
                s = _ig.spec_static("bcast", "", np.empty(0, dt), extra)
                if s is None:
                    return None
                ents.append(("b", s[1], i, int(extra), s[2]))
            elif kind == "allreduce":
                s = _ig.spec_static("allreduce", extra, np.empty(0, dt))
                if s is None:
                    return None
                ents.append(("g", s[1], i, (i,), s[2]))
            else:
                return None
    else:
        groups, folds = _group_plan(sig)
        if folds:
            return None
        for gi, (opname, dt, slots) in enumerate(groups):
            # bcast slots ride SUM groups root-masked to the identity,
            # so the group conservation sum covers them exactly.
            s = _ig.spec_static("allreduce", opname, np.empty(0, dt))
            if s is None:
                return None
            ents.append(("g", s[1], gi, slots, s[2]))
    return ("fused", tuple(ents))


def _build_pack(dev, sig, slots, roots):
    """Per-rank group pack: flatten + concatenate this rank's pending
    payloads of one (reducer, dtype) group into ONE buffer (offset
    table from datatype/device), masking non-root bcast slots to the
    reducer identity, with the output committed to the rank's own mesh
    device.  Packing on the owning rank's thread is what keeps the
    batch meeting point cheap: the last arriver assembles G committed
    group buffers instead of moving N stray slot arrays."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ompi_tpu.datatype.device import pack_segments

    def body(*xs):
        flats = []
        for j in range(len(slots)):
            f = xs[j].reshape(-1)
            if roots[j] is False:  # non-root bcast: contribute zeros
                f = jnp.zeros_like(f)
            flats.append(f)
        return pack_segments(flats)

    return jax.jit(body, out_shardings=SingleDeviceSharding(dev))


def _mesh_slot_outs(sig, xs):
    """Traced body of one session's mesh-mode batch: ``xs`` is its
    packed group buffers followed by its raw gather-fold slots;
    returns the per-slot outputs.  Shared by the single-batch and the
    cross-session combined executables so both trace the SAME ops per
    batch — the byte-identity contract of the serve plane."""
    from jax import lax

    from ompi_tpu.coll import device
    from ompi_tpu.datatype.device import segment_offsets

    red_map = {"MPI_SUM": lax.psum, "MPI_MAX": lax.pmax,
               "MPI_MIN": lax.pmin}
    groups, folds = _group_plan(sig)
    outs = [None] * len(sig)
    for gi, (opname, _dt, slots) in enumerate(groups):
        shapes = [sig[i][1] for i in slots]
        offs, lens, _total = segment_offsets(shapes)
        red = red_map[opname](xs[gi], "r")
        for j, i in enumerate(slots):
            outs[i] = red[offs[j]:offs[j] + lens[j]].reshape(shapes[j])
    for fi, i in enumerate(folds):
        fold = device._fold_fn(sig[i][3])
        outs[i] = fold(lax.all_gather(xs[len(groups) + fi], "r",
                                      tiled=False))
    return outs


def _mesh_nin(sig) -> int:
    groups, folds = _group_plan(sig)
    return len(groups) + len(folds)


def _build_fused_mesh(mesh, sig):
    """One jitted shard_map running a whole fused batch on the comm
    mesh.  Inputs are the per-rank packed group buffers (one per
    (reducer, dtype) group, already masked and concatenated by
    _build_pack) followed by the raw gather-fold slots; each group is
    reduced with ONE psum/pmax/pmin over the concatenation and sliced
    back out at the static offsets."""
    import jax
    from jax.sharding import PartitionSpec as P

    def body(*xs):
        return tuple(_mesh_slot_outs(sig, xs))

    # a stable program name for the device trace
    body.__name__ = body.__qualname__ = "ompi_fused_mesh"
    nin = _mesh_nin(sig)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("r"),) * nin,
        out_specs=(P(None),) * len(sig), check_vma=False))


def _build_fused_mesh_multi(mesh, sigs):
    """Cross-session combined dispatch (mesh mode): one shard_map
    carrying several sessions' fused batches back to back.  Each
    session's segment is computed exactly as its solo executable
    would — the combination only amortizes the dispatch."""
    import jax
    from jax.sharding import PartitionSpec as P

    nins = [_mesh_nin(s) for s in sigs]

    def body(*xs):
        outs = []
        off = 0
        for s, nin in zip(sigs, nins):
            outs.extend(_mesh_slot_outs(s, xs[off:off + nin]))
            off += nin
        return tuple(outs)

    nout = sum(len(s) for s in sigs)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("r"),) * sum(nins),
        out_specs=(P(None),) * nout, check_vma=False))


def _hbm_slot_outs(size, sig, xs):
    """Traced body of one session's hbm-mode batch over its slot-major
    ``len(sig)*size`` shards (shared by solo and cross-session
    combined executables — see _mesh_slot_outs)."""
    import jax.numpy as jnp

    from ompi_tpu.coll import device

    outs = []
    for i, (kind, _shape, _dt, extra) in enumerate(sig):
        shards = xs[i * size:(i + 1) * size]
        if kind == "bcast":
            outs.append(shards[extra])
        elif extra == "MPI_SUM":
            outs.append(jnp.sum(jnp.stack(shards), axis=0))
        elif extra == "MPI_MAX":
            outs.append(jnp.max(jnp.stack(shards), axis=0))
        elif extra == "MPI_MIN":
            outs.append(jnp.min(jnp.stack(shards), axis=0))
        else:
            outs.append(device._fold_fn(extra)(jnp.stack(shards)))
    return outs


def _build_fused_hbm(size, sig):
    """Fused batch for single-chip comms (coll/hbm): one jit taking
    slot-major ``n*size`` shards; each slot stacks + reduces (or picks
    the root shard for bcast).  The win is the single dispatch."""
    import jax

    def body(*xs):
        return tuple(_hbm_slot_outs(size, sig, xs))

    # a stable program name for the device trace
    body.__name__ = body.__qualname__ = "ompi_fused_hbm"
    return jax.jit(body)


def _build_fused_hbm_multi(size, sigs):
    """Cross-session combined dispatch (hbm mode): several sessions'
    slot-major shard lists concatenated into one jit call."""
    import jax

    def body(*xs):
        outs = []
        off = 0
        for s in sigs:
            n = len(s) * size
            outs.extend(_hbm_slot_outs(size, s, xs[off:off + n]))
            off += n
        return tuple(outs)

    return jax.jit(body)


class _XEntry:
    __slots__ = ("sig", "args", "outs", "err", "event")

    def __init__(self, sig, args) -> None:
        import threading
        self.sig = sig
        self.args = args
        self.outs = None
        self.err = None
        self.event = threading.Event()


class _XBatcher:
    """Process-global meeting point for cross-session batch
    coalescing.  Callers are the last-arriver threads of independent
    sessions' batch rendezvous (device.meet fn) — one thread per
    session batch.  The first arriver under a compatibility key
    becomes the leader: it holds the window open, then runs ONE
    combined executable over every batch that joined and hands each
    follower its slice.  Entries are sorted by signature before
    combining so the compiled-executable cache key is arrival-order
    independent."""

    def __init__(self) -> None:
        import threading
        self.lock = threading.Lock()
        self.groups = {}  # key -> list of _XEntry (open window)

    def run(self, key, sig, args, single_fn, multi_key, multi_build):
        import time as _time

        win_s = max(0, _xwin_var.value) / 1e6
        e = _XEntry(sig, args)
        with self.lock:
            grp = self.groups.get(key)
            leader = grp is None
            if leader:
                self.groups[key] = [e]
            else:
                grp.append(e)
        if leader:
            _time.sleep(win_s)
            with self.lock:
                entries = self.groups.pop(key)
            self._dispatch(entries, single_fn, multi_key, multi_build)
        if not e.event.wait(timeout=120.0):
            raise RuntimeError(
                "cross-session batch leader did not dispatch within "
                "120s (dvm_batch_window_us misconfigured or leader "
                "session died mid-window)")
        if e.err is not None:
            raise RuntimeError(
                f"cross-session combined dispatch failed: {e.err}"
            ) from e.err
        return e.outs

    def _dispatch(self, entries, single_fn, multi_key,
                  multi_build) -> None:
        from ompi_tpu.coll import device
        try:
            if len(entries) == 1:
                entries[0].outs = single_fn(entries[0].args)
            else:
                order = sorted(range(len(entries)),
                               key=lambda i: repr(entries[i].sig))
                sigs = tuple(entries[i].sig for i in order)
                jfn = device.compile_cache.get(
                    multi_key(sigs), lambda: multi_build(sigs))
                flat = [a for i in order for a in entries[i].args]
                outs = jfn(*flat)
                off = 0
                for i in order:
                    n = len(entries[i].sig)
                    entries[i].outs = tuple(outs[off:off + n])
                    off += n
                _pv_xbatches.add(1)
                _pv_xcolls.add(off)
        except BaseException as exc:  # noqa: BLE001
            for e in entries:
                e.err = exc
        finally:
            for e in entries:
                e.event.set()


_xbatcher = _XBatcher()


def _xdispatch(key, sig, args, single_fn, multi_key, multi_build):
    """Run one session's prepared fused batch: straight through when
    the cross-session window is closed (knob 0, or the pool reports
    <2 resident sessions), else through the batcher."""
    if _xwin_var.value <= 0 or _xsession_hint < 2:
        return single_fn(args)
    return _xbatcher.run(key, sig, args, single_fn, multi_key,
                         multi_build)


class _FusionEngine:
    """Per-comm, per-rank staging area for pending fusible collectives.
    Single-threaded (each rank owns its comm object); flush runs the
    whole batch through ONE device.meet rendezvous."""

    def __init__(self, comm) -> None:
        from ompi_tpu.coll import device
        self.comm = comm
        prov = getattr(comm.coll, "providers", None) or {}
        m = prov.get("allreduce_arr")
        self.mode = m if m in ("tpu", "hbm") else None
        self.pending = []
        self._abort_check = device.TpuCollModule._abort_check(None, comm)
        # finalize hook registration happens HERE, not first meet():
        # a batch enqueued and never waited on must still flush at
        # MPI_Finalize, even if no blocking collective ever ran
        device.track_state(comm.state)

    def enqueue(self, kind, x, extra, nbytes) -> FusedRequest:
        if getattr(x, "ndim", None) == 0:
            x, was_scalar = x.reshape(1), True
        else:
            was_scalar = False
        req = FusedRequest(self.comm.state.progress, self)
        self.pending.append(
            _Pending(kind, x, extra, was_scalar, nbytes, req))
        if len(self.pending) >= max(1, _max_ops_var.value):
            self.flush()
        return req

    def flush(self) -> None:
        if not self.pending:
            return
        batch, self.pending = self.pending, []
        tr = self.comm.state.tracer
        t0 = 0
        if tr is not None:
            # a flush is an operation of its own: members flush in
            # lockstep (each flush is one rendezvous), so it ticks the
            # communicator's sequence number itself (a FusedRequest,
            # unlike an NBCRequest, draws none) and its spans (this
            # one, the pack, the meet and its phases) are kept or
            # skipped on it, the same on every member, under a key
            # critpath can tell from the next flush's
            seq = _trace.coll_seq(self.comm)
            if tr.keep(_trace.CAT_COLL, seq):
                t0 = tr.start()
        try:
            outs = self._run(batch)
        except BaseException as e:  # noqa: BLE001
            for p in batch:
                p.req._fail(e)
            raise
        if t0:
            tr.end(t0, _trace.NAME_FUSED_FLUSH, _trace.CAT_COLL,
                   self.comm.cid, len(batch), seq)
        nbytes = 0
        for p, out in zip(batch, outs):
            nbytes += p.nbytes
            p.req._deliver(out.reshape(()) if p.was_scalar else out)
        band = self.comm.state.cid_band
        _pv_batches.add(1, band)
        _pv_colls.add(len(batch), band)
        _pv_bytes.add(nbytes, band)

    def _pack_groups(self, sig, batch):
        """Mesh-mode deposit payload: this rank's slots packed into one
        committed buffer per (reducer, dtype) group (masked for bcast)
        followed by the raw gather-fold slots.  Runs on the owning
        rank's thread BEFORE the rendezvous, so the batch meeting point
        only assembles G pre-placed group buffers — the placement cost
        that used to serialize on the last arriver."""
        import jax

        from ompi_tpu.coll import device

        comm = self.comm
        tr = comm.state.tracer
        seq = comm._coll_seq     # the flush's own (flush ticked it)
        t0 = tr.start() if tr is not None and tr.keep(
            _trace.CAT_COLL, seq) else 0
        # phase profiler (docs/DESIGN.md §18): the fused pack is the
        # host-pack phase of the op the following meet() dispatches,
        # which decides on the same sequence number
        tp = tr.lap() if tr is not None and tr.phase else 0
        mesh = comm.mesh()
        my_dev = mesh.devices.reshape(-1)[comm.rank]
        groups, folds = _group_plan(sig)
        deposit = []
        for gi, (opname, dt, slots) in enumerate(groups):
            roots = tuple(
                (sig[i][3] == comm.rank) if sig[i][0] == "bcast"
                else None for i in slots)
            packfn = device.compile_cache.get(
                ("fusedpack", my_dev.id, sig, gi, roots),
                lambda d=my_dev, s=slots, r=roots:
                    _build_pack(d, sig, s, r))
            args = [batch[i].x for i in slots]
            try:
                deposit.append(packfn(*args))
            except ValueError:
                # inputs committed to clashing devices: canonicalize
                deposit.append(packfn(*[jax.device_put(a, my_dev)
                                        for a in args]))
        deposit.extend(batch[i].x for i in folds)
        if tp:
            t1 = tr.lap_to(_trace.L_PACK, _trace.L_ENTRY)
            if tr.kept(_trace.CAT_PHASE, seq):
                tr.end_at(tp, t1, _trace.NAME_PH_PACK, _trace.CAT_PHASE,
                          comm.cid, seq)
        if t0:
            tr.end(t0, _trace.NAME_FUSED_PACK, _trace.CAT_COLL,
                   comm.cid, len(groups), len(sig))
        return deposit

    def _run(self, batch):
        from ompi_tpu.coll import device

        comm = self.comm
        size = comm.size
        sig = tuple(
            (p.kind, tuple(p.x.shape), np.dtype(p.x.dtype).str, p.extra)
            for p in batch)
        if self.mode == "hbm":
            import jax
            arrays = [p.x if device._is_jax_array(p.x)
                      else jax.device_put(np.asarray(p.x),
                                          comm.state.device)
                      for p in batch]
        else:
            arrays = self._pack_groups(sig, batch)
        mode = self.mode

        def fn(shards):
            sig0 = shards[0][0]
            for r, (s, _a) in enumerate(shards):
                if s != sig0:
                    raise RuntimeError(
                        f"fused-collective batch mismatch: rank {r} "
                        f"enqueued {s} but rank 0 enqueued {sig0}; "
                        "every member must issue the same nonblocking "
                        "device collectives between flushes")
            nslots = len(sig0)
            if mode == "hbm":
                args = [shards[r][1][i]
                        for i in range(nslots) for r in range(size)]

                def single_hbm(a):
                    jfn = device.compile_cache.get(
                        ("fused_hbm", size, sig0),
                        lambda: _build_fused_hbm(size, sig0))
                    return jfn(*a)

                outs = _xdispatch(
                    ("hbm", size), sig0, args, single_hbm,
                    lambda sigs: ("fusedx_hbm", size, sigs),
                    lambda sigs: _build_fused_hbm_multi(size, sigs))
            else:
                mesh = comm.mesh()
                dev_key = tuple(
                    d.id for d in mesh.devices.reshape(-1))
                nin = _mesh_nin(sig0)
                ins = [
                    device._assemble(
                        mesh, [shards[r][1][j] for r in range(size)])
                    for j in range(nin)]

                def single_mesh(a):
                    jfn = device.compile_cache.get(
                        ("fused", dev_key, sig0),
                        lambda: _build_fused_mesh(mesh, sig0))
                    return jfn(*a)

                outs = _xdispatch(
                    ("mesh", dev_key), sig0, ins, single_mesh,
                    lambda sigs: ("fusedx", dev_key, sigs),
                    lambda sigs: _build_fused_mesh_multi(mesh, sigs))
            # every output is replicated (psum/root-pick): all ranks
            # read the same arrays
            return [list(outs)] * size

        ck = _fused_ck(mode, sig) if _ig.on else None
        return device.meet(comm, (sig, arrays), fn, self._abort_check,
                           ck)


def _engine(comm) -> _FusionEngine:
    eng = comm.__dict__.get("_fusion_engine")
    if eng is None:
        eng = comm.__dict__["_fusion_engine"] = _FusionEngine(comm)
    return eng


def _as_arr(x):
    return x if hasattr(x, "dtype") and hasattr(x, "reshape") \
        else np.asarray(x)


def _eligible(comm, kind: str, x, opname, nbytes: int) -> bool:
    """Comm-consistent fusion gate: depends only on comm properties,
    the MCA knobs (process-wide), and dtype/op/nbytes — all of which
    MPI requires to match across members."""
    from ompi_tpu.coll import device
    if not _fusion_var.value or comm.size == 1:
        return False
    if _engine(comm).mode is None:
        return False
    if device._dtype_of(x).fields is not None:
        return False
    if kind == "allreduce" and opname not in device._XLA_REDUCERS \
            and opname not in device._GATHER_FOLD:
        return False
    return 0 < nbytes <= max(0, _threshold_var.value)


def _immediate(comm, value) -> FusedRequest:
    req = FusedRequest(comm.state.progress, None)
    req._deliver(value)
    return req


def iallreduce_arr(comm, x, op: Op) -> FusedRequest:
    """Nonblocking device-array allreduce; small payloads coalesce
    into the comm's pending fusion batch."""
    x = _as_arr(x)
    nbytes = _nbytes_of(x)
    if _eligible(comm, "allreduce", x, op.name, nbytes):
        return _engine(comm).enqueue("allreduce", x, op.name, nbytes)
    return _immediate(comm, comm.coll.allreduce_arr(comm, x, op))


def ibcast_arr(comm, x, root: int = 0) -> FusedRequest:
    """Nonblocking device-array broadcast; small payloads coalesce
    into the comm's pending fusion batch (masked-psum slot of the
    fused call)."""
    x = _as_arr(x)
    nbytes = _nbytes_of(x)
    if _eligible(comm, "bcast", x, None, nbytes):
        return _engine(comm).enqueue("bcast", x, int(root), nbytes)
    return _immediate(comm, comm.coll.bcast_arr(comm, x, root))


def flush_comm(comm) -> None:
    """Run this comm's pending fusion batch now (collective over the
    comm: all members must flush)."""
    eng = comm.__dict__.get("_fusion_engine")
    if eng is not None:
        eng.flush()


def flush_state(state) -> None:
    """Finalize hook: flush every comm's pending batch for this rank
    so no enqueued collective dies with the process (runs before the
    finalize fence — peers are still alive to rendezvous)."""
    first = None
    for comm in list(getattr(state, "comms", {}).values()):
        if comm is None:  # freed comm leaves its cid slot behind
            continue
        try:
            flush_comm(comm)
        except BaseException as e:  # noqa: BLE001
            if first is None:
                first = e
    if first is not None:
        raise first
