"""Device-buffer point-to-point — the ``btl/tpu`` HBM shim of the
north star (BASELINE.json; SURVEY §2.8 send/recv row).

Send/recv where both ends own devices moves the bytes
DEVICE-TO-DEVICE: the sender places the array on the receiver's chip
with ``jax.device_put`` (an ICI/D2D copy on real hardware — XLA
picks the transfer path) and the reference rides the pml as an
opaque payload through the inproc btl, so co-located rank-threads
(the TPU-host execution model) never bounce through host memory.
Crossing a process/host boundary, the payload wrapper pickles itself
to numpy — exactly ONE host staging, at the last possible moment
(the coll/cuda staging discipline, ref: ompi/mca/coll/cuda).

Eligibility mirrors coll/device: the D2D placement depends only on
peer locality and device ownership (never on argument residency), so
both sides always agree on the protocol — there is nothing to
diverge on because the receiver accepts the same wrapper either way.

API (on Communicator): ``send_arr`` / ``recv_arr`` /
``sendrecv_arr``.  Ordering and matching are the pml's (same
(cid, src, tag) discipline as byte messages).

The path accounts for itself.  Plain counters, always on, say which
way served a message: ``btl_tpu_d2d_sends`` / ``_d2d_bytes`` (placed
on the peer's own device), ``btl_tpu_byref_sends`` (a co-resident peer
that owns no device), ``btl_tpu_staged_sends`` / ``_staged_bytes``
(every byte that went through host memory: the pickle of a
cross-process send, and each chunk of a chunked pull) and
``btl_tpu_recv_moves`` (a ``recv_arr`` that had to place the payload
because it did not arrive on the rank's own device).  "Without host
bounce" is then a statement a job can hold the library to: the staged
counters and ``btl_tpu_recv_moves`` stay where they were.  With
tracing on every call records a ``p2p`` span named for its way
(``send_arr_d2d``, ``recv_arr_inplace``, ...) under the byte
messages' match id, and with ``trace_phase_enable`` banks its time in
the layer accumulators ``p2p_send`` / ``p2p_match`` / ``p2p_deliver``
(ompi_tpu/trace).
"""

from __future__ import annotations

import itertools
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ompi_tpu import trace as _trace
from ompi_tpu.mca.params import registry as _mca
from ompi_tpu.runtime import x64 as _x64

_pv_d2d_sends = _mca.register_pvar(
    "btl", "tpu", "d2d_sends",
    help="send_arr calls whose payload was placed on the co-resident "
         "peer's own device (jax.device_put at send time) and handed "
         "over by reference")
_pv_d2d_bytes = _mca.register_pvar(
    "btl", "tpu", "d2d_bytes",
    help="Bytes of the payloads counted in btl_tpu_d2d_sends")
_pv_byref_sends = _mca.register_pvar(
    "btl", "tpu", "byref_sends",
    help="send_arr calls to a co-resident peer that owns no device: "
         "the array was handed over by reference where it was")
_pv_staged_sends = _mca.register_pvar(
    "btl", "tpu", "staged_sends",
    help="send_arr calls whose payload went through host memory: "
         "pickled to numpy across a process boundary, or parked for "
         "the chunked pull (above btl_tpu_chunk_bytes)")
_pv_staged_bytes = _mca.register_pvar(
    "btl", "tpu", "staged_bytes",
    help="Bytes of device-array messages that went through host "
         "memory on the sender: each pickled payload and each chunk "
         "the pull protocol staged")
_pv_recv_moves = _mca.register_pvar(
    "btl", "tpu", "recv_moves",
    help="recv_arr calls that had to place the payload on the rank's "
         "device because it arrived elsewhere (another chip, or host "
         "memory after a staged send)")

_CAT_P2P = _trace.CAT_P2P
_L_SEND, _L_MATCH, _L_DELIVER = (
    _trace.L_P2P_SEND, _trace.L_P2P_MATCH, _trace.L_P2P_DELIVER)
# which way served a call IS its span's name
_D2D, _BYREF, _STAGED, _CHUNKED = _trace.NAMES_SEND_ARR
_INPLACE, _MOVED, _PULLED = _trace.NAMES_RECV_ARR


class DeviceArrayPayload:
    """Opaque pml payload carrying a device array by reference.

    Within a process it is never serialized (inproc passes the
    object).  Crossing a process boundary the wire codec's pickle
    fallback invokes ``__getstate__``, which host-stages to numpy —
    the single host bounce of the cross-host path."""

    __slots__ = ("arr",)

    def __init__(self, arr) -> None:
        self.arr = arr

    def __len__(self) -> int:
        """Payload size in bytes (the pml envelope's total)."""
        a = self.arr
        nbytes = getattr(a, "nbytes", None)
        if nbytes is None:
            nbytes = np.asarray(a).nbytes
        return int(nbytes)

    def __getstate__(self):
        host = np.asarray(self.arr)
        _pv_staged_sends.add(1)
        _pv_staged_bytes.add(host.nbytes)
        return {"np": host}

    def __setstate__(self, st) -> None:
        self.arr = st["np"]


# ---------------------------------------------------------------------------
# chunked cross-process rendezvous (the pipelined-schedule analog of
# ref: ompi/mca/pml/ob1/pml_ob1_sendreq.c:404-453): a large device
# array never host-stages whole.  The sender parks the DEVICE array
# in a registry and sends a small header; the receiver pulls chunks
# (a window of `pipeline_depth` ahead), each chunk d2h-staged at pull
# time, wired as an ordinary byte message, and h2d-placed on arrival.
# Peak host memory on both sides is a few chunks, not the array.
# ---------------------------------------------------------------------------

_chunk_var = _mca.register(
    "btl", "tpu", "chunk_bytes", 4 * 1024 * 1024, int,
    help="Cross-process device-array transfers larger than this are "
         "streamed in chunks of this size (bounded host staging); "
         "smaller ones ride one eager object frag")
_restore_grace_var = _mca.register(
    "btl", "tpu", "restore_grace_s", 300.0, float,
    help="Seconds a snapshot-restored parked transfer waits for its "
         "receiver's first pull before being garbage-collected (the "
         "receiver may have completed the pull before the snapshot "
         "was restored — an uncoordinated-capture race)")
_depth_var = _mca.register(
    "btl", "tpu", "pipeline_depth", 2, int,
    help="Chunks the receiver pulls ahead (overlaps d2h staging, "
         "wire transfer and h2d placement)")

T_PULL = -471            # pull-request object messages (any comm)
_DATA_BASE = -472_000    # chunk-data byte messages
_DATA_SPAN = 4096


class _XferHdr:
    """Rendezvous header: metadata only; rides the object channel
    with the USER tag so matching semantics are the pml's."""

    __slots__ = ("xfer_id", "shape", "dtype", "nbytes", "chunk")

    def __init__(self, xfer_id, shape, dtype, nbytes, chunk):
        self.xfer_id = xfer_id
        self.shape = shape
        self.dtype = dtype
        self.nbytes = nbytes
        self.chunk = chunk

    def __len__(self):
        return self.nbytes  # envelope total (probe/monitoring)


class _XferPull:
    """Receiver -> sender: stream chunks [start, start+count)."""

    __slots__ = ("xfer_id", "start", "count", "cid", "rank")

    def __init__(self, xfer_id, start, count, cid, rank):
        self.xfer_id = xfer_id
        self.start = start
        self.count = count
        self.cid = cid       # comm to send chunk data on
        self.rank = rank     # receiver's rank in that comm

    def __len__(self):
        return 32


class TpuRndvEngine:
    """Sender-side service: pending transfers + pull handling inside
    the progress loop.  ``max_staged_bytes`` is the high-water mark
    of live host-staged chunk bytes — tests assert the bound."""

    def __init__(self, state) -> None:
        self.state = state
        self._xfer_ids = itertools.count(1)
        self.pending: Dict[int, tuple] = {}   # id -> (flat, sent, total)
        self._inflight: list = []             # (req, nbytes)
        self._restored: Dict[int, float] = {}  # xid -> restore stamp
        self._gc_tombstones: set = set()       # grace-GC'd xids
        self.staged_bytes = 0
        self.max_staged_bytes = 0
        state.progress.register(self.progress, low_priority=True)

    def begin_send(self, flat) -> int:
        xid = next(self._xfer_ids)
        # chunking is in ELEMENTS (both sides derive the same count
        # from the header's chunk-bytes and the dtype): a byte-based
        # count loses tail elements whenever itemsize does not divide
        # chunk_bytes
        per = max(1, _chunk_var.value // flat.dtype.itemsize)
        nchunks = -(-int(flat.size) // per)
        self.pending[xid] = [flat, 0, nchunks, per]
        return xid

    def ft_reset(self) -> None:
        """Epoch reset (runtime/ft.py recover): every pre-epoch
        transfer is dead — the pml sequence space restarted, so the
        _XferHdr naming a pending entry will never be replayed, and a
        post-recovery xid colliding with a stale entry would hand the
        new receiver the OLD array (ADVICE r5 #1).  Drop everything
        and re-seed the id space past every xid this incarnation ever
        issued."""
        top = 0
        for xid in self.pending:
            top = max(top, xid)
        for xid in self._gc_tombstones:
            top = max(top, xid)
        # the counter itself may be past any surviving table entry
        # (completed transfers leave no trace): peek without consuming
        nxt = next(self._xfer_ids)
        top = max(top, nxt - 1)
        self.pending.clear()
        self._restored.clear()
        self._gc_tombstones.clear()
        self._inflight = []
        self.staged_bytes = 0
        self._xfer_ids = itertools.count(top + 1)

    def _reap(self) -> int:
        n = 0
        alive = []
        for req, nb in self._inflight:
            if req.complete:
                self.staged_bytes -= nb
                n += 1
            else:
                alive.append((req, nb))
        self._inflight = alive
        return n

    def cr_capture(self, lenient: bool = False) -> list:
        """Snapshot parked (not-yet-pulled) transfers: the data half
        of any _XferHdr a peer's cr_capture snapshots.  A partially
        pulled transfer cannot exist at a QUIESCED checkpoint — the
        puller would still be inside recv_arr, which no rank can be
        during a collective checkpoint — so there it is a protocol bug
        worth a loud failure.  The UNCOORDINATED path (``lenient``)
        has no quiesce: a peer legitimately mid-recv_arr is snapshot
        with its FULL parked array and a reset cursor — a restarted
        receiver re-pulls from chunk 0 (its pull state restarts with
        it), and a live capture never disturbs the in-progress pull
        (the snapshot is a copy)."""
        out = []
        for xid, (flat, sent, nchunks, per) in sorted(
                self.pending.items()):
            if sent and not lenient:
                raise RuntimeError(
                    "cr_capture with a partially pulled device "
                    "transfer (receiver mid-recv_arr at quiesce?)")
            out.append((xid, np.asarray(flat), nchunks, per))
        return out

    def cr_restore(self, entries: list) -> None:
        top = 0
        now = time.monotonic()
        for xid, arr, nchunks, per in entries:
            self.pending[xid] = [np.asarray(arr).reshape(-1), 0,
                                 nchunks, per]
            # a snapshot may predate the receiver FINISHING its pull
            # (uncoordinated capture): a restored entry no peer ever
            # claims would otherwise hold its host-staged array
            # forever.  Stamp it; progress GCs unclaimed restored
            # entries after restore_grace_s (a live restart's re-pull
            # arrives within the fence+replay, i.e. seconds).
            self._restored[xid] = now
            top = max(top, xid)
        if top:
            self._xfer_ids = itertools.count(top + 1)

    def progress(self) -> int:
        pml = self.state.pml
        n = self._reap()
        if self._restored:
            now = time.monotonic()
            for xid in [x for x, t in self._restored.items()
                        if now - t > _restore_grace_var.value]:
                del self._restored[xid]
                self.pending.pop(xid, None)  # unclaimed: receiver had
                #                              already completed its
                #                              pull before the snapshot
                #                              was restored
                self._gc_tombstones.add(xid)
        while True:
            msg = pml.poll_obj_any(T_PULL)
            if msg is None:
                break
            n += 1
            pull: _XferPull = msg.payload
            entry = self.pending.get(pull.xfer_id)
            self._restored.pop(pull.xfer_id, None)  # claimed: live
            if entry is None:
                if pull.xfer_id in self._gc_tombstones:
                    # the restore-grace GC dropped this transfer as
                    # unclaimed, but the receiver's re-pull was just
                    # slow: the data is gone — say so loudly so the
                    # receiver's hang is diagnosable (raise
                    # btl_tpu_restore_grace_s)
                    from ompi_tpu.util import output
                    output.get_stream("btl_tpu").output(
                        f"pull for restored transfer "
                        f"{pull.xfer_id} arrived after the "
                        f"restore-grace GC discarded it; the "
                        f"receiver's recv_arr cannot complete "
                        f"(raise btl_tpu_restore_grace_s)")
                continue  # duplicate/late pull
            flat, _, nchunks, per = entry
            comm = self.state.comms.get(pull.cid)
            tag = _DATA_BASE - (pull.xfer_id % _DATA_SPAN)
            from ompi_tpu.datatype import engine as dtmod
            for i in range(pull.start, pull.start + pull.count):
                piece = np.ascontiguousarray(
                    np.asarray(flat[i * per:(i + 1) * per]))
                nb = piece.nbytes
                _pv_staged_bytes.add(nb)
                self.staged_bytes += nb
                self.max_staged_bytes = max(self.max_staged_bytes,
                                            self.staged_bytes)
                req = pml.isend(piece.view(np.uint8), nb, dtmod.BYTE,
                                pull.rank, tag, comm)
                self._inflight.append((req, nb))
            entry[1] = max(entry[1], pull.start + pull.count)
            if entry[1] >= nchunks:
                # all chunks handed to the pml; the flat device array
                # may be released once the in-flight sends drain
                self.pending.pop(pull.xfer_id, None)
        return n


def _engine(state) -> TpuRndvEngine:
    eng = getattr(state, "_tpu_rndv", None)
    if eng is None:
        eng = TpuRndvEngine(state)
        state._tpu_rndv = eng
    return eng


def _pull_transfer(comm, src: int, hdr: _XferHdr):
    """Receiver side: window-ahead pulls; each chunk lands in a host
    buffer, moves to this rank's device, and the device assembles."""
    from ompi_tpu.datatype import engine as dtmod
    pml = comm.state.pml
    tag = _DATA_BASE - (hdr.xfer_id % _DATA_SPAN)
    dtype = np.dtype(hdr.dtype)
    per = max(1, hdr.chunk // dtype.itemsize)
    total_elems = hdr.nbytes // dtype.itemsize
    nchunks = -(-total_elems // per)
    depth = max(1, _depth_var.value)
    dev = comm.state.device
    posted: Dict[int, tuple] = {}
    pulled = 0

    def pull_upto(limit: int) -> None:
        nonlocal pulled
        limit = min(limit, nchunks)
        if limit <= pulled:
            return
        # post the recvs BEFORE requesting: chunk data then lands in
        # posted buffers, never the unexpected queue
        for i in range(pulled, limit):
            n_el = min(per, total_elems - i * per)
            buf = np.empty(n_el * dtype.itemsize, np.uint8)
            req = pml.irecv(buf, buf.size, dtmod.BYTE, src, tag, comm)
            posted[i] = (req, buf)
        pml.isend_obj(
            _XferPull(hdr.xfer_id, pulled, limit - pulled, comm.cid,
                      comm.rank), src, T_PULL, comm)
        pulled = limit

    parts = []
    pull_upto(depth)
    for i in range(nchunks):
        pull_upto(i + 1 + depth)  # keep the window full
        req, buf = posted.pop(i)
        req.wait()
        arr = buf.view(dtype)
        if dev is not None:
            arr = _x64.put(arr, dev, "recv_arr")
        parts.append(arr)
    if len(parts) == 1:
        out = parts[0]
    elif dev is not None:
        import jax.numpy as jnp
        out = jnp.concatenate(parts)
    else:
        out = np.concatenate(parts)
    return out.reshape(hdr.shape)


def _peer_local_device(comm, dst: int) -> Tuple[bool, Any]:
    """(peer_is_coresident_thread, peer_device_or_None).  Locality
    and device ownership are separate facts: a co-resident peer
    without a device still gets by-reference delivery (never the
    chunked wire path)."""
    state = comm.state
    world = getattr(state.rte, "world", None)
    if world is None:
        return False, None
    gdst = comm.group[dst]
    if not world.is_local(gdst):
        return False, None
    peer_state = world.states[gdst]
    dev = getattr(peer_state, "device", None) \
        if peer_state is not None else None
    return True, dev


def send_arr(comm, x, dst: int, tag: int = 0) -> None:
    """Device-aware send: D2D placement onto the receiver's chip when
    the peer is a co-resident rank-thread, by-reference delivery
    through the pml; host-staged exactly once otherwise.  PROC_NULL
    destinations are no-ops (MPI semantics — cart.Shift edges)."""
    from ompi_tpu.pml.request import PROC_NULL
    if dst == PROC_NULL:
        return
    tr = comm.state.tracer
    t0 = 0
    if tr is not None:
        if tr.phase:
            tr.p2p_enter(_L_SEND)
        t0 = tr.start_sampled(_CAT_P2P)
    try:
        way, nbytes, seq = _send(comm, x, dst, tag)
        if t0:
            tr.end(t0, way, _CAT_P2P, comm.cid, comm.rank, tag, seq,
                   nbytes)
    finally:
        # a send that raises still closes its interval: the time up to
        # the next boundary is the caller's
        if tr is not None and tr.phase:
            tr.p2p_return()


def _send(comm, x, dst: int, tag: int) -> Tuple[int, int, int]:
    """send_arr's work; (the way that served it, as its span's name,
    the payload's bytes, the envelope's sequence number)."""
    pml = comm.state.pml
    local, pdev = _peer_local_device(comm, dst)
    if local:
        if pdev is not None:
            x = _x64.put(x, pdev, "send_arr")
            nbytes = int(x.nbytes)
            _pv_d2d_sends.add(1)
            _pv_d2d_bytes.add(nbytes)
            return _D2D, nbytes, pml.isend_obj(
                DeviceArrayPayload(x), dst, tag, comm)
        if isinstance(x, np.ndarray):
            # co-resident by-reference delivery: copy so the user may
            # reuse the send buffer immediately (jax arrays are
            # immutable and need no copy)
            x = x.copy()
        _pv_byref_sends.add(1)
        payload = DeviceArrayPayload(x)
        return _BYREF, len(payload), pml.isend_obj(payload, dst, tag, comm)
    if not hasattr(x, "nbytes") or not hasattr(x, "reshape"):
        x = np.asarray(x)  # lists/tuples: one materialization
    nbytes = int(x.nbytes)
    dt = np.dtype(x.dtype)
    chunkable = dt.fields is None and not dt.hasobject \
        and np.dtype(str(dt)) == dt
    if nbytes > _chunk_var.value and chunkable:
        # cross-process large array: chunked rendezvous — the array
        # stays device-resident until the receiver pulls; each pull
        # host-stages ONE chunk (bounded staging; a one-shot pickle
        # would both materialize a full host copy and overflow the
        # shm ring for >ring-size payloads, ADVICE r3 #2).  Mutable
        # host arrays are copied ONCE up front: the send-buffer-reuse
        # guarantee must survive deferred pulls.
        if isinstance(x, np.ndarray):
            x = x.copy()
        eng = _engine(comm.state)
        flat = x.reshape(-1)
        xid = eng.begin_send(flat)
        hdr = _XferHdr(xid, tuple(np.shape(x)), str(dt), nbytes,
                       _chunk_var.value)
        _pv_staged_sends.add(1)   # its bytes count as the chunks stage
        return _CHUNKED, nbytes, pml.isend_obj(hdr, dst, tag, comm)
    if isinstance(x, np.ndarray):
        x = x.copy()
    # the pickle on the way out counts it (DeviceArrayPayload)
    return _STAGED, nbytes, pml.isend_obj(
        DeviceArrayPayload(x), dst, tag, comm)


def recv_arr(comm, src: int, tag: int = 0):
    """Matched receive of a device-array payload; the result lives on
    this rank's device (or stays a numpy array when the rank owns no
    device)."""
    from ompi_tpu.pml.request import PROC_NULL
    if src == PROC_NULL:
        return None
    tr = comm.state.tracer
    t0 = 0
    if tr is not None:
        if tr.phase:
            tr.p2p_enter(_L_MATCH)
        t0 = tr.start_sampled(_CAT_P2P)
    try:
        msg = comm.state.pml.recv_obj(src, tag, comm)
        if tr is not None and tr.phase:
            tr.lap_to(_L_MATCH, _L_DELIVER)
        payload = msg.payload
        if isinstance(payload, _XferHdr):
            arr, way = _pull_transfer(comm, msg.src, payload), _PULLED
        elif not isinstance(payload, DeviceArrayPayload):
            raise TypeError(
                f"recv_arr matched a non-device message (tag {tag} from "
                f"{src}); byte messages use Recv")
        else:
            arr, way = payload.arr, _INPLACE
            dev = comm.state.device
            if dev is not None and getattr(arr, "device", None) != dev:
                arr, way = _x64.put(arr, dev, "recv_arr"), _MOVED
                _pv_recv_moves.add(1)
        if t0:
            tr.end(t0, way, _CAT_P2P, comm.cid, msg.src, msg.tag,
                   msg.seq, msg.total)
    finally:
        # a receive that raises (an ULFM error, an abort, a byte
        # message under the tag) still closes its interval
        if tr is not None and tr.phase:
            tr.p2p_return()
    return arr


def sendrecv_arr(comm, x, dst: int, src: int, tag: int = 0):
    """Combined exchange (halo shifts): the send is eager-object, so
    posting it before the blocking receive is deadlock-free."""
    send_arr(comm, x, dst, tag)
    return recv_arr(comm, src, tag)
