"""Device-memory one-sided RMA tests (osc/device, ISSUE 14): the
promoted rma_counter / halo_stencil examples as byte-identity checks
between the pt2pt and device components, framework selection, segment
chunking, typed-atomic dtype routing, and epoch hygiene across ULFM
death and shrink (kernels/selection purged, blocked sync raises)."""

import time

import numpy as np
import pytest

from ompi_tpu import errhandler as eh
from ompi_tpu import osc
from ompi_tpu.errhandler import MPIException
from ompi_tpu.ft import ulfm
from ompi_tpu.mca.params import registry
from ompi_tpu.op import op as mpi_op
from ompi_tpu.testing import run_ranks

PF = eh.ERR_PROC_FAILED
PFP = eh.ERR_PROC_FAILED_PENDING
RV = eh.ERR_REVOKED


# ---- promoted example workloads (component-agnostic) ----------------
# osc.allocate routes through osc_select: a mesh-capable comm
# (devices=True) mints the device window, a host comm the pt2pt one —
# the SAME workload bytes must come back either way.

def _counter_workload(comm):
    """examples/rma_counter.py: fence put ring, passive atomic
    counter, fetch_and_op ticketing, compare_and_swap."""
    rank, size = comm.rank, comm.size
    out = {}

    ring = osc.allocate(comm, 16, disp_unit=8, name="ring")
    out["component"] = type(ring).__name__
    ring.fence()
    ring.put(np.full(2, rank, dtype=np.int64), (rank + 1) % size)
    ring.fence()
    out["ring"] = np.asarray(ring.memory).tobytes()

    # passive target: int64 counter on rank 0 (the 8-byte dtype takes
    # the device component's host-fallback atomic path)
    ctr = osc.allocate(comm, 8, disp_unit=8, name="ctr")
    tickets = []
    for _ in range(5):
        old = np.empty(1, dtype=np.int64)
        ctr.lock(0, osc.LOCK_SHARED)
        ctr.fetch_and_op(1, old, 0, op=mpi_op.SUM)
        ctr.unlock(0)
        tickets.append(int(old[0]))
    assert sorted(set(tickets)) == tickets  # monotone per origin
    comm.Barrier()
    got = np.empty(1, dtype=np.int64)
    ctr.lock(0, osc.LOCK_SHARED)
    ctr.get(got, 0)
    ctr.unlock(0)
    assert int(got[0]) == 5 * size
    out["counter"] = got.tobytes()

    # compare_and_swap election on an int32 slot (device-jitted dtype)
    slot = osc.allocate(comm, 4, disp_unit=4, name="cas")
    oldv = np.empty(1, dtype=np.int32)
    slot.lock(0, osc.LOCK_SHARED)
    slot.compare_and_swap(np.int32(0), np.int32(rank + 1), oldv, 0)
    slot.unlock(0)
    comm.Barrier()
    winner = np.empty(1, dtype=np.int32)
    slot.lock(0, osc.LOCK_SHARED)
    slot.get(winner, 0)
    slot.unlock(0)
    assert 1 <= int(winner[0]) <= size
    out["cas_winner_is_set"] = bool(winner[0] != 0)

    slot.free()
    ctr.free()
    ring.free()
    return out


def _halo_workload(comm):
    """examples/halo_stencil.py, RMA-flavored: each rank PUTS its
    tile edges into the neighbors' windows (west slot / east slot)
    instead of exchanging them with neighbor collectives."""
    rank, size = comm.rank, comm.size
    n = 32
    win = osc.allocate(comm, 2 * n * 4, disp_unit=4, name="halo")
    tile = (np.arange(n, dtype=np.float32) + 1) * (rank + 1)
    win.fence()
    win.put(tile, (rank + 1) % size, disp=0)       # right's west slot
    win.put(tile * 2, (rank - 1) % size, disp=n)   # left's east slot
    win.fence()
    halo = np.asarray(win.memory).tobytes()
    # one relaxation step off the received halos
    mem = np.frombuffer(halo, dtype=np.float32)
    west, east = mem[:n], mem[n:]
    new = (tile + west + east) / 3.0
    win.free()
    return {"component": type(win).__name__, "halo": halo,
            "tile": new.tobytes()}


def _expected_halo(rank, size):
    n = 32
    base = np.arange(n, dtype=np.float32) + 1
    west = base * ((rank - 1) % size + 1)
    east = base * 2 * ((rank + 1) % size + 1)
    return np.concatenate([west, east]).tobytes()


@pytest.mark.parametrize("workload", [_counter_workload, _halo_workload],
                         ids=["rma_counter", "halo_stencil"])
def test_promoted_examples_byte_identical(workload):
    n = 4
    host = run_ranks(n, workload, devices=False)
    dev = run_ranks(n, workload, devices=True)
    assert all(r["component"] == "Window" for r in host)
    assert all(r["component"] == "DeviceWindow" for r in dev)
    for r in range(n):
        for k in host[r]:
            if k == "component":
                continue
            assert host[r][k] == dev[r][k], (r, k)
    if workload is _halo_workload:
        for r in range(n):
            assert dev[r]["halo"] == _expected_halo(r, n)


# ---- framework selection --------------------------------------------

def test_osc_select_device_vs_pt2pt():
    """Win_create commits to the mesh only for device-committed
    buffers; --mca osc pt2pt overrides the verdict."""
    def fn(comm):
        import jax.numpy as jnp
        host_win = osc.create(comm, np.zeros(8, dtype=np.int64))
        dev_win = osc.create(comm, jnp.zeros(8, jnp.int32))
        kinds = (type(host_win).__name__, type(dev_win).__name__)
        host_win.free()
        dev_win.free()
        registry.set("osc", "pt2pt")
        comm.__dict__.pop("_osc_pick", None)
        try:
            forced = osc.allocate(comm, 64, name="forced")
            forced_kind = type(forced).__name__
            forced.free()
        finally:
            registry.set("osc", "")
            comm.__dict__.pop("_osc_pick", None)
        return kinds + (forced_kind,)

    res = run_ranks(2, fn, devices=True)
    assert all(r == ("Window", "DeviceWindow", "Window") for r in res)


def test_no_mesh_falls_back_to_pt2pt():
    def fn(comm):
        win = osc.allocate(comm, 32)
        kind = type(win).__name__
        win.free()
        return kind

    assert run_ranks(2, fn, devices=False) == ["Window", "Window"]


# ---- data plane -----------------------------------------------------

def test_large_transfers_chunked_by_segment():
    """Kernel mode: transfers larger than the calibrated segment are
    split into bucket kernels; bytes land exactly (including
    unaligned spans)."""
    def fn(comm):
        registry.set("osc_device_dma", "0")
        registry.set("osc_device_seg_bytes", "4096")
        try:
            win = osc.allocate(comm, 1 << 16, name="big")
            rng = np.random.default_rng(100 + comm.rank)
            blob = rng.integers(0, 256, 40001, dtype=np.uint8)
            win.fence()
            win.put(blob, (comm.rank + 1) % comm.size, disp=13)
            win.fence()
            back = np.empty(40001, dtype=np.uint8)
            win.get(back, comm.rank, disp=13)
            left = (comm.rank - 1) % comm.size
            exp = np.random.default_rng(100 + left).integers(
                0, 256, 40001, dtype=np.uint8)
            ok = bool(np.array_equal(back, exp))
            win.fence()
            win.free()
            return ok
        finally:
            registry.set("osc_device_seg_bytes", "0")
            registry.set("osc_device_dma", "1")

    assert all(run_ranks(4, fn, devices=True))


def test_dma_and_kernel_lowerings_byte_identical():
    """The default direct-DMA lowering and the whole-mesh ppermute
    kernel lowering must produce identical window bytes for the same
    op sequence — puts at odd offsets, zero-copy wholesale puts,
    accumulate, CAS and get_accumulate."""
    def run(comm, tag):
        rank, size = comm.rank, comm.size
        win = osc.allocate(comm, 256, disp_unit=1, name=f"eq-{tag}")
        win.fence()
        # odd-offset partial put
        win.put(np.arange(7, dtype=np.uint8) + rank,
                (rank + 1) % size, disp=3)
        win.fence()
        # wholesale put (DMA mode's zero-copy borrow path when the
        # buffer happens to be aligned); snapshot before the Barrier
        # so no rank reads a window a peer already rewrote this epoch
        snap = np.asarray(win.memory).view(np.uint8)[3:10].copy()
        comm.Barrier()
        whole = np.full(256, rank + 10, dtype=np.uint8)
        whole[3:10] = snap
        win.put(whole, (rank + 2) % size)
        win.fence()
        # typed ops
        win.accumulate(np.full(4, rank + 1, dtype=np.int32), 0,
                       disp=16, op=mpi_op.SUM)
        win.fence()
        old = np.empty(1, dtype=np.int32)
        win.lock(0, osc.LOCK_SHARED)
        if rank == 1:  # single origin: the winner must be
            win.compare_and_swap(np.int32(0), np.int32(rank + 1),
                                 old, 0, disp=32)  # deterministic
        res = np.empty(4, dtype=np.int32)
        win.get_accumulate(np.full(4, 2, dtype=np.int32), res, 0,
                           disp=16, op=mpi_op.NO_OP)
        win.unlock(0)
        win.fence()
        mem = np.asarray(win.memory).tobytes()
        win.free()
        return {"mem": mem, "res": res.tobytes()}

    # the registry is process-global and ranks are threads: flipping
    # the var inside the rank fn would let an early-finishing rank
    # switch its peers' lowering mid-sequence — set it once per run,
    # from the parent, around run_ranks
    via_dma = run_ranks(4, lambda c: run(c, "dma"), devices=True)
    registry.set("osc_device_dma", "0")
    try:
        via_krn = run_ranks(4, lambda c: run(c, "krn"), devices=True)
    finally:
        registry.set("osc_device_dma", "1")
    for r in range(4):
        assert via_dma[r]["mem"] == via_krn[r]["mem"], r
        assert via_dma[r]["res"] == via_krn[r]["res"], r


def test_accumulate_dtype_routing():
    """int32/float32 accumulate runs the jitted kernel; int64/float64
    take the host fallback — results identical either way."""
    def fn(comm):
        rank, size = comm.rank, comm.size
        out = {}
        for dt, tag in ((np.int32, "i4"), (np.float32, "f4"),
                        (np.int64, "i8"), (np.float64, "f8")):
            win = osc.allocate(comm, 8 * np.dtype(dt).itemsize,
                               disp_unit=np.dtype(dt).itemsize,
                               name=f"acc-{tag}")
            win.fence()
            win.accumulate(np.full(8, rank + 1, dtype=dt), 0,
                           op=mpi_op.SUM)
            win.fence()
            if rank == 0:
                out[tag] = np.asarray(win.memory).tobytes()
            # MPI_REPLACE and MPI_NO_OP through get_accumulate
            res = np.empty(8, dtype=dt)
            win.fence()
            win.get_accumulate(np.full(8, 99, dtype=dt), res, 0,
                               op=mpi_op.NO_OP)
            win.fence()
            total = size * (size + 1) // 2
            assert np.all(res == np.asarray(total, dtype=dt)), (tag, res)
            win.free()
        return out

    res = run_ranks(4, fn, devices=True)
    total = 4 * 5 // 2
    for tag, dt in (("i4", np.int32), ("f4", np.float32),
                    ("i8", np.int64), ("f8", np.float64)):
        assert res[0][tag] == np.full(8, total, dtype=dt).tobytes()


def test_bucket_keys_bounded():
    """Kernel mode: a size sweep must not mint one kernel per size —
    bucket widths are pow2-quantized, so distinct put-kernel keys
    stay logarithmic."""
    def fn(comm):
        from ompi_tpu.coll import device as cdev
        registry.set("osc_device_dma", "0")
        try:
            win = osc.allocate(comm, 1 << 14, name="sweep")
            win.fence()
            for nb in range(1, 200, 7):
                win.put(np.full(nb, comm.rank, dtype=np.uint8),
                        (comm.rank + 1) % comm.size)
            win.fence()
            with cdev.compile_cache._lock:
                keys = sum(1 for k in cdev.compile_cache._d
                           if k[0] == "osc_pput" and k[1] == win._dev_key
                           and k[2] == win._cap)
            win.free()
            return keys
        finally:
            registry.set("osc_device_dma", "1")

    res = run_ranks(2, fn, devices=True)
    # sizes 1..199 collapse onto ONE 256-byte bucket per (origin,
    # target) pair — 2 pairs in this 2-rank sweep
    assert all(k <= 2 for k in res), res


# ---- epoch hygiene (ULFM) -------------------------------------------

def test_fence_raises_after_peer_death():
    """A fence on a comm with a dead rank must raise, not hang."""
    def fn(comm):
        win = osc.allocate(comm, 64, name="chaos-fence")
        win.fence()
        if comm.rank == 0:
            ulfm.kill_now(comm.state)
        time.sleep(0.3)
        try:
            for _ in range(100):
                win.fence()
                time.sleep(0.02)
            return "no-raise"
        except MPIException as e:
            assert e.code in (PF, PFP, RV), e.code
            win.abandon()
            return "raised"

    r = run_ranks(4, fn, devices=True, allow_failures=True)
    assert r[0] is None and all(x == "raised" for x in r[1:]), r


def test_lock_raises_after_peer_death():
    """A passive-target lock of a dead rank completes with
    ERR_PROC_FAILED instead of spinning forever."""
    def fn(comm):
        win = osc.allocate(comm, 64, name="chaos-lock")
        win.fence()
        if comm.rank == 1:
            ulfm.kill_now(comm.state)
        time.sleep(0.3)
        try:
            for _ in range(100):
                win.lock(1, osc.LOCK_EXCLUSIVE)
                win.put(np.zeros(4, dtype=np.uint8), 1)
                win.unlock(1)
                time.sleep(0.02)
            return "no-raise"
        except MPIException as e:
            assert e.code in (PF, PFP, RV), e.code
            win.abandon()
            return "raised"

    r = run_ranks(3, fn, devices=True, allow_failures=True)
    assert r[1] is None and all(
        x == "raised" for i, x in enumerate(r) if i != 1), r


def test_shrink_purges_rma_kernels_and_selection():
    """ULFM shrink drops the dead mesh's compiled RMA kernels from
    the CompiledLRU, re-decides osc selection (_osc_pick) and purges
    the window shard tables of the revoked comm."""
    from ompi_tpu.coll import device as cdev

    def fn(comm):
        win = osc.allocate(comm, 256, name="purge")
        win.fence()
        win.put(np.arange(8, dtype=np.uint8), (comm.rank + 1) % comm.size)
        win.fence()
        dev_key = win._dev_key
        time.sleep(0.2)
        if comm.rank == 0:
            ulfm.kill_now(comm.state)
        time.sleep(0.3)
        comm.shrink()
        with cdev.compile_cache._lock:
            stale = sum(1 for k in cdev.compile_cache._d if dev_key in k)
        pick_purged = "_osc_pick" not in comm.__dict__
        world = comm.state.rte.world
        with world.shared_lock:
            tabs = sum(1 for k in world.shared
                       if isinstance(k, tuple) and k
                       and k[0] == "osc_devwin" and k[1] == comm.cid)
        return (stale, pick_purged, tabs)

    r = run_ranks(4, fn, devices=True, allow_failures=True)
    assert all(x == (0, True, 0) for x in r[1:]), r


def test_counter_byte_identity_across_shrink():
    """The acceptance demo: survivors shrink after a death and the
    promoted counter workload on the shrunken device comm is
    byte-identical to a fresh world of the survivor size."""
    def chaos(comm):
        comm.Barrier()
        if comm.rank == 0:
            ulfm.kill_now(comm.state)
        work = comm
        while work is comm:
            try:
                work.Barrier()
                time.sleep(0.05)
            except MPIException as e:
                assert e.code in (PF, PFP, RV), e.code
                work = work.shrink(name="survivors")
        return _counter_workload(work)

    got = run_ranks(4, chaos, devices=True, allow_failures=True,
                    timeout=180.0)
    ref = run_ranks(3, _counter_workload, devices=True)
    assert got[0] is None
    for i in range(1, 4):
        assert got[i] == ref[i - 1], i


def test_device_window_never_narrows_a_double():
    """With mpi_device_x64 off (the default) a device window still
    moves and accumulates every bit of a float64: the window is bytes
    (uint8) on the device, so nothing here can be narrowed to float32,
    and nothing has to be refused."""
    import jax

    assert not jax.config.jax_enable_x64
    # needs every one of the 53 significand bits
    vals = np.array([1.0 + 2.0 ** -52, -(2.0 ** 0.5), 3.0e-300, 7.0e300])

    def fn(comm):
        rank, size = comm.rank, comm.size
        win = osc.allocate(comm, 4 * 8, disp_unit=8, name="f64-bits")
        kind = type(win).__name__
        win.fence()
        win.put(vals * (rank + 1), (rank + 1) % size)
        win.fence()
        got = np.empty(4, np.float64)
        win.get(got, (rank + 1) % size)
        win.fence()
        win.accumulate(np.full(4, 2.0 ** -60), rank, op=mpi_op.MAX)
        win.fence()
        mem = np.asarray(win.memory).view(np.float64).copy()
        win.free()
        return kind, got.tobytes(), mem.tobytes()

    res = run_ranks(2, fn, devices=True)
    for rank, (kind, got, mem) in enumerate(res):
        assert kind == "DeviceWindow"
        assert got == (vals * (rank + 1)).tobytes()
        want = np.maximum(vals * ((rank - 1) % 2 + 1), 2.0 ** -60)
        assert mem == want.tobytes()
