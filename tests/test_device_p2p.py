"""Device-buffer p2p (btl/tpu shim): D2D placement between
co-resident rank-thread devices, by-reference delivery, host-staged
fallback across processes, and the halo pattern."""

import os
import subprocess
import sys

import numpy as np
import pytest

from ompi_tpu.testing import run_ranks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_send_recv_arr_roundtrip_on_devices():
    import jax

    def fn(comm):
        import jax.numpy as jnp
        x = jnp.full((64,), float(comm.rank + 1))
        nxt = (comm.rank + 1) % comm.size
        prv = (comm.rank - 1) % comm.size
        got = comm.sendrecv_arr(x, nxt, prv, tag=4)
        # result lives on MY device and carries the neighbor's value
        assert got.device == comm.state.device
        assert float(got[0]) == float(prv + 1)
        return True

    assert all(run_ranks(4, fn, devices=True))


def test_send_arr_lands_on_peer_device_no_host_bounce():
    """The sender PLACES the array on the receiver's chip: what
    arrives is already resident there (device_put at send time), and
    within a process the payload travels by reference."""
    import jax

    def fn(comm):
        import jax.numpy as jnp
        if comm.rank == 0:
            comm.send_arr(jnp.arange(8.0), 1, tag=9)
        elif comm.rank == 1:
            # peek at the raw payload before recv_arr converts
            msg = comm.state.pml.recv_obj(0, 9, comm)
            from ompi_tpu.btl.tpu import DeviceArrayPayload
            assert isinstance(msg.payload, DeviceArrayPayload)
            arr = msg.payload.arr
            assert arr.device == comm.state.device  # D2D, pre-placed
            assert float(np.asarray(arr)[3]) == 3.0
        comm.Barrier()
        return True

    assert all(run_ranks(2, fn, devices=True))


def test_matching_interleaves_with_byte_messages():
    def fn(comm):
        import jax.numpy as jnp
        if comm.rank == 0:
            comm.Send(np.array([7], np.int64), 1, tag=1)
            comm.send_arr(jnp.ones(4), 1, tag=1)
            comm.Send(np.array([8], np.int64), 1, tag=1)
        else:
            y = np.empty(1, np.int64)
            comm.Recv(y, 0, tag=1)
            assert y[0] == 7
            arr = comm.recv_arr(0, tag=1)
            assert float(arr[0]) == 1.0
            comm.Recv(y, 0, tag=1)
            assert y[0] == 8
        comm.Barrier()
        return True

    assert all(run_ranks(2, fn, devices=True))


def test_host_staged_across_processes():
    """Across a process boundary the wrapper pickles to numpy —
    exactly one host staging, correctness preserved."""
    r = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.mpirun", "-np", "2",
         "--timeout", "90",
         os.path.join(REPO, "tests", "_devp2p_prog.py")],
        capture_output=True, timeout=150,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": REPO + os.pathsep
             + os.environ.get("PYTHONPATH", "")},
        cwd=REPO)
    assert r.returncode == 0, r.stderr.decode()
    assert b"devp2p ok" in r.stdout


def test_halo_exchange_uses_device_path():
    """The halo pattern on devices: cart shifts via sendrecv_arr."""
    import jax

    def fn(comm):
        import jax.numpy as jnp
        cart = comm.Create_cart([2, 2], periods=[True, True])
        left, right = cart.Shift(1, 1)
        tile = jnp.full((4,), float(cart.rank))
        halo = cart.sendrecv_arr(tile, right, left, tag=2)
        assert float(halo[0]) == float(left)
        return True

    assert all(run_ranks(4, fn, devices=True))


def test_chunked_transfer_bounded_staging():
    """>chunk-sized arrays stream via the pull rendezvous: correct
    content and host staging bounded at a few chunks (ref:
    pml_ob1_sendreq.c:404-453 pipelined schedule)."""
    from ompi_tpu.testing import mpirun_run
    r = mpirun_run(2, os.path.join("tests", "_devp2p_big_prog.py"),
                   timeout=300, job_timeout=250)
    assert r.returncode == 0, r.stderr.decode()[-2000:]
    assert b"devp2p-big ok" in r.stdout


def test_chunked_256mib_across_simulated_nodes():
    """The VERDICT r3 #5 gate: a 256 MiB device send crosses a
    simulated two-node job (tcp transport) with bounded staging."""
    from ompi_tpu.testing import mpirun_run
    r = mpirun_run(2, os.path.join("tests", "_devp2p_big_prog.py"),
                   "--mb", "256",
                   extra=("--simulate-nodes", "2"),
                   timeout=400, job_timeout=350)
    assert r.returncode == 0, r.stderr.decode()[-2000:]
    assert b"devp2p-big ok" in r.stdout


def test_chunked_header_checkpoint_roundtrip():
    """A not-yet-received chunked transfer survives capture/restore:
    the receiver snapshots the header, the sender snapshots the
    parked data, and the pull completes after reinjection."""
    import numpy as np
    from ompi_tpu.btl import tpu as tpumod
    from ompi_tpu.mca.params import registry

    def fn(comm):
        if comm.rank == 0:
            eng = tpumod._engine(comm.state)
            flat = np.arange(5000, dtype=np.float64)
            xid = eng.begin_send(flat)
            cap = eng.cr_capture()
            assert len(cap) == 1 and cap[0][0] == xid
            eng.pending.clear()
            eng.cr_restore(cap)
            assert xid in eng.pending
            # fresh ids never collide with restored ones
            assert eng.begin_send(flat) > xid
            eng.pending.clear()
        else:
            # receiver-side: a captured xferhdr reinjects intact
            pml = comm.state.pml
            hdr = tpumod._XferHdr(7, (10, 500), "float64", 40000,
                                  registry.get("btl_tpu_chunk_bytes"))
            from ompi_tpu.pml.ob1 import MATCH_OBJ, UnexpectedMsg
            pml._unexpected.setdefault(comm.cid, []).append(
                UnexpectedMsg(MATCH_OBJ, comm.cid, 0, 4, 0,
                              len(hdr), None, hdr))
            msgs = pml.cr_capture()
            kinds = [m[4] for m in msgs]
            assert "xferhdr" in kinds, kinds
            pml._unexpected[comm.cid].clear()
            pml.cr_restore(msgs)
            m = pml._unexpected[comm.cid][0]
            assert isinstance(m.payload, tpumod._XferHdr)
            assert m.payload.shape == (10, 500)
            pml._unexpected[comm.cid].clear()
        comm.Barrier()
        return True

    assert all(run_ranks(2, fn))


def test_x64_off_send_arr_refuses_an_8_byte_host_buffer():
    """jax.device_put would hand the receiver float32: with
    mpi_device_x64 off (the default) the send raises MPI_ERR_TYPE
    naming the parameter, and a float32 buffer goes as before."""
    import jax
    from ompi_tpu import errhandler

    assert not jax.config.jax_enable_x64

    def fn(comm):
        nxt, prv = (comm.rank + 1) % comm.size, (comm.rank - 1) % comm.size
        with pytest.raises(errhandler.MPIException) as e:
            comm.send_arr(np.ones(8, np.float64), nxt, tag=9)
        assert "mpi_device_x64" in str(e.value)
        got = comm.sendrecv_arr(np.full(8, comm.rank, np.float32), nxt,
                                prv, tag=10)
        return e.value.code, str(got.dtype), float(got[0])

    res = run_ranks(2, fn, devices=True)
    assert res == [(errhandler.ERR_TYPE, "float32", 1.0),
                   (errhandler.ERR_TYPE, "float32", 0.0)]


# -- the path accounts for itself (ISSUE 34) -----------------------------------

COUNTERS = ("d2d_sends", "d2d_bytes", "byref_sends", "staged_sends",
            "staged_bytes", "recv_moves")
TRACE_ON = {"trace_enable": True, "trace_phase_enable": True,
            "trace_buffer_events": 65536, "trace_sample_auto": 0}


def btl_counters():
    import ompi_tpu.btl.tpu  # noqa: F401  (registers btl_tpu_*)
    from ompi_tpu.mca.params import registry
    pv = {p.full_name: p for p in registry.all_pvars()}
    return [pv["btl_tpu_" + n].read() for n in COUNTERS]


def world(n, fn, knobs=None, **kw):
    from ompi_tpu.mca.params import registry
    knobs = knobs or {}
    saved = {k: registry.get(k) for k in knobs}
    for k, v in knobs.items():
        registry.set(k, v)
    try:
        return run_ranks(n, fn, timeout=240, **kw)
    finally:
        for k, v in saved.items():
            registry.set(k, v)


def ring_moved(comm, x, calls):
    """What the process-wide counters moved by over ``calls`` ring
    exchanges of every rank."""
    nxt, prv = (comm.rank + 1) % comm.size, (comm.rank - 1) % comm.size
    comm.Barrier()
    before = btl_counters()
    comm.Barrier()        # nobody counts before everybody has read
    for _ in range(calls):
        out = comm.sendrecv_arr(x, nxt, prv, tag=3)
    comm.Barrier()
    return [a - b for a, b in zip(btl_counters(), before)], out


def test_counters_of_a_d2d_send():
    """Rank-threads that each own a device: every send is placed on
    the peer's own device and counted so, with its bytes; nothing is
    staged and nothing has to be placed again on arrival."""
    def fn(comm):
        import jax.numpy as jnp
        moved, out = ring_moved(comm, jnp.ones(256, jnp.float32), 5)
        return moved, out.device == comm.state.device

    for moved, at_home in world(4, fn, devices=True):
        assert moved == [20, 20 * 1024, 0, 0, 0, 0] and at_home


def test_counters_of_a_by_reference_send():
    """Co-resident rank-threads that own no device: by reference."""
    def fn(comm):
        moved, out = ring_moved(comm, np.ones(256, np.float32), 5)
        return moved, type(out).__name__

    for moved, kind in world(2, fn):
        assert moved == [0, 0, 10, 0, 0, 0] and kind == "ndarray"


def test_counters_of_staged_sends_across_processes():
    """A pickled send counts once with its bytes, in the sender's
    process; a chunked pull counts once and every chunk's bytes as it
    is staged; the receiver, which owns a device, places what arrives
    from host memory (one move for the pickled payload)."""
    from ompi_tpu.testing import mpirun_run
    r = mpirun_run(2, os.path.join("tests", "_devp2p_counters_prog.py"),
                   mca=(("btl_tpu_chunk_bytes", "4096"),),
                   timeout=200, job_timeout=150)
    assert r.returncode == 0, r.stderr.decode()[-2000:]
    got = {}
    for line in r.stdout.decode().splitlines():
        if "devp2p-counters" in line:
            w = line[line.index("devp2p-counters"):].split()
            got[w[1], w[2]] = [int(x.split("=")[1]) for x in w[3:]]
    assert got["rank=0", "pickled"] == [0, 0, 0, 1, 1024, 0]
    assert got["rank=0", "chunked"] == [0, 0, 0, 1, 20000, 0]
    # the receiver staged nothing itself
    assert got["rank=1", "pickled"][:5] == [0] * 5
    assert got["rank=1", "chunked"][:5] == [0] * 5


def test_recv_moves_counts_a_payload_on_another_device():
    """A payload that arrives by reference on the SENDER's device (a
    send that did not place it) is placed by the receiver and counted."""
    def fn(comm):
        import jax
        import jax.numpy as jnp
        from ompi_tpu.btl.tpu import DeviceArrayPayload
        comm.Barrier()
        before = btl_counters()
        comm.Barrier()
        out = None
        if comm.rank == 0:
            x = jax.device_put(jnp.arange(8.0), comm.state.device)
            comm.state.pml.isend_obj(DeviceArrayPayload(x), 1, 9, comm)
        else:
            out = comm.recv_arr(0, tag=9)
            assert out.device == comm.state.device
            assert float(out[3]) == 3.0
        comm.Barrier()
        return [a - b for a, b in zip(btl_counters(), before)]

    assert world(2, fn, devices=True) == [[0, 0, 0, 0, 0, 1]] * 2


def p2p_closure(comm, n_ops, between=None):
    """(wall ns, layer deltas) of a loop of ring exchanges, each
    completed, with ``between()`` after each when given."""
    import time

    import jax
    import jax.numpy as jnp
    tr = comm.state.tracer
    nxt, prv = (comm.rank + 1) % comm.size, (comm.rank - 1) % comm.size
    xs = [jax.device_put(jnp.arange(1024, dtype=jnp.float32) + k,
                         comm.device) for k in (0, 1)]

    def step(k):
        jax.block_until_ready(comm.sendrecv_arr(xs[k & 1], nxt, prv, 3))
        if between is not None:
            jax.block_until_ready(between(comm, xs[k & 1]))

    for k in range(5):
        step(k)
    comm.Barrier()
    before = tr.layer_totals() if tr is not None else {}
    t0 = time.perf_counter_ns()
    for k in range(n_ops):
        step(k)
    wall = time.perf_counter_ns() - t0
    comm.Barrier()
    after = tr.layer_totals() if tr is not None else {}
    return wall, {k: after[k] - before[k] for k in after}


def test_p2p_layer_account_closes():
    """200 ring exchanges on 4 thread-ranks: p2p_send + p2p_match +
    p2p_deliver + caller is the loop's wall time within the tracer's
    own cost, and no collective's accumulator moves."""
    from ompi_tpu import trace
    res = world(4, lambda comm: p2p_closure(comm, 200), TRACE_ON,
                devices=True)
    for wall, d in res:
        mine = sum(d[k] for k in ("p2p_send", "p2p_match", "p2p_deliver",
                                  "caller"))
        assert abs(mine - wall) <= 0.03 * wall, (mine, wall, d)
        assert min(d["p2p_send"], d["p2p_match"], d["p2p_deliver"],
                   d["caller"]) > 0
        assert sum(d[k] for k in trace.LAYER_CLOSURE) == mine
        assert d["rendezvous"] == 0


@pytest.mark.parametrize("knobs", [{}, {"trace_enable": True,
                                        "trace_phase_enable": False}],
                         ids=["tracing_off", "phase_off"])
def test_p2p_accumulators_rest_without_the_phase_profiler(knobs):
    """No tracer, or a tracer without trace_phase_enable: no p2p
    accumulator (and no caller interval) moves."""
    from ompi_tpu.mca.params import registry

    def layer_pvars():
        return {p.full_name: p.read() for p in registry.all_pvars()
                if p.full_name.startswith("trace_layer_")}

    def fn(comm):
        comm.Barrier()
        before = layer_pvars()
        comm.Barrier()
        _wall, d = p2p_closure(comm, 20)
        comm.Barrier()
        return before == layer_pvars() and not any(d.values())

    assert all(world(4, fn, dict({"trace_enable": False}, **knobs),
                     devices=True))


def test_a_collective_between_two_messages_keeps_the_account_closed():
    """sendrecv_arr, allreduce_arr, sendrecv_arr, ...: the caller
    interval a message's return opens is closed by the collective's
    shim entry and the one the shim's return opens by the next
    message's entry, so the twelve accumulators still sum to the wall
    time, with one rendezvous an iteration."""
    from ompi_tpu import trace
    from ompi_tpu.op import op as mpi_op
    res = world(4, lambda comm: p2p_closure(
        comm, 100, lambda c, x: c.allreduce_arr(x, mpi_op.SUM)),
        dict(TRACE_ON, coll_pipeline_enable=False), devices=True)
    for wall, d in res:
        total = sum(d[k] for k in trace.LAYER_CLOSURE)
        assert abs(total - wall) <= 0.03 * wall, (total, wall, d)
        assert d["rendezvous"] == 100
        assert min(d["p2p_send"], d["p2p_match"], d["entry"], d["exit"],
                   d["caller"]) > 0


def test_p2p_spans_name_the_way_and_share_the_match_id():
    """Every send_arr / recv_arr records a p2p span named for the way
    that served it; the sender's and the receiver's span of one
    message carry the same cid:src:tag:seq and its bytes."""
    def fn(comm):
        import jax
        import jax.numpy as jnp
        x = jax.device_put(jnp.ones(512, jnp.float32), comm.device)
        nxt, prv = (comm.rank + 1) % comm.size, (comm.rank - 1) % comm.size
        for _ in range(3):
            comm.sendrecv_arr(x, nxt, prv, tag=12)
        comm.Barrier()
        return [(e["name"], e["args"]["mid"], e["args"]["bytes"])
                for e in comm.state.tracer.snapshot()
                if e["cat"] == "p2p" and "_arr_" in e["name"]]

    res = world(4, fn, TRACE_ON, devices=True)
    for rank, spans in enumerate(res):
        sends = [s for s in spans if s[0] == "send_arr_d2d"]
        recvs = [s for s in spans if s[0] == "recv_arr_inplace"]
        assert len(sends) == len(recvs) == 3 == len(spans) // 2
        assert all(b == 2048 for _n, _m, b in spans)
        # what I sent is what my right neighbour received, in order
        theirs = [m for n, m, _b in res[(rank + 1) % 4]
                  if n == "recv_arr_inplace"]
        assert [m for _n, m, _b in sends] == theirs
        assert all(m.split(":")[1:3] == [str(rank), "12"]
                   for _n, m, _b in sends)


def test_a_message_call_that_raises_closes_its_interval():
    """recv_arr that matches a byte-channel object raises TypeError
    after the match: the open interval is banked and the caller's
    opened all the same (without it ``caller`` would bank nothing in
    this loop, and the time after the raise would go to p2p_deliver)."""
    def fn(comm):
        tr = comm.state.tracer
        peer = 1 - comm.rank
        comm.Barrier()
        before = tr.layer_totals()
        for _ in range(50):
            comm.state.pml.isend_obj("no array", peer, 5, comm)
            with pytest.raises(TypeError, match="non-device message"):
                comm.recv_arr(peer, tag=5)
            assert tr._t_cur == 0 and tr._t_ret > 0
        tr.p2p_enter(0)         # a boundary, to bank the last caller
        after = tr.layer_totals()
        return {k: after[k] - before[k] for k in after}

    for d in world(2, fn, TRACE_ON, devices=True):
        assert min(d["p2p_match"], d["p2p_deliver"], d["caller"]) > 0
        assert d["p2p_send"] == 0
