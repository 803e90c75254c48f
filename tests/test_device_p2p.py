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
