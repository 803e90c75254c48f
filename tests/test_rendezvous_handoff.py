"""How a rendezvous publish reaches a waiter (coll/device.Rendezvous).

A waiter on the condvar is woken by ``notify_all`` alone; only a waiter
that may be parked in its idle selector is rung, after the meeting's
lock is dropped; a rank that sits in a meeting still serves what
targets it, sees abort flags and hits the stall limit.  Everything here
is asserted by counts and events, never by how long something took.
"""

import contextlib
import os
import select
import threading
import time

import numpy as np
import pytest

from ompi_tpu.coll import device
from ompi_tpu.coll.device import Rendezvous
from ompi_tpu.mca.params import registry
from ompi_tpu.op import op as mpi_op
from ompi_tpu.runtime.progress import Progress
from ompi_tpu.testing import run_ranks

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

SIZES = [2, 4, 8]
JOIN = 60.0


def _pvar(name):
    return next(p for p in registry.all_pvars() if p.full_name == name)


def _counters():
    return (_pvar("coll_device_rdv_doorbells").read(),
            _pvar("coll_device_rdv_parks").read())


def _one_chip(rank):
    return jax.devices()[0]


def _idle_fd_readable(progress):
    # poll, not select.select: a world leaves its ranks' idle fds open,
    # so late in a long worker process these number above FD_SETSIZE
    poller = select.poll()
    for fd in progress._idle_sel.get_map():
        poller.register(fd, select.POLLIN)
    return bool(poller.poll(0))


@contextlib.contextmanager
def _knobs(**kv):
    """Set rendezvous knobs for a test and put them back."""
    old = {k: registry.get(k) for k in kv}
    for k, v in kv.items():
        registry.set(k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            registry.set(k, v)


def _foreign_fd(progress):
    """Give ``progress`` an idle fd that nothing in this process writes:
    what btl/shm's doorbell FIFO or a tcp socket is to a process rank."""
    r, w = os.pipe()
    progress.register_idle_fd(r)
    return r, w


def _close_foreign(progress, fds):
    progress.unregister_idle_fd(fds[0])
    for fd in fds:
        os.close(fd)


WAKES = ["idle_fds", "no_idle_fds"]


def _progress(wake):
    """An engine for each branch of ``_wait_for``: one that parks in its
    idle selector (self-pipe and a foreign fd), one with nothing to
    select on, which sweeps and goes back to the condvar."""
    p = Progress()
    if wake == "no_idle_fds":
        return p, None
    p.enable_thread_wakeup()
    return p, _foreign_fd(p)


def _join_all(threads):
    for t in threads:
        t.join(JOIN)
    assert not any(t.is_alive() for t in threads), "a member never returned"


# ---------------------------------------------------------------------------
# (1) every waiter on the condvar: nobody parks, nobody is rung
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", SIZES)
def test_waiters_on_the_condvar_are_rung_by_nobody(n):
    gate = threading.Barrier(n)
    counts = {}

    def fn(comm):
        pr = comm.state.progress
        x = jax.device_put(jnp.full((16,), comm.rank + 1.0, jnp.float32),
                           comm.device)
        comm.allreduce_arr(x, mpi_op.SUM)   # compile, and init's leftovers
        rv = device._get_rendezvous(comm)
        gate.wait(JOIN)
        if comm.rank == 0:
            # a look never runs out, however loaded the box: every
            # waiter of the block below stays on the condvar
            cv_wait = rv.cv.wait
            rv.cv.wait = lambda timeout=None: cv_wait(JOIN)
        pr.doorbell.clear()
        for drain in list(pr._idle_drains.values()):
            drain()
        gate.wait(JOIN)
        if comm.rank == 0:
            counts["before"] = _counters()
        gate.wait(JOIN)
        try:
            for _ in range(200):
                r = comm.allreduce_arr(x, mpi_op.SUM)
            gate.wait(JOIN)
        finally:
            if comm.rank == 0:
                del rv.cv.wait
        if comm.rank == 0:
            counts["after"] = _counters()
        return float(np.asarray(r)[0]), _idle_fd_readable(pr)

    res = run_ranks(n, fn, device_map=_one_chip)
    # right answers, and no unread byte in any self-pipe
    assert res == [(n * (n + 1) / 2.0, False)] * n
    assert counts["after"] == counts["before"]    # doorbells, parks


# ---------------------------------------------------------------------------
# (2) waiters that parked are rung once each, after the lock
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", SIZES)
def test_parked_waiters_are_rung_once_after_the_lock(n):
    gate = threading.Barrier(n)
    parked = [threading.Event() for _ in range(n)]
    rings = []                  # (rank rung, publisher held the lock)
    recording = threading.Event()
    counts = {}

    def fn(comm):
        pr = comm.state.progress
        rank = comm.rank
        x = jax.device_put(jnp.full((16,), rank + 1.0, jnp.float32),
                           comm.device)
        comm.allreduce_arr(x, mpi_op.SUM)   # compile
        rv = device._get_rendezvous(comm)
        fds = _foreign_fd(pr)
        idle_wait, wakeup = pr.idle_wait, pr.wakeup

        def spy_idle_wait(timeout):
            parked[rank].set()
            return idle_wait(timeout)

        def spy_wakeup():
            if recording.is_set():
                rings.append((rank, rv.cv._is_owned()))
            return wakeup()

        pr.idle_wait, pr.wakeup = spy_idle_wait, spy_wakeup
        try:
            gate.wait(JOIN)
            if rank == n - 1:
                counts["start"] = _counters()
            gate.wait(JOIN)
            if rank == n - 1:
                # held back until every other member sits in its idle
                # selector (flagged under the lock before it got there)
                for ev in parked[:-1]:
                    assert ev.wait(JOIN)
                counts["before"] = _counters()
                recording.set()
            r = comm.allreduce_arr(x, mpi_op.SUM)
            gate.wait(JOIN)
            if rank == n - 1:
                recording.clear()
                counts["after"] = _counters()
            gate.wait(JOIN)
        finally:
            del pr.idle_wait, pr.wakeup
            _close_foreign(pr, fds)
        return float(np.asarray(r)[0])

    res = run_ranks(n, fn, device_map=_one_chip)
    assert res == [n * (n + 1) / 2.0] * n
    assert sorted(r for r, _ in rings) == list(range(n - 1)), rings
    assert not any(held for _, held in rings), rings
    assert counts["after"][0] - counts["before"][0] == n - 1
    # and each left the condvar once, however often it parked since
    assert counts["after"][1] - counts["start"][1] == n - 1


@pytest.mark.parametrize("n", SIZES)
def test_look_that_lost_the_lock_to_the_publisher_does_not_park(n):
    """The look-once-more: a timed-out wait takes the lock back only
    after the publisher has dropped it, so the publisher saw no flag.
    The waiter has to find its answer there and never reach the
    selector, where nobody would ring it."""
    gate = threading.Barrier(n)
    in_wait = [threading.Event() for _ in range(n)]
    published = threading.Event()
    late_parks = []
    counts = {}
    who = {}                    # thread -> rank

    def fn(comm):
        pr = comm.state.progress
        rank = comm.rank
        x = jax.device_put(jnp.full((16,), rank + 1.0, jnp.float32),
                           comm.device)
        comm.allreduce_arr(x, mpi_op.SUM)   # compile
        rv = device._get_rendezvous(comm)
        fds = _foreign_fd(pr)
        idle_wait = pr.idle_wait
        who[threading.get_ident()] = rank

        def spy_idle_wait(timeout):
            if published.is_set():
                late_parks.append(rank)
            return idle_wait(timeout)

        pr.idle_wait = spy_idle_wait
        gate.wait(JOIN)
        if rank == 0:
            notify_all = rv.cv.notify_all

            def spy_notify_all():
                notify_all()
                published.set()

            def lost_wait(timeout=None):
                # a 2 ms look that times out and then queues for the
                # lock behind the publisher
                in_wait[who[threading.get_ident()]].set()
                rv.cv.release()
                try:
                    assert published.wait(JOIN)
                finally:
                    rv.cv.acquire()
                return False

            rv.cv.notify_all, rv.cv.wait = spy_notify_all, lost_wait
        gate.wait(JOIN)
        try:
            if rank == n - 1:
                for ev in in_wait[:-1]:
                    assert ev.wait(JOIN)
                counts["before"] = _counters()
            r = comm.allreduce_arr(x, mpi_op.SUM)
            gate.wait(JOIN)
            if rank == n - 1:
                counts["after"] = _counters()
            gate.wait(JOIN)
        finally:
            if rank == 0:
                del rv.cv.notify_all, rv.cv.wait
            del pr.idle_wait
            _close_foreign(pr, fds)
        return float(np.asarray(r)[0])

    res = run_ranks(n, fn, device_map=_one_chip)
    assert res == [n * (n + 1) / 2.0] * n
    assert late_parks == []
    assert counts["after"] == counts["before"]   # nobody flagged, none rung


# ---------------------------------------------------------------------------
# (3) a rank that sits in a meeting serves what targets it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", SIZES)
def test_rank_in_a_meeting_serves_passive_target_rma(n):
    from ompi_tpu import osc

    gate = threading.Barrier(n)
    log = []                    # rank 0's looks and sweeps, and its rings

    def fn(comm):
        pr = comm.state.progress
        rank = comm.rank
        mem = np.zeros(1, dtype=np.int64)
        win = osc.create(comm, mem)
        x = jax.device_put(jnp.full((16,), rank + 1.0, jnp.float32),
                           comm.device)
        comm.allreduce_arr(x, mpi_op.SUM)   # compile
        rv = device._get_rendezvous(comm)
        gate.wait(JOIN)
        old = np.full(1, -1, dtype=np.int64)
        if rank == 0:
            me = threading.get_ident()
            cv_wait, sweep, wakeup = rv.cv.wait, pr.progress, pr.wakeup

            def spy_wait(timeout=None):
                got = cv_wait(timeout)
                if not got and threading.get_ident() == me:
                    log.append("look")
                return got

            def spy_sweep():
                log.append("sweep")
                return sweep()

            def spy_wakeup():
                log.append("ring")
                return wakeup()

            rv.cv.wait, pr.progress, pr.wakeup = \
                spy_wait, spy_sweep, spy_wakeup
        gate.wait(JOIN)
        try:
            if rank == n - 1:
                # not in the meeting yet: wait until everyone else is,
                # then aim an atomic at rank 0, which sits in it.  It
                # completes only if rank 0 sweeps while it waits
                deadline = time.monotonic() + JOIN
                while rv.snapshot()["count"] < n - 1:
                    assert time.monotonic() < deadline
                    time.sleep(0.001)
                log.append("aimed")
                win.fetch_and_op(5, old, 0, op=mpi_op.SUM)
                log.append("served")
            r = comm.allreduce_arr(x, mpi_op.SUM)
        finally:
            if rank == 0:
                del rv.cv.wait, pr.progress, pr.wakeup
        gate.wait(JOIN)
        out = (float(np.asarray(r)[0]), int(mem[0]), int(old[0]))
        win.free()
        return out

    with _knobs(coll_device_rendezvous_timeout=JOIN):
        res = run_ranks(n, fn, device_map=_one_chip)
    assert [r[0] for r in res] == [n * (n + 1) / 2.0] * n
    assert res[0][1] == 5 and res[n - 1][2] == 0
    # the old bound, in looks: rank 0 sweeps as soon as its selector is
    # rung, or at its next 2 ms look if it was still on the condvar (one
    # more if the look raced the ring), never later
    a, s = log.index("aimed"), log.index("served")
    assert "ring" in log[a:s] and "sweep" in log[a:s], log[a:s]
    looks = 0
    for ev in log[a + 1 + log[a:s].index("ring"):s]:
        if ev == "look":
            looks += 1
            assert looks <= 2, log[a:s]
        elif ev == "sweep":
            looks = 0


# ---------------------------------------------------------------------------
# (4) abort flags and the stall limit are looked at while waiting
# ---------------------------------------------------------------------------

def _waiters(n, wake, abort_check=None):
    """n - 1 members wait for one that never comes; returns what each
    raised."""
    rv = Rendezvous(n)
    raised = [None] * (n - 1)
    made = [_progress(wake) for _ in range(n - 1)]

    def member(rank):
        try:
            rv.run(rank, rank, lambda slots: slots, abort_check,
                   progress=made[rank][0])
        except BaseException as e:  # noqa: BLE001 — the test's subject
            raised[rank] = e

    threads = [threading.Thread(target=member, args=(r,), daemon=True)
               for r in range(n - 1)]
    for t in threads:
        t.start()
    return rv, threads, raised, made


@pytest.mark.parametrize("wake", WAKES)
@pytest.mark.parametrize("n", SIZES)
def test_abort_check_surfaces_on_every_waiter(n, wake):
    flag = threading.Event()

    def abort_check():
        if flag.is_set():
            raise RuntimeError("peer aborted (test)")

    with _knobs(coll_device_rendezvous_timeout=JOIN):
        rv, threads, raised, made = _waiters(n, wake, abort_check)
        deadline = time.monotonic() + JOIN
        while rv.snapshot()["count"] < n - 1:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        flag.set()              # no doorbell, no notify: only a look finds it
        _join_all(threads)
    for p, fds in made:
        if fds:
            _close_foreign(p, fds)
    assert [str(e) for e in raised] == ["peer aborted (test)"] * (n - 1)


@pytest.mark.parametrize("wake", WAKES)
@pytest.mark.parametrize("n", SIZES)
def test_stall_limit_raises_on_every_waiter(n, wake):
    with _knobs(coll_device_rendezvous_poll=0.05,
                coll_device_rendezvous_timeout=0.3):
        rv, threads, raised, made = _waiters(n, wake)
        _join_all(threads)
    for p, fds in made:
        if fds:
            _close_foreign(p, fds)
    assert all(isinstance(e, RuntimeError) and "stalled" in str(e)
               for e in raised), raised


# ---------------------------------------------------------------------------
# (5) an error in the publisher's computation reaches every member
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wake", WAKES)
@pytest.mark.parametrize("n", SIZES)
def test_publishers_error_reaches_every_member(n, wake):
    rv = Rendezvous(n)
    raised = [None] * n
    made = [_progress(wake) for _ in range(n)]

    def boom(slots):
        raise ValueError("kernel refused (test)")

    def member(rank):
        try:
            rv.run(rank, rank, boom, None, progress=made[rank][0])
        except RuntimeError as e:
            raised[rank] = e

    threads = [threading.Thread(target=member, args=(r,), daemon=True)
               for r in range(n)]
    for t in threads:
        t.start()
    _join_all(threads)
    for p, fds in made:
        if fds:
            _close_foreign(p, fds)
    assert all(e is not None and "failed on a peer" in str(e)
               and isinstance(e.__cause__, ValueError) for e in raised), raised
    # and the meeting point is whole again: the next generation runs
    assert rv.snapshot()["count"] == 0 and not rv.snapshot()["pending_gens"]
