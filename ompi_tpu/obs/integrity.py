"""Silent-data-corruption plane for device collectives (DESIGN.md §25).

Every fault plane before this one models failure as something *loud*:
a dead rank trips ULFM, a dead host trips the liveness grace, a slow
host trips the §24 gray-failure scorer.  The accelerator failure mode
that actually kills large training runs is the opposite — a chip that
computes wrong answers while passing every heartbeat.  This module
closes that rung: an online, sampled, algebraic integrity check that
rides the existing collective dispatch instead of doubling it.

Detection model (per sampled op, knob ``integrity_sample``)::

    gate      each rank folds a cheap checksum ("digest") of its own
              contribution at deposit time — exact modular sum for
              int dtypes, float64 sum with a relative tolerance band
              for floats, exact extremum for MAX/MIN — and wraps its
              deposit in a ``_Checked`` carrier;
    verify    the executing rank (the rendezvous last-arriver, which
              already holds every rank's deposit AND the reduced
              output) cross-checks the fold of the per-rank claims
              against the digest of the reduced data.  The check is
              algebraic: digest(reduce(x_0..x_n)) == fold(digest(x_r))
              holds exactly for int SUM (mod 2^width), MAX and MIN,
              and within a reassociation band for float SUM;
    bisect    on mismatch, a bisection round re-digests every rank's
              deposited operand against the claim it made at the
              gate.  A divergent rank corrupted its operand *after*
              digesting it — that chip is convicted.  No divergence
              means the reduction itself went wrong: the executing
              chip is convicted;
    survive   the poisoned op is retried from the pristine sources
              (byte-identical result, never a failed job), the
              conviction flows to the §24 health plane as the ``sdc``
              signal (immediate quarantine, drain/park/migrate), and
              state older than the detection window restores from the
              §14 checkpoint ladder.

Sampling is comm-consistent without any extra communication: the
rendezvous runs ONE rank's closure, so either every rank wraps an op
or none may.  Each rank keeps an identical per-comm op countdown
(collective call sequences are identical across ranks by MPI
ordering), so the decision is deterministic and lockstep.  The
countdown is adaptive like trace sampling: it starts at 1-in-1 and
doubles toward the ``integrity_sample`` cap every
``integrity_sample_auto`` banked checks, so a fresh (or freshly
suspect) world is checked densely and a proven-clean one cheaply.

``sample`` and ``fold`` are hotpath_audit-enforced (tools/
hotpath_audit.py): the always-on per-op cost is one dict lookup and
integer countdown; the per-sampled-check cost is one NumPy reduction
per operand.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ompi_tpu.mca.params import registry

_enable_var = registry.register(
    "integrity", "", "enable", 0, int,
    help="Arm the SDC-detection plane for device collectives: sampled "
         "algebraic checksum cross-checks on the rendezvous path, "
         "bisection attribution, retry-from-source and health-plane "
         "conviction on mismatch")
_sample_var = registry.register(
    "integrity", "", "sample", 64, int,
    help="Steady-state check sampling period cap (1-in-N sampled "
         "collectives carry an integrity check; 1 = every op).  The "
         "live period starts at 1 and doubles toward this cap as "
         "clean checks bank up — the trace-sampler adaptation model")
_sample_auto_var = registry.register(
    "integrity", "", "sample_auto", 256, int,
    help="Banked clean checks per period doubling (adaptive sampler "
         "ramp rate); 0 pins the period at integrity_sample")
_rel_tol_var = registry.register(
    "integrity", "", "rel_tol", 1e-4, float,
    help="Relative tolerance band for float SUM digests (reassociated "
         "device reductions round differently from the float64 host "
         "fold; int/MAX/MIN digests are exact and ignore this)")

_pv_checks = registry.register_pvar(
    "integrity", "", "checks",
    help="Device-collective ops that carried a sampled integrity "
         "check (gate + verify both counted here once)")
_pv_mismatches = registry.register_pvar(
    "integrity", "", "mismatches",
    help="Integrity checks whose reduced-data digest disagreed with "
         "the fold of per-rank claims (each triggers bisection)")
_pv_convictions = registry.register_pvar(
    "integrity", "", "convictions",
    help="Chips convicted of silent data corruption by the bisection "
         "round (attributed to a specific rank/host)")
_pv_retries = registry.register_pvar(
    "integrity", "", "retry_ops",
    help="Poisoned collectives re-executed from pristine per-rank "
         "sources after a conviction (byte-identical recovery — "
         "never a failed job)")

#: module arm flag — a plain attribute so the coll hot path pays one
#: module-dict lookup (``_ig.on``) per op when the plane is off.
on = False

#: live sampler parameters, cached from the knobs at refresh() time so
#: the audited sample() never touches registry properties.
_cap = 64
_auto = 256
_rel_tol = 1e-4

#: fold codes — the digest algebra each spec selects.
F_INTSUM, F_FSUM, F_MAX, F_MIN = 1, 2, 3, 4

#: process-global conviction registry (the doctor's evidence) and the
#: hook list the DVM uses to feed the §24 health plane.
_conv_lock = threading.Lock()
convicted: List[Dict[str, Any]] = []
_hooks: List[Callable[[Dict[str, Any]], None]] = []

_UVIEW = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def refresh() -> None:
    """Re-read the knobs into the cached module globals.  Called from
    obs.attach (i.e. every mpi_init) and directly by tests/probes
    after twiddling integrity_* knobs mid-process."""
    global on, _cap, _auto, _rel_tol
    _cap = max(1, int(_sample_var.value or 1))
    _auto = max(0, int(_sample_auto_var.value or 0))
    _rel_tol = float(_rel_tol_var.value or 0.0)
    on = bool(_enable_var.value)


def set_armed(flag: bool) -> None:
    """Probe/benchmark toggle: arm or disarm without touching knobs
    (the trace_overhead integrity arm flips this per chunk)."""
    global on
    on = bool(flag)


def install_convict_hook(fn: Callable[[Dict[str, Any]], None]) -> None:
    """Register a conviction listener (the DVM wires the health
    plane's note_sdc through this).  Idempotent per function."""
    with _conv_lock:
        if fn not in _hooks:
            _hooks.append(fn)


def remove_convict_hook(fn: Callable[[Dict[str, Any]], None]) -> None:
    with _conv_lock:
        if fn in _hooks:
            _hooks.remove(fn)


def convicted_snapshot() -> List[Dict[str, Any]]:
    """Copy of the conviction registry (doctor capture / metrics)."""
    with _conv_lock:
        return [dict(r) for r in convicted]


def reset() -> None:
    """Test/probe helper: clear convictions and per-run sampler state
    is per-comm (dies with the world), so only the registry needs it."""
    with _conv_lock:
        del convicted[:]


# -- spec construction (what can be checked, and how) ------------------------

def spec(kind: str, opname: str, x: Any, root: int = 0):
    """Build the check spec for one collective, or None when the op
    is not algebraically checkable (exotic reduce op, non-numeric
    dtype).  The result depends only on (kind, opname, dtype), never
    on rank-local state, so every rank derives the same spec and the
    comm-consistency invariant holds.

    Spec tuple: ``(kind, foldcode, itemsize[, root[, typed]])``.
    """
    if not on:
        return None
    return spec_static(kind, opname, x, root)


def spec_static(kind: str, opname: str, x: Any, root: int = 0):
    """spec() without the arm-flag gate — for cached Plan objects that
    outlive arm/disarm; their executor re-gates on ``on`` per call."""
    try:
        dt = np.dtype(getattr(x, "dtype", None) or np.asarray(x).dtype)
    except TypeError:
        return None
    k = dt.kind
    # bool excluded: device reductions treat PRED SUM as OR, which the
    # modular-sum digest would flag as corruption.
    if k in "iu":
        base = F_INTSUM
    elif k == "f":
        base = F_FSUM
    else:
        return None
    if kind in ("allreduce", "redscat"):
        if opname == "MPI_SUM":
            return (kind, base, dt.itemsize)
        if opname == "MPI_MAX":
            return (kind, F_MAX, dt.itemsize)
        if opname == "MPI_MIN":
            return (kind, F_MIN, dt.itemsize)
        return None
    if kind in ("gather", "alltoall"):
        # conservation checks: the op moves data without combining it,
        # so total content (modular/float sum) is invariant.
        return (kind, base, dt.itemsize)
    if kind == "bcast":
        return (kind, base, dt.itemsize, int(root))
    return None


def spec_typed(kind: str, opname: str, typed):
    """spec() for a typed collective (a datatype argument): what a
    rank deposits is the buffer its datatype addresses, not the
    operand, so the spec carries the call's ``datatype/device.Typed``
    as a fifth element.  Digests then read the packed stream
    (``typed.operand``) and the outputs in the base type
    (``typed.answer``: MPI_DOUBLE may travel as uint64 bit patterns,
    whose integer extremum is not the doubles')."""
    ck = spec(kind, opname, np.empty(0, typed.dtype))
    return None if ck is None else ck[:3] + (0, typed)


# -- digests (the per-operand checksums) -------------------------------------

def fold(a, code):
    """Scalar fold of a prepared 1-D array: the hot reduction of the
    sampled check path (hotpath_audit-enforced — one NumPy reduction,
    no allocation beyond the scalar)."""
    if code == 1:
        return int(np.add.reduce(a, dtype=np.uint64))
    if code == 2:
        return float(np.add.reduce(a, dtype=np.float64))
    if code == 3:
        return a.max().item()
    return a.min().item()


def digest(x: Any, code: int):
    """Checksum one operand.  Int dtypes fold as a uint64 modular sum
    (exact mod 2^width at compare time); floats fold in float64."""
    a = np.asarray(x)
    if a.size == 0:
        return 0 if code != 2 else 0.0
    if code == F_INTSUM:
        u = _UVIEW.get(a.dtype.itemsize, np.uint64)
        try:
            a = a.view(u)
        except (ValueError, TypeError):
            a = np.ascontiguousarray(a).view(u)
        return fold(a.ravel(), 1)
    return fold(a.ravel(), code)


def _fold_claims(code: int, ds: List[Any]):
    """Combine per-rank claims with the same algebra the reduction
    used (python-int exact for modular sums)."""
    if code in (F_INTSUM, F_FSUM):
        t = 0
        for d in ds:
            t += d
        return t
    if code == F_MAX:
        return max(ds)
    return min(ds)


def _eq(code: int, a, b, itemsize: int, tol: float) -> bool:
    if code == F_INTSUM:
        m = (1 << (8 * itemsize)) - 1
        return (int(a) & m) == (int(b) & m)
    try:
        fa, fb = float(a), float(b)
    except (TypeError, ValueError):
        return a == b
    if fa != fa or fb != fb or fa in (float("inf"), float("-inf")) \
            or fb in (float("inf"), float("-inf")):
        # non-finite digests are unjudgeable (NaN-poisoned data is a
        # model problem, not chip corruption) — fail open.
        return True
    if code == F_FSUM and tol > 0.0:
        return abs(fa - fb) <= tol * max(abs(fa), abs(fb), 1.0)
    return fa == fb


# -- sampling (per-op hot path) ----------------------------------------------

def _new_state(comm):
    # countdown, live period, banked-clean-checks. Lives in the comm's
    # instance dict so looped worlds start fresh and sibling comms
    # sample independently (their op sequences differ).
    st = [0, 1, 0]
    comm.__dict__["_ig_state"] = st
    return st


def sample(comm):
    """Deterministic 1-in-N sampling decision for the next collective
    on ``comm`` (hotpath_audit-enforced: dict lookup + integer
    countdown).  Every rank advances an identical counter over an
    identical op sequence, so the decision is comm-consistent without
    communication — the invariant the last-arriver execution model
    requires."""
    st = comm.__dict__.get("_ig_state")
    if st is None:
        st = _new_state(comm)
    c = st[0]
    if c > 0:
        st[0] = c - 1
        return 0
    p = st[1]
    b = st[2] + 1
    st[2] = b
    if _auto > 0 and b >= _auto and p < _cap:
        p = p + p
        if p > _cap:
            p = _cap
        st[1] = p
        st[2] = 0
    st[0] = p - 1
    return 1


# -- the gate (wrap a sampled op) --------------------------------------------

class _Checked:
    """Per-rank deposit carrier for a sampled op: ``v`` is what enters
    the datapath (the device_sdc injector retargets this binding to a
    corrupted copy — the source stays pristine), ``src`` a pristine
    HOST copy for retry (donating plan programs may invalidate the
    original device buffers, so retry never reads them), ``d`` the
    digest claimed at the gate."""

    __slots__ = ("v", "src", "d", "rank")

    def __init__(self, v, src, d, rank):
        self.v = v
        self.src = src
        self.d = d
        self.rank = rank


def _digest_for(ck, value):
    if ck[0] == "fused":
        arrays = value[1]
        out = []
        for ent in ck[1]:
            out.append(digest(arrays[ent[2]], ent[1]))
        return tuple(out)
    if len(ck) > 4:
        value = ck[4].operand(value)
    return digest(value, ck[1])


def gate(comm, value, fn, ck):
    """Wrap (value, fn) for one sampled collective.  Returns the pair
    unchanged when this op is not sampled.  Called from the coll meet
    path only when a spec exists (ck is not None) and the plane is
    armed."""
    if not sample(comm):
        return value, fn
    _pv_checks.add(1)
    if ck[0] == "fused":
        src = (value[0], [np.array(a, copy=True) for a in value[1]])
    else:
        src = np.array(value, copy=True)
    c = _Checked(value, src, _digest_for(ck, src), comm.rank)

    def checked_fn(shards, _fn=fn, _ck=ck, _comm=comm):
        return _run_checked(_comm, _fn, _ck, shards)

    return c, checked_fn


# -- verify / bisect / convict / retry (executing-rank side) -----------------

def _run_checked(comm, fn, ck, shards):
    outs = fn([s.v for s in shards])
    try:
        ok = _verify(ck, shards, outs)
    except Exception:
        # A checker defect must never take down the datapath: the
        # plane's contract is "never a failed job" — fail open.
        return outs
    if ok:
        return outs
    _pv_mismatches.add(1)
    from ompi_tpu import obs as _obs
    _obs.record_event(_obs.EV_SDC_MISMATCH, getattr(comm, "cid", 0),
                      int(getattr(comm, "_dev_seq", 0)),
                      _obs.intern(ck[0]), rank=comm.rank)
    bad = _bisect(ck, shards)
    if bad < 0:
        # no rank's operand diverged from its gate claim: the
        # reduction itself was computed wrong — the executing chip
        # (this one) is the culprit.
        bad = comm.rank
    _convict(comm, bad, ck[0])
    outs = fn([s.src for s in shards])
    _pv_retries.add(1)
    _obs.record_event(_obs.EV_SDC_RETRY, getattr(comm, "cid", 0),
                      int(getattr(comm, "_dev_seq", 0)), bad,
                      rank=comm.rank)
    return outs


def _verify(ck, shards, outs) -> bool:
    kind = ck[0]
    if kind == "fused":
        out0 = outs[0]
        for ent in ck[1]:
            if not _verify_entry(ent, shards, out0):
                return False
        return True
    code, isz = ck[1], ck[2]
    claims = [s.d for s in shards]
    if len(ck) > 4:
        outs = [ck[4].answer(o) for o in outs]
    if kind == "allreduce":
        outd = digest(outs[0], code)
        return _eq(code, _fold_claims(code, claims), outd, isz, _rel_tol)
    if kind == "redscat":
        outd = _fold_claims(code, [digest(o, code) for o in outs])
        return _eq(code, _fold_claims(code, claims), outd, isz, _rel_tol)
    if kind == "gather":
        outd = digest(outs[0], code)
        return _eq(code, _fold_claims(code, claims), outd, isz, _rel_tol)
    if kind == "alltoall":
        outd = _fold_claims(code, [digest(o, code) for o in outs])
        return _eq(code, _fold_claims(code, claims), outd, isz, _rel_tol)
    if kind == "bcast":
        outd = digest(outs[0], code)
        # bcast moves bytes verbatim: digests of identical data are
        # identical, so the compare is exact even for floats.
        return _eq(code, claims[ck[3]], outd, isz, 0.0)
    return True


def _verify_entry(ent, shards, out0) -> bool:
    """One fused-batch entry: ``("g", code, ci, slots, isz)`` folds
    the per-rank claim at index ``ci`` against the output slots;
    ``("b", code, ci, root, isz)`` is an exact root-claim match (hbm
    bcast)."""
    ekind, code, ci = ent[0], ent[1], ent[2]
    if ekind == "g":
        claims = [s.d[ci] for s in shards]
        parts = [digest(out0[i], code) for i in ent[3]]
        return _eq(code, _fold_claims(code, claims),
                   _fold_claims(code, parts), ent[4], _rel_tol)
    if ekind == "b":
        root = ent[3]
        return _eq(code, shards[root].d[ci],
                   digest(out0[ci], code), ent[4], 0.0)
    return True


def _bisect(ck, shards) -> int:
    """Attribution round: re-digest every rank's deposited operand
    (the value that actually entered the datapath) against the claim
    it made at the gate.  A diverging rank corrupted its operand in
    the detection window — convict it.  Returns -1 when every operand
    still matches its claim (compute-side corruption)."""
    kind = ck[0]
    for r, s in enumerate(shards):
        d2 = _digest_for(ck, s.v)
        if kind == "fused":
            if d2 != s.d:
                return r
        elif not _eq(ck[1], d2, s.d, ck[2], 0.0):
            return r
    return -1


def _convict(comm, rank: int, kind: str) -> None:
    grank = rank
    host = 0
    try:
        grank = comm.group[rank]
        st = comm._peer_state(grank)
        host = int(getattr(getattr(st, "rte", None), "node_id", 0) or 0)
    except Exception:
        pass
    _pv_convictions.add(1)
    rec = {"rank": int(grank), "host": host,
           "cid": int(getattr(comm, "cid", 0)), "kind": kind}
    from ompi_tpu import obs as _obs
    _obs.record_event(_obs.EV_SDC_CONVICT, int(grank), host,
                      _obs.intern(kind), rank=comm.rank)
    with _conv_lock:
        convicted.append(rec)
        hooks = list(_hooks)
    for h in hooks:
        try:
            h(rec)
        except Exception:
            pass


# -- fault-injection support -------------------------------------------------

def flip_value(value):
    """Corrupt one operand the way a bad chip would: flip a high
    mantissa/magnitude bit of the middle element.  Understands the
    ``_Checked`` carrier (retargets ``.v``, leaving ``.src`` and the
    gate claim pristine — exactly the divergence _bisect attributes)
    and fused-batch deposits.  On an unwrapped value (op not sampled)
    the corruption is silent — the honest semantics of sampled
    detection."""
    if isinstance(value, _Checked):
        value.v = _flip_inner(value.v)
        return value
    return _flip_inner(value)


def _flip_inner(value):
    flipped = getattr(value, "flipped", None)
    if flipped is not None:
        # a deposit that is more than an array (coll/ragged.Deposit)
        return flipped(_flip_array)
    if isinstance(value, tuple) and len(value) == 2 \
            and isinstance(value[1], list) and value[1]:
        arrays = list(value[1])
        arrays[0] = _flip_array(arrays[0])
        return (value[0], arrays)
    return _flip_array(value)


def _flip_array(x):
    a = np.asarray(x)
    if a.size == 0:
        return x
    flat = np.ascontiguousarray(a).copy()
    bv = flat.view(np.uint8).reshape(-1)
    isz = max(1, a.dtype.itemsize)
    # last byte of the middle element: sign/exponent/high-magnitude
    # bits live there on little-endian, so SUM/MAX/MIN digests all see
    # the flip.
    mid = (a.size // 2) * isz + isz - 1
    bv[mid] ^= 0x40
    return flat.reshape(a.shape)
