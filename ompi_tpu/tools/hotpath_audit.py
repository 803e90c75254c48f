"""hotpath_audit: AST lint holding the trace hot path to its budget.

The always-on tracing budget (DESIGN.md §9) is enforced structurally:
the functions that run once per message / per collective may not
allocate container objects, build strings, or read the wall clock.
Reviewing that by eye does not survive refactors, so tier-1 tests run
this audit and fail when a hot function regresses.

Banned inside a declared hot function:

  * tuple / list displays in Load context (allocation per call) —
    Store-context targets (``a, b = req.tr``) are unpacking, not
    allocation, and stay legal
  * dict / set displays and every comprehension flavor
  * f-strings and string concatenation via ``%`` / ``.format`` calls
  * calls to the ``dict`` / ``list`` / ``set`` / ``tuple`` /
    ``frozenset`` builtins
  * any reference to ``time.time`` (including sneaking it in via a
    default argument) — hot timestamps are ``perf_counter_ns`` only

Usage: ``python -m ompi_tpu.tools.hotpath_audit`` exits nonzero and
prints one line per violation; ``audit()`` returns them as a list for
the tier-1 test.
"""

from __future__ import annotations

import ast
import sys
from typing import Dict, List, Tuple

# (module path relative to the package root, {qualified function: ...})
# Qualified names are "Class.method" or bare "function".
HOT_FUNCTIONS: Dict[str, Tuple[str, ...]] = {
    "ompi_tpu/trace/__init__.py": (
        "Tracer.start",
        "Tracer.start_sampled",
        "Tracer.end",
        "Tracer.tick_ns",
        "Tracer.hist_add",
        # per-job request tag (DESIGN.md §23): brackets every run on
        # every resident rank when request tracing is on — two int
        # ring stores, the same cost class as hist_add
        "Tracer.req_mark",
        "coll_begin",
        "coll_end",
        # the operation categories' keep-or-skip decision and the
        # layer accumulators' boundaries (ISSUE 26): with
        # trace_phase_enable on they run on EVERY blocking device
        # collective of every rank, several times an operation (the
        # rendezvous' own boundaries are inline in device.Rendezvous)
        "Tracer.kept",
        "Tracer.keep",
        "Tracer.end_at",
        "Tracer.end_at2",
        "Tracer.lap",
        "Tracer.lap_to",
        # a device-array message's entry and return (ISSUE 34)
        "Tracer.p2p_enter",
        "Tracer.p2p_return",
    ),
    "ompi_tpu/pml/ob1.py": (
        "PmlOb1._trace_p2p_end",
    ),
    # phase-profiler record points (ISSUE 13 / DESIGN.md §18): they
    # run once per rendezvous wait / segment / dispatched op whenever
    # trace_phase_enable is on, so they obey the same no-allocation
    # rules as the tracer itself — the ph context tuple is built ONCE
    # per op at the gate, never inside these
    "ompi_tpu/coll/device.py": (
        "_phase_fn",
        # the publisher's steps of a traced meeting (ISSUE 26)
        "_mesh_exec",
        "_stacked_exec",
    ),
    # the compiled-plan executor (DESIGN.md §12) runs once per
    # large-message collective in steady state: span shell, the single
    # rendezvous, integer pvar adds.  Packing, key construction and
    # plan/executable resolution live in helpers off this path
    "ompi_tpu/coll/plan.py": (
        "Plan.execute",
    ),
    # the progress sweep runs on every blocking wait iteration; the
    # checkpoint drain tick rides every 8th sweep for the rest of the
    # job once one checkpoint has been taken — neither may allocate
    # on its idle path (ISSUE 8: the async drain hook must not tax
    # ranks that aren't checkpointing)
    "ompi_tpu/runtime/progress.py": (
        "Progress.progress",
    ),
    # the telemetry scrape tick rides the progress sweep's SAMPLED
    # tracer-timing reads whenever obs_scrape_interval_ms > 0 on a
    # traced rank (ISSUE 10): no clock read of its own, a round-robin
    # single-histogram integer copy only when the interval elapses —
    # and never an allocation either way
    "ompi_tpu/obs/__init__.py": (
        "Scraper.tick",
    ),
    "ompi_tpu/cr/ckpt.py": (
        "Engine.tick",
    ),
    # the fleet-controller decision tick rides the same sampled
    # progress sweeps as Scraper.tick on every resident pool
    # rank-thread (ISSUE 12): gate-first, integer decisions only —
    # resizes and event recording happen in apply(), off this path
    "ompi_tpu/serve/controller.py": (
        "FleetController.tick",
    ),
    # device-osc data-plane entries (ISSUE 14): the trace/pvar shells
    # around every one-sided op — a sampled span start, the impl call,
    # and integer pvar adds.  All argument building (bucket keys,
    # padded staging, kernel lookups) lives in the _impl tier below
    # these, off the audited path
    "ompi_tpu/osc/device.py": (
        "DeviceWindow.put",
        "DeviceWindow.get",
        "DeviceWindow._acc_entry",
    ),
    # the session-journal flush tick rides the DVM heartbeat loop
    # every period for the life of the pool (ISSUE 15): a dirty-flag
    # check that is allocation-free when no bookkeeping record is
    # pending — the common case, since attach/detach are rare
    "ompi_tpu/tools/dvm.py": (
        "_Journal.tick",
        # the host-liveness sweep (ISSUE 16) also rides the heartbeat
        # loop every period: pure integer compares over preallocated
        # per-host lists; the expensive lost-domain collection runs
        # off-path in _host_collect
        "DVMServer._host_tick",
        # the progress-stall watchdog scan (DESIGN.md §23) ticks at
        # obs_watchdog_ms/2 for the life of the pool when armed:
        # integer compares over the session table only — stack/fence
        # capture lives off-path in _watchdog_collect
        "DVMServer._watchdog_tick",
    ),
    # the gray-failure health scoring tick (DESIGN.md §24) rides the
    # same heartbeat loop as _host_tick whenever health_enable is on
    # for a multi-host pool: integer EWMA reads, threshold compares
    # and streak counters over preallocated per-host lists.  State
    # transitions only LATCH here (pending[h] = 1); the event
    # recording, quarantine drain and placement rebuild run off-path
    # in DVMServer._health_collect
    "ompi_tpu/obs/health.py": (
        "HealthPlane.tick",
    ),
    # the sdc-integrity plane (DESIGN.md §25) touches EVERY device
    # collective when armed: sample() is the 1-in-N countdown gate on
    # the meet path (integer decrement over a preallocated per-comm
    # list), fold() combines per-rank digests at verify time.  The
    # expensive halves — host copies, digesting, bisection, retry —
    # run only on the sampled 1-in-N ops inside gate()/_run_checked
    "ompi_tpu/obs/integrity.py": (
        "sample",
        "fold",
    ),
}

_BANNED_BUILTIN_CALLS = ("dict", "list", "set", "tuple", "frozenset")


class _HotVisitor(ast.NodeVisitor):
    def __init__(self, fname: str, func: str) -> None:
        self.fname = fname
        self.func = func
        self.violations: List[str] = []

    def _flag(self, node: ast.AST, what: str) -> None:
        self.violations.append(
            f"{self.fname}:{node.lineno}: {self.func}: {what}")

    # -- container allocations ------------------------------------------
    def visit_Tuple(self, node: ast.Tuple) -> None:
        if isinstance(node.ctx, ast.Load):
            self._flag(node, "tuple allocation")
        self.generic_visit(node)

    def visit_List(self, node: ast.List) -> None:
        if isinstance(node.ctx, ast.Load):
            self._flag(node, "list allocation")
        self.generic_visit(node)

    def visit_Dict(self, node: ast.Dict) -> None:
        self._flag(node, "dict allocation")
        self.generic_visit(node)

    def visit_Set(self, node: ast.Set) -> None:
        self._flag(node, "set allocation")
        self.generic_visit(node)

    def visit_ListComp(self, node: ast.ListComp) -> None:
        self._flag(node, "list comprehension")
        self.generic_visit(node)

    def visit_SetComp(self, node: ast.SetComp) -> None:
        self._flag(node, "set comprehension")
        self.generic_visit(node)

    def visit_DictComp(self, node: ast.DictComp) -> None:
        self._flag(node, "dict comprehension")
        self.generic_visit(node)

    def visit_GeneratorExp(self, node: ast.GeneratorExp) -> None:
        self._flag(node, "generator expression")
        self.generic_visit(node)

    # -- string building ------------------------------------------------
    def visit_JoinedStr(self, node: ast.JoinedStr) -> None:
        self._flag(node, "f-string")
        self.generic_visit(node)

    # -- calls ----------------------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id in _BANNED_BUILTIN_CALLS:
            self._flag(node, f"call to {fn.id}()")
        if isinstance(fn, ast.Attribute) and fn.attr == "format":
            self._flag(node, "str.format call")
        self.generic_visit(node)

    # -- wall clock ------------------------------------------------------
    def visit_Attribute(self, node: ast.Attribute) -> None:
        if (node.attr == "time"
                and isinstance(node.value, ast.Name)
                and node.value.id == "time"):
            self._flag(node, "time.time reference")
        self.generic_visit(node)


def _iter_functions(tree: ast.Module):
    """Yield (qualified_name, node) for module-level functions and
    class methods (one nesting level — the audit scope)."""
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node.name, node
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    yield f"{node.name}.{sub.name}", sub


def audit_source(src: str, funcnames: Tuple[str, ...],
                 fname: str = "<source>") -> List[str]:
    """Audit the given source text; returns violation strings and a
    line per declared hot function that was not found (a renamed hot
    function silently escaping the audit is itself a failure)."""
    tree = ast.parse(src, filename=fname)
    found = {}
    for qual, node in _iter_functions(tree):
        if qual in funcnames:
            found[qual] = node
    out: List[str] = []
    for qual in funcnames:
        node = found.get(qual)
        if node is None:
            out.append(f"{fname}: hot function {qual} not found "
                       f"(renamed? update HOT_FUNCTIONS)")
            continue
        v = _HotVisitor(fname, qual)
        # visit body + defaults (a mutable/allocating default is read
        # at def time, but a time.time default smuggles the banned
        # clock into the call path)
        v.visit(node)
        out.extend(v.violations)
    return out


def audit() -> List[str]:
    """Audit every declared hot function in the live source tree."""
    import ompi_tpu
    import os
    root = os.path.dirname(os.path.dirname(
        os.path.abspath(ompi_tpu.__file__)))
    out: List[str] = []
    for rel, funcs in HOT_FUNCTIONS.items():
        path = os.path.join(root, rel)
        with open(path) as fh:
            src = fh.read()
        out.extend(audit_source(src, funcs, fname=rel))
    return out


def main(argv=None) -> int:
    violations = audit()
    for v in violations:
        sys.stdout.write(v + "\n")
    if violations:
        sys.stdout.write(f"hotpath_audit: {len(violations)} "
                         f"violation(s)\n")
        return 1
    n = sum(len(f) for f in HOT_FUNCTIONS.values())
    sys.stdout.write(f"hotpath_audit: {n} hot functions clean\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
