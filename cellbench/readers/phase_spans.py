"""Reader: the library's phase spans (``--mca trace_enable 1 --mca
trace_phase_enable 1``), gathered from every rank-thread's tracer ring
for the window.

The value is the mean time per KEPT unit, in microseconds: the summed
duration of the metric's phases over the spans kept, divided by
(spans kept / ``spans_per_unit``).  Sampling backs off
(``trace_sample_auto``), so the ops run are never the divisor.  A kept
rendezvous records two ``ph_rdv_wait`` spans (before the deposit and
before the collect), hence ``spans_per_unit`` 2 there.  ``ph_execute``
fences with ``block_until_ready`` on kept ops, so these are shares of
a traced iteration, never an end-to-end number.
"""
import numpy as np


def read(spec: dict, facts: dict, say):
    want = [facts["phases"].index(p) for p in spec["phases"]]
    rows = facts["spans"].reshape(-1, 4)
    rows = rows[np.isin(rows[:, 0], want)]
    if not len(rows):
        return None
    units = len(rows) / spec.get("spans_per_unit", 1)
    say(f"phase_spans {spec['name']}: {len(rows)} kept spans of "
        f"{spec['phases']} over {facts['iters']} iterations x "
        f"{facts['ranks']} ranks")
    return float(rows[:, 2].sum()) / units / 1e3
