"""Reader: exact counters of the library, summed.  The value is the
window's delta of the MPI_T performance variables the metric's file
names under ``pvars`` (every run reads all integer pvars before and
after the window, between host barriers), added up, times ``scale``,
over ``per``:

``rank_iteration``: iterations x ranks (a rank's share of an iteration),
``iteration``: iterations (what one rank does on behalf of all, such as
  the publisher of a rendezvous),
``{"pvar": name}``: another variable's delta over the window.

With ``subtract_from`` naming a number the run holds (``iter_us``) the
value is that number less the quotient, signed: what the counters do
not cover.

The ``trace_layer_*`` variables are the library's layer accumulators
(ompi_tpu/trace, ``LAYERS``): nanoseconds and counts banked on every
operation of every rank-thread while ``trace_phase_enable`` is on, and
published process-wide.  Where the program has none of the variables
(an older program), or the divisor did not move, the reader returns
nothing; it never returns 0 for "not there".
"""


def read(spec: dict, facts: dict, say):
    before, after = facts["pvars_before"], facts["pvars_after"]
    names = spec["pvars"]
    if not any(n in after for n in names):
        return None

    def delta(n):
        return after.get(n, 0) - before.get(n, 0)

    per = spec.get("per", "rank_iteration")
    if per == "rank_iteration":
        div = facts["iters"] * facts["ranks"]
    elif per == "iteration":
        div = facts["iters"]
    else:
        div = delta(per["pvar"])
    if not div > 0:
        return None
    total = sum(delta(n) for n in names)
    say(f"pvar_sum {spec['name']}: {total} over {div} ({per}) from "
        + str({n: delta(n) for n in names}))
    v = total * spec.get("scale", 1) / div
    if "subtract_from" in spec:
        v = facts[spec["subtract_from"]] - v
    return v
