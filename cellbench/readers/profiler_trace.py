"""Reader: the jax.profiler trace of the window, reduced by
cellbench/tracered.py.  ``field`` picks the number:

``kernel_us``: device time of the collective's programs per iteration
  on the fullest device (the cell's ``kernel_events`` patterns).
``collective_roofline``: the ONE share of a roofline, for every
  collective cell of one chip or four: the least time the chip could
  take for the operation's REQUIRED bytes (cellbench/bytes.py: one
  table by operation, ranks, size and chip count, whatever implements
  it; the packed stream where the call takes a datatype) against
  cellbench/peaks.json, over ``kernel_us``.  Never clipped: a share
  over 100% is a wrong count.  On a 2x2 host a chip drives fewer ICI
  links than the published aggregate counts, so a four-chip share reads
  low, never over 100%.  An operation the table has no row for is a
  KeyError, not a silence.  (``move_roofline`` and ``typed_roofline``
  of PRs 27 to 37 are this field.)
``device_idle_pct``: 1 - busy/window on the fullest device.

Where the trace shows no device plane, or no program matches, the
reader returns nothing; it never returns 0 for a share.
"""
from cellbench import bytes as required


def read(spec: dict, facts: dict, say):
    tr = facts.get("trace") or {}
    if not tr or facts["platform"] != "tpu":
        return None
    field = spec["field"]
    if field == "device_idle_pct":
        return 100.0 * (1.0 - tr["fullest_busy_s"] / tr["window_s"])
    if not tr["kernel_events_matched"]:
        say(f"trace: no program matched {facts['kernel_events']}")
        return None
    k = tr["kernel_s_per_iter"]
    if field == "kernel_us":
        return k * 1e6
    if field == "collective_roofline":
        call = (facts["op"], facts["ranks"], facts["bytes_per_rank"],
                facts["chips"])
        least, bound = required.least_seconds(*call, facts["peaks"])
        say(f"roofline: least {least * 1e6:.3f} us, bound by {bound} "
            f"({required.required(*call)} bytes)")
        return 100.0 * least / k
    raise KeyError(field)
