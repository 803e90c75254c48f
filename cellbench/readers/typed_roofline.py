"""Reader: a typed reduction's share of its roofline on one chip.  The
least time the chip could take for the operation's REQUIRED bytes
(cellbench/bytes_typed.py: by operation, ranks and packed size,
whatever implements it) against cellbench/peaks.json, over the device
time per iteration of the programs the cell's ``kernel_events`` name
(cellbench/tracered.py's ``kernel_s_per_iter``: the typed program, pack
included, since the pack is inside it).

Never clipped: a share over 100% is a wrong count.  Where the trace
shows no device plane, or no program matched (a library that has no
typed program, or names it otherwise), the reader returns nothing; it
never returns 0.
"""
from cellbench import bytes_typed


def read(spec: dict, facts: dict, say):
    tr = facts.get("trace") or {}
    if not tr or facts["platform"] != "tpu" \
            or not tr["kernel_events_matched"]:
        return None
    least, bound = bytes_typed.least_seconds(
        facts["op"], facts["ranks"], facts["bytes_per_rank"],
        facts["chips"], facts["peaks"])
    need = bytes_typed.required(facts["op"], facts["ranks"],
                                facts["bytes_per_rank"], facts["chips"])
    say(f"typed roofline: least {least * 1e6:.3f} us, bound by {bound} "
        f"({need} bytes)")
    return 100.0 * least / tr["kernel_s_per_iter"]
