"""Reader: a data-movement collective's share of its roofline across
chips.  The least time a chip could take for the operation's REQUIRED
bytes (cellbench/bytes_mesh.py, by operation, ranks and size, whatever
implements it) against cellbench/peaks.json, over the device time per
iteration of the programs the cell's ``kernel_events`` name, on the
fullest device (cellbench/tracered.py's ``kernel_s_per_iter``).

Never clipped: a share over 100% is a wrong count.  Where the trace
shows no device plane, or no program matched (a program that does not
name its exchange programs so), the reader returns nothing; it never
returns 0.
"""
from cellbench import bytes_mesh


def read(spec: dict, facts: dict, say):
    tr = facts.get("trace") or {}
    if not tr or facts["platform"] != "tpu" \
            or not tr["kernel_events_matched"]:
        return None
    least, bound = bytes_mesh.least_seconds(
        facts["op"], facts["ranks"], facts["bytes_per_rank"],
        facts["peaks"])
    need = bytes_mesh.required(facts["op"], facts["ranks"],
                               facts["bytes_per_rank"])
    say(f"mesh roofline: least {least * 1e6:.3f} us, bound by {bound} "
        f"({need} bytes)")
    return 100.0 * least / tr["kernel_s_per_iter"]
