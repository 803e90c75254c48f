"""NOT a reader of any metric since PR 38: ``typed_roofline`` is
``collective_roofline`` now (readers/profiler_trace.py over the one
table, cellbench/bytes.py).  The file stays only because
``tests/test_cellbench_typed.py`` imports it and a ``benchmark`` PR may
edit no file outside ``cellbench/``; the first PR that may edit that
test deletes it with ``cellbench/bytes_typed.py`` (PERF.md section 7).
The same share, the same way: never clipped, nothing where no program
matched, never 0.
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
