"""NOT part of the benchmark since PR 38: ``cellbench/bytes.py`` holds
the one table of required bytes, the two rules that lived here among
its rows, and no metric's reader imports this file.  It stays, cut to a
view of that table with the domain it had (bcast and alltoall across
chips, the chip count implied), only because
``tests/test_cellbench_move.py`` imports it and a ``benchmark`` PR may
edit no file outside ``cellbench/``: the first PR that may edit that
test deletes this file with it (PERF.md section 7).
"""
from __future__ import annotations

from cellbench import bytes as table

CHIPS = 4     # any count over one picks the table's across-chips rows


def required(op: str, ranks: int, bytes_per_rank: int) -> dict:
    if op not in ("bcast", "alltoall"):
        raise KeyError(f"no required-bytes rule for {op!r} across chips")
    return table.required(op, ranks, bytes_per_rank, CHIPS)


def least_seconds(op: str, ranks: int, bytes_per_rank: int,
                  peaks: dict) -> tuple:
    required(op, ranks, bytes_per_rank)
    return table.least_seconds(op, ranks, bytes_per_rank, CHIPS, peaks)
