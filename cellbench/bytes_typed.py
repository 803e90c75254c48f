"""NOT part of the benchmark since PR 38: ``cellbench/bytes.py`` holds
the one table of required bytes, the two rules that lived here among
its rows (S is the packed stream, as that file says), and no metric's
reader imports this file.  It stays, cut to a view of that table with
the domain it had (reduce_scatter_block and allreduce on one chip),
only because ``tests/test_cellbench_typed.py`` imports it and a
``benchmark`` PR may edit no file outside ``cellbench/``: the first PR
that may edit that test deletes this file with it (PERF.md section 7).
"""
from __future__ import annotations

from cellbench import bytes as table

OPS = ("reduce_scatter_block", "allreduce")


def required(op: str, ranks: int, packed_bytes_per_rank: int,
             chips: int) -> dict:
    if chips != 1 or op not in OPS:
        raise KeyError(f"no typed required-bytes rule for {op!r} on "
                       f"{chips} chip(s)")
    return table.required(op, ranks, packed_bytes_per_rank, chips)


def least_seconds(op: str, ranks: int, packed_bytes_per_rank: int,
                  chips: int, peaks: dict) -> tuple:
    required(op, ranks, packed_bytes_per_rank, chips)
    return table.least_seconds(op, ranks, packed_bytes_per_rank, chips,
                               peaks)
