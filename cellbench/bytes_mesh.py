"""Bytes a data-movement operation REQUIRES across chips (one rank per
chip), whatever implements it, and the least time a chip could take
for them.

The count is by operation, ranks and size alone, so a later PR that
changes the algorithm (fewer hops, one program instead of segments) is
held to the same work.  cellbench/bytes.py has the reductions and the
one-chip rules; this file has what moves data over ICI:

  bcast of S bytes: every non-root chip receives S and the root sends
  every byte at least once: S over ICI per chip, and S written through
  the receiver's HBM.
  alltoall with B bytes per pair on P chips (S = P * B per rank): each
  chip sends (P - 1) * B over ICI, and reads and writes its P blocks
  through HBM: 2 * P * B.

The peaks are cellbench/peaks.json's, and the warning is bytes.py's:
the ICI peak is the published per-chip aggregate (200 GB/s).  On a 2x2
host a chip drives fewer links than that aggregate counts, so a
four-chip share reads low by construction and can never pass 100%.
"""
from __future__ import annotations


def required(op: str, ranks: int, bytes_per_rank: int) -> dict:
    """{"hbm": bytes through one chip's HBM, "ici": bytes one chip
    sends or receives over ICI} for one operation."""
    p, s = ranks, bytes_per_rank
    if op == "bcast":
        return {"hbm": s, "ici": s}
    if op == "alltoall":
        return {"hbm": 2 * s, "ici": (p - 1) * (s // p)}
    raise KeyError(f"no required-bytes rule for {op!r} across chips")


def least_seconds(op: str, ranks: int, bytes_per_rank: int,
                  peaks: dict) -> tuple:
    """(seconds, which peak bounds it) for a device kind's row of
    peaks.json."""
    need = required(op, ranks, bytes_per_rank)
    t = {"hbm": need["hbm"] / peaks["hbm_bytes_per_s"],
         "ici": need["ici"] / peaks["ici_bytes_per_s"]}
    bound = max(t, key=t.get)
    return t[bound], bound
