"""Bytes an operation REQUIRES, whatever implements it, and the least
time a chip could take for them.

The count is by operation, ranks and size alone, so a later PR that
changes the kernel (fewer passes, another algorithm) is held to the
same work.  Arithmetic copied from benchmarks/device_sweep.py
``_min_traffic_factor`` (listed in PERF.md for a later PR to delete
there), with the four-chip ICI term added.

One chip (every rank's buffer in the one HBM):
  allreduce of S bytes by P ranks reads the P distinct inputs and
  writes one shared output: (P + 1) * S through HBM.
  alltoall with B bytes per pair reads and writes every block once:
  2 * P * P * B through HBM.
Across chips (one rank per chip):
  allreduce of S bytes per rank sends 2 * (P - 1) / P * S per chip
  over ICI (reduce-scatter + allgather, the bandwidth-optimal
  schedule), and per chip reads S and writes S through HBM at the
  least.  The larger of the two times bounds it; ICI does.

The ICI peak is the published per-chip aggregate (1,600 Gbit/s =
200 GB/s).  On a 2x2 host a chip has fewer links in use than that
aggregate counts, so a four-chip share reads low by construction and
can never pass 100%.
"""
from __future__ import annotations


def required(op: str, ranks: int, bytes_per_rank: int, chips: int) -> dict:
    """{"hbm": bytes through one chip's HBM, "ici": bytes one chip
    sends over ICI} for one operation."""
    p, s = ranks, bytes_per_rank
    if chips == 1:
        if op == "allreduce":
            return {"hbm": (p + 1) * s, "ici": 0}
        if op == "alltoall":
            return {"hbm": 2 * p * s, "ici": 0}   # s = P * B per rank
    elif op == "allreduce":
        return {"hbm": 2 * s, "ici": 2 * (p - 1) * s // p}
    raise KeyError(f"no required-bytes rule for {op!r} on {chips} chip(s)")


def least_seconds(op: str, ranks: int, bytes_per_rank: int, chips: int,
                  peaks: dict) -> tuple:
    """(seconds, which peak bounds it) for a device kind's row of
    peaks.json."""
    need = required(op, ranks, bytes_per_rank, chips)
    t = {"hbm": need["hbm"] / peaks["hbm_bytes_per_s"],
         "ici": need["ici"] / peaks["ici_bytes_per_s"]}
    bound = max(t, key=t.get)
    return t[bound], bound
