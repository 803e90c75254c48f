"""Bytes a TYPED reduction REQUIRES on one chip, whatever implements
it, and the least time the chip could take for them.

The count is by operation, ranks and PACKED size alone, so a later PR
that changes the kernel (a strided read instead of a gather, a native
reduction instead of an emulated one, one pass instead of three) is
held to the same work.  cellbench/bytes.py has the contiguous float32
rules; this file has the collectives that take a datatype:

  S is the packed stream per rank: count x the datatype's size (the
  bytes the operation is defined on, not the buffer the datatype
  addresses, whose skipped elements nobody needs to read).  With every
  rank's buffer in the one HBM,
  reduce_scatter_block by P ranks reads the P packed streams and writes
  P result blocks of S / P: (P + 1) * S through HBM;
  allreduce reads the P packed streams and writes one shared result:
  (P + 1) * S through HBM.

Nothing crosses ICI on one chip.  Across chips there is no rule here
yet: no cell runs a typed collective on a mesh.
"""
from __future__ import annotations

OPS = ("reduce_scatter_block", "allreduce")


def required(op: str, ranks: int, packed_bytes_per_rank: int,
             chips: int) -> dict:
    """{"hbm": bytes through the chip's HBM, "ici": 0} for one
    operation."""
    if chips != 1 or op not in OPS:
        raise KeyError(f"no typed required-bytes rule for {op!r} on "
                       f"{chips} chip(s)")
    return {"hbm": (ranks + 1) * packed_bytes_per_rank, "ici": 0}


def least_seconds(op: str, ranks: int, packed_bytes_per_rank: int,
                  chips: int, peaks: dict) -> tuple:
    """(seconds, which peak bounds it) for a device kind's row of
    peaks.json."""
    need = required(op, ranks, packed_bytes_per_rank, chips)
    return need["hbm"] / peaks["hbm_bytes_per_s"], "hbm"
