"""Bytes a collective REQUIRES, whatever implements it, and the least
time a chip could take for them: the ONE table behind the one roofline
share (``collective_roofline``) that every collective cell reports.

The count is by operation, ranks, size and chip count alone, so a later
PR that changes the kernel or the schedule (fewer passes, another
algorithm, fewer hops, one program instead of segments, a strided read
instead of a gather, a native reduction instead of an emulated one) is
held to the same work, and a new cell of any pair below needs no new
rule.  P ranks, S bytes per rank; S need not be a multiple of anything
(a ragged 24,000,012 B is served as it is: padding is not required
work).  For a call that takes a datatype S is the PACKED stream, count
x the datatype's size: the bytes the operation is defined on, not the
buffer the datatype addresses, whose skipped elements nobody needs to
read (cellbench/traffic/blocking_typed.py hands it over so).

One chip (every rank's buffer in the one HBM, nothing crosses ICI):
  allreduce reads the P distinct inputs and writes one shared result:
  (P + 1) * S through HBM.
  reduce_scatter_block reads the P inputs and writes P result blocks
  of S / P: (P + 1) * S through HBM.
  alltoall with B bytes per pair (S = P * B per rank) reads and writes
  every block once: 2 * P * S = 2 * P * P * B through HBM.
Across chips (one rank per chip):
  allreduce sends 2 * (P - 1) / P * S per chip over ICI
  (reduce-scatter + allgather, the bandwidth-optimal schedule), and per
  chip reads S and writes S through HBM at the least.
  bcast: every non-root chip receives S and the root sends every byte
  at least once: S over ICI per chip, and S written through the
  receiver's HBM.
  alltoall with B bytes per pair (S = P * B): each chip sends
  (P - 1) * B over ICI, and reads and writes its P blocks through HBM:
  2 * S.
The larger of the two times bounds an operation; across chips ICI does.
Arithmetic copied from benchmarks/device_sweep.py
``_min_traffic_factor`` (listed in PERF.md for a later PR to delete
there), with the ICI terms added.

No rule: a typed collective or a reduce_scatter_block across chips (no
generator of this benchmark produces one; the rule it gets is added
HERE, as a row), and point-to-point (``sendrecv``): a chip-to-chip
``jax.device_put`` is no program, so its device time has no source to
divide a rule by (PERF.md section 7).

The ICI peak is the published per-chip aggregate (1,600 Gbit/s =
200 GB/s).  On a 2x2 host a chip has fewer links in use than that
aggregate counts, so a four-chip share reads low by construction and
can never pass 100%.
"""
from __future__ import annotations

def _fold_on_one_chip(p: int, s: int) -> dict:
    """P inputs read, S of results written, whatever their split."""
    return {"hbm": (p + 1) * s, "ici": 0}


# (operation, across chips?) -> (P, S) -> required bytes
RULES = {
    ("allreduce", False): _fold_on_one_chip,
    ("reduce_scatter_block", False): _fold_on_one_chip,
    ("alltoall", False): lambda p, s: {"hbm": 2 * p * s, "ici": 0},
    ("allreduce", True):
        lambda p, s: {"hbm": 2 * s, "ici": 2 * (p - 1) * s // p},
    ("bcast", True): lambda p, s: {"hbm": s, "ici": s},
    ("alltoall", True):
        lambda p, s: {"hbm": 2 * s, "ici": (p - 1) * (s // p)},
}


def required(op: str, ranks: int, bytes_per_rank: int, chips: int) -> dict:
    """{"hbm": bytes through one chip's HBM, "ici": bytes one chip
    sends over ICI} for one operation; KeyError, naming the pair, where
    the table has no row for it."""
    rule = RULES.get((op, chips > 1))
    if rule is None:
        raise KeyError(f"no required-bytes rule for {op!r} on {chips} "
                       f"chip(s)")
    return rule(ranks, bytes_per_rank)


def least_seconds(op: str, ranks: int, bytes_per_rank: int, chips: int,
                  peaks: dict) -> tuple:
    """(seconds, which peak bounds it) for a device kind's row of
    peaks.json."""
    need = required(op, ranks, bytes_per_rank, chips)
    t = {"hbm": need["hbm"] / peaks["hbm_bytes_per_s"],
         "ici": need["ici"] / peaks["ici_bytes_per_s"]}
    bound = max(t, key=t.get)
    return t[bound], bound
