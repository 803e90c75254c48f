"""The plain reference for a device-array point-to-point ring: what
every rank is owed after ``comm.sendrecv_arr(x, r + 1, r - 1, tag)``.

Nothing here imports ompi_tpu or takes anything the library made.  The
inputs are cellbench/reference.py's counter-based stream.  Every rank
holds TWO inputs, one per parity of the iteration, each its own stream
of the seed, the rank and the parity: iteration k sends input k mod 2,
so two messages in a row never carry the same bytes, and a message
delivered one iteration late, twice, or out of order is wrong in every
element.

ring: in iteration k rank r is owed elements [lo, hi) of the stream
of rank (r - 1) mod P and parity k mod 2, bit for bit (limit 0).
"""
from __future__ import annotations

import numpy as np

from cellbench import reference

PATTERNS = ("ring",)


def stream_key(seed: int, rank: int, parity: int) -> int:
    """32-bit key of (seed, rank, parity): reference.stream_key over
    2 * rank + parity, so no two inputs of a run share a stream."""
    return reference.stream_key(seed, 2 * int(rank) + (int(parity) & 1))


def source(pattern: str, ranks: int, rank: int) -> int:
    """The rank whose input ``rank`` receives."""
    if pattern not in PATTERNS:
        raise KeyError(f"no point-to-point reference for pattern "
                       f"{pattern!r}")
    return (rank - 1) % ranks


def destination(pattern: str, ranks: int, rank: int) -> int:
    """The rank that receives ``rank``'s input."""
    if pattern not in PATTERNS:
        raise KeyError(f"no point-to-point reference for pattern "
                       f"{pattern!r}")
    return (rank + 1) % ranks


def expected(pattern: str, seed: int, ranks: int, elems: int, rank: int,
             iteration: int, lo: int, hi: int) -> np.ndarray:
    """Elements [lo, hi) of what rank ``rank`` is owed in iteration
    ``iteration``: float32, compared exactly."""
    if not (0 <= rank < ranks and iteration >= 0
            and 0 <= lo <= hi <= elems):
        raise ValueError(f"rank {rank} of {ranks}, iteration {iteration}, "
                         f"[{lo}, {hi}) of {elems} elements")
    key = stream_key(seed, source(pattern, ranks, rank), iteration & 1)
    return reference.values_from_key(np.uint32(key), lo, hi)


def gap(got: np.ndarray, ref: np.ndarray) -> float:
    """The number compared: largest |got - ref|, which has to be 0."""
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - ref))) if got.size else 0.0
