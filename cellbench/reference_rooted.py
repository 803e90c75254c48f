"""The plain reference for rooted data movement: what every rank is
owed after ``comm.<op>_arr(x, root)``.

Nothing here imports ompi_tpu or takes anything the library made.  The
inputs are cellbench/reference.py's counter-based stream: every rank's
input is its OWN stream of the seed, the root's included, so an answer
that is the rank's own input, or another non-root's, is wrong in every
element.

bcast: every rank, the root too, is owed elements [lo, hi) of the
ROOT's stream, bit for bit (limit 0, as the alltoall).
"""
from __future__ import annotations

import numpy as np

from cellbench import reference

OPS = ("bcast",)


def expected(op: str, seed: int, ranks: int, elems: int, rank: int,
             root: int, lo: int, hi: int) -> np.ndarray:
    """Elements [lo, hi) of the result rank ``rank`` is owed: float32,
    compared exactly."""
    if op not in OPS:
        raise KeyError(f"no rooted reference for operation {op!r}")
    if not (0 <= root < ranks and 0 <= rank < ranks
            and 0 <= lo <= hi <= elems):
        raise ValueError(f"rank {rank}, root {root} of {ranks} ranks, "
                         f"[{lo}, {hi}) of {elems} elements")
    return reference.values(seed, root, lo, hi)


def gap(got: np.ndarray, ref: np.ndarray) -> float:
    """The number compared: largest |got - ref|, which has to be 0."""
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - ref))) if got.size else 0.0
