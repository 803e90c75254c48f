"""The plain reference: inputs from the seed, the answer each rank is
owed, and the comparison that decides ``correct``.

Nothing here imports ompi_tpu or takes anything the library made.  The
inputs are a counter-based stream: element ``i`` of rank ``r`` under
seed ``s`` is a pure function of (s, r, i), so the device can make a
rank's whole input in one jitted call (``values_from_key(..., xp=jax.numpy)``)
and the host can regenerate any block of any rank's input afterwards
(``xp=numpy``) without holding 2 GiB.  Every step is exact integer
arithmetic mod 2**32 followed by an exact conversion to float32, so
both sides produce the same bits.

Values are multiples of 2**-24 uniform in [-0.5, 0.5): the whole
float32 significand is in use, so unlike examples/device_smoke.py's
small integers a SUM of them rounds in float32 and is far from exact in
bfloat16: a lower-precision computation shows in the gap.
"""
from __future__ import annotations

import numpy as np

_M = 0xFFFFFFFF


def stream_key(seed: int, rank: int) -> int:
    """32-bit key of (seed, rank); seed may exceed 2**31."""
    x = (int(seed) * 0x9E3779B1 + int(rank) * 0x85EBCA77 + 0x165667B1) & _M
    x = ((x ^ (x >> 15)) * 0x2C1B3C6D) & _M
    x = ((x ^ (x >> 12)) * 0x297A2D39) & _M
    return x ^ (x >> 15)


def values_from_key(key, lo: int, hi: int, xp=np):
    """float32 elements [lo, hi) of the stream with 32-bit ``key``.
    ``xp`` is numpy (the reference) or jax.numpy (the generator, on the
    device; there ``key`` is a traced uint32, so one compiled program
    serves every seed and rank)."""
    u = xp.uint32
    x = xp.arange(lo, hi, dtype=u) ^ key
    # lowbias32 finalizer: every output bit depends on every input bit
    x = (x ^ (x >> u(16))) * u(0x7FEB352D)
    x = (x ^ (x >> u(15))) * u(0x846CA68B)
    x = x ^ (x >> u(16))
    # 24 random bits, centred: an exact int -> float32 conversion and an
    # exact scaling by a power of two, so host and device agree bit for
    # bit and every value uses the whole float32 significand
    i = (x >> u(8)).astype(xp.int32) - xp.int32(1 << 23)
    return i.astype(xp.float32) * xp.float32(2.0 ** -24)


def values(seed: int, rank: int, lo: int, hi: int) -> np.ndarray:
    """float32 elements [lo, hi) of rank ``rank``'s input, on the host."""
    return values_from_key(np.uint32(stream_key(seed, rank)), lo, hi)


def expected(op: str, seed: int, ranks: int, elems: int, rank: int,
             lo: int, hi: int) -> np.ndarray:
    """Elements [lo, hi) of the result rank ``rank`` is owed, float64
    for a reduction (so the reference's own rounding is not in the
    gap) and float32 for pure data movement (compared exactly).

    allreduce: every rank is owed the SUM over ranks of the inputs.
    alltoall: rank r's input is ``ranks`` blocks of m = elems // ranks;
    its output block s is rank s's block r."""
    if op == "allreduce":
        acc = np.zeros(hi - lo, np.float64)
        for s in range(ranks):
            acc += values(seed, s, lo, hi)
        return acc
    if op == "alltoall":
        m = elems // ranks
        out = np.empty(hi - lo, np.float32)
        j = lo
        while j < hi:
            s, off = divmod(j, m)
            take = min(hi - j, m - off)
            out[j - lo:j - lo + take] = values(
                seed, s, rank * m + off, rank * m + off + take)
            j += take
        return out
    raise KeyError(f"no reference for operation {op!r}")


def exact(op: str) -> bool:
    """Pure data movement is compared bit for bit (limit 0)."""
    return op == "alltoall"


def block_starts(seed: int, elems: int, block: int, blocks: int):
    """Which blocks of an answer are compared: all of it when it is no
    larger than blocks*block, else the first block, the last block and
    ``blocks - 2`` more drawn from the seed."""
    if elems <= block * blocks:
        return np.array([0], np.int64), elems
    rng = np.random.default_rng([int(seed) & _M, elems])
    mid = rng.integers(0, elems - block, size=blocks - 2)
    return np.concatenate([[0], np.sort(mid), [elems - block]]).astype(
        np.int64), block


def gap(op: str, got: np.ndarray, ref: np.ndarray) -> float:
    """The number compared.  Reduction: largest |got - ref| over the
    largest |ref| (one scale per answer, so an element that sums to
    almost nothing cannot blow the ratio up).  Data movement: largest
    |got - ref|, which has to be 0."""
    got = np.asarray(got, np.float64)
    d = float(np.max(np.abs(got - ref))) if got.size else 0.0
    if exact(op):
        return d
    return d / max(float(np.max(np.abs(ref))), 1e-30)
