"""The plain reference for TYPED reductions: MPI_DOUBLE inputs from
the seed, the answer each rank is owed after
``comm.reduce_scatter_arr(x, op, datatype, count)`` or
``comm.allreduce_arr(x, op, datatype, count)`` with a vector datatype,
and the comparison that decides ``correct``.

Nothing here imports ompi_tpu or takes anything the library made; the
datatype is known here only as the three integers of
``MPI_Type_vector(count, blocklength, stride)``.

The inputs are a counter-based stream, as cellbench/reference.py's:
element ``i`` of rank ``r`` under seed ``s`` is a pure function of
(s, r, i).  Here an element is an IEEE-754 binary64 made of two 32-bit
hashes of (key, i) that ARE its bit pattern, put together with integer
operations only, so the device and the host make the same bits by
construction and no float conversion is trusted on either side:

- all 52 fraction bits are random (53-bit significands: what a solver
  sends), both signs;
- the exponent field is drawn from [1, 2042]: magnitudes from 2**-1022
  to just under 2**1020, far beyond float32's range on both sides.  No
  zero, subnormal, infinity or NaN exists in the stream, so "equal" and
  "same bits" are one thing, and a SUM over up to 8 ranks cannot
  overflow;
- on a quarter of the indices (chosen by a hash of the index and the
  seed, the same on every rank) every rank holds the SAME high word
  (sign, exponent, top 20 fraction bits) and its own low word: there a
  MAX or MIN is decided by the low 32 fraction bits alone, negative
  values included, which is what a comparison that drops or rounds
  them, or orders negative bit patterns the wrong way, gets wrong.

The answer is compared exactly (limit 0): a MAX or MIN picks one of
its inputs.  An answer computed in any narrower format differs in
nearly every element: float32 (24 bits, 8 exponent bits) and a
TPU v5e's float64 (two float32 words: 48 bits, float32's exponent
range; my chip run, PR 32, read 2**-49 off with magnitudes near 1 and
0.0 for 1e-300) turn most of this stream into 0 or infinity.

An answer may arrive as float64 or as the binary64 BIT PATTERNS in a
uint64 array, the carrier of MPI_DOUBLE on a device that holds no
binary64 (``as_doubles``); both are the same 8 bytes an element.
"""
from __future__ import annotations

import numpy as np

from cellbench import reference

OPS = ("reduce_scatter_block", "allreduce")
REDUCERS = {"MPI_MAX": np.maximum, "MPI_MIN": np.minimum, "MPI_SUM": np.add}
_LO_SALT = 0x5BD1E995          # the second hash's stream
_SHARED_RANK = 0xFFFF          # no rank: the key every rank shares
_EXPONENTS = 2042              # exponent fields 1 .. 2042


def _hash32(x, u):
    """lowbias32: every output bit depends on every input bit."""
    x = (x ^ (x >> u(16))) * u(0x7FEB352D)
    x = (x ^ (x >> u(15))) * u(0x846CA68B)
    return x ^ (x >> u(16))


def words_from_key(key, shared, idx, xp=np):
    """(low, high) uint32 words of the binary64 elements at uint32
    indices ``idx`` of the stream with 32-bit ``key``; ``shared`` is
    the key all ranks of the seed have in common.  ``xp`` is numpy (the
    reference) or jax.numpy (the generator, on the device; there the
    keys are traced uint32s, so one program serves every seed and
    rank)."""
    u = xp.uint32
    lo = _hash32((idx ^ key) + u(_LO_SALT), u)
    g = _hash32(idx ^ shared, u)
    top = xp.where((g & u(3)) == u(0), _hash32(g + u(_LO_SALT), u),
                   _hash32(idx ^ key, u))
    sign = top & u(0x80000000)
    expo = (u(1) + ((top >> u(20)) & u(0x7FF)) % u(_EXPONENTS)) << u(20)
    return lo, sign | expo | (top & u(0x000FFFFF))


def keys(seed: int, rank: int):
    """(this rank's key, the seed's shared key), as Python ints."""
    return (reference.stream_key(seed, rank),
            reference.stream_key(seed, _SHARED_RANK))


def values_at(seed: int, rank: int, idx) -> np.ndarray:
    """float64 elements at indices ``idx`` of rank ``rank``'s input, on
    the host."""
    idx = np.asarray(idx, np.uint32)
    key, shared = keys(seed, rank)
    lo, hi = words_from_key(np.uint32(key), np.uint32(shared), idx)
    bits = (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)
    return bits.view(np.float64)


def packed_index(vector: dict, lo: int, hi: int) -> np.ndarray:
    """Buffer indices of packed positions [lo, hi) of one
    ``vector(count, blocklength, stride)``: block b starts at
    b * stride and holds ``blocklength`` elements."""
    k = np.arange(lo, hi, dtype=np.int64)
    bl = int(vector["blocklength"])
    return (k // bl) * int(vector["stride"]) + k % bl


def packed_elems(vector: dict) -> int:
    return int(vector["count"]) * int(vector["blocklength"])


def span_elems(vector: dict) -> int:
    """Elements of the buffer the datatype addresses (its true extent)."""
    return (int(vector["count"]) - 1) * int(vector["stride"]) \
        + int(vector["blocklength"])


def expected(op: str, reduce: str, seed: int, ranks: int, vector: dict,
             rank: int, lo: int, hi: int) -> np.ndarray:
    """Elements [lo, hi) of the float64 result rank ``rank`` is owed.

    The packed stream of rank s is its buffer's elements at
    ``packed_index``.  reduce_scatter_block: the stream is ``ranks``
    blocks of m; rank r is owed the reduction over ranks of block r.
    allreduce: every rank is owed the reduction of the whole stream."""
    if op not in OPS:
        raise KeyError(f"no typed reference for operation {op!r}")
    n = packed_elems(vector)
    m = n // ranks if op == "reduce_scatter_block" else n
    if not (0 <= rank < ranks and 0 <= lo <= hi <= m):
        raise ValueError(f"rank {rank} of {ranks}, [{lo}, {hi}) of {m}")
    base = rank * m if op == "reduce_scatter_block" else 0
    idx = packed_index(vector, base + lo, base + hi)
    red = REDUCERS[reduce]
    acc = values_at(seed, 0, idx)
    for s in range(1, ranks):
        acc = red(acc, values_at(seed, s, idx))
    return acc


def exact(reduce: str) -> bool:
    """A MAX or MIN picks one of its inputs: compared bit for bit."""
    return reduce in ("MPI_MAX", "MPI_MIN")


def as_doubles(got) -> np.ndarray:
    """An answer as float64 values: uint64 is the bit-pattern carrier
    (the same bytes), anything else is converted, which is exact for
    every narrower float, so a narrower computation shows as it is."""
    got = np.asarray(got)
    if got.dtype == np.uint64:
        return got.view(np.float64)
    return got.astype(np.float64)


def gap(reduce: str, got: np.ndarray, ref: np.ndarray) -> float:
    """The number compared.  MAX / MIN: largest |got - ref|, which has
    to be 0 (infinite where the difference overflows, or either is a
    NaN).  SUM: largest |got - ref| over the largest |ref|."""
    got = as_doubles(got)
    if got.shape != ref.shape:
        return float("inf")
    with np.errstate(over="ignore", invalid="ignore"):
        d = np.where(got == ref, 0.0, np.abs(got - ref))
        d = float(np.max(d)) if d.size else 0.0
    if d != d:
        return float("inf")        # a NaN fails
    if exact(reduce):
        return d
    return d / max(float(np.max(np.abs(ref))), 1e-300)
