"""The plain reference for a ragged exchange on device arrays: the key
exchange of NAS Parallel Benchmarks 3.x MPI, kernel IS (``IS/is.c``,
function ``rank()``), and what every rank is owed after its
``MPI_Alltoallv``.

Nothing here imports ompi_tpu or takes anything the library made: the
keys of every rank come from the seed, the counts from IS's rule, and a
rank's receive buffer is a concatenation of slices, nothing cleverer.

A *class* is IS's table row: ``total_keys_log2``, ``max_key_log2``,
``num_buckets_log2`` (S: 16, 11, 9; C: 27, 23, 10).  On P ranks
(a power of two) ``NUM_KEYS = TOTAL_KEYS / P`` keys a rank,
``shift = max_key_log2 - num_buckets_log2``, and, for P < 256,
``SIZE_OF_BUFFERS = 3 * NUM_KEYS / 2`` elements of receive buffer.

The rule, as ``rank()`` has it::

    key        = floor(MAX_KEY / 4 * (u1 + u2 + u3 + u4)), u uniform on [0, 1)
    bucket_size[b]     = my keys with key >> shift == b
    bucket_size_totals = MPI_Allreduce(bucket_size, MPI_SUM)
    key_buff1          = my keys grouped by bucket, buckets ascending
                         (inside a bucket: as they came)
    acc = 0; loc = 0; j = 0
    for b in 0 .. NUM_BUCKETS - 1:
        acc += bucket_size_totals[b]; loc += bucket_size[b]
        if acc >= (j + 1) * NUM_KEYS:
            send_count[j] = loc; loc = 0; owner j ends at bucket b; j += 1
    send_displ = exclusive prefix sums of send_count
    recv_count = MPI_Alltoall(send_count, 1 int a pair)
    recv_displ = exclusive prefix sums of recv_count
    key_buff2  = MPI_Alltoallv(key_buff1, send_count, send_displ, MPI_INT,
                               recv_count, recv_displ, MPI_INT)

A rank that no bucket is left for sends and receives nothing.

The uniforms are not NPB's ``randlc``: each is 24 bits of
cellbench/reference.py's counter-based hash of the seed, the rank and
the parity (``u_k = h(4 i + k) >> 8`` over 2**24), so the sum of four
is an exact integer and ``key = (h1 + h2 + h3 + h4) >> (26 -
max_key_log2)``: integer arithmetic mod 2**32 on the device and on the
host alike, the same bits on both.  The distribution is IS's (a sum of
four uniforms).  Every rank holds TWO key sets, one per parity of the
iteration, each with its own counts (IS changes two keys, and so two
counts, every iteration).
"""
from __future__ import annotations

import numpy as np

from cellbench import reference

CLASSES = {
    "S": {"total_keys_log2": 16, "max_key_log2": 11, "num_buckets_log2": 9},
    "C": {"total_keys_log2": 27, "max_key_log2": 23, "num_buckets_log2": 10},
}


def stream_key(seed: int, rank: int, parity: int) -> int:
    """32-bit key of (seed, rank, parity), as cellbench/reference_p2p.py
    forms it: no two key sets of a run share a stream."""
    return reference.stream_key(seed, 2 * int(rank) + (int(parity) & 1))


def num_keys(cls: dict, ranks: int) -> int:
    """NUM_KEYS: keys a rank holds."""
    return (1 << cls["total_keys_log2"]) // ranks


def size_of_buffers(cls: dict, ranks: int) -> int:
    """SIZE_OF_BUFFERS for NUM_PROCS < 256: the receive buffer's
    elements, the ``capacity`` of the call."""
    return 3 * num_keys(cls, ranks) // 2


def shift_of(cls: dict) -> int:
    return cls["max_key_log2"] - cls["num_buckets_log2"]


def keys_from_key(key, n: int, max_key_log2: int, xp=np):
    """The ``n`` int32 keys of the stream with 32-bit ``key``.  ``xp``
    is numpy (the reference) or jax.numpy (the generator, on the
    device; there ``key`` is a traced uint32)."""
    u = xp.uint32
    total = xp.zeros((n,), u)
    for k in range(4):
        x = (xp.arange(n, dtype=u) * u(4) + u(k)) ^ key
        # lowbias32, as cellbench/reference.values_from_key
        x = (x ^ (x >> u(16))) * u(0x7FEB352D)
        x = (x ^ (x >> u(15))) * u(0x846CA68B)
        x = x ^ (x >> u(16))
        total = total + (x >> u(8))
    return (total >> u(26 - max_key_log2)).astype(xp.int32)


def keys(seed: int, rank: int, parity: int, cls: dict,
         ranks: int) -> np.ndarray:
    """Rank ``rank``'s keys of parity ``parity``, on the host."""
    return keys_from_key(np.uint32(stream_key(seed, rank, parity)),
                         num_keys(cls, ranks), cls["max_key_log2"])


def bucket_sizes(ks: np.ndarray, cls: dict) -> np.ndarray:
    return np.bincount(ks >> shift_of(cls),
                       minlength=1 << cls["num_buckets_log2"]).astype(
                           np.int64)


def grouped(ks: np.ndarray, cls: dict) -> np.ndarray:
    """key_buff1: the keys grouped by bucket, buckets ascending, inside
    a bucket as they came."""
    return ks[np.argsort(ks >> shift_of(cls), kind="stable")]


def distribute(sizes: np.ndarray, totals: np.ndarray, nkeys: int,
               ranks: int):
    """IS's rule: (send_count, last bucket of every owner; -1 and a
    count of 0 for a rank no bucket is left for)."""
    send = np.zeros(ranks, np.int64)
    last = np.full(ranks, -1, np.int64)
    acc = loc = j = 0
    for b in range(len(totals)):
        acc += int(totals[b])
        loc += int(sizes[b])
        if j < ranks and acc >= (j + 1) * nkeys:
            send[j], last[j] = loc, b
            loc = 0
            j += 1
    return send, last


def owned(last: np.ndarray, rank: int):
    """(first, last) bucket rank ``rank`` owns; first > last: none."""
    if last[rank] < 0:
        return 0, -1
    prev = last[:rank][last[:rank] >= 0]
    return (int(prev[-1]) + 1 if prev.size else 0), int(last[rank])


def part(seed: int, rank: int, parity: int, cls: dict, ranks: int) -> dict:
    """What one rank brings to one parity's exchange, on the host: its
    bucket sizes and its key_buff1."""
    ks = keys(seed, rank, parity, cls, ranks)
    return {"sizes": bucket_sizes(ks, cls), "buff1": grouped(ks, cls)}


def assemble(parts: list) -> dict:
    """One parity's exchange from every rank's ``part``: ``buff1``
    (key_buff1 of every rank), ``counts[i][j]`` (what rank i sends rank
    j), ``last`` (the owners' last buckets)."""
    ranks = len(parts)
    totals = np.sum([p["sizes"] for p in parts], axis=0)
    nkeys = int(totals.sum()) // ranks
    counts = np.zeros((ranks, ranks), np.int64)
    last = None
    for r, p in enumerate(parts):
        counts[r], last = distribute(p["sizes"], totals, nkeys, ranks)
    return {"buff1": [p["buff1"] for p in parts], "counts": counts,
            "last": last}


def exchange(seed: int, parity: int, cls: dict, ranks: int) -> dict:
    """Everything one parity's exchange is made of, from the seed."""
    return assemble([part(seed, r, parity, cls, ranks)
                     for r in range(ranks)])


def owed(ex: dict, rank: int) -> np.ndarray:
    """key_buff2 of rank ``rank``: the slices every rank sends it, in
    source-rank order (its ``sum(rcounts)`` elements)."""
    counts = ex["counts"]
    sdispls = np.cumsum(counts, axis=1) - counts
    return np.concatenate([
        ex["buff1"][i][sdispls[i, rank]:sdispls[i, rank] + counts[i, rank]]
        for i in range(counts.shape[0])])


def gap(got: np.ndarray, ref: np.ndarray) -> float:
    """The number compared: largest |got - ref|, which has to be 0
    (data movement).  A length that differs is infinitely far."""
    got, ref = np.asarray(got), np.asarray(ref)
    if got.shape != ref.shape:
        return float("inf")
    if not got.size:
        return 0.0
    return float(np.max(np.abs(got.astype(np.int64)
                               - ref.astype(np.int64))))
