"""The plain reference for expert-parallel dispatch and combine: one MoE
layer of DeepSeek-V3 over EP ranks, as DeepEP's normal kernels move it,
and what every rank is owed after each exchange.

Nothing here imports ompi_tpu or takes anything the library made: the
routing comes from the seed by the rule below, the records and the
stand-in expert outputs are counter-based bit patterns of the seed, and
a rank's result is a list of rows, nothing cleverer.

A *setting* (``setting()``) holds the configuration's numbers: hidden
size H, routed experts E, experts a token K, groups G and groups a token
KG, the routed scaling factor, tokens a rank T, ranks P and the spread
of the per-expert offset.  The rule, for every rank and parity (routing
set), ties broken by the lower index (a stable argsort)::

    z[t, e]      = N(0, 1) from the seed (float32) + off[e],   off[e] ~ N(0, 0.15) per routing set
    s            = sigmoid(z)                                    # scoring_func sigmoid
    group g      = experts 32g .. 32g+31                         # n_group 8
    gscore[t, g] = sum of the 2 largest s[t, e] in group g       # noaux_tc's group score
    keep         = the 4 groups of largest gscore                # topk_group 4
    top8[t]      = the 8 largest s[t, e] over kept groups        # num_experts_per_tok 8
    w[t]         = s[t, top8] / sum(s[t, top8]) * 2.5            # norm_topk_prob, routed_scaling_factor
    owner(e)     = e // 64                                       # 64 experts a chip, EP 4
    send rows    = token t once to every rank in {owner(e) : e in top8[t]}, grouped by rank,
                   tokens ascending inside a rank's block       # DeepEP's dedup
    combine      = rank j sends back, for every row it received, that row's stand-in expert output,
                   in the order it received them; rank i's owed buffer is its send order again

A dispatch row is a token's record, ``record_words`` uint32 words: H
FP8 e4m3 bytes (4 a word), H / 128 float32 scales (1x128 activation
tiles), the K expert ids (int32) and the K gate weights (float32), all
but the last two seeded bit patterns.  A combine row is H bfloat16
values, the stand-in of expert outputs for one (source, token,
destination), seeded bit patterns of finite, normal values (a v5e
flushes a bfloat16 subnormal to zero on every load, so no value the
chip computes is one).  Both are pure functions of the seed and the
row's identity, so the device makes whole buffers in one jitted call
(``xp=jax.numpy``) and the host regenerates any row afterwards
(``xp=numpy``).
"""
from __future__ import annotations

import numpy as np

from cellbench import reference

RECORD, STANDIN = 1, 2


def setting(config: dict, traffic: dict, tiny: bool = False) -> dict:
    """The numbers the rule reads: the configuration's (DeepSeek-V3's
    own keys at the top level of its file) and the mix's; the
    development mode divides the tokens a rank."""
    tokens = traffic["tokens_per_rank"]
    if tiny:
        tokens = max(8, tokens // traffic["tiny_divisor"])
    c = config
    return {"hidden": c["hidden_size"], "experts": c["n_routed_experts"],
            "topk": c["num_experts_per_tok"], "groups": c["n_group"],
            "topk_groups": c["topk_group"],
            "scale": c["routed_scaling_factor"],
            "tile": c["deployment"]["activation_scale_tile"],
            "ranks": c["ep_ranks"], "tokens": tokens,
            "sigma": c["assumed"]["routing_offset"]["sigma"]}


def record_words(st: dict) -> int:
    """uint32 words of a dispatch row: FP8 bytes, scales, ids, weights."""
    return st["hidden"] // 4 + st["hidden"] // st["tile"] + 2 * st["topk"]


def capacity(st: dict) -> int:
    """Rows a rank can be sent: every token of every rank once."""
    return st["ranks"] * st["tokens"]


def key(seed: int, what: int, rank: int, parity: int) -> int:
    """32-bit stream key of one rank's records or stand-ins of a
    parity."""
    return reference.stream_key(seed, ((what * 64 + int(rank)) << 1)
                                | (int(parity) & 1))


def bits(key, counter, xp=np):
    """lowbias32 of ``counter ^ key`` (cellbench/reference.py's
    finalizer): uint32 bits, the same on the host and the device."""
    u = xp.uint32
    x = counter.astype(u) ^ key
    x = (x ^ (x >> u(16))) * u(0x7FEB352D)
    x = (x ^ (x >> u(15))) * u(0x846CA68B)
    return x ^ (x >> u(16))


def route(seed: int, rank: int, parity: int, st: dict) -> dict:
    """DeepSeek-V3's routing of rank ``rank``'s tokens in routing set
    ``parity``: ``ids`` (T, K) int32, ``w`` (T, K) float32, ``owners``
    (T, P) bool (the ranks a token is sent to)."""
    T, E, K = st["tokens"], st["experts"], st["topk"]
    G, KG, P = st["groups"], st["topk_groups"], st["ranks"]
    off = np.random.default_rng([int(seed), int(parity), 0]).normal(
        0.0, st["sigma"], E).astype(np.float32)
    z = np.random.default_rng([int(seed), int(parity), 1, int(rank)]) \
        .standard_normal((T, E), np.float32) + off
    s = (1.0 / (1.0 + np.exp(-z))).astype(np.float32)
    per = E // G
    gscore = np.sort(s.reshape(T, G, per), axis=2)[:, :, -2:].sum(axis=2)
    keep = np.argsort(-gscore, axis=1, kind="stable")[:, :KG]
    kept = np.zeros((T, G), bool)
    np.put_along_axis(kept, keep, True, axis=1)
    masked = np.where(np.repeat(kept, per, axis=1), s, -np.inf)
    ids = np.argsort(-masked, axis=1, kind="stable")[:, :K].astype(np.int32)
    top = np.take_along_axis(s, ids, axis=1)
    w = (top / top.sum(axis=1, keepdims=True) * np.float32(st["scale"])) \
        .astype(np.float32)
    owners = np.zeros((T, P), bool)
    np.put_along_axis(owners, ids // (E // P), True, axis=1)
    return {"ids": ids, "w": w, "owners": owners}


def send_order(owners: np.ndarray) -> list:
    """DeepEP's dedup: for every destination rank, ascending, the
    tokens sent to it, ascending."""
    return [np.flatnonzero(owners[:, j]).astype(np.int32)
            for j in range(owners.shape[1])]


def records(key, tok, ids, w, words: int, xp=np):
    """The dispatch rows of tokens ``tok`` (any int array) of one rank:
    ``words - 2K`` seeded words, then the token's ids and weights."""
    K = ids.shape[1]
    seeded = words - 2 * K
    u = xp.uint32
    ctr = tok.astype(u)[:, None] * u(words) \
        + xp.arange(seeded, dtype=u)[None, :]
    if xp is np:
        idw, ww = ids[tok].view(np.uint32), w[tok].view(np.uint32)
    else:
        from jax import lax
        idw = lax.bitcast_convert_type(ids[tok], xp.uint32)
        ww = lax.bitcast_convert_type(w[tok], xp.uint32)
    return xp.concatenate([bits(key, ctr, xp), idw, ww], axis=1)


def standins(key, src, tok, st: dict, xp=np):
    """The combine rows a destination sends back for rows that came
    from ranks ``src``, tokens ``tok``, as uint16 bfloat16 bit
    patterns: finite (the exponent's top bit clear) and normal (an
    exponent field of 0 made 1), as every value a chip computes is; a
    v5e flushes a bfloat16 subnormal to zero on every load."""
    u = xp.uint32
    H = st["hidden"]
    row = (src.astype(u) * u(st["tokens"]) + tok.astype(u)) * u(H)
    x = bits(key, row[:, None] + xp.arange(H, dtype=u)[None, :], xp) \
        & u(0xBFFF)
    x = x | xp.where((x & u(0x7F80)) == u(0), u(0x80), u(0))
    return x.astype(xp.uint16)


def exchange(seed: int, parity: int, st: dict) -> dict:
    """One routing set of every rank: ``routes`` (``route`` a rank),
    ``order`` (``send_order`` a rank) and ``counts[i][j]``, the rows
    rank i dispatches to rank j."""
    P = st["ranks"]
    routes = [route(seed, r, parity, st) for r in range(P)]
    order = [send_order(r["owners"]) for r in routes]
    counts = np.array([[len(b) for b in o] for o in order], np.int64)
    return {"routes": routes, "order": order, "counts": counts,
            "parity": parity}


def dispatch_owed(seed: int, ex: dict, rank: int, st: dict, lo: int,
                  hi: int) -> np.ndarray:
    """Rows [lo, hi) of what rank ``rank`` is owed after the dispatch:
    every source's block for it, sources ascending."""
    out = []
    at = 0
    for i, o in enumerate(ex["order"]):
        tok = o[rank]
        a, b = max(lo, at), min(hi, at + len(tok))
        if a < b:
            r = ex["routes"][i]
            out.append(records(np.uint32(key(seed, RECORD, i,
                                             ex["parity"])),
                               tok[a - at:b - at], r["ids"], r["w"],
                               record_words(st)))
        at += len(tok)
    return np.concatenate(out) if out else np.zeros(
        (0, record_words(st)), np.uint32)


def combine_owed(seed: int, ex: dict, rank: int, st: dict, lo: int,
                 hi: int) -> np.ndarray:
    """Rows [lo, hi) of what rank ``rank`` is owed after the combine:
    its own send order again, each row the stand-in its destination
    made for it."""
    out = []
    at = 0
    for j, tok in enumerate(ex["order"][rank]):
        a, b = max(lo, at), min(hi, at + len(tok))
        if a < b:
            t = tok[a - at:b - at]
            out.append(standins(np.uint32(key(seed, STANDIN, j,
                                              ex["parity"])),
                                np.full(t.shape, rank, np.int32), t, st))
        at += len(tok)
    return np.concatenate(out) if out else np.zeros(
        (0, st["hidden"]), np.uint16)


def received(ex: dict, rank: int):
    """(source rank, token) of every row rank ``rank`` receives in the
    dispatch, in the order it receives them."""
    src = [np.full(len(o[rank]), i, np.int32)
           for i, o in enumerate(ex["order"])]
    tok = [o[rank] for o in ex["order"]]
    return np.concatenate(src), np.concatenate(tok)


def gap(got: np.ndarray, ref: np.ndarray) -> float:
    """The number compared: largest |got - ref| over the unsigned words,
    which has to be 0 (data movement).  A shape that differs is
    infinitely far."""
    got, ref = np.asarray(got), np.asarray(ref)
    if got.shape != ref.shape:
        return float("inf")
    if not got.size:
        return 0.0
    return float(np.max(np.abs(got.astype(np.int64) - ref.astype(np.int64))))
