"""Pallas TPU kernel: fused embedding-similarity + running top-k.

The semantic cache GET hot path (paper §3.5): score a tile of queries against
the whole vector DB and keep the best k, without materialising the full
(Q, N) similarity matrix in HBM.

Tiling: grid = (Q/TQ, N/TN), N minor (sequential); VMEM scratch carries a
running (TQ, K) score/index accumulator across N tiles.  Per tile the MXU
does a (TQ, D) x (D, TN) matmul; top-k extraction is K unrolled
max-extract-mask passes (K is small), then a merge of the 2K candidates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

# python float so the kernel doesn't capture a traced constant; shared with
# the jnp oracle (and VectorStore's host fallback) so every implementation
# agrees on the dead-slot sentinel and its `> NEG / 2` liveness test
from repro.kernels.cache_topk.ref import NEG

# cosine scores are compared against per-query thresholds: a single bf16
# MXU pass (the TPU default for f32 operands) moves them by ~1e-3, so the
# score matmuls ask for full f32 precision
_F32 = jax.lax.Precision.HIGHEST


def _extract_topk(scores: jax.Array, idx: jax.Array, k: int):
    """scores: (TQ, M) fp32; idx: (TQ, M) int32 -> ((TQ,k), (TQ,k)) best-first."""
    outs_s, outs_i = [], []
    cols = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    for _ in range(k):
        m = jnp.max(scores, axis=1)
        am = jnp.argmax(scores, axis=1).astype(jnp.int32)
        picked = cols == am[:, None]
        gi = jnp.sum(jnp.where(picked, idx, 0), axis=1)
        outs_s.append(m)
        outs_i.append(gi)
        scores = jnp.where(picked, NEG, scores)
    return jnp.stack(outs_s, axis=1), jnp.stack(outs_i, axis=1)


def _kernel(q_ref, db_ref, out_s_ref, out_i_ref, acc_s, acc_i, *, k: int,
            tile_n: int, n_valid: int):
    ni = pl.program_id(1)
    n_tiles = pl.num_programs(1)

    @pl.when(ni == 0)
    def _init():
        acc_s[...] = jnp.full_like(acc_s, NEG)
        acc_i[...] = jnp.zeros_like(acc_i)

    q = q_ref[...].astype(jnp.float32)           # (TQ, D)
    db = db_ref[...].astype(jnp.float32)         # (TN, D)
    scores = jax.lax.dot_general(q, db, (((1,), (1,)), ((), ())),
                                 precision=_F32,
                                 preferred_element_type=jnp.float32)  # (TQ, TN)
    base = ni * tile_n
    gidx = base + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(gidx < n_valid, scores, NEG)   # mask padded DB rows

    tile_s, tile_i = _extract_topk(scores, gidx, k)

    comb_s = jnp.concatenate([acc_s[...], tile_s], axis=1)
    comb_i = jnp.concatenate([acc_i[...], tile_i], axis=1)
    new_s, new_i = _extract_topk(comb_s, comb_i, k)
    acc_s[...] = new_s
    acc_i[...] = new_i

    @pl.when(ni == n_tiles - 1)
    def _write():
        out_s_ref[...] = acc_s[...]
        out_i_ref[...] = acc_i[...]


def _shortlist_kernel(q_ref, db_ref, codes_ref, sl_ref, tm_ref, th_ref,
                      out_s_ref, out_i_ref, acc_s, acc_i, picked, *, k: int,
                      tile_n: int):
    li = pl.program_id(1)
    ni = pl.program_id(2)
    n_l = pl.num_programs(1)
    n_n = pl.num_programs(2)

    @pl.when((li == 0) & (ni == 0))
    def _init():
        acc_s[...] = jnp.full_like(acc_s, NEG)
        acc_i[...] = jnp.full_like(acc_i, -1)

    @pl.when(ni == 0)
    def _clear():
        picked[...] = jnp.zeros_like(picked)

    # score the query tile against this db block, filters applied here so
    # the gather below moves final scores only
    q = q_ref[...].astype(jnp.float32)                 # (TQ, D)
    db = db_ref[...].astype(jnp.float32)               # (TN, D)
    s = jax.lax.dot_general(q, db, (((1,), (1,)), ((), ())),
                            precision=_F32,
                            preferred_element_type=jnp.float32)  # (TQ, TN)
    allowed = ((tm_ref[...] >> codes_ref[...]) & 1) == 1          # (TQ, TN)
    s = jnp.where(allowed & (s >= th_ref[...]), s, NEG)

    # one-hot gather on the MXU: row r of this block lands in shortlist
    # column j iff sl[qi, j] == r.  Every shortlist entry names exactly one
    # db row, so across the N blocks exactly one block adds its score and
    # the others add zeros; f32 precision keeps the score exact.
    sl = sl_ref[...]                                   # (TQ, TL) int32
    tq, tl = sl.shape
    rows = ni * tile_n + jax.lax.broadcasted_iota(jnp.int32, (tile_n, tl), 0)
    qrow = jax.lax.broadcasted_iota(jnp.int32, (tq, tl), 0)
    acc = picked[...]
    for qi in range(tq):
        onehot = (rows == sl[qi:qi + 1, :]).astype(jnp.float32)   # (TN, TL)
        got = jax.lax.dot_general(s, onehot, (((1,), (0,)), ((), ())),
                                  precision=_F32,
                                  preferred_element_type=jnp.float32)
        acc = acc + jnp.where(qrow == qi, got, 0.0)
    picked[...] = acc

    @pl.when(ni == n_n - 1)
    def _merge():
        scores = jnp.where(sl >= 0, picked[...], NEG)
        tile_s, tile_i = _extract_topk(scores, sl, k)
        comb_s = jnp.concatenate([acc_s[...], tile_s], axis=1)
        comb_i = jnp.concatenate([acc_i[...], tile_i], axis=1)
        new_s, new_i = _extract_topk(comb_s, comb_i, k)
        acc_s[...] = new_s
        acc_i[...] = new_i

    @pl.when((li == n_l - 1) & (ni == n_n - 1))
    def _write():
        out_s_ref[...] = acc_s[...]
        out_i_ref[...] = acc_i[...]


def shortlist_topk_pallas(q: jax.Array, db: jax.Array, codes: jax.Array,
                          shortlist: jax.Array, type_mask: jax.Array,
                          threshold: jax.Array, k: int,
                          tile_l: int = 512, tile_n: int = 512):
    """Fused gather + cosine + per-query threshold + type-masked top-k.

    q: (Q, D); db: (N, D); codes: (N,) int32; shortlist: (Q, L) int32 (-1 pad);
    type_mask/threshold: (Q,).  Returns (scores (Q, k), idx (Q, k)); slots that
    survive no mask carry idx = -1.  The (Q, L) candidate matrix is never
    materialised in HBM.

    Grid = (Q/8, L/TL, N/TN), N minor.  Each step scores an 8-query tile
    against one (TN, D) db block on the MXU and gathers the scores of the
    shortlist entries that fall in that block with a one-hot matmul; after
    the last block the tile's top-k merges into a running (8, k)
    accumulator carried across shortlist tiles.  The db streams from HBM
    block by block, so its size is bounded by HBM, not VMEM — at the price
    of reading all of it once per (query tile, shortlist tile).
    """
    Q, D = q.shape
    N = db.shape[0]
    L = shortlist.shape[1]
    tile_q = 8
    tile_l = min(tile_l, max(128, 1 << (L - 1).bit_length()))
    tile_n = min(tile_n, max(128, 1 << (N - 1).bit_length()))
    padq = (-Q) % tile_q
    padl = (-L) % tile_l
    padn = (-N) % tile_n
    qp = jnp.pad(q, ((0, padq), (0, 0)))
    dbp = jnp.pad(db, ((0, padn), (0, 0)))
    codesp = jnp.pad(codes.astype(jnp.int32), (0, padn))[None, :]
    slp = jnp.pad(shortlist, ((0, padq), (0, padl)), constant_values=-1)
    tmp = jnp.pad(type_mask.astype(jnp.int32), (0, padq))[:, None]
    thp = jnp.pad(threshold.astype(jnp.float32), (0, padq))[:, None]
    grid = (qp.shape[0] // tile_q, slp.shape[1] // tile_l,
            dbp.shape[0] // tile_n)

    out_s, out_i = pl.pallas_call(
        functools.partial(_shortlist_kernel, k=k, tile_n=tile_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, D), lambda qi, li, ni: (qi, 0)),
            pl.BlockSpec((tile_n, D), lambda qi, li, ni: (ni, 0)),
            pl.BlockSpec((1, tile_n), lambda qi, li, ni: (0, ni)),
            pl.BlockSpec((tile_q, tile_l), lambda qi, li, ni: (qi, li)),
            pl.BlockSpec((tile_q, 1), lambda qi, li, ni: (qi, 0)),
            pl.BlockSpec((tile_q, 1), lambda qi, li, ni: (qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_q, k), lambda qi, li, ni: (qi, 0)),
            pl.BlockSpec((tile_q, k), lambda qi, li, ni: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((qp.shape[0], k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_q, k), jnp.float32),
            pltpu.VMEM((tile_q, k), jnp.int32),
            pltpu.VMEM((tile_q, tile_l), jnp.float32),
        ],
        name="shortlist_topk",
        interpret=kernels.interpret(),
    )(qp, dbp, codesp, slp, tmp, thp)
    s, i = out_s[:Q], out_i[:Q]
    return s, jnp.where(s > NEG / 2, i, -1)


def similarity_topk_pallas(q: jax.Array, db: jax.Array, k: int,
                           tile_q: int = 128, tile_n: int = 512):
    """q: (Q, D); db: (N, D). Returns (scores (Q,k), idx (Q,k))."""
    Q, D = q.shape
    N = db.shape[0]
    tile_q = min(tile_q, max(8, Q))
    tile_n = min(tile_n, max(128, 1 << (N - 1).bit_length()))
    padq = (-Q) % tile_q
    padn = (-N) % tile_n
    qp = jnp.pad(q, ((0, padq), (0, 0)))
    dbp = jnp.pad(db, ((0, padn), (0, 0)))
    grid = (qp.shape[0] // tile_q, dbp.shape[0] // tile_n)

    out_s, out_i = pl.pallas_call(
        functools.partial(_kernel, k=k, tile_n=tile_n, n_valid=N),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, D), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((tile_n, D), lambda qi, ni: (ni, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_q, k), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((tile_q, k), lambda qi, ni: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((qp.shape[0], k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_q, k), jnp.float32),
            pltpu.VMEM((tile_q, k), jnp.int32),
        ],
        name="similarity_topk",
        interpret=kernels.interpret(),
    )(qp, dbp)
    return out_s[:Q], out_i[:Q]
