"""Pallas TPU kernels: GQA decode attention over a deep KV cache.

Three kernels share the same online-softmax core:

* **dense** — per-slot contiguous (B, T, Hkv, D) caches.  The grid is
  (B * Hkv, T/TT), KV-time minor, carrying online-softmax state in VMEM.
  All G query heads of a KV group ride along in one (G, D) block so the
  cache is read once per KV head, not once per Q head — this is the GQA
  arithmetic-intensity win (G MACs per loaded KV element).
* **paged** — a single global (N, P, Hkv, D) page pool plus per-slot page
  tables (B, MP).  The scalar-prefetched table drives the per-tile KV
  gather: the BlockSpec index map reads ``table[b, page]`` before the tile
  runs, so each grid step DMAs exactly one physical page and the
  online-softmax state is carried across pages.  Slots sharing prefix pages
  (copy-on-write prefix cache) stream the same physical page without any
  per-slot copy.  The page loop STOPS at each slot's live page count
  (``pos // P + 1``): dead grid steps skip all compute and repeat the last
  live block index, so Pallas elides their DMA entirely.
* **paged flash-prefill** — a short query block (S tokens at positions
  ``pos .. pos+S-1``) against the SAME paged layout: the flash grid keeps
  the page table on the KV side, so a suffix prefill (or a speculative
  verify step) reads already-resident prefix pages in place — no
  gather-copy into a transient dense cache.  Per-row causal masking uses
  each query's own absolute position.

Masking uses the per-request position (scalar-prefetched), so continuous-
batching slots with different lengths share one kernel launch.  The paged
kernel additionally takes ``window`` as a prefetched scalar so families with
per-layer traced sliding windows (gemma3 local:global) dispatch through one
program.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.kernels.decode_attention import tuning

NEG = -3.0e38


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            tile_t: int, window: int, scale: float, n_kv_heads: int):
    bk = pl.program_id(0)
    ti = pl.program_id(1)
    n_t = pl.num_programs(1)

    @pl.when(ti == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    b = bk // n_kv_heads
    pos = pos_ref[b]

    q = q_ref[0].astype(jnp.float32)            # (G, D)
    k = k_ref[0].astype(jnp.float32)            # (TT, D)
    v = v_ref[0].astype(jnp.float32)            # (TT, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (G, TT)
    kpos = ti * tile_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos <= pos
    if window > 0:
        mask = mask & (pos - kpos < window)
    s = jnp.where(mask, s, NEG)

    m_prev = m_scr[...][:, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(mask, jnp.exp(s - m_cur[:, None]), 0.0)
    l_cur = l_scr[...][:, 0] * alpha + jnp.sum(p, axis=1)
    acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_scr[...] = m_cur[:, None]
    l_scr[...] = l_cur[:, None]
    acc_scr[...] = acc

    @pl.when(ti == n_t - 1)
    def _write():
        denom = jnp.maximum(l_scr[...][:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                            pos: jax.Array, window: int = 0,
                            tile_t: Optional[int] = None):
    """q: (B, Hq, D); caches: (B, T, Hkv, D); pos: (B,). Returns (B, Hq, D).

    ``tile_t=None`` resolves the KV-time tile from the measured autotune
    table (kernels/decode_attention/tuning.py) for this depth + dtype."""
    B, Hq, D = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    if tile_t is None:
        tile_t = tuning.pick_tile_t(T, k_cache.dtype)
    tile_t = min(tile_t, T)
    padt = (-T) % tile_t
    kp = jnp.pad(k_cache, ((0, 0), (0, padt), (0, 0), (0, 0)))
    vp = jnp.pad(v_cache, ((0, 0), (0, padt), (0, 0), (0, 0)))
    Tp = kp.shape[1]
    # (B, T, Hkv, D) -> (B*Hkv, T, D);  q -> (B*Hkv, G, D)
    kf = kp.transpose(0, 2, 1, 3).reshape(B * Hkv, Tp, D)
    vf = vp.transpose(0, 2, 1, 3).reshape(B * Hkv, Tp, D)
    qf = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    grid = (B * Hkv, Tp // tile_t)
    scale = 1.0 / (D ** 0.5)

    out = pl.pallas_call(
        functools.partial(_kernel, tile_t=tile_t, window=window, scale=scale,
                          n_kv_heads=Hkv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, G, D), lambda bk, ti, pos_ref: (bk, 0, 0)),
                pl.BlockSpec((1, tile_t, D), lambda bk, ti, pos_ref: (bk, ti, 0)),
                pl.BlockSpec((1, tile_t, D), lambda bk, ti, pos_ref: (bk, ti, 0)),
            ],
            out_specs=pl.BlockSpec((1, G, D), lambda bk, ti, pos_ref: (bk, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, D), q.dtype),
        name="decode_attention",
        interpret=kernels.interpret(),
    )(pos.astype(jnp.int32), qf, kf, vf)
    return out.reshape(B, Hq, D)


def _paged_kernel(tbl_ref, pos_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page: int, n_kv_heads: int,
                  scale: float, softcap: float):
    bk = pl.program_id(0)
    pi = pl.program_id(1)
    n_p = pl.num_programs(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    b = bk // n_kv_heads
    pos = pos_ref[b]
    win = win_ref[0]
    # grid stop at the slot's LIVE page count: steps past the cursor's page
    # skip all compute (and their index map repeats the last live page, so
    # Pallas elides the redundant DMA) instead of masking unmapped pages
    live = jnp.minimum(pos // page + 1, n_p)

    @pl.when(pi < live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)            # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)         # (P, D)
        v = v_ref[0, 0].astype(jnp.float32)         # (P, D)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (G, P)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = pi * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos <= pos
        # boolean arithmetic only: Mosaic cannot lower a select on i1
        # vectors, which is what jnp.where over booleans becomes
        mask = mask & ((win <= 0) | (pos - kpos < win))
        s = jnp.where(mask, s, NEG)

        m_prev = m_scr[...][:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur[:, None]), 0.0)
        l_cur = l_scr[...][:, 0] * alpha + jnp.sum(p, axis=1)
        acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        m_scr[...] = m_cur[:, None]
        l_scr[...] = l_cur[:, None]
        acc_scr[...] = acc

    @pl.when(pi == n_p - 1)
    def _write():
        denom = jnp.maximum(l_scr[...][:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def paged_decode_attention_pallas(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array, table: jax.Array,
                                  pos: jax.Array, window=0,
                                  softcap: float = 0.0):
    """q: (B, Hq, D); pages: (N, P, Hkv, D); table: (B, MP) int32 physical
    page per logical page (-1 = unmapped); pos: (B,). Returns (B, Hq, D).

    The grid is (B * Hkv, MP): one physical page DMA per step, selected by
    the scalar-prefetched page table inside the BlockSpec index map.  The
    grid STOPS at each slot's live page count (``pos // P + 1``): steps past
    it skip all compute, and their index map pins the last live page so
    consecutive identical block indices elide the DMA — unmapped tail
    entries are never even fetched (they are additionally clamped to page 0
    so tracing with an empty table stays in bounds).
    """
    B, Hq, D = q.shape
    _, P, Hkv, _ = k_pages.shape
    MP = table.shape[1]
    G = Hq // Hkv
    grid = (B * Hkv, MP)
    scale = 1.0 / (D ** 0.5)
    # (N, P, Hkv, D) -> (N, Hkv, P, D): a (1, 1, P, D) block is one page of
    # one KV head, addressed by (table[b, pi], h)
    kf = k_pages.transpose(0, 2, 1, 3)
    vf = v_pages.transpose(0, 2, 1, 3)
    qf = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    tbl = jnp.maximum(table, 0).astype(jnp.int32)
    win = jnp.broadcast_to(jnp.asarray(window, jnp.int32), (1,))

    def kv_map(bk, pi, tbl_ref, pos_ref, win_ref):
        b = bk // Hkv
        live_last = jnp.minimum(pos_ref[b] // P, MP - 1)
        return (tbl_ref[b, jnp.minimum(pi, live_last)], bk % Hkv, 0, 0)

    out = pl.pallas_call(
        functools.partial(_paged_kernel, page=P, n_kv_heads=Hkv, scale=scale,
                          softcap=softcap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, G, D), lambda bk, pi, t, p, w: (bk, 0, 0)),
                pl.BlockSpec((1, 1, P, D), kv_map),
                pl.BlockSpec((1, 1, P, D), kv_map),
            ],
            out_specs=pl.BlockSpec((1, G, D), lambda bk, pi, t, p, w: (bk, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, D), q.dtype),
        name="paged_decode_attention",
        interpret=kernels.interpret(),
    )(tbl, pos.astype(jnp.int32), win, qf, kf, vf)
    return out.reshape(B, Hq, D)


def _paged_prefill_kernel(tbl_ref, pos_ref, win_ref, q_ref, k_ref, v_ref,
                          o_ref, m_scr, l_scr, acc_scr, *, page: int,
                          n_kv_heads: int, n_q: int, group: int, scale: float,
                          softcap: float):
    bk = pl.program_id(0)
    pi = pl.program_id(1)
    n_p = pl.num_programs(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    b = bk // n_kv_heads
    pos = pos_ref[b]                 # absolute position of query row 0
    win = win_ref[0]
    # the deepest query attends through page (pos + n_q - 1) // P
    live = jnp.minimum((pos + n_q - 1) // page + 1, n_p)

    @pl.when(pi < live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)            # (S*G, D)
        k = k_ref[0, 0].astype(jnp.float32)         # (P, D)
        v = v_ref[0, 0].astype(jnp.float32)         # (P, D)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (S*G, P)
        s = s * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = pi * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # row r is query (r // G): its own causal frontier is pos + r // G
        qpos = pos + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        mask = kpos <= qpos
        mask = mask & ((win <= 0) | (qpos - kpos < win))
        s = jnp.where(mask, s, NEG)

        m_prev = m_scr[...][:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur[:, None]), 0.0)
        l_cur = l_scr[...][:, 0] * alpha + jnp.sum(p, axis=1)
        acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        m_scr[...] = m_cur[:, None]
        l_scr[...] = l_cur[:, None]
        acc_scr[...] = acc

    @pl.when(pi == n_p - 1)
    def _write():
        denom = jnp.maximum(l_scr[...][:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def paged_prefill_attention_pallas(q: jax.Array, k_pages: jax.Array,
                                   v_pages: jax.Array, table: jax.Array,
                                   pos: jax.Array, window=0,
                                   softcap: float = 0.0):
    """Paged flash-prefill: q (B, S, Hq, D) with query j of slot b at
    absolute position ``pos[b] + j``; pages (N, P, Hkv, D); table (B, MP);
    pos (B,). Returns (B, S, Hq, D).

    The flash grid is (B * Hkv, MP) with the page table on the KV side —
    one physical page DMA per step — and the whole (S*G, D) query block
    resident, so suffix prefill / speculative verify reads shared prefix
    pages IN PLACE instead of gathering them into a dense scratch cache.
    The page loop stops at the deepest query's live page count, exactly as
    in the decode kernel.
    """
    B, S, Hq, D = q.shape
    _, P, Hkv, _ = k_pages.shape
    MP = table.shape[1]
    G = Hq // Hkv
    grid = (B * Hkv, MP)
    scale = 1.0 / (D ** 0.5)
    kf = k_pages.transpose(0, 2, 1, 3)
    vf = v_pages.transpose(0, 2, 1, 3)
    # (B, S, Hq, D) -> (B, Hkv, S, G, D) -> (B*Hkv, S*G, D): row r of a
    # block is query (r // G), query-head group member (r % G)
    qf = q.reshape(B, S, Hkv, G, D).transpose(0, 2, 1, 3, 4)
    qf = qf.reshape(B * Hkv, S * G, D)
    tbl = jnp.maximum(table, 0).astype(jnp.int32)
    win = jnp.broadcast_to(jnp.asarray(window, jnp.int32), (1,))

    def kv_map(bk, pi, tbl_ref, pos_ref, win_ref):
        b = bk // Hkv
        live_last = jnp.minimum((pos_ref[b] + S - 1) // P, MP - 1)
        return (tbl_ref[b, jnp.minimum(pi, live_last)], bk % Hkv, 0, 0)

    out = pl.pallas_call(
        functools.partial(_paged_prefill_kernel, page=P, n_kv_heads=Hkv,
                          n_q=S, group=G, scale=scale, softcap=softcap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, S * G, D), lambda bk, pi, t, p, w: (bk, 0, 0)),
                pl.BlockSpec((1, 1, P, D), kv_map),
                pl.BlockSpec((1, 1, P, D), kv_map),
            ],
            out_specs=pl.BlockSpec((1, S * G, D),
                                   lambda bk, pi, t, p, w: (bk, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((S * G, 1), jnp.float32),
                pltpu.VMEM((S * G, 1), jnp.float32),
                pltpu.VMEM((S * G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, S * G, D), q.dtype),
        name="paged_prefill_attention",
        interpret=kernels.interpret(),
    )(tbl, pos.astype(jnp.int32), win, qf, kf, vf)
    out = out.reshape(B, Hkv, S, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, S, Hq, D)
