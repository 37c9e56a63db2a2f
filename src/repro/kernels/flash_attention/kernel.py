"""Pallas TPU kernel: blocked causal attention with online softmax
(prefill hot path), sliding-window aware.

Grid = (BH, S/TQ, S/TK), KV minor.  VMEM scratch carries the running
(m, l, acc) online-softmax state per q tile.  Causal + window masking is
applied per (q,k) tile from global iotas; tiles entirely outside the window
still execute (uniform grid) but contribute nothing — the beyond-paper perf
pass prunes them analytically in the roofline model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG = -3.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            tile_q: int, tile_k: int, window: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)          # (TQ, D)
    k = k_ref[0].astype(jnp.float32)          # (TK, D)
    v = v_ref[0].astype(jnp.float32)          # (TK, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (TQ, TK)

    qpos = qi * tile_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * tile_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos <= qpos
    if window > 0:
        mask = mask & (qpos - kpos < window)
    s = jnp.where(mask, s, NEG)

    m_prev = m_scr[...][:, 0]                  # (TQ,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    # rows with no valid entries: p == exp(NEG - m) -> 0 via masking
    p = jnp.where(mask, p, 0.0)
    l_cur = l_scr[...][:, 0] * alpha + jnp.sum(p, axis=1)
    acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_scr[...] = m_cur[:, None]
    l_scr[...] = l_cur[:, None]
    acc_scr[...] = acc

    @pl.when(ki == n_k - 1)
    def _write():
        denom = jnp.maximum(l_scr[...][:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           window: int = 0, tile_q: int = 128,
                           tile_k: int = 128):
    """q,k,v: (BH, S, D) -> (BH, S, D). Causal (+ optional sliding window)."""
    BH, S, D = q.shape
    tile_q = min(tile_q, S)
    tile_k = min(tile_k, S)
    pad = (-S) % tile_q
    padk = (-S) % tile_k
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, padk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, padk), (0, 0)))
    grid = (BH, qp.shape[1] // tile_q, kp.shape[1] // tile_k)
    scale = 1.0 / (D ** 0.5)

    out = pl.pallas_call(
        functools.partial(_kernel, tile_q=tile_q, tile_k=tile_k,
                          window=window, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, tile_k, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, tile_k, D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_q, D), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, qp.shape[1], D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tile_q, 1), jnp.float32),
            pltpu.VMEM((tile_q, 1), jnp.float32),
            pltpu.VMEM((tile_q, D), jnp.float32),
        ],
        name="flash_attention",
        interpret=kernels.interpret(),
    )(qp, kp, vp)
    return out[:, :S]
