"""Jit'd dispatch wrapper for flash attention (kernel <-> oracle).

GQA note: callers pass (B, S, H, hd) tensors; the wrapper flattens heads and
repeats KV heads to match Q heads.  (The kernel itself is head-agnostic; a
grouped variant that avoids the repeat is a recorded follow-up optimisation.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


def flash_attention(q, k, v, window: int = 0, use_pallas: bool = False):
    """q: (B, S, Hq, hd); k,v: (B, S, Hkv, hd) -> (B, S, Hq, hd)."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    if Hkv != Hq:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hq, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hq, S, hd)
    fn = flash_attention_pallas if use_pallas else _ref_jit
    if use_pallas:
        of = fn(qf, kf, vf, window=window)
    else:
        of = fn(qf, kf, vf, window)
    return of.reshape(B, Hq, S, hd).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("window",))
def _ref_jit(q, k, v, window):
    return flash_attention_ref(q, k, v, window)
