"""Jit'd dispatch wrappers for GQA decode attention (kernel <-> oracle).

``decode_attention`` serves dense per-slot caches; ``paged_decode_attention``
serves the global page pool + per-slot page tables of the paged KV cache
(serving/kv_cache.PagePool); ``paged_prefill_attention`` scores a short
multi-token query block per slot against the same paged layout (suffix
prefill reading shared prefix pages in place, and the speculative-decode
verify step).  All pairs are parity-tested in tests/test_kernels.py; the jnp
oracles are the in-jit path the model uses when ``cfg.use_pallas`` is off.
With ``use_pallas`` the kernel is compiled on a TPU and interpreted
elsewhere, by the one rule in ``repro.kernels.interpret``.  ``tile_t`` for
the dense kernel resolves from the tile table (tuning.py) unless pinned by
the caller.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, paged_decode_attention_pallas,
    paged_prefill_attention_pallas)
from repro.kernels.decode_attention.ref import (
    decode_attention_ref, paged_decode_attention_ref,
    paged_prefill_attention_ref)


@functools.partial(jax.jit, static_argnames=("window",))
def _ref_jit(q, k_cache, v_cache, pos, window):
    return decode_attention_ref(q, k_cache, v_cache, pos, window)


def decode_attention(q, k_cache, v_cache, pos, window: int = 0,
                     use_pallas: bool = False,
                     tile_t: Optional[int] = None):
    if use_pallas:
        return decode_attention_pallas(q, k_cache, v_cache, pos,
                                       window=window, tile_t=tile_t)
    return _ref_jit(q, k_cache, v_cache, pos, window)


def paged_decode_attention(q, k_pages, v_pages, table, pos, window=0,
                           softcap: float = 0.0,
                           use_pallas: bool = False):
    """``window`` may be a python int or a traced int scalar (per-layer
    sliding windows are scanned *data* in the gemma3 stack), so there is no
    static-argname jit wrapper here — callers are jitted model steps.
    ``softcap`` is static (a ModelConfig constant)."""
    if use_pallas:
        return paged_decode_attention_pallas(q, k_pages, v_pages, table, pos,
                                             window=window, softcap=softcap)
    return paged_decode_attention_ref(q, k_pages, v_pages, table, pos, window,
                                      softcap=softcap)


def paged_prefill_attention(q, k_pages, v_pages, table, pos, window=0,
                            softcap: float = 0.0,
                            use_pallas: bool = False):
    """Multi-token paged attention: q (B, S, Hq, D), query j of slot b at
    absolute position ``pos[b] + j``.  Same traced-``window`` contract as
    ``paged_decode_attention``; callers are jitted model steps."""
    if use_pallas:
        return paged_prefill_attention_pallas(q, k_pages, v_pages, table,
                                              pos, window=window,
                                              softcap=softcap)
    return paged_prefill_attention_ref(q, k_pages, v_pages, table, pos,
                                       window, softcap=softcap)
