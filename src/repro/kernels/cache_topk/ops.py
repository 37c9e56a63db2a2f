"""Jit'd dispatch wrapper for fused similarity+top-k (kernel <-> oracle)."""
from __future__ import annotations

import functools

import jax
import numpy as np

from repro.kernels.cache_topk.kernel import (shortlist_topk_pallas,
                                             similarity_topk_pallas)
from repro.kernels.cache_topk.ref import shortlist_topk_ref, similarity_topk_ref


@functools.partial(jax.jit, static_argnames=("k",))
def _ref_jit(q, db, k):
    return similarity_topk_ref(q, db, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _shortlist_ref_jit(q, db, codes, shortlist, type_mask, threshold, k):
    return shortlist_topk_ref(q, db, codes, shortlist, type_mask, threshold, k)


def similarity_topk(q, db, k: int, use_pallas: bool = False):
    """q: (Q, D); db: (N, D) -> (scores (Q,k), idx (Q,k)) as numpy arrays."""
    if use_pallas:
        s, i = similarity_topk_pallas(jax.numpy.asarray(q), jax.numpy.asarray(db),
                                      k)
    else:
        s, i = _ref_jit(jax.numpy.asarray(q), jax.numpy.asarray(db), k)
    return np.asarray(s), np.asarray(i)


def shortlist_topk(q, db, codes, shortlist, type_mask, threshold, k: int,
                   use_pallas: bool = False):
    """Masked shortlist scoring: gather + cosine + threshold + type-masked
    top-k fused in one pass (the IVF probe hot path).

    q: (Q, D); db: (N, D); codes: (N,) int; shortlist: (Q, L) int (-1 pad);
    type_mask: (Q,) int bitmask; threshold: (Q,) f32.
    Returns numpy (scores (Q, k), idx (Q, k)); unfilled slots have idx = -1.
    """
    jnp_ = jax.numpy
    args = (jnp_.asarray(q), jnp_.asarray(db),
            jnp_.asarray(codes, jnp_.int32), jnp_.asarray(shortlist, jnp_.int32),
            jnp_.asarray(type_mask, jnp_.int32), jnp_.asarray(threshold, jnp_.float32))
    if use_pallas:
        s, i = shortlist_topk_pallas(*args, k)
    else:
        s, i = _shortlist_ref_jit(*args, k)
    return np.asarray(s), np.asarray(i)
