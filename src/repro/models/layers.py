"""Core transformer layers shared by every architecture family.

Everything is a pure function of (params-dict, inputs).  One attention
implementation serves all modes:

* train / prefill:   x (B, S, d), causal(+sliding-window) mask
* decode:            x (B, 1, d) + KV cache written in place at ``pos``
* cross-attention:   precomputed encoder KV (whisper)

GQA is computed without materialising repeated KV heads (q reshaped to
(B, S, Hkv, G, hd)) which keeps both memory and the `model`-axis sharding of
KV heads clean.  Sliding windows are *data* (a traced scalar per layer), so a
single code path scans over heterogeneous local/global stacks (gemma3).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.params import Initializer


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------
def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def apply_norm(p: Dict, x: jax.Array, cfg: ModelConfig, prefix: str) -> jax.Array:
    if cfg.norm == "layer":
        return layer_norm(x, p[prefix + "_w"], p[prefix + "_b"], cfg.norm_eps)
    return rms_norm(x, p[prefix + "_w"], cfg.norm_eps)


def init_norm(init: Initializer, cfg: ModelConfig, d: int, prefix: str) -> Dict:
    out = {prefix + "_w": init.ones((d,))}
    if cfg.norm == "layer":
        out[prefix + "_b"] = init.zeros((d,))
    return out


# --------------------------------------------------------------------------
# Rotary embeddings
# --------------------------------------------------------------------------
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32. fp32 trig, dtype-preserving."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, :, None] * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------
def init_attention(init: Initializer, cfg: ModelConfig, d: Optional[int] = None) -> Dict:
    d = d or cfg.d_model
    p = {
        "wq": init.fan_in((d, cfg.q_dim)),
        "wk": init.fan_in((d, cfg.kv_dim)),
        "wv": init.fan_in((d, cfg.kv_dim)),
        "wo": init.fan_in((cfg.q_dim, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = init.zeros((cfg.q_dim,))
        p["bk"] = init.zeros((cfg.kv_dim,))
        p["bv"] = init.zeros((cfg.kv_dim,))
    if cfg.qk_norm:
        p["q_norm_w"] = init.ones((cfg.hd,))
        p["k_norm_w"] = init.ones((cfg.hd,))
    return p


def _project_qkv(p: Dict, x: jax.Array, cfg: ModelConfig):
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    k = jnp.einsum("bsd,dq->bsq", x, p["wk"])
    v = jnp.einsum("bsd,dq->bsq", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.hd)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.hd)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm_w"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm_w"], cfg.norm_eps)
    return q, k, v


def project_kv(p: Dict, x: jax.Array, cfg: ModelConfig):
    """KV projection only (whisper cross-attention precompute at prefill)."""
    B, S, _ = x.shape
    k = jnp.einsum("bsd,dq->bsq", x, p["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.hd)
    v = jnp.einsum("bsd,dq->bsq", x, p["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.hd)
    if cfg.qkv_bias:
        k = k + p["bk"].reshape(cfg.n_kv_heads, cfg.hd)
        v = v + p["bv"].reshape(cfg.n_kv_heads, cfg.hd)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm_w"], cfg.norm_eps)
    return k, v


_SCORE_BUDGET = 1 << 33   # global fp32 score elements*4B per q block (~8 GiB)


def _sdpa(q, k, v, cfg: ModelConfig, *, q_pos=None, kv_pos=None, window=0,
          causal=True):
    """Grouped scaled-dot-product attention, q-block tiled.

    q: (B, S, Hq, hd);  k, v: (B, T, Hkv, hd).  Masking (causal + sliding
    window) is built per q-block from positions, so the full (S, T) score
    matrix never materialises — per block the live score tile is
    (B, Hq, qb, T), with qb chosen to a fixed byte budget.  The block loop is
    ``lax.map`` in production (O(1) compile) and a python loop under
    cfg.unroll_layers (dry-run cost calibration; see launch/specs.py).
    Returns (B, S, Hq*hd).
    """
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv

    def block(qb_q, qb_pos):
        """qb_q: (B, qb, Hq, hd); qb_pos: (B, qb) or None."""
        qg = qb_q.reshape(B, qb_q.shape[1], Hkv, G, hd)
        s = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                       preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(jnp.float32(hd))
        if cfg.logit_softcap > 0:
            c = cfg.logit_softcap
            s = c * jnp.tanh(s / c)
        if qb_pos is not None:
            kp = kv_pos if kv_pos is not None else jnp.arange(T, dtype=jnp.int32)
            if kp.ndim == 1:
                kp = kp[None, :]
            qp = qb_pos[:, :, None]
            m = kp[:, None, :] <= qp
            w = jnp.asarray(window, jnp.int32)
            m = m & jnp.where(w > 0, qp - kp[:, None, :] < w, True)
            s = jnp.where(m[:, None, None, :, :], s, jnp.float32(-1e30))
        w_ = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bkgst,btkh->bskgh", w_, v)
        return o.reshape(B, qb_q.shape[1], Hq * hd)

    use_pos = causal and q_pos is not None
    qb = max(128, _SCORE_BUDGET // max(B * Hq * T * 4, 1))
    if cfg.unroll_layers:
        # dry-run cost calibration: only the op *counts* matter, not peak
        # memory — one big block keeps the unrolled HLO small
        qb = S
    if S <= qb or S <= 128:
        return block(q, q_pos if use_pos else None)

    qb = min(qb, S)
    pad = (-S) % qb
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if use_pos:
            q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)))
    nb = (S + pad) // qb
    qs = jnp.moveaxis(q.reshape(B, nb, qb, Hq, hd), 1, 0)          # (nb, B, qb, ...)
    ps = jnp.moveaxis(q_pos.reshape(B, nb, qb), 1, 0) if use_pos else None

    def run(qb_q, qb_pos):
        return jax.checkpoint(block)(qb_q, qb_pos) if cfg.remat else block(qb_q, qb_pos)

    if cfg.unroll_layers:
        outs = [run(qs[i], ps[i] if ps is not None else None) for i in range(nb)]
        out = jnp.stack(outs, 0)
    elif use_pos:
        out = jax.lax.map(lambda ab: run(ab[0], ab[1]), (qs, ps))
    else:
        out = jax.lax.map(lambda a: run(a, None), qs)
    out = jnp.moveaxis(out, 0, 1).reshape(B, S + pad, Hq * hd)
    return out[:, :S]


def attention(
    p: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,                 # (B, S) absolute positions of x
    window=0,                             # traced ok; <=0 = full attention
    cache: Optional[Dict] = None,         # {"k","v": (B,Smax,Hkv,hd), "pos": (B,) int32}
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    causal: bool = True,
    use_rope: bool = True,
) -> Tuple[jax.Array, Optional[Dict]]:
    """Returns (out (B,S,d), updated_cache_or_None)."""
    B, S, _ = x.shape

    if cross_kv is not None:
        q = jnp.einsum("bsd,dq->bsq", x, p["wq"]).reshape(B, S, cfg.n_heads, cfg.hd)
        if cfg.qkv_bias:
            q = q + p["bq"].reshape(cfg.n_heads, cfg.hd)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm_w"], cfg.norm_eps)
        k, v = cross_kv
        out = _sdpa(q, k, v, cfg, causal=False)
        return jnp.einsum("bsq,qd->bsd", out, p["wo"]), None

    q, k, v = _project_qkv(p, x, cfg)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is None:
        out = _sdpa(q, k, v, cfg, q_pos=positions, kv_pos=positions,
                    window=window, causal=causal)
    elif "k_pages" in cache:
        out, new_cache = _decode_attn_paged(q, k, v, cache, cfg, window=window)
    elif _use_context_parallel_decode(cfg, S, cache):
        out, new_cache = _decode_attn_context_parallel(
            q, k, v, cache, cfg, positions=positions, window=window)
    else:
        # decode (S small, usually 1): write new KV at cache["pos"], attend over
        # the whole cache buffer with positional masking.
        Smax = cache["k"].shape[1]
        pos = cache["pos"]  # (B,) next write index
        idx = pos[:, None] + jnp.arange(S)[None, :]           # (B, S)
        # one-hot write only for short decode steps — at prefill length the
        # (S, Smax) hit matrix would dwarf the cache itself
        scatter = (_scatter_kv_onehot if (cfg.sharded_cache_update and S <= 16)
                   else _scatter_kv)
        k_cache = scatter(cache["k"], k, idx)
        v_cache = scatter(cache["v"], v, idx)
        kv_pos = jnp.arange(Smax, dtype=jnp.int32)
        out = _sdpa(q, k_cache, v_cache, cfg, q_pos=positions, kv_pos=kv_pos,
                    window=window)
        new_cache = {"k": k_cache, "v": v_cache, "pos": pos + S}

    return jnp.einsum("bsq,qd->bsd", out, p["wo"]), new_cache


def _scatter_kv(buf: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """buf: (B, Smax, H, hd); new: (B, S, H, hd); idx: (B, S) write positions.

    O(S) scatter (not O(Smax)) so decode cache updates do not inflate the
    memory roofline term.
    """
    B, S = idx.shape
    bidx = jnp.broadcast_to(jnp.arange(B, dtype=idx.dtype)[:, None], (B, S))
    return buf.at[bidx, idx].set(new.astype(buf.dtype))


def _scatter_kv_onehot(buf: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """Sharding-friendly KV write (§Perf, cfg.sharded_cache_update).

    A gather/scatter on a *sequence-sharded* cache makes GSPMD all-gather the
    whole cache per layer per step.  The one-hot masked update is elementwise
    over the sharded seq dim, so every shard touches only its own slice —
    O(Smax/shards) traffic instead of O(Smax x shards).
    """
    Smax = buf.shape[1]
    seq = jnp.arange(Smax, dtype=idx.dtype)[None, :, None]      # (1, Smax, 1)
    hit = (seq == idx[:, None, :])                              # (B, Smax, S)
    any_hit = hit.any(axis=2)[..., None, None]                  # (B, Smax, 1, 1)
    upd = jnp.einsum("bts,bshd->bthd", hit.astype(buf.dtype), new.astype(buf.dtype))
    return jnp.where(any_hit, upd, buf)


def _decode_attn_paged(q, k_new, v_new, cache, cfg: ModelConfig, *, window):
    """Decode-shaped attention against the paged KV pool (serving/kv_cache).

    ``cache`` is one layer's slice of the paged cache: ``k_pages``/``v_pages``
    (N, P, Hkv, hd) global pools, ``table`` (B, MP) physical page per logical
    page (-1 = unmapped) and ``pos`` (B,) write cursors.  The S new tokens'
    KV is scattered at positions ``pos .. pos+S-1`` into each slot's mapped
    pages — the scheduler guarantees those pages are uniquely owned
    (copy-on-write forks shared pages before admission), so slots never
    write into pages other slots read.  A write whose position falls on an
    UNMAPPED page (right-padding past a slot's reservation, idle slots) is
    routed to the pinned trash page 0 instead of being clamped: jax clamps
    out-of-range scatters, which would smear pad KV into a live page.

    S == 1 is the decode hot path (paged decode kernel); S > 1 is the paged
    flash-prefill path (suffix prefill reading shared prefix pages in
    place, and the speculative-decode verify block) — query j attends
    causally through position ``pos + j``.  Both dispatch to the
    kernels/decode_attention family: Pallas when ``cfg.use_pallas``, the
    jnp oracle otherwise.  ``window`` may be traced (per-layer scanned
    data).  NOTE: correctness of the attention READ requires every page
    holding positions ``<= pos+S-1`` to be mapped — prefill against a
    fresh, unmapped paged cache is meaningless (the scheduler always maps
    prompt + suffix pages before this runs).
    """
    from repro.kernels.decode_attention import ops as da_ops

    B, S = q.shape[0], q.shape[1]
    P = cache["k_pages"].shape[1]
    MP = cache["table"].shape[1]
    pos = cache["pos"]                                    # (B,)
    idx = pos[:, None] + jnp.arange(S, dtype=pos.dtype)[None, :]   # (B, S)
    pg = idx // P
    entry = jnp.take_along_axis(cache["table"], jnp.clip(pg, 0, MP - 1),
                                axis=1)                   # (B, S)
    valid = (pg < MP) & (entry >= 0)
    phys = jnp.where(valid, entry, 0)                     # invalid -> trash
    off = idx % P
    k_pages = cache["k_pages"].at[phys, off].set(
        k_new.astype(cache["k_pages"].dtype))
    v_pages = cache["v_pages"].at[phys, off].set(
        v_new.astype(cache["v_pages"].dtype))
    Hq, hd = q.shape[2], q.shape[3]
    if S == 1:
        out = da_ops.paged_decode_attention(
            q[:, 0], k_pages, v_pages, cache["table"], pos, window=window,
            softcap=cfg.logit_softcap, use_pallas=cfg.use_pallas)
        out = out.reshape(B, 1, Hq * hd)
    else:
        out = da_ops.paged_prefill_attention(
            q, k_pages, v_pages, cache["table"], pos, window=window,
            softcap=cfg.logit_softcap, use_pallas=cfg.use_pallas)
        out = out.reshape(B, S, Hq * hd)
    new_cache = {"k_pages": k_pages, "v_pages": v_pages,
                 "table": cache["table"], "pos": pos + S}
    return out, new_cache


def _use_context_parallel_decode(cfg: ModelConfig, S: int, cache) -> bool:
    from repro.launch import meshctx
    ctx = meshctx.current()
    return (cfg.context_parallel_decode and S == 1 and ctx is not None
            and cfg.n_kv_heads % ctx.model_size != 0
            and cache["k"].shape[1] % ctx.model_size == 0)


def _decode_attn_context_parallel(q, k_new, v_new, cache, cfg: ModelConfig,
                                  *, positions, window):
    """Distributed flash-decode over a sequence-sharded KV cache (§Perf).

    The plain einsum path makes GSPMD all-gather the cache to execute the
    positional scatter.  Here the cache stays put: each model shard writes
    its own sequence slice locally (out-of-range scatter drops) and computes
    a partial online-softmax (m, l, o); two tiny collectives (pmax + psum)
    combine the shards — the context-parallel analogue of the Pallas decode
    kernel's running (m, l, acc).
    """
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.launch import meshctx

    ctx = meshctx.current()
    B, _, Hq, hd = q.shape
    Smax = cache["k"].shape[1]
    model = ctx.model_axis
    data = ctx.data_axes
    msz = ctx.model_size
    S_l = Smax // msz
    batch_sharded = B % max(ctx.data_size, 1) == 0
    b_ax = data if batch_sharded else None

    qspec = P(b_ax, None, None, None)
    cspec = P(b_ax, model, None, None)
    pspec = P(b_ax)

    @partial(meshctx.shard_map, mesh=ctx.mesh,
             in_specs=(qspec, qspec, qspec, cspec, cspec, pspec, pspec),
             out_specs=(P(b_ax, None, None), cspec, cspec))
    def _cp(q_l, kn, vn, kc, vc, pos, qpos):
        mi = jax.lax.axis_index(model)
        off = mi * S_l
        li = (pos - off).astype(jnp.int32)                   # local write index
        bidx = jnp.arange(kc.shape[0])
        kc = kc.at[bidx, li].set(kn[:, 0].astype(kc.dtype), mode="drop")
        vc = vc.at[bidx, li].set(vn[:, 0].astype(vc.dtype), mode="drop")

        Hkv = kc.shape[2]
        G = Hq // Hkv
        # keep the cache in bf16 end-to-end: accumulate in f32 via the MXU
        # instead of materialising an f32 cache copy (§Perf iteration 3)
        qg = q_l[:, 0].reshape(-1, Hkv, G, hd)
        s = jnp.einsum("bkgh,btkh->bkgt", qg, kc,
                       preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(jnp.float32(hd))
        if cfg.logit_softcap > 0:
            c = cfg.logit_softcap
            s = c * jnp.tanh(s / c)
        kv_pos = off + jnp.arange(S_l, dtype=jnp.int32)
        m_ok = kv_pos[None, :] <= qpos[:, None]
        w = jnp.asarray(window, jnp.int32)
        m_ok = m_ok & jnp.where(w > 0, qpos[:, None] - kv_pos[None, :] < w, True)
        s = jnp.where(m_ok[:, None, None, :], s, -3.0e38)

        m_loc = jnp.max(s, axis=-1)                           # (B,Hkv,G)
        m_glb = jax.lax.pmax(m_loc, model)
        p = jnp.where(m_ok[:, None, None, :], jnp.exp(s - m_glb[..., None]), 0.0)
        l_loc = p.sum(-1)
        o_loc = jnp.einsum("bkgt,btkh->bkgh", p.astype(vc.dtype), vc,
                           preferred_element_type=jnp.float32)
        l_glb = jax.lax.psum(l_loc, model)
        o_glb = jax.lax.psum(o_loc, model)
        o = o_glb / jnp.maximum(l_glb, 1e-30)[..., None]
        return o.reshape(-1, 1, Hq * hd).astype(q_l.dtype), kc, vc

    out, k_cache, v_cache = _cp(q, k_new, v_new, cache["k"], cache["v"],
                                cache["pos"], positions[:, 0])
    new_cache = {"k": k_cache, "v": v_cache, "pos": cache["pos"] + 1}
    return out, new_cache


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
                  dtype=None) -> Dict:
    dtype = dtype or cfg.dtype
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": jnp.zeros((n_layers, batch), jnp.int32),
    }


def init_paged_kv_cache(cfg: ModelConfig, batch: int, n_pages: int,
                        page_size: int, max_pages: int, n_layers: int,
                        dtype=None) -> Dict:
    """Paged layout: one global page pool per layer + per-slot page tables.

    HBM is bounded by ``n_pages * page_size`` tokens per layer regardless of
    the slot count — short requests stop reserving ``max_len`` of cache, and
    prefix pages are shared across slots (see serving/kv_cache.PagePool).
    ``table`` and ``pos`` are replicated over the layer axis so the cache
    stays a leading-scan-dim pytree like the dense layout.
    """
    dtype = dtype or cfg.dtype
    shape = (n_layers, n_pages, page_size, cfg.n_kv_heads, cfg.hd)
    return {
        "k_pages": jnp.zeros(shape, dtype),
        "v_pages": jnp.zeros(shape, dtype),
        "table": jnp.full((n_layers, batch, max_pages), -1, jnp.int32),
        "pos": jnp.zeros((n_layers, batch), jnp.int32),
    }


# --------------------------------------------------------------------------
# MLP variants
# --------------------------------------------------------------------------
def init_mlp(init: Initializer, cfg: ModelConfig, d: Optional[int] = None,
             d_ff: Optional[int] = None) -> Dict:
    d = d or cfg.d_model
    d_ff = d_ff or cfg.d_ff
    if cfg.act in ("silu", "geglu"):
        return {
            "w_gate": init.fan_in((d, d_ff)),
            "w_up": init.fan_in((d, d_ff)),
            "w_down": init.fan_in((d_ff, d)),
        }
    return {  # plain (whisper): up -> gelu -> down, with biases
        "w_up": init.fan_in((d, d_ff)),
        "b_up": init.zeros((d_ff,)),
        "w_down": init.fan_in((d_ff, d)),
        "b_down": init.zeros((d,)),
    }


def mlp(p: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.act == "silu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
        return h @ p["w_down"]
    if cfg.act == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"], approximate=True) * (x @ p["w_up"])
        return h @ p["w_down"]
    h = jax.nn.gelu(x @ p["w_up"] + p["b_up"], approximate=True)
    return h @ p["w_down"] + p["b_down"]


# --------------------------------------------------------------------------
# Embedding / unembedding
# --------------------------------------------------------------------------
def embed(tokens: jax.Array, table: jax.Array, scale: bool = False) -> jax.Array:
    x = jnp.take(table, tokens, axis=0)
    if scale:
        x = x * jnp.sqrt(jnp.float32(table.shape[1])).astype(x.dtype)
    return x


def unembed(x: jax.Array, table_or_w: jax.Array, tied: bool) -> jax.Array:
    if tied:
        return jnp.einsum("bsd,vd->bsv", x, table_or_w)
    return jnp.einsum("bsd,dv->bsv", x, table_or_w)
