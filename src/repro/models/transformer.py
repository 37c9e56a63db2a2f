"""Decoder-only language models for every family (dense / moe / hybrid / ssm / vlm).

Structure discipline: every repeated stack is a ``lax.scan`` over stacked
per-layer parameters, so XLA compile time is O(1) in depth — essential for
the 512-device dry-run sweep.  Heterogeneous layer patterns are expressed as
*data* scanned alongside the params:

* gemma3 5:1 local:global  -> per-layer window array (0 = full attention)
* llama4 dense/MoE 1:1     -> scan over groups of (dense layer, MoE layer)
* zamba2                    -> scan over groups of (g mamba blocks, shared attn)
* xlstm sLSTM every 6th     -> scan over groups of (5 mLSTM, 1 sLSTM)

The forward returns ``(logits, new_cache, aux)`` where ``aux`` carries MoE
load-balance loss.  ``cache`` is family-specific but always a pytree with the
scan dimension leading, created by ``init_cache``.

Decode accepts ``(B, S)`` token blocks with per-row absolute positions, not
just single tokens: S > 1 serves both the paged suffix prefill (unmatched
prompt tail after a prefix-trie hit) and speculative verify (k+1 positions
scored in one step).  Attention-family caches scatter the block's KV first
and attend second with per-row causal masks, so a later cursor rewind makes
any suffix of the block dead weight rather than corruption.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import meshctx
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.config import ModelConfig
from repro.models.params import Initializer


# --------------------------------------------------------------------------
# Stacked initializer: prepend a leading layer dim to every shape.
# fan-in stays correct because Initializer.fan_in reads shape[-2].
# --------------------------------------------------------------------------
class StackedInit:
    def __init__(self, inner: Initializer, n: int):
        self._inner, self._n = inner, n

    def __getattr__(self, name):
        fn = getattr(self._inner, name)
        if name in ("normal", "fan_in", "zeros", "ones"):
            return lambda shape, *a, **k: fn((self._n,) + tuple(shape), *a, **k)
        if name == "uniform":
            return lambda shape, lo, hi: fn((self._n,) + tuple(shape), lo, hi)
        return fn


def _shard_x(x: jax.Array) -> jax.Array:
    ctx = meshctx.current()
    if ctx is None:
        return x
    spec = jax.sharding.PartitionSpec(ctx.data_axes, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(ctx.mesh, spec))


# --------------------------------------------------------------------------
# Block bodies (single layer, unstacked params)
# --------------------------------------------------------------------------
def _attn_block(p, x, cfg: ModelConfig, *, positions, window, cache=None):
    h = L.apply_norm(p, x, cfg, "attn_norm")
    a, new_kv = L.attention(p, h, cfg, positions=positions, window=window, cache=cache)
    return x + a, new_kv


def _mlp_block(p, x, cfg: ModelConfig):
    h = L.apply_norm(p, x, cfg, "mlp_norm")
    return x + L.mlp(p, h, cfg)


def _moe_block(p, x, cfg: ModelConfig):
    h = L.apply_norm(p, x, cfg, "mlp_norm")
    y, aux = M.moe_apply(p, h, cfg)
    return x + y, aux


def _init_attn_layer(si, cfg: ModelConfig) -> Dict:
    p = L.init_attention(si, cfg)
    p.update(L.init_norm(si, cfg, cfg.d_model, "attn_norm"))
    return p


def _init_mlp_layer(si, cfg: ModelConfig) -> Dict:
    p = L.init_mlp(si, cfg)
    p.update(L.init_norm(si, cfg, cfg.d_model, "mlp_norm"))
    return p


def _init_moe_layer(si, cfg: ModelConfig) -> Dict:
    p = M.init_moe(si, cfg)
    p.update(L.init_norm(si, cfg, cfg.d_model, "mlp_norm"))
    return p


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def init_lm(cfg: ModelConfig, key: jax.Array) -> Dict:
    cfg.validate()
    init = Initializer(key, cfg.dtype)
    p: Dict = {"embed": init.normal((cfg.vocab, cfg.d_model))}
    if not cfg.tie_embeddings:
        p["unembed"] = init.fan_in((cfg.d_model, cfg.vocab))
    p.update(L.init_norm(init, cfg, cfg.d_model, "final_norm"))

    fam = cfg.family
    if fam in ("dense", "vlm"):
        si = StackedInit(init, cfg.n_layers)
        lp = _init_attn_layer(si, cfg)
        lp.update(_init_mlp_layer(si, cfg))
        p["layers"] = lp
    elif fam == "moe":
        il = cfg.moe_interleave
        G = cfg.n_layers // il
        si = StackedInit(init, G)
        if il == 1:
            lp = _init_attn_layer(si, cfg)
            lp.update(_init_moe_layer(si, cfg))
            p["layers"] = lp
        else:
            assert il == 2, "moe_interleave in {1,2} supported"
            dense = _init_attn_layer(si, cfg)
            dense.update(_init_mlp_layer(si, cfg))
            moe = _init_attn_layer(si, cfg)
            moe.update(_init_moe_layer(si, cfg))
            p["groups"] = {"dense": dense, "moe": moe}
    elif fam == "hybrid":
        g = cfg.hybrid_group
        G = cfg.n_layers // (g + 1)
        rem = cfg.n_layers - G * (g + 1)
        gi = StackedInit(init, G)
        ggi = StackedInit(gi, g)  # (G, g, ...) nested stack
        p["mamba"] = S.init_mamba2(ggi, cfg)
        if rem:
            p["mamba_tail"] = S.init_mamba2(StackedInit(init, rem), cfg)
        shared = StackedInit(init, cfg.n_shared_attn)
        sp = _init_attn_layer(shared, cfg)
        sp.update(_init_mlp_layer(shared, cfg))
        p["shared_attn"] = sp
        p["group_proj"] = StackedInit(init, G).fan_in((cfg.d_model, cfg.d_model))
    elif fam == "ssm":
        k = cfg.slstm_interval
        assert k > 1 and cfg.n_layers % k == 0
        G = cfg.n_layers // k
        gi = StackedInit(init, G)
        p["mlstm"] = S.init_mlstm(StackedInit(gi, k - 1), cfg)
        p["slstm"] = S.init_slstm(gi, cfg)
    else:
        raise ValueError(fam)
    return p


def _window_array(cfg: ModelConfig) -> jax.Array:
    w = np.zeros((cfg.n_layers,), np.int32)
    for i in range(cfg.n_layers):
        w[i] = 0 if cfg.layer_is_global(i) else cfg.sliding_window
    return jnp.asarray(w)


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return {"kv": L.init_kv_cache(cfg, batch, max_len, cfg.n_layers)}
    if fam == "moe":
        return {"kv": L.init_kv_cache(cfg, batch, max_len, cfg.n_layers)}
    if fam == "hybrid":
        g = cfg.hybrid_group
        G = cfg.n_layers // (g + 1)
        rem = cfg.n_layers - G * (g + 1)
        st = S.init_mamba_state(cfg, batch)
        out = {
            "mamba": jax.tree.map(lambda a: _tile(a, (G, g)), st),
            "kv": L.init_kv_cache(cfg, batch, max_len, G),
        }
        if rem:
            out["mamba_tail"] = jax.tree.map(lambda a: _tile(a, (rem,)), st)
        return out
    if fam == "ssm":
        k = cfg.slstm_interval
        G = cfg.n_layers // k
        m = S.init_mlstm_state(cfg, batch)
        s = S.init_slstm_state(cfg, batch)
        return {
            "mlstm": jax.tree.map(lambda a: _tile(a, (G, k - 1)), m),
            "slstm": jax.tree.map(lambda a: _tile(a, (G,)), s),
        }
    raise ValueError(fam)


#: families whose layer state is attention KV only, so it can live in pages
PAGED_FAMILIES = ("dense", "vlm", "moe")


def init_paged_cache(cfg: ModelConfig, batch: int, n_pages: int,
                     page_size: int, max_pages: int) -> Dict:
    """Paged KV cache (attention-only families): a global page pool + per-
    slot page tables instead of one dense (B, max_len) region per slot.  The
    serving scheduler owns the page allocator / prefix trie metadata
    (serving/kv_cache.PagePool); recurrent families have no paged layout."""
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(f"paged KV cache requires an attention-only family, "
                         f"got {cfg.family!r}")
    return {"paged": L.init_paged_kv_cache(cfg, batch, n_pages, page_size,
                                           max_pages, cfg.n_layers)}


def _tile(a: jax.Array, lead: Tuple[int, ...]) -> jax.Array:
    return jnp.zeros(lead + a.shape, a.dtype)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def forward(
    params: Dict,
    tokens: jax.Array,                  # (B, S) int32
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array] = None,
    cache: Optional[Dict] = None,
    img_embeds: Optional[jax.Array] = None,   # vlm: (B, n_img, d)
    return_hidden: bool = False,
) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """Returns (logits (B, S[, +n_img], V), new_cache, aux_loss).
    With return_hidden=True the first output is the final-norm hidden state
    (B, S, d) instead of logits (embedding / judging paths)."""
    B, Stok = tokens.shape
    x = L.embed(tokens, params["embed"], scale=cfg.scale_embed)
    if img_embeds is not None:
        x = jnp.concatenate([img_embeds.astype(x.dtype), x], axis=1)
    Bx, Sx, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(Sx, dtype=jnp.int32)[None], (Bx, Sx))
    x = _shard_x(x)

    fam = cfg.family
    aux = jnp.float32(0.0)
    new_cache = None

    # attention caches arrive under "kv" (dense per-slot) or "paged" (global
    # page pool + page tables); the stacks scan either layout transparently
    kv_key = "paged" if (cache is not None and "paged" in cache) else "kv"
    if fam in ("dense", "vlm"):
        x, new_kv = _dense_stack(params, x, cfg, positions, cache, kv_key)
        new_cache = None if new_kv is None else {kv_key: new_kv}
    elif fam == "moe":
        x, new_kv, aux = _moe_stack(params, x, cfg, positions, cache, kv_key)
        new_cache = None if new_kv is None else {kv_key: new_kv}
    elif fam == "hybrid":
        x, new_cache = _hybrid_stack(params, x, cfg, positions, cache)
    elif fam == "ssm":
        x, new_cache = _ssm_stack(params, x, cfg, cache)
    else:
        raise ValueError(fam)

    x = L.apply_norm(params, x, cfg, "final_norm")
    if return_hidden:
        return x, new_cache, aux
    with jax.named_scope("logits"):
        logits = L.unembed(
            x, params["embed"] if cfg.tie_embeddings else params["unembed"],
            cfg.tie_embeddings)
        if cfg.final_softcap > 0:
            c = cfg.final_softcap
            logits = (c * jnp.tanh(logits.astype(jnp.float32) / c)
                      ).astype(logits.dtype)
    return logits, new_cache, aux


def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def _stack_scan(body, carry, xs, cfg: ModelConfig):
    """lax.scan over the layer stack, or a python loop when
    cfg.unroll_layers (dry-run cost calibration: XLA's HLO cost analysis
    counts while-loop bodies once, so calibration compiles must be flat).
    Either way the stack's ops carry the named scope ``layers``."""
    with jax.named_scope("layers"):
        if not cfg.unroll_layers:
            return jax.lax.scan(body, carry, xs)
        length = jax.tree.leaves(xs)[0].shape[0]
        ys = []
        for i in range(length):
            x_i = jax.tree.map(lambda a: a[i], xs)
            carry, y = body(carry, x_i)
            ys.append(y)
        if all(len(jax.tree.leaves(y)) == 0 for y in ys):
            stacked = ys[0]
        else:
            stacked = jax.tree.map(lambda *vs: jnp.stack(vs), *ys)
        return carry, stacked


def _slice_cache(kv: Optional[Dict], reshape_groups: Optional[Tuple[int, int]] = None):
    if kv is None:
        return None
    if reshape_groups is not None:
        G, per = reshape_groups
        kv = jax.tree.map(lambda a: a.reshape((G, per) + a.shape[1:]), kv)
    return kv


def _dense_stack(params, x, cfg, positions, cache, kv_key="kv"):
    kv = None if cache is None else cache[kv_key]
    windows = _window_array(cfg)  # config-derived constant (not a parameter)

    def body(carry, xs):
        h = carry
        lp, w, kv_l = xs
        h, new_kv = _attn_block(lp, h, cfg, positions=positions, window=w, cache=kv_l)
        h = _mlp_block(lp, h, cfg)
        h = _shard_x(h)
        return h, new_kv

    x, new_kv = _stack_scan(_maybe_remat(body, cfg), x, (params["layers"], windows, kv), cfg)
    return x, new_kv


def _moe_stack(params, x, cfg, positions, cache, kv_key="kv"):
    kv = None if cache is None else cache[kv_key]
    il = cfg.moe_interleave
    if il == 1:
        def body(carry, xs):
            h, aux = carry
            lp, kv_l = xs
            h, new_kv = _attn_block(lp, h, cfg, positions=positions, window=0, cache=kv_l)
            h, a = _moe_block(lp, h, cfg)
            h = _shard_x(h)
            return (h, aux + a), new_kv

        (x, aux), new_kv = _stack_scan(
            _maybe_remat(body, cfg), (x, jnp.float32(0.0)), (params["layers"], kv), cfg)
        return x, new_kv, aux / cfg.n_layers

    G = cfg.n_layers // 2
    kv2 = _slice_cache(kv, (G, 2))

    def body(carry, xs):
        h, aux = carry
        gp, kv_g = xs
        kv_d = None if kv_g is None else jax.tree.map(lambda a: a[0], kv_g)
        kv_m = None if kv_g is None else jax.tree.map(lambda a: a[1], kv_g)
        h, nk_d = _attn_block(gp["dense"], h, cfg, positions=positions, window=0, cache=kv_d)
        h = _mlp_block(gp["dense"], h, cfg)
        h, nk_m = _attn_block(gp["moe"], h, cfg, positions=positions, window=0, cache=kv_m)
        h, a = _moe_block(gp["moe"], h, cfg)
        h = _shard_x(h)
        new_kv = None if nk_d is None else jax.tree.map(
            lambda u, v: jnp.stack([u, v]), nk_d, nk_m)
        return (h, aux + a), new_kv

    (x, aux), new_kv2 = _stack_scan(
        _maybe_remat(body, cfg), (x, jnp.float32(0.0)), (params["groups"], kv2), cfg)
    new_kv = None if new_kv2 is None else jax.tree.map(
        lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), new_kv2)
    return x, new_kv, aux / G


def _hybrid_stack(params, x, cfg, positions, cache):
    g = cfg.hybrid_group
    G = cfg.n_layers // (g + 1)
    rem = cfg.n_layers - G * (g + 1)
    mamba_c = None if cache is None else cache["mamba"]
    kv = None if cache is None else cache["kv"]
    want_state = cache is not None

    def mamba_body(h, xs):
        mp, mc = xs
        y, new_mc = S.mamba2_forward(mp, h, cfg, state=mc, return_state=want_state)
        return h + y, new_mc

    shared = params["shared_attn"]

    def group_body(carry, xs):
        h, i = carry
        gp_mamba, proj, mc_g, kv_g = xs
        h, new_mc = _stack_scan(mamba_body, h, (gp_mamba, mc_g), cfg)
        sp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i % cfg.n_shared_attn, 0, keepdims=False), shared)
        h, new_kv = _attn_block(sp, h, cfg, positions=positions, window=0, cache=kv_g)
        h = _mlp_block(sp, h, cfg)
        h = h @ proj          # per-group unshared projection (zamba2)
        h = _shard_x(h)
        return (h, i + 1), (new_mc, new_kv)

    (x, _), (new_mamba, new_kv) = _stack_scan(
        _maybe_remat(group_body, cfg), (x, jnp.int32(0)),
        (params["mamba"], params["group_proj"], mamba_c, kv), cfg)

    new_tail = None
    if rem:
        tail_c = None if cache is None else cache["mamba_tail"]
        x, new_tail = _stack_scan(mamba_body, x, (params["mamba_tail"], tail_c), cfg)

    if cache is None:
        return x, None
    out = {"mamba": new_mamba, "kv": new_kv}
    if rem:
        out["mamba_tail"] = new_tail
    return x, out


def _ssm_stack(params, x, cfg, cache):
    k = cfg.slstm_interval
    G = cfg.n_layers // k
    m_c = None if cache is None else cache["mlstm"]
    s_c = None if cache is None else cache["slstm"]
    want_state = cache is not None

    def mlstm_body(h, xs):
        mp, mc = xs
        y, new_mc = S.mlstm_forward(mp, h, cfg, state=mc, return_state=want_state)
        return h + y, new_mc

    def group_body(h, xs):
        gp_m, gp_s, mc_g, sc_g = xs
        h, new_m = _stack_scan(mlstm_body, h, (gp_m, mc_g), cfg)
        y, new_s = S.slstm_forward(gp_s, h, cfg, state=sc_g, return_state=want_state)
        h = h + y
        h = _shard_x(h)
        return h, (new_m, new_s)

    x, (new_m, new_s) = _stack_scan(
        _maybe_remat(group_body, cfg), x, (params["mlstm"], params["slstm"], m_c, s_c), cfg)
    if cache is None:
        return x, None
    return x, {"mlstm": new_m, "slstm": new_s}
