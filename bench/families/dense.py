"""The dense GQA decoder family: a stack of identical pre-norm blocks, each
grouped-query attention with rotary positions (biases on q, k and v where
``attention_bias`` says) and a SwiGLU MLP, RMS norms, and a tied vocabulary
projection.  Every hidden layer attends.  It covers the configurations
``bench/configs/qwen2-1.5b.json`` (Qwen2) and ``granite-3-2b.json``
(Granite 3.0, whose four scalar multipliers are optional keys: the
embedding, attention and residual multipliers and the logits scaling).

What it gives the harness (``common.load_family``): the served weights'
layout, the float32 reference and its float8 control, the program's
``ModelConfig``, one decode step's operations and bytes, and its number of
attention layers.  Nothing of the program is imported at the top: only
``program_config`` imports it, inside the function, so the reference stays
independent of the code under test.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import HI, _mm, _q8, _rms, _rope

BYTES_PER_ELEM = 2          # weights and cache in bfloat16


def param_shapes(c: Dict[str, Any]) -> Dict[str, Any]:
    """Shape tree of the served weights (the program's layout)."""
    d, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    hd = c["head_dim"]
    q, kv, ff = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd, \
        c["intermediate_size"]
    layers = {"wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
              "wo": (L, q, d), "attn_norm_w": (L, d),
              "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d),
              "mlp_norm_w": (L, d)}
    if c["attention_bias"]:
        layers.update(bq=(L, q), bk=(L, kv), bv=(L, kv))
    return {"embed": (V, d), "final_norm_w": (d,), "layers": layers}


def logits(params, tokens, c: Dict[str, Any], fp8: bool = False):
    """(B, S, V) float32 logits of ``tokens`` (B, S) from position 0."""
    f32 = lambda a: a.astype(jnp.float32)
    H, Hk, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    # Granite's scalar multipliers; 1 (and 1/sqrt(head_dim)) where a
    # configuration has none
    em = c.get("embedding_multiplier", 1.0)
    am = c.get("attention_multiplier", 1.0 / np.sqrt(hd))
    rm = c.get("residual_multiplier", 1.0)
    ls = c.get("logits_scaling", 1.0)
    B, S = tokens.shape
    emb = f32(params["embed"])
    if fp8:
        emb = _q8(emb, 1)
    x = emb[tokens] * em
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        p = jax.tree.map(f32, p)
        h = _rms(x, p["attn_norm_w"], eps)
        q, k, v = (_mm(h, p[w], fp8) for w in ("wq", "wk", "wv"))
        if c["attention_bias"]:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = _rope(q.reshape(B, S, H, hd), theta)
        k = _rope(k.reshape(B, S, Hk, hd), theta)
        v = v.reshape(B, S, Hk, hd)
        q = q.reshape(B, S, Hk, H // Hk, hd)
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, k, precision=HI) * am
        s = jnp.where(causal, s, -jnp.inf)
        a = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1), v,
                       precision=HI).reshape(B, S, H * hd)
        x = x + rm * _mm(a, p["wo"], fp8)
        h = _rms(x, p["mlp_norm_w"], eps)
        g = jax.nn.silu(_mm(h, p["w_gate"], fp8)) * _mm(h, p["w_up"], fp8)
        return x + rm * _mm(g, p["w_down"], fp8), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, f32(params["final_norm_w"]), eps)
    return jnp.einsum("bsd,vd->bsv", x if not fp8 else _q8(x, -1), emb,
                      precision=HI) / ls


def program_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` for the configuration file: the arch's
    own config with every size taken from the file, kernels on."""
    from repro import configs
    cfg = configs.get(c["arch"], dtype=c["dtype"])
    return dataclasses.replace(
        cfg, n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], norm_eps=c["rms_norm_eps"],
        rope_theta=c["rope_theta"], qkv_bias=c["attention_bias"],
        tie_embeddings=c["tie_word_embeddings"], use_pallas=True)


def decode_weights(c: Dict[str, Any]) -> int:
    """Weight elements one decode step reads: every projection and the tied
    vocabulary projection (norms and biases left out)."""
    d, ff, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    hd = c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return c["num_hidden_layers"] * layer + V * d


def decode_cost(c: Dict[str, Any], pos: int):
    """(operations, bytes) of one decode step of one token at ``pos``: two
    operations per weight plus attention over the ``pos + 1`` cached
    positions in every layer; every weight once in bfloat16 and the live
    keys and values of every layer."""
    L, hd = c["num_hidden_layers"], c["head_dim"]
    hq, hk = c["num_attention_heads"], c["num_key_value_heads"]
    live = pos + 1
    flops = 2 * decode_weights(c) + L * 4 * hq * hd * live
    nbytes = BYTES_PER_ELEM * (decode_weights(c) + L * 2 * hk * hd * live)
    return float(flops), float(nbytes)


def attention_layers(c: Dict[str, Any]) -> int:
    """Layers that call the paged decode kernel once a step: all of them."""
    return c["num_hidden_layers"]
