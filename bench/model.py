"""Weights from the seed, and the plain reference of the served models.

The benchmark makes the weights itself, in the program's parameter layout
and in the type they are served in, on the device in one jitted call from
``--seed``; it hands them to the program's ``Engine``.  The reference below
imports nothing of the program: it is the dense GQA decoder of the
configuration file written out in ``jax.numpy`` at float32 with matmuls at
``HIGHEST`` precision, no kernels, no cache and no batching of its own.  It
runs after the measured window, one scan over the layers, so that it never
holds more than the served weights and one layer in float32.

``fp8=True`` gives the control: the same reference with every weight and
every matmul input rounded to float8 (e4m3, scaled per output channel and
per token), the precision below the served bfloat16 that a later change
might be tempted to serve in.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _shapes(c: Dict[str, Any]) -> Dict[str, Any]:
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


def param_shapes(c: Dict[str, Any]) -> Dict[str, Any]:
    """Shape tree of the served weights (the program's layout)."""
    return _shapes(c)


def _draw(key, path: str, shape, dtype):
    name = path.split("/")[-1]
    if name.endswith("norm_w"):                       # around 1
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name in ("bq", "bk", "bv"):
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "embed":
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:                                             # fan-in scaled
        x = jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-2])
    return x.astype(dtype)


def make_weights(c: Dict[str, Any], seed: int):
    """All weights in ``c["dtype"]``, drawn on the device in ONE jitted call.
    ``seed`` may exceed 32 bits; it is folded into the key."""
    shapes = _shapes(c)
    leaves = []

    def walk(tree, prefix):
        for k in sorted(tree):
            v = tree[k]
            p = f"{prefix}/{k}" if prefix else k
            if isinstance(v, dict):
                walk(v, p)
            else:
                leaves.append((p, v))
    walk(shapes, "")
    dtype = jnp.dtype(c["dtype"])

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(leaves))
        flat = {p: _draw(k, p, s, dtype) for k, (p, s) in zip(keys, leaves)}
        out: Dict[str, Any] = {}
        for p, x in flat.items():
            node = out
            parts = p.split("/")
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = x
        return out

    hi, lo = divmod(int(seed), 2 ** 31)
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi % (2 ** 31))
    return init(key)


# -- the reference ------------------------------------------------------------

def _q8(x, axis):
    """Round to float8 e4m3 with an amax scale along ``axis`` (kept dims)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-12) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    """x (..., k) @ w (k, n) in float32; fp8 rounds x per token and w per
    output channel first."""
    if fp8:
        x = _q8(x, -1)
        w = _q8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (B, S, H, hd) at positions 0..S-1, rotating the two halves."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _gaps(params, tokens, served, mask, cfg_items, fp8):
    c = dict(cfg_items)
    ref = logits(params, tokens, c)
    best = ref.max(-1)
    if fp8:
        pick = jnp.argmax(logits(params, tokens, c, fp8=True), -1)
    else:
        pick = served
    got = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    return jnp.where(mask, best - got, 0.0)


def token_gaps(params, rows, c: Dict[str, Any], fp8: bool = False,
               width: int = 256) -> np.ndarray:
    """The gap, in reference logits, by which each served token (or, with
    ``fp8``, the control's first choice) lies below the reference's best.

    ``rows``: ``[(prompt_ids, served_ids)]``.  Row ``b`` is run from
    position 0 over ``prompt + served[:-1]``; the logits at the last prompt
    position and after predict ``served``.  Returns (n_rows, width) gaps,
    0 where nothing is compared."""
    B = len(rows)
    tokens = np.zeros((B, width), np.int32)
    served = np.zeros((B, width), np.int32)
    mask = np.zeros((B, width), bool)
    for b, (p, s) in enumerate(rows):
        seq = list(p) + list(s[:-1])
        if len(seq) > width:
            raise ValueError(f"row {b}: {len(seq)} tokens > {width}")
        tokens[b, :len(seq)] = seq
        lo = len(p) - 1
        served[b, lo:lo + len(s)] = s
        mask[b, lo:lo + len(s)] = True
    items = tuple(sorted((k, v) for k, v in c.items()
                         if isinstance(v, (int, float, str, bool))))
    return np.asarray(_gaps(params, jnp.asarray(tokens), jnp.asarray(served),
                            jnp.asarray(mask), items, fp8))
