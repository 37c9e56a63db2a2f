"""Weights from the seed, and the comparison of served tokens with the
plain reference of the served model.

The benchmark makes the weights itself, in the layout that the
configuration's family (``bench/families/<family>.py``) gives and in the
type they are served in, on the device in one jitted call from ``--seed``;
it hands them to the program's ``Engine``.  The reference is the family's
``logits``, which imports nothing of the program: the architecture written
out in ``jax.numpy`` at float32 with matmuls at ``HIGHEST`` precision, no
kernels, no cache and no batching of its own, from the helpers below.  It
runs after the measured window, one scan over the layers, so that it never
holds more than the served weights and one layer in float32.

``fp8=True`` gives the control: the same reference with every weight and
every matmul input rounded to float8 (e4m3, scaled per output channel and
per token), the precision below the served bfloat16 that a later change
might be tempted to serve in.
"""
from __future__ import annotations

import functools
import json
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import common

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def param_shapes(c: Dict[str, Any],
                 bench_dir: str = common.BENCH_DIR) -> Dict[str, Any]:
    """Shape tree of the served weights (the program's layout), as the
    configuration's family gives it."""
    return common.load_family(c, bench_dir).param_shapes(c)


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


def make_weights(c: Dict[str, Any], seed: int,
                 bench_dir: str = common.BENCH_DIR):
    """All weights in ``c["dtype"]``, drawn on the device in ONE jitted call.
    ``seed`` may exceed 32 bits; it is folded into the key."""
    shapes = param_shapes(c, bench_dir)
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


@functools.partial(jax.jit, static_argnames=("cfg_json", "fp8", "bench_dir"))
def _gaps(params, tokens, served, mask, cfg_json, fp8, bench_dir):
    c = json.loads(cfg_json)
    logits = common.load_family(c, bench_dir).logits
    ref = logits(params, tokens, c)
    best = ref.max(-1)
    if fp8:
        pick = jnp.argmax(logits(params, tokens, c, fp8=True), -1)
    else:
        pick = served
    got = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    return jnp.where(mask, best - got, 0.0)


def token_gaps(params, rows, c: Dict[str, Any], fp8: bool = False,
               width: int = 256,
               bench_dir: str = common.BENCH_DIR) -> np.ndarray:
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
    return np.asarray(_gaps(params, jnp.asarray(tokens), jnp.asarray(served),
                            jnp.asarray(mask), json.dumps(c, sort_keys=True),
                            fp8, bench_dir))
