"""Token samplers: greedy / temperature / top-k, pure functions of (logits, key).

``temperature == 0`` (greedy, the default) is the mode the speculative
decoder requires: acceptance compares the draft's argmax against the
verifier's argmax position-by-position, which is only meaningful when both
sides are deterministic.  The scheduler's spec gate checks this config, not
the sample() call site.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    temperature: float = 0.0   # 0 = greedy
    top_k: int = 0             # 0 = no truncation


def sample(logits: jax.Array, key: jax.Array, sc: SamplerConfig) -> jax.Array:
    """logits: (B, V) -> (B,) int32, under the named scope ``sampler``."""
    with jax.named_scope("sampler"):
        if sc.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        x = logits.astype(jnp.float32) / sc.temperature
        if sc.top_k > 0:
            kth = jax.lax.top_k(x, sc.top_k)[0][:, -1:]
            x = jnp.where(x < kth, -jnp.inf, x)
        return jax.random.categorical(key, x, axis=-1).astype(jnp.int32)
