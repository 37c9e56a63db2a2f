"""``tile_t`` selection for the decode-attention kernels.

The dense decode kernel tiles the KV-time axis; the best tile trades VMEM
residency against grid-step overhead and depends on the cache depth and
dtype (bf16 tiles stream twice the elements per byte).  Instead of the old
hardcoded ``tile_t=512``, callers resolve the tile from a small table keyed
by ``(dtype, cache-depth bucket)``, with ``DEFAULT_TILE_T`` as the fallback
for points not in it.  NOT MEASURED ON A CHIP: the only sweep behind the
table timed the jnp oracle on a CPU (``benchmarks/kernel_bench.py``), so
the entries are untested guesses until a chip run tunes them.  For the *paged* kernels the time tile is
pinned to the page size by construction, so ``page_size`` (when given)
rounds the pick down to a whole number of pages.

``kernel_bench.py`` prints the resolved choice next to each kernel row so
a tuning regression is visible in the benchmark output, not silent.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

DEFAULT_TILE_T = 512

# (canonical dtype name, cache-depth bucket) -> tile_t.  Buckets are the
# power-of-two depth the cache pads to.  Not measured on a chip (see the
# module docstring).
_TABLE = {
    ("bfloat16", 512): 256,
    ("bfloat16", 1024): 512,
    ("bfloat16", 2048): 512,
    ("bfloat16", 4096): 1024,
    ("bfloat16", 8192): 1024,
    ("float32", 512): 256,
    ("float32", 1024): 256,
    ("float32", 2048): 512,
    ("float32", 4096): 512,
    ("float32", 8192): 512,
}


def _bucket(n: int) -> int:
    return max(512, 1 << (max(n, 1) - 1).bit_length())


def tile_choice(max_len: int, dtype, page_size: Optional[int] = None
                ) -> Tuple[int, str]:
    """Resolve ``(tile_t, source)`` for a cache of depth ``max_len``.

    ``source`` is ``"table"`` when the (dtype, depth-bucket) point is in
    the table and ``"default"`` otherwise — benchmark output discloses it.
    """
    name = jnp.dtype(dtype).name
    key = (name, _bucket(max_len))
    tile, source = _TABLE.get(key, DEFAULT_TILE_T), \
        ("table" if key in _TABLE else "default")
    if page_size is not None and page_size > 0:
        tile = max(page_size, tile // page_size * page_size)
    return min(tile, _bucket(max_len)), source


def pick_tile_t(max_len: int, dtype, page_size: Optional[int] = None) -> int:
    return tile_choice(max_len, dtype, page_size)[0]
