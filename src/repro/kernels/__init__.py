"""Pallas TPU kernels for the hot spots LLMBridge optimises: semantic-cache
similarity search (``cache_topk``) and paged decode/prefill attention
(``decode_attention``), plus ``flash_attention``.

``interpret()`` is the one rule that decides how a Pallas kernel runs:
compiled by Mosaic on a TPU, through the Pallas interpreter on any other
backend (the CPU test path).  Every kernel entry point asks it while it
traces; no wrapper or caller chooses on its own, so nothing on a TPU runs
interpreted.  Tests that need the compiled branch off the chip (compiling
for a described TPU topology) patch this function inside the test.
"""
from __future__ import annotations

import jax


def interpret() -> bool:
    """True when Pallas kernels must run interpreted: on every backend but
    the TPU."""
    return jax.default_backend() != "tpu"
