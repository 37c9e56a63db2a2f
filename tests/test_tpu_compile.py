"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing here runs: each test lowers a kernel (or a whole decode step) at
qwen2-1.5b widths against a ``v5e:2x2`` topology that the TPU compiler
describes without a chip attached, and asserts that Mosaic accepted it —
the compiled HLO holds a ``tpu_custom_call``.  This catches what interpret
mode cannot: ops Mosaic cannot lower, misaligned blocks, VMEM overuse.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file.  The kernels pick interpret mode
by ``repro.kernels.interpret`` (interpreted off the TPU), so each test
steers that rule to the compiled branch for its own duration.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs, kernels
from repro.kernels.cache_topk.kernel import (shortlist_topk_pallas,
                                             similarity_topk_pallas)
from repro.kernels.decode_attention.kernel import (
    paged_decode_attention_pallas, paged_prefill_attention_pallas)
from repro.models import init_model, init_paged_cache
from repro.serving.engine import decode_step

CFG = configs.get("qwen2-1.5b")          # published widths, bf16
HQ, HKV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.hd
PAGE = 16
N_PAGES = 1024                           # 16k cached tokens per layer
MAX_PAGES = 64                           # 1024-token context per slot
SLOTS = 8
EMBED_DIM = 64                           # semantic-cache embedding width
DB_ROWS = 16_384


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def compiled(monkeypatch):
    """Steer the interpret rule to the compiled (TPU) branch."""
    monkeypatch.setattr(kernels, "interpret", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_args(one_chip, q_shape):
    bf16 = jnp.bfloat16
    return (_sds(q_shape, bf16, one_chip),
            _sds((N_PAGES, PAGE, HKV, HD), bf16, one_chip),
            _sds((N_PAGES, PAGE, HKV, HD), bf16, one_chip),
            _sds((SLOTS, MAX_PAGES), jnp.int32, one_chip),
            _sds((SLOTS,), jnp.int32, one_chip))


def test_paged_decode_kernel_compiles(one_chip, compiled):
    args = _paged_args(one_chip, (SLOTS, HQ, HD))
    assert "tpu_custom_call" in _hlo(paged_decode_attention_pallas, *args)


@pytest.mark.parametrize("S", [4, 64])
def test_paged_prefill_kernel_compiles(one_chip, compiled, S):
    args = _paged_args(one_chip, (SLOTS, S, HQ, HD))
    assert "tpu_custom_call" in _hlo(paged_prefill_attention_pallas, *args)


def test_similarity_topk_kernel_compiles(one_chip, compiled):
    fn = functools.partial(similarity_topk_pallas, k=4)
    hlo = _hlo(fn, _sds((8, EMBED_DIM), jnp.float32, one_chip),
               _sds((DB_ROWS, EMBED_DIM), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("L", [200, 4096])
def test_shortlist_topk_kernel_compiles(one_chip, compiled, L):
    fn = functools.partial(shortlist_topk_pallas, k=4)
    Q = 5
    hlo = _hlo(fn, _sds((Q, EMBED_DIM), jnp.float32, one_chip),
               _sds((DB_ROWS, EMBED_DIM), jnp.float32, one_chip),
               _sds((DB_ROWS,), jnp.int32, one_chip),
               _sds((Q, L), jnp.int32, one_chip),
               _sds((Q,), jnp.int32, one_chip),
               _sds((Q,), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


def test_full_width_paged_decode_step_compiles(one_chip, compiled):
    """One whole qwen2-1.5b decode step over the paged cache — 28 layers at
    d_model 1536 and the 151,936-token vocab, bf16 — with the paged kernel
    compiled inside it, and small enough for one 16 GB chip."""
    cfg = dataclasses.replace(CFG, use_pallas=True)
    params = jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: init_paged_cache(
        cfg, SLOTS, N_PAGES, PAGE, MAX_PAGES))
    on_chip = lambda a: _sds(a.shape, a.dtype, one_chip)  # noqa: E731
    params, cache = jax.tree.map(on_chip, (params, cache))
    step = jax.jit(functools.partial(decode_step, cfg=cfg))
    compiled_step = step.lower(
        params, _sds((SLOTS, 1), jnp.int32, one_chip),
        _sds((SLOTS, 1), jnp.int32, one_chip), cache).compile()
    assert "tpu_custom_call" in compiled_step.as_text()
    mem = compiled_step.memory_analysis()
    if mem is not None:
        used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        assert used < 16e9, f"decode step needs {used / 1e9:.1f} GB"
