"""Compile a configuration's serving path for a described TPU v5e chip, with
no chip attached: the paged decode and prefill attention kernels at its
head geometry, and one whole paged decode step at its published widths.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py --config granite-3-2b

Nothing runs, so this says nothing about results or times; it shows what
the chip's compiler would refuse and the step's memory.
A script and not a test: only one process at a time may load the TPU
library, and ``tests/test_tpu_compile.py`` already does so under pytest.
"""
from __future__ import annotations

import argparse
import functools
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import common, harness
    from repro import kernels
    from repro.kernels.decode_attention.kernel import (
        paged_decode_attention_pallas, paged_prefill_attention_pallas)
    from repro.models import init_model, init_paged_cache
    from repro.serving.engine import decode_step

    jax.config.update("jax_enable_compilation_cache", False)
    c = common.load_json("configs", args.config)
    cfg = harness.program_config(c)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    kernels.interpret = lambda: False          # the compiled branch

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    P, MP, NP, B = 16, 16, 17, 1               # one slot, as the adapter runs
    bf = jnp.bfloat16
    pages = sds((NP, P, cfg.n_kv_heads, cfg.hd), bf)
    table, pos = sds((B, MP), jnp.int32), sds((B,), jnp.int32)
    ok = True
    for name, fn, q in (
            ("paged_decode_attention", paged_decode_attention_pallas,
             sds((B, cfg.n_heads, cfg.hd), bf)),
            ("paged_prefill_attention", paged_prefill_attention_pallas,
             sds((B, 64, cfg.n_heads, cfg.hd), bf))):
        t = time.perf_counter()
        try:
            comp = jax.jit(fn).lower(q, pages, pages, table, pos).compile()
        except Exception as e:  # noqa: BLE001 — report what the compiler said
            print(f"FAIL {name}: {type(e).__name__}: {str(e)[:2000]}")
            ok = False
            continue
        calls = sorted(set(re.findall(r'custom_call_target="([^"]+)"',
                                      comp.as_text())))
        print(f"ok {name}: compiled in {time.perf_counter() - t:.2f}s, "
              f"custom calls {calls}")
    params = jax.eval_shape(functools.partial(init_model, cfg),
                                  jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, B, NP, P, MP))
    cache = jax.tree.map(lambda a: sds(a.shape, a.dtype), cache)
    for S in (1, 64):
        t = time.perf_counter()
        try:
            comp = jax.jit(functools.partial(decode_step, cfg=cfg)).lower(
                params, sds((B, S), jnp.int32), sds((B, S), jnp.int32),
                cache).compile()
        except Exception as e:  # noqa: BLE001
            print(f"FAIL decode_step S={S}: {type(e).__name__}: "
                  f"{str(e)[:2000]}")
            ok = False
            continue
        mem = comp.memory_analysis()
        print(f"ok decode_step S={S}: compiled in "
              f"{time.perf_counter() - t:.2f}s; tpu_custom_call "
              f"{'tpu_custom_call' in comp.as_text()}; argument bytes "
              f"{mem.argument_size_in_bytes}, temp bytes "
              f"{mem.temp_size_in_bytes}")
    print("ALL OK" if ok else "SOME FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
