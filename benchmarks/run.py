"""Benchmark harness: one module per paper table/figure (+ roofline/kernels).

Prints ``name,us_per_call,derived`` CSV.  Select subsets with
``python -m benchmarks.run [fig1 fig45 fig6 fig7 latency kernels roofline]``.
"""
from __future__ import annotations

import sys
import traceback

MODULES = {
    "fig1": ("benchmarks.context_lastk", "Fig 1a/1b last-k context"),
    "fig45": ("benchmarks.model_selection", "Fig 4/5 model selection"),
    "fig6": ("benchmarks.smart_context", "Fig 6 smart context"),
    "fig7": ("benchmarks.smart_cache", "Fig 7 smart cache"),
    "latency": ("benchmarks.serving_latency", "§5.1 latency table"),
    "throughput": ("benchmarks.proxy_throughput", "batched pipeline rps"),
    "kernels": ("benchmarks.kernel_bench", "kernel microbench"),
    "roofline": ("benchmarks.roofline_table", "§Roofline table"),
}


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    want = sys.argv[1:] or list(MODULES)
    print("name,us_per_call,derived")
    failed = []
    for key in want:
        mod_name, _desc = MODULES[key]
        try:
            mod = __import__(mod_name, fromlist=["run"])
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{str(derived).replace(',', ';')}")
        except Exception:
            failed.append(key)
            traceback.print_exc()
            print(f"{key}.FAILED,0.0,exception")
    if failed:
        raise SystemExit(f"benchmark failures: {failed}")


if __name__ == "__main__":
    main()
