"""A throwaway benchmark tree for the harness's own tests: the repository's
``BENCHMARK.json`` and ``bench/`` data, model families, metric readers and
kernel counts copied into a temporary directory,
plus a tiny configuration, two tiny mixes and their cells, added as files
only.  The tiny streamed cell reports what the repository's streamed cell
lists; the whole-answer cell, a kind the repository does not have yet,
reports the metrics such a cell would list.  The harness code itself stays where it is; only the data it finds
by name moves."""
from __future__ import annotations

import json
import os
import shutil

from bench import common

TINY_CONFIG = {
    "arch": "qwen2-1.5b", "family": "dense", "source": "test",
    "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rms_norm_eps": 1e-06, "rope_theta": 10000.0,
    "attention_bias": True, "tie_word_embeddings": True,
    "dtype": "bfloat16", "engine_max_len": 256, "reduced": [],
}
STREAM_MIX = {
    "arrivals": "poisson", "stream": True, "courses": 2, "system_bytes": 300,
    "question_bytes": [60, 66], "turns": [1, 3],
    "max_tokens": {"median": 6, "sigma": 0.3, "min": 4, "max": 8},
    "repeat_share": 0.0,
}
CACHED_MIX = {
    "arrivals": "poisson", "stream": False, "courses": 0,
    "question_bytes": [60, 66], "turns": [1, 3],
    "max_tokens": {"median": 6, "sigma": 0.3, "min": 4, "max": 8},
    "repeat_share": 0.7, "zipf_s": 1.0,
    "cache_fill": {"objects": 300, "answer_bytes": [120, 200]},
}


def cell_spec(config, mix, rate):
    return {"config": config, "traffic": mix, "rate_rps": rate,
            "grace_s": 30, "trace_seconds": 1, "sample_requests": 3,
            "cache_relevance": 0.6, "limits": {"logit_gap": 0.005},
            "why": "test"}


def make_tree(tmp: str) -> str:
    """Copy the benchmark's data into ``tmp`` and add the tiny cells.
    Returns the new ``bench`` directory."""
    bench_dir = os.path.join(tmp, "bench")
    for kind in ("configs", "families", "traffic", "workloads", "metrics",
                 "kernels"):
        shutil.copytree(os.path.join(common.BENCH_DIR, kind),
                        os.path.join(bench_dir, kind))
    shutil.copy(os.path.join(common.BENCH_DIR, "peaks.json"), bench_dir)
    bench = common.load_benchmark()

    def put(kind, name, obj):
        with open(os.path.join(bench_dir, kind, name + ".json"), "w") as f:
            json.dump(obj, f)

    put("configs", "tiny", TINY_CONFIG)
    put("traffic", "tiny-stream", STREAM_MIX)
    put("traffic", "tiny-cached", CACHED_MIX)
    put("workloads", "tiny.tiny-stream", cell_spec("tiny", "tiny-stream", 2.0))
    put("workloads", "tiny.tiny-cached", cell_spec("tiny", "tiny-cached", 3.0))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    for cell, mix in (("tiny.tiny-stream", "tiny-stream"),
                      ("tiny.tiny-cached", "tiny-cached")):
        bench["workloads"].append({"name": cell, "config": "tiny",
                                   "traffic": mix, "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "qwen2-1.5b.classroom-stream" in m.get("workloads", []):
            m["workloads"].append("tiny.tiny-stream")
    # the whole-answer cell's metrics, as a cell of that kind lists them;
    # ``idle_share.whatsapp`` is read by the reader of its stem
    bench["end_to_end"].append({
        "name": "latency_p95_ms", "unit": "ms", "better": "lower",
        "bound": 0.25, "source": "host_clock",
        "workloads": ["tiny.tiny-cached"]})
    for name, unit, layer in (("cache_hit_rate", "%", "semantic cache"),
                              ("cache_stage_ms.p50", "ms", "semantic cache"),
                              ("idle_share.whatsapp", "%", "device")):
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "program_counter", "layer": layer,
            "moves": "latency_p95_ms", "workloads": ["tiny.tiny-cached"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return bench_dir
