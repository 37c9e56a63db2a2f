"""The harness end to end on the CPU at a tiny size: a cell, a mix, a
configuration and a metric added as files only are found by name and run
through the front door; ``correct`` comes out true on the sound path and
false on each fault these cells can have; and a run without a TPU, or
outside a checkout, fails and prints no result."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import common, run
from bench.tests import tiny

SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_tree"))
    bench_dir = tiny.make_tree(tmp)
    # a new per-layer metric, added as a file and an entry only
    with open(os.path.join(bench_dir, "metrics", "answered_share.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    return 100.0 * sum(r['ok'] for r in ctx.records)"
                " / len(ctx.records)\n")
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"].append({
        "name": "answered_share", "unit": "%", "better": "higher",
        "source": "host_clock", "layer": "proxy pipeline",
        "moves": "ttft_p50_ms", "workloads": ["tiny.tiny-stream"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    return bench_dir


def _run(tree, cell, trace=False, fault=None, seconds=3.0):
    return run.run_cell(cell, SEED, seconds, trace, bench_dir=tree,
                        require_tpu=False, t_start=time.monotonic(),
                        fault=fault)


def test_new_cell_mix_config_and_metric_found_by_name(tree):
    out = _run(tree, "tiny.tiny-stream", trace=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 6
    assert out["metrics"]["answered_share"]["value"] == 100.0
    assert out["metrics"]["prefill_tokens_per_req"]["value"] == 64.0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"logit_gap", "unmatched", "cache_wrong",
                                  "wire_mismatch", "lost"}
    assert "busy_s" in out["device"] and "window_s" in out["device"]


def test_end_to_end_metrics_of_a_streamed_cell(tree):
    out = _run(tree, "tiny.tiny-stream")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_a_listed_metric_with_nothing_to_read_fails_the_run(tree):
    # on the CPU the kernels run interpreted, so the readers of the decode
    # step and its kernel find nothing in the trace
    with pytest.raises(RuntimeError, match="decode_step_ms"):
        run.run_cell("tiny.tiny-stream", SEED, 3.0, True, bench_dir=tree,
                     require_tpu=False, t_start=time.monotonic(),
                     strict=True)


def test_cached_cell_serves_from_the_cache_and_checks_it(tree):
    out = _run(tree, "tiny.tiny-cached")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"latency_p95_ms", "setup_s"}


def _altered_token(server):
    """A token altered where it is produced: the decode step's logits put
    one fixed id first."""
    decode = server.engine.decode

    def bad(tokens, positions, cache):
        logits, cache = decode(tokens, positions, cache)
        if tokens.shape[1] == 1:
            logits = logits.at[..., 7].add(1e4)
        return logits, cache
    server.engine.decode = bad


def _altered_cache_answer(server):
    """A cache answer altered where it is produced: another object's text."""
    cache = server.bridge.cache
    get = cache.smart_get_batch

    def bad(*a, **kw):
        results, usages = get(*a, **kw)
        out = []
        for hit, text, types, tq in results:
            if hit:
                text = text[:-5] + "XXXXX"
            out.append((hit, text, types, tq))
        return out, usages
    cache.smart_get_batch = bad


def _dropped_delta(server):
    """A streamed delta lost on its way to the wire."""
    stream = server.bridge.request_stream

    def bad(preq, *a, **kw):
        for k, chunk in enumerate(stream(preq, *a, **kw)):
            if k != 1:
                yield chunk
    server.bridge.request_stream = bad


@pytest.mark.parametrize("cell,fault,number", [
    ("tiny.tiny-stream", _altered_token, "logit_gap"),
    ("tiny.tiny-cached", _altered_cache_answer, "cache_wrong"),
    ("tiny.tiny-stream", _dropped_delta, "wire_mismatch"),
])
def test_a_broken_timed_path_is_not_correct(tree, cell, fault, number):
    out = _run(tree, cell, fault=fault)
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"]


def _cli(args, cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_fails_without_a_result():
    p = _cli(["bench/run.py", "--workload", "qwen2-1.5b.classroom-stream",
              "--seed", "1", "--seconds", "2", "--trace", "0"],
             common.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_outside_a_checkout_fails_without_a_result(tmp_path):
    import shutil
    shutil.copytree(common.BENCH_DIR, tmp_path / "bench")
    shutil.copy(os.path.join(common.ROOT, "BENCHMARK.json"), tmp_path)
    p = _cli(["bench/run.py", "--workload", "qwen2-1.5b.classroom-stream",
              "--seed", "1", "--seconds", "2", "--trace", "0"],
             str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
