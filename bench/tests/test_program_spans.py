"""The readers of the program's spans (``bench/program_spans.py`` and the
six metrics on it) on synthetic spans with known answers: two interleaved
streams, a ring that dropped spans from inside the window, the idle time
by span; then the tiny traced cell, which lists the six, reads a value for
each on the CPU."""
import json
import os
import time
import types

import numpy as np
import pytest

from bench import common, program_spans, run, trace
from bench.tests import tiny
from repro.core import telemetry
from repro.core.telemetry import Span

SEED = 2 ** 31 + 91
METRICS = {"token_host_ms.p50": ("ms", "adapter and scheduler",
                                 "itl_p95_ms"),
           "token_host_cpu_ms.mean": ("ms", "adapter and scheduler",
                                      "itl_p95_ms"),
           "device_wait_ms.p50": ("ms", "engine and model", "itl_p95_ms"),
           "sse_lag_ms.p50": ("ms", "proxy pipeline", "itl_p95_ms"),
           "sched_build_ms.p50": ("ms", "adapter and scheduler",
                                  "ttft_p50_ms"),
           "decode_steps_per_token": ("calls/token", "adapter and scheduler",
                                      "itl_p95_ms")}

_ids = iter(range(1, 10 ** 6))


def _span(name, rid, thread, t0, t1, parent=None, cpu=None, **attrs):
    c0 = 10.0 + t0
    return Span(next(_ids), parent, rid, name, thread, t0, t1, c0,
                c0 + (t1 - t0 if cpu is None else cpu), attrs or None)


def _two_streams():
    """Streams A (worker thread 1, handler 11) and B (2, 12), interleaved:
    A's host intervals 6 and 6 ms, B's 8 ms; waits 4, 4, 4 ms (A) and 3, 3
    ms (B); chunk lags 1 and 3 ms (A), 2 ms (B); builds 1.5 and 2.5 ms.
    The host intervals' CPU time is 2 ms each on A, 5 ms on B (the waits
    use none)."""
    S = []
    for rid, w, h, t in (("A", 1, 11, 1.0), ("B", 2, 12, 1.002)):
        S.append(_span("http.request", rid, h, t - 0.01, t + 0.05))
    S.append(_span("adapter.scheduler_build", "A", 1, 0.99, 0.9915))
    S.append(_span("adapter.scheduler_build", "B", 2, 0.992, 0.9945))
    for rid, w, waits in (("A", 1, [(1.000, 1.004), (1.010, 1.014),
                                    (1.020, 1.024)]),
                          ("B", 2, [(1.002, 1.005), (1.013, 1.016)])):
        cpu = 0.0
        for k, (a, b) in enumerate(waits):
            sp = _span("sched.wait", rid, w, a, b, cpu=0.0)
            if k:                       # CPU time since the last wait
                cpu += 0.002 if rid == "A" else 0.005
            S.append(sp._replace(c0=100.0 + cpu, c1=100.0 + cpu))
    for rid, w, h, seq, emit_end, write_end in (
            ("A", 1, 11, 0, 1.0045, 1.0055), ("A", 1, 11, 1, 1.0145, 1.0175),
            ("B", 2, 12, 0, 1.0055, 1.0075)):
        S.append(_span("stream.emit", rid, w, emit_end - 1e-4, emit_end,
                       seq=seq))
        S.append(_span("http.sse_write", rid, h, write_end - 2e-4,
                       write_end, seq=seq))
    # the final chunk (no sequence number) and a request not in the window
    S.append(_span("http.sse_write", "A", 11, 1.03, 1.031, seq=-1))
    S.append(_span("sched.wait", "warm-0", 3, 0.5, 0.6))
    S.append(_span("sched.wait", "A", 1, 200.0, 200.1))     # after the window
    return S


def _ctx(spans, dropped=0, records=None, monkeypatch=None):
    monkeypatch.setattr(telemetry, "snapshot", lambda: (spans, dropped))
    recs = records or [
        {"rid": "A", "ok": True, "stream": True, "n_tokens": 4,
         "sent": 0.98, "end": 1.06, "deltas": [1.0, 1.011, 1.021, 1.03]},
        {"rid": "B", "ok": True, "stream": True, "n_tokens": 3,
         "sent": 0.985, "end": 1.06, "deltas": [1.003, 1.014, 1.02]}]
    return types.SimpleNamespace(before={"t": 0.9}, after={"t": 100.0},
                                 records=recs, trace=None)


def _read(name, ctx):
    return common.load_reader("metrics", name).read(ctx)


def test_readers_on_two_interleaved_streams(monkeypatch):
    ctx = _ctx(_two_streams(), monkeypatch=monkeypatch)
    assert _read("token_host_ms.p50", ctx) == pytest.approx(6.0)
    assert _read("token_host_cpu_ms.mean", ctx) == pytest.approx(3.0)
    assert _read("device_wait_ms.p50", ctx) == pytest.approx(4.0)
    assert _read("sse_lag_ms.p50", ctx) == pytest.approx(2.0)
    assert _read("sched_build_ms.p50", ctx) == pytest.approx(2.0)
    assert _read("decode_steps_per_token", ctx) == pytest.approx(5 / 7)


def test_a_failed_request_is_left_out(monkeypatch):
    ctx = _ctx(_two_streams(), monkeypatch=monkeypatch)
    ctx.records[1]["ok"] = False
    assert _read("device_wait_ms.p50", ctx) == pytest.approx(4.0)
    assert _read("token_host_ms.p50", ctx) == pytest.approx(6.0)
    assert _read("decode_steps_per_token", ctx) == pytest.approx(3 / 4)


def test_a_window_the_ring_dropped_spans_from_reads_nothing(monkeypatch):
    spans = [s for s in _two_streams() if s.t0 > 0.9]
    ctx = _ctx(spans, dropped=3, monkeypatch=monkeypatch)
    for name in METRICS:
        assert _read(name, ctx) is None, name
    # spans dropped before the window opened cost it nothing
    early = _span("sched.wait", "warm-1", 3, 0.1, 0.2)
    ctx = _ctx([early] + spans, dropped=3, monkeypatch=monkeypatch)
    assert _read("device_wait_ms.p50", ctx) == pytest.approx(4.0)


def test_no_spans_read_nothing(monkeypatch):
    ctx = _ctx([], monkeypatch=monkeypatch)
    for name in METRICS:
        assert _read(name, ctx) is None, name


def test_token_cycle_pairs_host_and_wait(monkeypatch):
    ctx = _ctx(_two_streams(), monkeypatch=monkeypatch)
    cyc = program_spans.token_cycle(ctx, program_spans.window(ctx))
    assert cyc["tokens"] == 3
    assert cyc["host_ms"] == pytest.approx(6.0)
    assert cyc["cycle_ms"] == pytest.approx(10.0)   # 6+4, 6+4, 8+3
    gaps = [11, 10, 9, 11, 6]
    assert cyc["delta_gap_ms"] == pytest.approx(float(np.median(gaps)))


def test_idle_time_by_innermost_span(monkeypatch):
    """One stream: idle from its send to the first op lands on the handler
    (the worker is only in the model stage); idle between two ops lands on
    the worker's leaf spans."""
    S = [_span("http.request", "A", 11, 0.99, 1.03),
         _span("http.sse_write", "A", 11, 1.004, 1.009, seq=0)]
    model = _span("proxy.stage.model", "A", 1, 0.99, 1.03, parent=S[0].id)
    step = _span("sched.step", "A", 1, 1.004, 1.015, parent=model.id)
    S += [model, step,
          _span("sched.update", "A", 1, 1.004, 1.006, parent=step.id),
          _span("adapter.detokenize", "A", 1, 1.006, 1.008, parent=model.id),
          _span("sched.dispatch", "A", 1, 1.008, 1.010, parent=step.id)]
    ctx = _ctx(S, monkeypatch=monkeypatch, records=[
        {"rid": "A", "ok": True, "stream": True, "n_tokens": 2,
         "sent": 0.99, "end": 1.03, "deltas": [1.0, 1.01]}])
    ctx.trace = trace.Trace(t0=0.99, t1=1.02, ops=[
        ("%a", 1.000, 1.004), ("%b", 1.010, 1.020)], modules=[], spans=[],
        device_planes=["/device:TPU:0"])
    idle = program_spans.idle_by_span(ctx, program_spans.window(ctx))
    assert idle == pytest.approx({"http.request": 0.010,
                                  "sched.update": 0.002,
                                  "adapter.detokenize": 0.002,
                                  "sched.dispatch": 0.002})


def test_span_cost_is_a_time():
    assert 0 < program_spans.span_cost(1000) < 1e-3


# -- the tiny traced cell ---------------------------------------------------------

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("spans_tree"))
    bench_dir = tiny.make_tree(tmp)
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    have = {m["name"] for m in bench["per_layer"]}
    for name, (unit, layer, moves) in METRICS.items():
        if name not in have:
            bench["per_layer"].append({
                "name": name, "unit": unit, "better": "lower",
                "source": "program_counter", "layer": layer,
                "moves": moves, "workloads": ["tiny.tiny-stream"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    return bench_dir


def test_the_tiny_traced_cell_reads_all_six(tree):
    out = run.run_cell("tiny.tiny-stream", SEED, 3.0, True, bench_dir=tree,
                       require_tpu=False, t_start=time.monotonic())
    assert out["correct"], out["checks"]
    got = out["metrics"]
    for name in METRICS:
        assert name in got, name
        assert got[name]["value"] > 0, name
    assert got["decode_steps_per_token"]["value"] == pytest.approx(
        got["decode_calls_per_token"]["value"])
