"""The trace reduction: device ops and programs, host spans and the clock
alignment, busy and idle time, and the kernel readers, on a small trace."""
import json
import os
import types

import pytest

from bench import common, harness, trace

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_v5e.json")


def _profile(doc):
    """A ``ProfileData`` look-alike from the recorded trace's JSON form."""
    ev = lambda e: types.SimpleNamespace(
        name=e["name"], start_ns=e["start_ns"], duration_ns=e["duration_ns"])
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=p["name"], lines=[
            types.SimpleNamespace(name=ln["name"],
                                  events=[ev(e) for e in ln["events"]])
            for ln in p["lines"]])
        for p in doc["planes"]])


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        doc = json.load(f)
    return doc, trace.reduce_profile(_profile(doc), doc["m0"], doc["m1"])


def test_reduction_aligns_clocks_and_keeps_device_events(recorded):
    doc, tr = recorded
    assert tr.device_planes and all("TPU" in p for p in tr.device_planes)
    assert len(tr.ops) == doc["n_ops"] and len(tr.modules) == doc["n_modules"]
    # every device event lies between the start and end marks
    assert tr.t0 - 1e-3 <= tr.ops[0][1] and tr.ops[-1][2] <= tr.t1 + 1e-3
    assert all(n.startswith("bench.") for n, _, _ in tr.spans)


def test_busy_and_idle(recorded):
    doc, tr = recorded
    busy = trace.busy(tr)
    assert busy == sorted(busy)
    assert all(a[1] < b[0] for a, b in zip(busy, busy[1:]))   # merged
    total = trace.length(busy)
    assert 0 < total <= tr.t1 - tr.t0
    assert total == pytest.approx(doc["busy_s"], rel=1e-6)
    idle = trace.gaps(busy, tr.t0, tr.t1)
    assert trace.length(idle) + total == pytest.approx(tr.t1 - tr.t0)


def test_interval_arithmetic_by_hand():
    u = trace.union([(0, 2), (1, 3), (5, 6), (7, 9)], 0.5, 8)
    assert u == [(0.5, 3), (5, 6), (7, 8)]
    assert trace.length(u) == pytest.approx(4.5)
    assert trace.intersect(u, [(2, 5.5), (7.5, 10)]) == \
        [(2, 3), (5, 5.5), (7.5, 8)]
    assert trace.gaps(u, 0, 10) == [(0, 0.5), (3, 5), (6, 7), (8, 10)]
    spans = [("bench.a", 0, 10), ("bench.b", 2, 4)]
    assert trace.host_activity(spans, 3) == "bench.b"
    assert trace.host_activity(spans, 5) == "bench.a"
    assert trace.host_activity(spans, 11) == "no span"


def test_decode_kernel_and_steps_found_by_name(recorded):
    doc, tr = recorded
    cell = harness.load_cell("qwen2-1.5b.classroom-stream")
    ctx = harness.Context(cell=cell, config=cell.config, records=[],
                          before={}, after={}, setup_s=0.0, calls=[],
                          searches=[], peaks=common.peaks("TPU v5 lite"),
                          cache_dim=64, trace=tr)
    k = ctx.kernel("paged_decode_attention")
    assert len(ctx.kernel_ops(k)) == doc["n_decode_kernels"]
    steps = ctx.decode_steps()
    assert len(steps) == doc["n_decode_steps"] == 1
    # one decode kernel per layer in the decode step
    assert doc["n_decode_kernels"] == cell.config["num_hidden_layers"]
    s, e = steps[0]
    assert all(s <= a <= e for a, _ in ctx.kernel_ops(k))
    # the prefill kernel of the same cell is not taken for the decode one
    prefill = "%closed_call.7 = bf16[2,384,128]{2,1,0} custom-call(%x)"
    assert not k.is_call(prefill, cell.config)
    assert k.is_call("%closed_call.15 = bf16[2,6,128]{2,1,0:T(8,128)} "
                     "custom-call(%fusion.42)", cell.config)


def test_op_labels():
    name = ("%fusion.54 = (bf16[8960]{0:T(1024)}, bf16[8960]{0}) "
            "fusion(bf16[1536]{0} %x), kind=kLoop")
    assert trace.op_label(name) == "%fusion.54 fusion"
    assert trace.is_container("%while.16 = (s32[]{:T(128)}, "
                              "bf16[1,1,1536]{2,1,0}) while(%tuple.3)")
    assert not trace.is_container(name)


def test_step_and_kernel_readers_on_the_recorded_trace(recorded):
    """The readers that need a chip, fed the recorded trace and one decode
    call at a mid position: values a v5e decode step of qwen2-1.5b has."""
    from bench import common as c
    doc, tr = recorded
    cell = harness.load_cell("qwen2-1.5b.classroom-stream")
    ctx = harness.Context(cell=cell, config=cell.config, records=[],
                          before={}, after={}, setup_s=0.0,
                          calls=[(tr.t0 + 1e-3, 1, [100])], searches=[],
                          peaks=c.peaks("TPU v5 lite"), cache_dim=64,
                          trace=tr)
    read = lambda name: c.load_reader("metrics", name).read(ctx)
    step_ms = read("decode_step_ms")
    assert 3.77 < step_ms < 10.0          # above the 3.77 ms weight floor
    mfu = read("decode_step_mfu")
    flops = c.load_reader("kernels", "decode_step").cost(cell.config, 100)[0]
    assert mfu == pytest.approx(100 * flops / (step_ms * 1e-3 * 197e12))
    assert 0.1 < mfu < 1.0
    roof = read("paged_decode_attention_roofline")
    assert 0.5 < roof < 100.0
