"""Spans and counters of the served path (``core/telemetry.py``): nesting,
parents and request ids across the worker-thread hand-off, the ring's
bound and its dropped count, counters, the compile listener, a streamed
request through the front door, and the stage records' durations; plus the
names the device trace reads (jitted programs, kernels, named scopes)."""
import collections
import functools
import http.client
import itertools
import json
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import configs, kernels
from repro.core import Preference, ProxyRequest, build_bridge, telemetry


def _mine(ids):
    return [s for s in telemetry.spans() if s.id in ids]


def test_nesting_parents_and_request_ids_across_a_handoff():
    seen = {}

    def worker(parent):
        with telemetry.handoff(parent):
            with telemetry.span("w.outer", cpu=True) as a:
                with telemetry.span("w.inner", k=3) as b:
                    pass
        seen.update(a=a.id, b=b.id)

    with telemetry.span("root", rid="req-1") as root:
        with telemetry.span("child") as child:
            t = threading.Thread(target=worker, args=(telemetry.current(),))
            t.start()
            t.join(timeout=30)
    assert not t.is_alive()
    by = {s.name: s for s in _mine({root.id, child.id, seen["a"],
                                    seen["b"]})}
    assert by["root"].parent is None
    assert by["child"].parent == root.id
    assert by["w.outer"].parent == child.id
    assert by["w.inner"].parent == seen["a"]
    assert {s.rid for s in by.values()} == {"req-1"}
    assert by["w.inner"].attrs == {"k": 3}
    assert by["w.outer"].thread != by["root"].thread
    assert by["root"].t0 <= by["child"].t0 <= by["w.outer"].t0
    assert by["w.inner"].t1 <= by["w.outer"].t1 <= by["child"].t1
    assert by["w.outer"].c1 >= by["w.outer"].c0
    assert by["w.inner"].c0 is None and by["root"].c1 is None
    assert telemetry.current() is None


def test_a_handoff_without_a_parent_names_the_request():
    out = {}

    def worker():
        with telemetry.handoff(None, rid="req-2"):
            with telemetry.span("w") as sp:
                pass
        out["id"] = sp.id

    t = threading.Thread(target=worker)
    t.start()
    t.join(timeout=30)
    (s,) = _mine({out["id"]})
    assert s.parent is None and s.rid == "req-2"


def test_the_ring_is_bounded_and_says_what_it_dropped(monkeypatch):
    monkeypatch.setattr(telemetry, "_ring", collections.deque(maxlen=4))
    monkeypatch.setattr(telemetry, "_closes", itertools.count())
    assert telemetry.snapshot() == ([], 0)
    for i in range(3):
        with telemetry.span("s", i=i):
            pass
    assert telemetry.snapshot()[1] == 0
    for i in range(3, 10):
        with telemetry.span("s", i=i):
            pass
    spans, dropped = telemetry.snapshot()
    assert [s.attrs["i"] for s in spans] == [6, 7, 8, 9]
    assert dropped == 6


def test_counters_add_up():
    before = telemetry.counters().get("test.c", 0)
    telemetry.count("test.c")
    telemetry.count("test.c", 4)
    assert telemetry.counters()["test.c"] == before + 5


def test_a_compile_is_counted_and_recorded_as_a_span():
    before = telemetry.counters().get("compiles", 0)
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 0.125)
    with telemetry.span("compiling") as outer:
        f(jnp.zeros((7, 13), jnp.float32)).block_until_ready()
    assert telemetry.counters()["compiles"] >= before + 1
    comp = [s for s in telemetry.spans()
            if s.name == "jax.compile" and s.parent == outer.id]
    assert comp
    assert all(outer.t0 <= s.t0 <= s.t1 <= outer.t1 + 1e-3 for s in comp)


def test_stage_record_duration_is_its_span():
    bridge = build_bridge()
    resp = bridge.request(ProxyRequest(
        prompt="how do spans nest?", user="u-span", request_id="rq-stage",
        preference=Preference.BALANCED))
    recs = resp.metadata.stage_records
    assert recs
    spans = {s.name: s for s in telemetry.spans() if s.rid == "rq-stage"}
    for rec in recs:
        sp = spans["proxy.stage." + rec.name]
        assert rec.duration == sp.t1 - sp.t0


# -- a streamed request through the front door ------------------------------

@pytest.fixture(scope="module")
def front_door():
    from repro.launch.serve import open_front_door
    from repro.models import init_model
    from repro.serving.engine import Engine
    cfg = configs.get_reduced("qwen2-1.5b")
    engine = Engine(cfg, init_model(cfg, jax.random.PRNGKey(0)), max_len=64)
    bridge, srv = open_front_door(engine, port=0)
    bridge.adapter.max_engine_tokens = 6
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield bridge, srv.server_address
    srv.shutdown()
    srv.server_close()
    bridge.close()
    t.join(timeout=30)


def _stream(addr, rid, content):
    conn = http.client.HTTPConnection(*addr, timeout=300)
    conn.request("POST", "/v1/chat/completions", json.dumps({
        "model": "auto", "stream": True, "x_allow_cache": False,
        "messages": [{"role": "user", "content": content}]}),
        {"Content-Type": "application/json", "x-request-id": rid})
    resp = conn.getresponse()
    assert resp.status == 200
    body = resp.read().decode()
    conn.close()
    return [line for line in body.split("\n") if line.startswith("data: ")]


def test_a_streamed_request_is_one_tree_of_spans(front_door):
    bridge, addr = front_door
    steps0 = telemetry.counters().get("sched.decode_steps", 0)
    frames = _stream(addr, "rq-stream", "what does a span cost?")
    assert frames[-1] == "data: [DONE]"
    mine = [s for s in telemetry.spans() if s.rid == "rq-stream"]
    by_id = {s.id: s for s in mine}
    names = collections.Counter(s.name for s in mine)

    def ancestors(s):
        while s.parent is not None:
            s = by_id[s.parent]
            yield s

    (root,) = [s for s in mine if s.name == "http.request"]
    assert root.parent is None
    stages = [s for s in mine if s.name.startswith("proxy.stage.")]
    assert {s.name for s in stages} >= {"proxy.stage.model"}
    assert all(s.parent == root.id and s.thread != root.thread
               for s in stages)
    (model,) = [s for s in stages if s.name == "proxy.stage.model"]
    steps = [s for s in mine if s.name == "sched.step"]
    assert steps and all(model in ancestors(s) for s in steps)
    # exactly one wait per decode step, each inside its step
    waits = [s for s in mine if s.name == "sched.wait"]
    assert len(waits) == telemetry.counters()["sched.decode_steps"] - steps0
    assert all(w.c1 >= w.c0 for w in waits)     # the waits read CPU time
    per_step = collections.Counter(w.parent for w in waits
                                   if by_id[w.parent].name == "sched.step")
    assert len(per_step) == len(waits) and set(per_step.values()) == {1}
    for name in ("adapter.tokenize", "adapter.scheduler_build",
                 "adapter.detokenize", "sched.admit", "sched.dispatch",
                 "sched.update", "engine.decode", "proxy.finalize"):
        assert names[name] >= 1, name
    # one emit and one SSE write per chunk, matched by sequence number
    emits = sorted(s.attrs["seq"] for s in mine if s.name == "stream.emit")
    writes = sorted(s.attrs["seq"] for s in mine
                    if s.name == "http.sse_write" and s.attrs["seq"] >= 0)
    assert emits == writes == list(range(len(emits)))
    assert len(emits) + 2 == len(frames)        # + the final chunk, [DONE]
    assert all(s.thread == root.thread for s in mine
               if s.name == "http.sse_write")
    serving = bridge.stats()["serving"]["spec"][bridge.pool.list()[0].name]
    assert serving["schedulers"] >= 1 and serving["prefill_tokens"] > 0
    assert bridge.stats()["telemetry"]["sched.built"] >= 1


# -- names the device trace reads ------------------------------------------------

def test_engine_programs_are_named():
    from repro.models import init_model
    from repro.serving.engine import Engine
    cfg = configs.get_reduced("qwen2-1.5b")
    eng = Engine(cfg, init_model(cfg, jax.random.PRNGKey(0)), max_len=32)
    cache = eng.new_cache(1, 32)
    one = jnp.zeros((1, 1), jnp.int32)
    dec = eng._decode.lower(eng.params, one, one, cache).as_text(
        debug_info=True)
    pre = eng._prefill.lower(eng.params, jnp.zeros((1, 8), jnp.int32),
                             cache).as_text(debug_info=True)
    assert "module @jit_decode_step" in dec
    assert "module @jit_prefill_step" in pre
    for scope in ("/layers/", "/logits/"):
        assert scope in dec and scope in pre
    from repro.serving.sampler import SamplerConfig, sample
    txt = jax.jit(functools.partial(sample, sc=SamplerConfig())).lower(
        jnp.zeros((2, 16)), jax.random.PRNGKey(0)).as_text(debug_info=True)
    assert "/sampler" in txt


def _kernel_calls():
    from repro.kernels.cache_topk import kernel as topk
    from repro.kernels.decode_attention import kernel as dec
    from repro.kernels.flash_attention import kernel as flash
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    S = jax.ShapeDtypeStruct
    B, HQ, HKV, D, P, NP, MP = 2, 4, 2, 128, 16, 8, 4
    pages = (S((NP, P, HKV, D), bf), S((NP, P, HKV, D), bf),
             S((B, MP), i32), S((B,), i32))
    return {
        "decode_attention": (dec.decode_attention_pallas, (
            S((B, HQ, D), bf), S((B, 64, HKV, D), bf), S((B, 64, HKV, D), bf),
            S((B,), i32))),
        "paged_decode_attention": (dec.paged_decode_attention_pallas,
                                   (S((B, HQ, D), bf),) + pages),
        "paged_prefill_attention": (dec.paged_prefill_attention_pallas,
                                    (S((B, 8, HQ, D), bf),) + pages),
        "flash_attention": (flash.flash_attention_pallas, (
            S((4, 128, D), bf), S((4, 128, D), bf), S((4, 128, D), bf))),
        "shortlist_topk": (functools.partial(topk.shortlist_topk_pallas,
                                             k=4), (
            S((8, 64), f32), S((512, 64), f32), S((512,), i32),
            S((8, 128), i32), S((8,), i32), S((8,), f32))),
        "similarity_topk": (functools.partial(topk.similarity_topk_pallas,
                                              k=4), (
            S((8, 64), f32), S((512, 64), f32))),
    }


KERNELS = ("decode_attention", "paged_decode_attention",
           "paged_prefill_attention", "flash_attention", "shortlist_topk",
           "similarity_topk")


@pytest.mark.parametrize("name", KERNELS)
def test_kernels_are_named(monkeypatch, name):
    """Each kernel, lowered for the TPU (no compile, no chip), names its
    Mosaic call."""
    monkeypatch.setattr(kernels, "interpret", lambda: False)
    fn, args = _kernel_calls()[name]
    txt = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert f'kernel_name = "{name}"' in txt
