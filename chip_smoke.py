#!/usr/bin/env python3
"""Chip smoke run: LLMBridge serving qwen2-1.5b at its published width on one
TPU chip, through the paged scheduler and the OpenAI front door.

    python chip_smoke.py          # from the root of a checkout

Everything runs in this one process, because a TPU chip belongs to one
process at a time; the HTTP server runs on a thread of it.  The weights are
random, drawn on the device from ``SEED``; nothing is downloaded.  Phases:

1. device  — the first JAX device must be a TPU; on any other backend the
             script exits 1 and prints no result.
2. paged   — a full-width bf16 engine (28 layers, d_model 1536, vocab
             151,936) with the Pallas kernels compiled serves a group of
             requests that share a course-prompt prefix through the paged
             ``Scheduler``: prefix pages are shared, every request decodes
             its full budget, the page pool stays consistent.
3. kernels — the paged decode and prefill attention kernels against their
             jnp references at qwen2-1.5b widths (``ATTN_ATOL``); then one
             whole paged decode step with ``use_pallas=True`` against the
             jnp step (``use_pallas=False``) on the same chip: the compiled
             step holds a ``tpu_custom_call``, the reference holds none, and
             the logits agree within ``LOGIT_RTOL`` (relative L2).  The
             semantic cache's ``similarity_topk`` and ``shortlist_topk``
             kernels are checked against their references too.
4. http    — the OpenAI front door over a pool of that one engine: a plain
             request, a streamed one (SSE ending in ``[DONE]``) and a repeat
             whose plan consults the semantic cache first (its vector search
             runs on the chip); every answer must come from the engine.

Lines before the last are observations of this one run (device, compile
and phase seconds, tokens, peak device memory), not benchmark metrics.  The
last line is one JSON object, ``{"ok": true, "device": {...}}``; a failed
phase exits nonzero before it is printed.
"""
from __future__ import annotations

import dataclasses
import functools
import http.client
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen2-1.5b"
SEED = 0
PUBLISHED = {"n_layers": 28, "d_model": 1536, "vocab": 151_936,
             "dtype": "bfloat16"}
# The attention op alone, kernel against reference on the same bf16
# inputs: the tolerance of the interpret-mode parity tests
# (tests/test_kernels.py) for bf16.
ATTN_ATOL = 2e-2
# A whole decode step: the kernel and the jnp reference round to bf16 at
# different points (eps 2**-8) in each of the 28 layers, and random-weight
# layers add such errors roughly in quadrature, so the logits are held to
# 5 x sqrt(layers) x eps in relative L2 — a gross fault (a wrong page, a
# wrong mask) moves whole rows and lands far outside it.
LOGIT_RTOL = 5 * PUBLISHED["n_layers"] ** 0.5 * 2.0 ** -8
PAGE = 16
MAX_NEW = 16
COURSE_PROMPT = ("You are the teaching assistant of CS101, Introduction to "
                 "Computer Systems. Answer briefly and cite the lecture. ")


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def note(msg: str) -> None:
    print(msg, flush=True)


# -- phase 2: paged scheduler ---------------------------------------------------

def phase_paged(engine, tok) -> int:
    """Six students of one course through four paged slots, twice: the
    first pass compiles, the second runs warm.  Returns tokens generated."""
    import jax.numpy as jnp

    from repro.serving.scheduler import Request, Scheduler

    questions = ["What is virtual memory?", "How does a cache miss cost?",
                 "Why do page tables have levels?", "What is a TLB?",
                 "When does a context switch happen?", "What is DMA?"]
    generated = 0
    for run in ("cold", "warm"):
        sched = Scheduler(engine, n_slots=4, paged=True, page_size=PAGE)
        for i, q in enumerate(questions):
            ids = tok.encode(COURSE_PROMPT + q)
            sched.submit(Request(rid=i, user=f"student{i}",
                                 prompt=jnp.asarray(ids, jnp.int32),
                                 max_new=MAX_NEW))
        t0 = time.perf_counter()
        done = sched.run_to_completion()
        secs = time.perf_counter() - t0
        check(sorted(r.rid for r in done) == list(range(len(questions))),
              f"paged: {len(done)} of {len(questions)} requests finished")
        for r in done:
            check(len(r.generated) == MAX_NEW,
                  f"paged: request {r.rid} decoded {len(r.generated)} tokens")
            check(all(0 <= t < engine.cfg.vocab for t in r.generated),
                  f"paged: request {r.rid} emitted an id outside the vocab")
        check(sched.shared_tokens > 0, "paged: no prompt page was shared")
        sched.pool.check()
        generated += sum(len(r.generated) for r in done)
        note(f"paged[{run}]: {len(done)} requests, "
             f"{sum(len(r.generated) for r in done)} tokens, "
             f"prefill_tokens={sched.prefill_tokens} "
             f"shared_tokens={sched.shared_tokens} "
             f"peak_slots={sched.peak_live} in {secs:.3f}s")
    return generated


# -- phase 3: compiled kernels against the references ----------------------------

def _paged_step_inputs(engine, seed: int):
    """A paged cache with random KV in distinct pages per slot and cursors
    that straddle page boundaries, plus one new token per slot."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = engine.cfg
    B, MP = 4, 8
    pos = np.array([PAGE - 1, PAGE, 2 * PAGE + 9, MP * PAGE - 1], np.int32)
    n_pages = 1 + B * MP                     # page 0 is the trash page
    table = np.full((B, MP), -1, np.int32)
    for b in range(B):
        live = int(pos[b]) // PAGE + 1
        table[b, :live] = 1 + b * MP + np.arange(live)
    cache = engine.new_paged_cache(B, n_pages, PAGE, MP)["paged"]
    kk, kv, kt = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = cache["k_pages"].shape
    Ln = shape[0]
    cache = {
        "k_pages": jax.random.normal(kk, shape, cache["k_pages"].dtype),
        "v_pages": jax.random.normal(kv, shape, cache["v_pages"].dtype),
        "table": jnp.broadcast_to(jnp.asarray(table)[None], (Ln, B, MP)),
        "pos": jnp.broadcast_to(jnp.asarray(pos)[None], (Ln, B)),
    }
    tokens = jax.random.randint(kt, (B, 1), 3, cfg.vocab, jnp.int32)
    positions = jnp.asarray(pos)[:, None]
    return tokens, positions, {"paged": cache}


def _attention_ops() -> None:
    """The paged attention kernels alone, compiled, against their jnp
    references on the same bf16 inputs at qwen2-1.5b head widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.kernels.decode_attention import ops as da_ops

    cfg = configs.get(ARCH)
    B, MP, S = 4, 8, 16
    pos = jnp.asarray([PAGE - 1, PAGE, 2 * PAGE + 9, MP * PAGE - S],
                      jnp.int32)
    table = jnp.arange(1, 1 + B * MP, dtype=jnp.int32).reshape(B, MP)
    ks = jax.random.split(jax.random.PRNGKey(SEED + 2), 4)
    pages = (1 + B * MP, PAGE, cfg.n_kv_heads, cfg.hd)
    kp = jax.random.normal(ks[0], pages, jnp.bfloat16)
    vp = jax.random.normal(ks[1], pages, jnp.bfloat16)
    for name, fn, q in (
            ("paged_decode_attention", da_ops.paged_decode_attention,
             jax.random.normal(ks[2], (B, cfg.n_heads, cfg.hd), jnp.bfloat16)),
            ("paged_prefill_attention", da_ops.paged_prefill_attention,
             jax.random.normal(ks[3], (B, S, cfg.n_heads, cfg.hd),
                               jnp.bfloat16))):
        got = np.asarray(fn(q, kp, vp, table, pos, use_pallas=True),
                         np.float32)
        want = np.asarray(fn(q, kp, vp, table, pos, use_pallas=False),
                          np.float32)
        err = float(np.abs(got - want).max())
        note(f"kernels: {name} {got.shape} max_abs_err={err:.3e}")
        check(err <= ATTN_ATOL,
              f"kernels: {name} max_abs_err {err:.3e} > {ATTN_ATOL}")


def _rel_l2(a, b) -> float:
    import numpy as np
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def phase_kernels(engine) -> None:
    import jax
    import numpy as np

    from repro.kernels.cache_topk import ops as topk_ops
    from repro.serving.engine import decode_step

    _attention_ops()
    args = (engine.params,) + _paged_step_inputs(engine, SEED + 1)
    step = jax.jit(functools.partial(decode_step, cfg=engine.cfg))
    t0 = time.perf_counter()
    compiled = step.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    check("tpu_custom_call" in compiled.as_text(),
          "kernels: the use_pallas decode step holds no tpu_custom_call")
    ref_step = jax.jit(functools.partial(
        decode_step, cfg=dataclasses.replace(engine.cfg, use_pallas=False)))
    ref = ref_step.lower(*args).compile()
    check("tpu_custom_call" not in ref.as_text(),
          "kernels: the jnp reference step holds a kernel")
    with jax.default_matmul_precision("highest"):
        ref_hi = ref_step.lower(*args).compile()
    got, want, want_hi = (np.asarray(fn(*args)[0], np.float32)
                          for fn in (compiled, ref, ref_hi))
    check(np.isfinite(got).all(), "kernels: non-finite logits")
    rel = _rel_l2(got, want)
    rows = " ".join(f"{_rel_l2(got[b], want[b]):.3e}"
                    for b in range(got.shape[0]))
    note(f"kernels: paged decode step logits {got.shape} vs jnp reference: "
         f"rel_l2={rel:.3e} (rows {rows}) "
         f"max_abs/max_ref={np.abs(got - want).max() / np.abs(want).max():.3e} "
         f"argmax_agree={(got.argmax(-1) == want.argmax(-1)).mean():.2f}; "
         f"jnp reference vs itself at highest matmul precision: "
         f"rel_l2={_rel_l2(want_hi, want):.3e}; step compile {compile_s:.3f}s")
    check(rel <= LOGIT_RTOL, f"kernels: rel_l2 {rel:.3e} > {LOGIT_RTOL:.3e}")

    # semantic-cache search kernels, compiled, against their references
    rng = np.random.default_rng(SEED)

    def unit(n, d):
        x = rng.normal(size=(n, d)).astype(np.float32)
        return x / np.linalg.norm(x, axis=1, keepdims=True)

    q, db = unit(5, 64), unit(3000, 64)
    codes = rng.integers(0, 7, 3000).astype(np.int32)
    sl = rng.integers(-1, 3000, size=(5, 700)).astype(np.int32)
    tm = rng.integers(1, 2 ** 7, 5).astype(np.int32)
    th = rng.uniform(-0.2, 0.2, 5).astype(np.float32)
    # the kernels score in f32; so must the references on this chip
    with jax.default_matmul_precision("highest"):
        s_ref, i_ref = topk_ops.similarity_topk(q, db, 4, use_pallas=False)
        sl_ref = topk_ops.shortlist_topk(q, db, codes, sl, tm, th, 4,
                                         use_pallas=False)
    s_pl, i_pl = topk_ops.similarity_topk(q, db, 4, use_pallas=True)
    check(np.array_equal(i_ref, i_pl), "kernels: similarity_topk indices")
    check(np.allclose(s_ref, s_pl, atol=1e-4), "kernels: similarity_topk scores")
    s_ref, i_ref = sl_ref
    s_pl, i_pl = topk_ops.shortlist_topk(q, db, codes, sl, tm, th, 4,
                                         use_pallas=True)
    check(np.array_equal(i_ref, i_pl), "kernels: shortlist_topk indices")
    live = i_ref >= 0
    check(np.allclose(s_ref[live], s_pl[live], atol=1e-4),
          "kernels: shortlist_topk scores")
    note("kernels: similarity_topk and shortlist_topk match their "
         "references")


# -- phase 4: the OpenAI front door ----------------------------------------------

def _post(addr, body: dict):
    conn = http.client.HTTPConnection(*addr, timeout=600)
    try:
        conn.request("POST", "/v1/chat/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read().decode()
    finally:
        conn.close()


def _sse_events(raw: str):
    return [line[len("data: "):] for line in raw.splitlines()
            if line.startswith("data: ")]


def phase_http(engine) -> None:
    from repro.launch import serve

    bridge, server = serve.open_front_door(engine, port=0)
    # course material a deployment loads into the semantic cache up front:
    # the repeat below searches these rows on the chip before it decodes
    bridge.cache.delegated_put(
        "Lecture 3 covers scheduling. Round robin gives each process a time "
        "slice. Priority schedulers can starve low-priority work. Aging "
        "fixes starvation by raising priority over time.")
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    addr = server.server_address
    prompt = [{"role": "user", "content": COURSE_PROMPT +
               "Why does a prompt-centric internet need a cost-aware proxy?"}]
    try:
        def engine_answer(name, body, stream=False):
            n0 = engine.n_prefill_tokens
            t0 = time.perf_counter()
            status, raw = _post(addr, body)
            secs = time.perf_counter() - t0
            check(status == 200, f"http[{name}]: status {status}: {raw[:300]}")
            if stream:
                events = _sse_events(raw)
                check(events and events[-1] == "[DONE]",
                      f"http[{name}]: SSE did not end in [DONE]")
                chunks = [json.loads(e) for e in events[:-1]]
                text = "".join(c["choices"][0]["delta"].get("content", "")
                               for c in chunks)
                md = chunks[-1].get("x_llmbridge", {})
            else:
                wire = json.loads(raw)
                text = wire["choices"][0]["message"]["content"]
                md = wire["x_llmbridge"]
            check(md.get("model_used") == ARCH,
                  f"http[{name}]: answered by {md.get('model_used')!r}")
            check(not md.get("cache_hit"), f"http[{name}]: a cache hit")
            check(engine.n_prefill_tokens > n0,
                  f"http[{name}]: the engine never prefilled")
            check(len(text) > 0, f"http[{name}]: empty generated text")
            note(f"http[{name}]: 200 from {md['model_used']} "
                 f"policy={md.get('policy')} chars={len(text)} "
                 f"in {secs:.3f}s")
            return md

        engine_answer("plain", {
            "messages": prompt, "max_tokens": MAX_NEW, "user": "smoke-a",
            "x_allow_cache": False})
        engine_answer("stream", {
            "messages": prompt, "max_tokens": MAX_NEW, "user": "smoke-b",
            "stream": True, "x_allow_cache": False}, stream=True)
        searches = bridge.cache.store.n_flat_searches
        md = engine_answer("repeat", {
            "messages": prompt, "max_tokens": MAX_NEW, "user": "smoke-c"})
        check("cache" in (md.get("policy") or ""),
              "http[repeat]: the plan skipped the semantic cache")
        check(bridge.cache.store.n_flat_searches > searches,
              "http[repeat]: the semantic cache was never searched")
    finally:
        server.shutdown()
        server.server_close()
        bridge.close()
        thread.join(timeout=30)


def main() -> int:
    try:
        import jax
    except ImportError as e:
        print(f"FAIL device: JAX is not importable: {e}", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"FAIL device: the first JAX device is {dev.platform!r}, "
              "not a TPU", file=sys.stderr)
        return 1
    try:
        from repro.data.tokenizer import ByteTokenizer
        from repro.launch import compile_cache, serve
    except ImportError as e:
        print(f"FAIL setup: run from the root of a checkout ({e})",
              file=sys.stderr)
        return 1
    note(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
         f"compile cache {compile_cache.enable()}")

    phase = "engine"
    try:
        t0 = time.perf_counter()
        engine = serve.build_engine(ARCH, seed=SEED)
        jax.block_until_ready(engine.params)
        cfg = engine.cfg
        for key, want in PUBLISHED.items():
            check(getattr(cfg, key) == want,
                  f"engine: {key}={getattr(cfg, key)!r}, published {want!r}")
        n_params = sum(x.size for x in jax.tree.leaves(engine.params))
        note(f"engine: {ARCH} {n_params / 1e9:.3f}B params bf16, "
             f"use_pallas={cfg.use_pallas}, random weights seed {SEED}, "
             f"{time.perf_counter() - t0:.3f}s")
        tokens = 0
        for phase, run in (
                ("paged", lambda: phase_paged(engine, ByteTokenizer())),
                ("kernels", lambda: phase_kernels(engine)),
                ("http", lambda: phase_http(engine))):
            t0 = time.perf_counter()
            tokens += run() or 0
            note(f"phase {phase}: {time.perf_counter() - t0:.3f}s")
    except Exception as e:  # noqa: BLE001 — report the failed phase, exit 1
        import traceback
        traceback.print_exc()
        print(f"FAIL {phase}: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    note(f"observed: {tokens} tokens generated by the paged scheduler "
         f"phase; peak_bytes_in_use="
         f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
