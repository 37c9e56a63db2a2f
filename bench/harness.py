"""One cell, end to end: set up the front door in this process, drive it
from the load generator child for the window, then read the metrics and
decide ``correct``.

Nothing here knows a cell, mix, configuration or metric by name: each is a
file under ``bench/`` that ``common`` finds by the name ``BENCHMARK.json``
gives.  The system under test is the program's own entry
(``launch/serve.py``: ``open_front_door`` over an ``Engine``); the
benchmark adds only wrappers installed from outside, which record what the
timed path produced (the served token ids, the prompts the engine saw) and,
in a traced run, name the host's spans.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import common

LOADGEN = os.path.join(common.BENCH_DIR, "loadgen.py")
FAILED_MODELS = ("none", "timeout", "error")


@dataclasses.dataclass
class Cell:
    name: str
    spec: Dict[str, Any]        # bench/workloads/<name>.json
    config: Dict[str, Any]      # bench/configs/<config>.json
    mix: Dict[str, Any]         # bench/traffic/<traffic>.json
    bench_dir: str


def load_cell(name: str, bench_dir: str = common.BENCH_DIR) -> Cell:
    spec = common.load_json("workloads", name, bench_dir)
    return Cell(name=name, spec=spec,
                config=common.load_json("configs", spec["config"], bench_dir),
                mix=common.load_json("traffic", spec["traffic"], bench_dir),
                bench_dir=bench_dir)


def program_config(c: Dict[str, Any], bench_dir: str = common.BENCH_DIR):
    """The program's ``ModelConfig`` for the configuration file, as its
    family (``bench/families/<family>.py``) maps it."""
    return common.load_family(c, bench_dir).program_config(c)


# -- wrappers installed from outside --------------------------------------------

class Recorder:
    """What the timed path produced, and (traced) where the host was.

    * ``Engine.decode``: every call's tokens and positions (device arrays,
      read after the window) and its host time; S > 1 calls are prefills.
    * ``bridge.request``/``request_stream``: per request id, the served
      token ids (stream chunks, or the ids the tokenizer decoded) and the
      text the bridge produced.
    * the vector store's ``search``: shortlist rows scored per call.
    """

    def __init__(self, bridge, engine, trace: bool):
        import jax
        self.served: Dict[str, Dict[str, Any]] = {}
        self.calls: List[tuple] = []        # (t, tokens, positions)
        self.searches: List[tuple] = []     # (t, shortlist rows)
        self._tl = threading.local()
        self._lock = threading.Lock()
        ann = jax.profiler.TraceAnnotation
        span = (lambda name: ann(name)) if trace else (
            lambda name: _Null())

        decode = engine.decode

        def rec_decode(tokens, positions, cache):
            with span("bench.engine.decode" if tokens.shape[1] == 1
                      else "bench.engine.prefill"):
                self.calls.append((time.monotonic(), tokens, positions))
                return decode(tokens, positions, cache)
        engine.decode = rec_decode

        request, request_stream = bridge.request, bridge.request_stream

        def rec_request(preq, *a, **kw):
            rid = preq.request_id
            self._tl.rid = rid
            try:
                with span("bench.bridge.request"):
                    resp = request(preq, *a, **kw)
            finally:
                self._tl.rid = None
            self._finish(rid, resp, stream=False)
            return resp

        def rec_stream(preq, *a, **kw):
            rid = preq.request_id
            rec = self._entry(rid, stream=True)
            inner = request_stream(preq, *a, **kw)
            try:
                with span("bench.bridge.request_stream"):
                    for chunk in inner:
                        rec["tokens"].extend(int(t) for t in chunk.token_ids)
                        if chunk.final and chunk.response is not None:
                            self._finish(rid, chunk.response, stream=True)
                        yield chunk
            finally:
                inner.close()
        bridge.request, bridge.request_stream = rec_request, rec_stream

        model = bridge.pool.list()[0]
        model.tokenizer = _RecordingTokenizer(model.tokenizer, self)

        cache = bridge.cache
        get = cache.smart_get_batch

        def rec_get(*a, **kw):
            with span("bench.cache.get"):
                return get(*a, **kw)
        cache.smart_get_batch = rec_get
        store = cache.store
        search = store.search

        def rec_search(*a, **kw):
            before = store.n_shortlist_rows
            out = search(*a, **kw)
            self.searches.append((time.monotonic(),
                                  store.n_shortlist_rows - before))
            return out
        store.search = rec_search

    def _entry(self, rid, stream):
        with self._lock:
            return self.served.setdefault(rid, {"tokens": [], "text": None,
                                                "stream": stream})

    def _finish(self, rid, resp, stream):
        rec = self._entry(rid, stream)
        rec["text"] = resp.text

    def note_decoded(self, ids):
        rid = getattr(self._tl, "rid", None)
        if rid is not None:
            self._entry(rid, stream=False)["tokens"] = [int(t) for t in ids]


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class _RecordingTokenizer:
    def __init__(self, inner, recorder: Recorder):
        self._inner, self._rec = inner, recorder

    def decode(self, ids):
        self._rec.note_decoded(ids)
        return self._inner.decode(ids)

    def __getattr__(self, name):
        return getattr(self._inner, name)


# -- the system under test --------------------------------------------------------

class Server:
    """The front door over an engine of the cell's configuration, weights
    from the seed, serving on a loopback port from a thread of this
    process."""

    def __init__(self, cell: Cell, seed: int, trace: bool = False):
        import jax

        from bench import model as bmodel
        from repro.launch import serve
        from repro.models import init_model
        from repro.serving.engine import Engine

        cfg = program_config(cell.config, cell.bench_dir)
        t = time.monotonic()
        self.params = bmodel.make_weights(cell.config, seed, cell.bench_dir)
        jax.block_until_ready(self.params)
        self.t_weights = time.monotonic() - t
        # the program's layout, read from its own init without running it
        want = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                            jax.eval_shape(functools.partial(init_model, cfg),
                                           jax.random.PRNGKey(0)))
        got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), self.params)
        if want != got:
            raise RuntimeError(f"weights layout differs from the program's: "
                               f"{got} != {want}")
        self.engine = Engine(cfg, self.params,
                             max_len=cell.config["engine_max_len"])
        self.bridge, self.http = serve.open_front_door(self.engine, port=0)
        # the adapter's documented per-instance cap, raised to the mix's
        # largest max_tokens (the served default is 32)
        self.bridge.adapter.max_engine_tokens = cell.mix["max_tokens"]["max"]
        self.recorder = Recorder(self.bridge, self.engine, trace)
        self.thread = threading.Thread(target=self.http.serve_forever,
                                       daemon=True)
        self.thread.start()
        self.address = self.http.server_address
        self.model_name = self.engine.cfg.name

    def fill_cache(self, objects) -> float:
        """Load past questions and answers through the cache's own PUT."""
        from repro.core.cache import CachedType
        t = time.monotonic()
        for o in objects:
            self.bridge.cache.put(o["answer"],
                                  keys=[(CachedType.PROMPT, o["question"])])
        return time.monotonic() - t

    def warm_search(self, seed: int) -> None:
        """Compile the vector search for every shape the window can give
        it: a few searches through the store, and on the IVF path the
        shortlist kernel at every width the window can produce (the
        shortlist is padded to a power of two >= 128 and holds at most
        every row)."""
        store = self.bridge.cache.store
        n = len(store)
        rng = np.random.default_rng([seed, 6])
        for _ in range(4):
            store.search(rng.normal(size=store.dim).astype(np.float32),
                         top_k=4)
        if n < store.crossover or not store.use_pallas:
            return
        from repro.kernels.cache_topk import ops as topk_ops
        q = np.zeros((1, store.dim), np.float32)
        q[0, 0] = 1.0
        db = np.zeros((n, store.dim), np.float32)
        codes = np.zeros((n,), np.int32)
        w = 128
        while True:
            sl = np.full((1, w), -1, np.int32)
            sl[0, 0] = 0
            topk_ops.shortlist_topk(q, db, codes, sl,
                                    np.full((1,), -1, np.int32),
                                    np.full((1,), -1.0, np.float32), 4,
                                    use_pallas=True)
            if w >= n:
                break
            w *= 2

    def close(self) -> None:
        self.http.shutdown()
        self.http.server_close()
        self.bridge.close()
        self.thread.join(timeout=30)


def post(address, body: Dict[str, Any], rid: str, timeout: float = 900,
         patience: float = 120.0):
    """One request from this process (warm-up); a 429/503 refusal is
    retried after its ``Retry-After`` for up to ``patience`` seconds."""
    import http.client
    give_up = time.monotonic() + patience
    while True:
        conn = http.client.HTTPConnection(*address, timeout=timeout)
        try:
            conn.request("POST", "/v1/chat/completions", json.dumps(body),
                         {"Content-Type": "application/json",
                          "x-request-id": rid})
            resp = conn.getresponse()
            raw = resp.read().decode(errors="replace")
            retry = resp.getheader("Retry-After")
        finally:
            conn.close()
        if resp.status in (429, 503) and time.monotonic() < give_up:
            time.sleep(float(retry or 1.0))
            continue
        if resp.status != 200 or "provider_error" in raw:
            raise RuntimeError(f"warm-up request {rid}: {resp.status} "
                               f"{raw[:300]}")
        return raw


def settle(bridge, patience: float = 120.0) -> float:
    """Wait until the front door's overload controller is back at its
    normal level (set-up traffic may have raised it); returns the wait."""
    from repro.core.overload import LoadLevel
    t = time.monotonic()
    while (bridge.overload.tick() != LoadLevel.NORMAL
           and time.monotonic() - t < patience):
        time.sleep(0.25)
    return time.monotonic() - t


# -- the measured window ------------------------------------------------------

def counters(server: Server) -> Dict[str, Any]:
    st = server.bridge.stats()
    return {
        "t": time.monotonic(),
        "n_prefill_tokens": server.engine.n_prefill_tokens,
        "cache_hits": st["cache"]["hits"],
        "cache_misses": st["cache"]["misses"],
        "paths": {p: {s: {"count": v["count"]}
                      for s, v in d.get("stages", {}).items()}
                  for p, d in st["paths"].items()},
        "durations": {p: {s: server.bridge._stats.durations(p, s)
                          for s in d.get("stages", {})}
                      for p, d in st["paths"].items()},
        "overload": st["overload"].get("level"),
    }


def run_window(server: Server, requests, seconds: float, grace: float,
               trace_window: Optional[tuple] = None, tag: str = "w"):
    """Drive ``requests`` (due offsets in [0, seconds)) from the load
    generator child.  Returns (records, counters before, counters after,
    trace or None)."""
    plan = {"host": server.address[0], "port": server.address[1],
            "requests": [dict(r.wire(), rid=f"{tag}-{r.i}")
                         for r in requests]}
    before = counters(server)
    t0 = time.monotonic() + 0.5
    plan.update(t0=t0, deadline=t0 + seconds + grace)
    trace = None
    with tempfile.TemporaryFile("w+") as f:
        json.dump(plan, f)
        f.seek(0)
        proc = subprocess.Popen([sys.executable, LOADGEN], stdin=f,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True)
        try:
            if trace_window is not None:
                from bench import trace as btrace
                trace = btrace.capture(t0 + trace_window[0], trace_window[1])
            out, err = proc.communicate(timeout=seconds + grace + 60)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.communicate()
    if proc.returncode != 0:
        raise RuntimeError(f"load generator exited {proc.returncode}: "
                           f"{err[-2000:]}")
    after = counters(server)
    records = [json.loads(line) for line in out.splitlines() if line.strip()]
    for r in records:
        r["t0"] = t0
        r["close"] = t0 + seconds
        r["deadline"] = t0 + seconds + grace
    return records, before, after, trace


def judge_records(records, served) -> None:
    """Mark each record ok/failed and attach what the server recorded."""
    for r in records:
        s = served.get(r["rid"], {})
        x = r.get("x") or {}
        r["ok"] = bool(r["status"] == 200 and r["done"] and not r["error"]
                       and x.get("model_used") not in FAILED_MODELS)
        r["cache_hit"] = bool(x.get("cache_hit"))
        r["n_tokens"] = len(s.get("tokens", [])) if not r["cache_hit"] else 0


# -- what metric readers see ----------------------------------------------------

@dataclasses.dataclass
class Context:
    """Everything a metric reader (``bench/metrics/<name>.py``) may read."""
    cell: Cell
    config: Dict[str, Any]
    records: List[Dict[str, Any]]          # requests due in the window
    before: Dict[str, Any]                 # counters at the window's opening
    after: Dict[str, Any]                  # ... once every request ended
    setup_s: float
    calls: List[tuple]                     # (t, tokens per row, each
                                           # row's first position, traced)
    searches: List[tuple]                  # (t, shortlist rows)
    peaks: Dict[str, Any]
    cache_dim: int
    trace: Any = None                      # bench.trace.Trace or None

    def kernel(self, name: str):
        return common.load_reader("kernels", name, self.cell.bench_dir)

    def family(self):
        """The configuration's model family (``bench/families/``)."""
        return common.load_family(self.config, self.cell.bench_dir)

    def kernel_ops(self, k):
        """(start, end) of the traced window's calls of kernel module ``k``
        (``bench/kernels/<name>.py``), told by its ``is_call``."""
        tr = self.trace
        if tr is None:
            return []
        return [(s, e) for n, s, e in tr.ops
                if tr.t0 <= s <= tr.t1 and k.is_call(n, self.config)]

    def calls_in_trace(self, rows_tokens: int):
        """Engine calls with ``rows_tokens`` tokens per row issued inside
        the traced window."""
        tr = self.trace
        if tr is None:
            return []
        return [c for c in self.calls
                if c[1] == rows_tokens and tr.t0 <= c[0] <= tr.t1]

    def decode_steps(self):
        """(start, end) of the device runs of the decode-step program: the
        programs that hold the decode kernel (one token per row)."""
        tr = self.trace
        if tr is None:
            return []
        marks = [s for s, _ in
                 self.kernel_ops(self.kernel("paged_decode_attention"))]
        out, j = [], 0
        for _, s, e in tr.modules:
            if not tr.t0 <= s <= tr.t1:
                continue
            while j < len(marks) and marks[j] < s:
                j += 1
            if j < len(marks) and marks[j] <= e:
                out.append((s, e))
        return out

    def idle_share(self):
        """% of the traced time with a request in flight and no device op."""
        from bench import trace as btrace
        tr = self.trace
        if tr is None:
            return None
        infl = btrace.inflight(self.records, tr.t0, tr.t1)
        total = btrace.length(infl)
        if total <= 0:
            return None
        busy = btrace.intersect(btrace.busy(tr), infl)
        return float(100.0 * (1.0 - btrace.length(busy) / total))
