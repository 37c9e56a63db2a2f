"""Serving launcher: scheduler demo + the OpenAI-compatible HTTP front door.

Both entry points serve one architecture at its published width (``--arch``,
bf16, random weights from seed 0; no weights are downloaded) on an
``Engine`` with the Pallas kernels on — compiled on a TPU, interpreted
elsewhere (``repro.kernels.interpret``):

* ``python -m repro.launch.serve --arch qwen2-1.5b --paged`` — the
  continuous-batching scheduler over a stream of synthetic requests that
  share a course-prompt prefix, the same Engine/Scheduler pair the LLMBridge
  model pool uses.

* ``python -m repro.launch.serve --http 8000 --arch qwen2-1.5b`` — a stdlib
  HTTP server exposing LLMBridge, over a pool of that one engine-backed
  model, behind the OpenAI wire surface:

  - ``POST /v1/chat/completions`` — maps the JSON body through
    ``ChatCompletionRequest.from_wire``/``to_proxy`` onto the intent API.
    ``"stream": true`` answers Server-Sent Events: one ``data: {chunk}``
    frame per delta (``ChatCompletionChunk`` wire shape) terminated by
    ``data: [DONE]``; a client that disconnects mid-stream cancels decode
    server-side (slot freed, pages released, only generated tokens billed).
    Without ``stream`` the full ``ChatCompletionResponse`` is returned as
    one JSON body.  LLMBridge intents ride ``x_``-prefixed extension
    fields (``x_max_cost``, ``x_preference``, ...) and the disclosure
    metadata comes back under ``x_llmbridge``.  A request whose engine
    failed is a failure on the wire too: 502 with an error envelope, or an
    SSE ``error`` frame and no ``[DONE]``.
  - ``GET /v1/models`` — the model pool, OpenAI list shape.

  Point any OpenAI client at it::

      client = openai.OpenAI(base_url="http://localhost:8000/v1",
                             api_key="unused")

  ``make_server(bridge)`` fronts any bridge, engine-backed or SIM.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import signal
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from repro.core import telemetry
from repro.core.api import (ChatCompletionChunk, ChatCompletionRequest,
                            ChatCompletionResponse)
from repro.core.overload import OverloadError
from repro.launch import compile_cache


# -- OpenAI-compatible HTTP front door ----------------------------------------

def _provider_error(resp):
    """The failure a response stands for, or None: the proxy resolves an
    exhausted provider (a raising engine among them) to model ``"error"``
    so a batch lives on — on the wire it must read as a failure."""
    if resp is not None and resp.metadata.model_used == "error":
        return resp.text
    return None


def make_server(bridge, host: str = "127.0.0.1", port: int = 8000
                ) -> ThreadingHTTPServer:
    """Build (don't start) a ``ThreadingHTTPServer`` fronting ``bridge``.

    Returned unstarted so tests can bind port 0 and read
    ``server.server_address``; call ``serve_forever()`` (or spin it on a
    thread) to serve.
    """

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):   # quiet: the demo prints stats
            pass

        def handle_one_request(self):
            self._rid_hdr = None       # fresh identity per keep-alive request
            super().handle_one_request()

        # -- helpers ---------------------------------------------------------
        def _request_id(self) -> str:
            """Durable per-request identity: honor a client-supplied
            ``Idempotency-Key`` / ``x-request-id`` header (the idempotent-
            retry key — re-sending it replays the recorded outcome instead
            of double-charging), else generate one.  Echoed on EVERY
            response: 2xx, error envelopes, and the SSE preamble.  Cached
            per request — ``handle_one_request`` resets it, because one
            handler instance serves every request on a keep-alive
            connection."""
            if getattr(self, "_rid_hdr", None) is None:
                supplied = (self.headers.get("Idempotency-Key")
                            or self.headers.get("x-request-id") or "").strip()
                self._rid_hdr = (supplied[:128] if supplied
                                 else f"req_{uuid.uuid4().hex[:16]}")
            return self._rid_hdr

        def _json(self, code: int, payload, headers=None) -> None:
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.send_header("x-request-id", self._request_id())
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _error(self, code: int, message: str,
                   etype: str = "invalid_request_error",
                   ecode: str = "bad_request",
                   retry_after: float = None) -> None:
            """OpenAI-style error envelope on every non-2xx path: the
            ``error.code`` is a stable machine tag and the per-request id
            header rides every response (2xx included via ``_json``)."""
            headers = {}
            if retry_after is not None:
                headers["Retry-After"] = str(max(1, math.ceil(retry_after)))
            self._json(code, {"error": {"message": message, "type": etype,
                                        "code": ecode}}, headers=headers)

        def _overloaded(self, e: OverloadError) -> None:
            """429/503 + Retry-After from a structured shed: 503 when the
            whole proxy is browning out (load_shed), 429 when this request
            specifically was refused (queue caps, infeasible deadline)."""
            code = 503 if e.reason == "load_shed" else 429
            self._error(code, str(e),
                        etype="overloaded_error",
                        ecode=e.reason, retry_after=e.retry_after)

        # -- routes ----------------------------------------------------------
        def do_GET(self) -> None:
            if self.path.rstrip("/") == "/v1/models":
                models = [{"id": m.name, "object": "model",
                           "owned_by": "llmbridge"}
                          for m in bridge.pool.list()]
                self._json(200, {"object": "list", "data": models})
            else:
                self._error(404, f"unknown path {self.path}",
                            ecode="not_found")

        def do_POST(self) -> None:
            if self.path.rstrip("/") != "/v1/chat/completions":
                self._error(404, f"unknown path {self.path}",
                            ecode="not_found")
                return
            # the root span of everything this request does, on any thread
            with telemetry.span("http.request", rid=self._request_id()):
                self._chat_completions()

        def _chat_completions(self) -> None:
            try:
                n = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(n) or b"{}"
                try:
                    wire = json.loads(raw)
                except json.JSONDecodeError as e:
                    self._error(400, f"malformed JSON body: {e}",
                                ecode="invalid_json")
                    return
                creq = ChatCompletionRequest.from_wire(wire)
                if not creq.messages:
                    raise ValueError("messages must be non-empty")
                preq = creq.to_proxy()
            except (ValueError, TypeError, KeyError) as e:
                self._error(400, f"bad request: {e}")
                return
            # the durable identity feeds the proxy's WAL + dedup window
            preq.request_id = self._request_id()
            rid = f"chatcmpl-{int(time.time() * 1000):x}"
            created = int(time.time())
            try:
                # the overload gate runs before ANY work — and before the
                # SSE preamble, so a streaming request sheds with a clean
                # 429/503 instead of a broken event stream
                bridge.overload.admit(preq.user)
                if creq.stream:
                    self._stream(preq, rid=rid, created=created,
                                 model=creq.model)
                else:
                    resp = bridge.request(preq)
                    failure = _provider_error(resp)
                    if failure is not None:
                        self._error(502, failure, etype="server_error",
                                    ecode="provider_error")
                        return
                    out = ChatCompletionResponse.from_proxy(
                        resp, rid=rid, created=created, model=creq.model)
                    self._json(200, out.to_wire())
            except OverloadError as e:
                self._overloaded(e)
            except (BrokenPipeError, ConnectionResetError):
                raise                      # client gone: nothing to answer
            except Exception as e:
                self._error(500, f"internal error: {type(e).__name__}: {e}",
                            etype="server_error", ecode="internal_error")

        def _stream(self, preq, *, rid: str, created: int, model: str) -> None:
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Connection", "close")
            self.send_header("x-request-id", self._request_id())
            self.end_headers()
            gen = bridge.request_stream(preq)
            first = True
            try:
                for chunk in gen:
                    failure = _provider_error(chunk.response)
                    if failure is not None:
                        err = {"error": {"message": failure,
                                         "type": "server_error",
                                         "code": "provider_error"}}
                        self.wfile.write(b"data: " + json.dumps(err).encode()
                                         + b"\n\n")
                        self.wfile.flush()
                        return
                    with telemetry.span("http.sse_write", seq=chunk.seq):
                        wire = ChatCompletionChunk.from_stream(
                            chunk, rid=rid, created=created, model=model,
                            first=first).to_wire()
                        first = False
                        self.wfile.write(b"data: " + json.dumps(wire).encode()
                                         + b"\n\n")
                        self.wfile.flush()
                self.wfile.write(b"data: [DONE]\n\n")
                self.wfile.flush()
            except (BrokenPipeError, ConnectionResetError):
                # client hung up: closing the generator cancels decode —
                # the slot tears down and only generated tokens settle
                gen.close()

    return ThreadingHTTPServer((host, port), Handler)


def install_drain_handler(bridge, server, grace: float = 2.0) -> bool:
    """SIGTERM → graceful drain: the overload controller pins to SHED (the
    front door answers 503 + ``Retry-After``), in-flight requests finish and
    settle their realized tokens, then the serve loop exits and ``close``
    writes the final snapshots.  ``grace`` keeps the accept loop alive for a
    window after the signal so late arrivals (a load balancer that has not
    yet deregistered the pod) get the structured 503 instead of a hung
    connection.  Returns False when not on the main thread (tests run the
    server on a worker thread; they drain explicitly)."""
    def _drain(signum, frame):
        bridge.begin_drain()
        # shutdown() off-thread after the grace window: serve_forever keeps
        # answering (503 for new work) until it returns — close() then
        # flushes + snapshots
        import threading

        def _stop():
            time.sleep(grace)
            server.shutdown()

        threading.Thread(target=_stop, daemon=True).start()
    try:
        signal.signal(signal.SIGTERM, _drain)
        return True
    except ValueError:          # not on the main thread
        return False


def build_engine(arch: str, seed: int = 0):
    """An ``Engine`` of ``arch`` at its published width in bf16, random
    weights drawn on the device from ``seed``, Pallas kernels on."""
    import jax

    from repro import configs
    from repro.models import init_model
    from repro.serving.engine import Engine

    cfg = dataclasses.replace(configs.get(arch), use_pallas=True)
    params = jax.jit(init_model, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    return Engine(cfg, params, max_len=256)


def open_front_door(engine, host: str = "127.0.0.1", port: int = 8000,
                    data_dir=None):
    """``(bridge, server)``: the OpenAI front door over an LLMBridge whose
    pool is one ``engine``-backed model, built and bound but not started.
    Every answer is decoded by ``engine`` or served from the semantic cache
    (whose vector search runs the ``cache_topk`` kernels) — no SIM model
    can stand in.

    The front door runs with overload control ON: under sustained load the
    bridge browns out (degrade -> cache-only -> shed) and this surface
    answers 429/503 + ``Retry-After`` instead of queueing unboundedly.
    With ``data_dir`` the bridge is crash-safe (WAL ledger + persistent
    cache)."""
    from repro.core import ModelPool, build_bridge, pool_model_from_config
    from repro.data.tokenizer import ByteTokenizer

    model = pool_model_from_config(engine.cfg, engine=engine,
                                   tokenizer=ByteTokenizer())
    bridge = build_bridge(pool=ModelPool([model]), use_pallas_cache=True,
                          data_dir=data_dir)
    bridge.enable_overload()
    return bridge, make_server(bridge, host=host, port=port)


def serve_http(host: str, port: int, arch: str, data_dir=None) -> None:
    """Serve the OpenAI surface over an ``arch`` engine until ^C/SIGTERM.
    SIGTERM drains gracefully: shed new work, settle in-flight, fsync
    journals, final snapshot."""
    bridge, server = open_front_door(build_engine(arch), host=host,
                                     port=port, data_dir=data_dir)
    install_drain_handler(bridge, server)
    bound = server.server_address
    print(f"LLMBridge OpenAI-compatible surface on http://{bound[0]}:{bound[1]}/v1")
    print(f"  model pool: {arch} (engine-backed)")
    print("  POST /v1/chat/completions   (stream: true -> SSE)")
    print("  GET  /v1/models")
    if data_dir is not None:
        print(f"  durable state in {data_dir} (SIGTERM drains gracefully)")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        bridge.begin_drain()
        server.shutdown()
    finally:
        server.server_close()
        bridge.close()


# -- scheduler demo -----------------------------------------------------------

def demo(args) -> None:
    import jax.numpy as jnp

    from repro.data.tokenizer import ByteTokenizer
    from repro.serving.sampler import SamplerConfig
    from repro.serving.scheduler import Request, Scheduler

    engine = build_engine(args.arch)
    tok = ByteTokenizer()
    sched = Scheduler(engine, n_slots=args.slots,
                      sampler=SamplerConfig(temperature=args.temperature, top_k=40),
                      paged=args.paged, page_size=args.page_size)

    # a shared "course prompt" prefix ahead of each question gives the paged
    # prefix trie something to share, like the paper's classroom workload
    prompts = [f"course CS101 system prompt; user question number {i} "
               f"about topic {i % 5}" for i in range(args.requests)]
    t0 = time.time()
    for i, p in enumerate(prompts):
        ids = tok.encode(p)[:64]
        sched.submit(Request(rid=i, user=f"user{i % args.users}",
                             prompt=jnp.asarray(ids, jnp.int32),
                             max_new=args.max_new))
    done = sched.run_to_completion()
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in done)
    print(f"served {len(done)} requests / {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s, slots={args.slots}, compile included)")
    if args.paged:
        print(f"  paged: prefill_tokens={sched.prefill_tokens} "
              f"shared_tokens={sched.shared_tokens} "
              f"peak_slots={sched.peak_live} cow={sched.pool.n_cow} "
              f"evictions={sched.pool.n_evictions}")
    for r in done[:4]:
        print(f"  [{r.user} rid={r.rid}] -> {tok.decode(r.generated)[:48]!r}")


def main() -> None:
    from repro import configs

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=configs.ARCH_IDS,
                    help="architecture served at its published width "
                         "(bf16, random weights from seed 0)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--users", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV pool + copy-on-write prefix sharing "
                         "(attention-only archs)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve the OpenAI-compatible surface instead of "
                         "the scheduler demo")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--data-dir", default=None, metavar="DIR",
                    help="durable state directory (WAL ledger + persistent "
                         "semantic cache + graceful SIGTERM drain)")
    args = ap.parse_args()
    compile_cache.enable()
    if args.http is not None:
        serve_http(args.host, args.http, args.arch, data_dir=args.data_dir)
    else:
        demo(args)


if __name__ == "__main__":
    main()
