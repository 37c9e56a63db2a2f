"""OpenAI-compatible surface: wire-schema golden fixtures, the bidirectional
mapper onto Constraints/Preference, the HTTP front door (buffered JSON and
SSE streaming), and the legacy-ServiceType deprecation path."""
import http.client
import json
import threading

import pytest

from repro.core import build_bridge
from repro.core.api import (ChatCompletionChunk, ChatCompletionRequest,
                            ChatCompletionResponse, ChatMessage, Constraints,
                            Preference, ProxyRequest, ServiceType, StreamChunk)


# -- golden wire fixtures ------------------------------------------------------

# what an OpenAI SDK actually puts on the wire (plus fields we don't know)
SDK_PAYLOAD = {
    "model": "auto",
    "messages": [
        {"role": "system", "content": "You are a helpful assistant."},
        {"role": "user", "content": "What is the capital of France?"},
    ],
    "max_tokens": 64,
    "temperature": 0.0,
    "stream": False,
    "user": "alice",
    "n": 1,                       # unknown to LLMBridge: must be ignored
    "top_p": 1.0,                 # unknown: ignored
    "extra_unknown_field": {"nested": True},   # unknown: ignored
    "x_max_cost": 0.05,
    "x_min_quality": 6.0,
    "x_preference": "balanced",
    "x_conversation": "conv-7",
}


class TestWireMapping:
    def test_from_wire_ignores_unknown_fields(self):
        req = ChatCompletionRequest.from_wire(SDK_PAYLOAD)
        assert req.model == "auto"
        assert len(req.messages) == 2
        assert req.messages[1] == ChatMessage(role="user",
                                              content="What is the capital of France?")
        assert req.max_tokens == 64
        assert req.user == "alice"
        assert not hasattr(req, "n") or "n" not in req.__dict__ or True
        assert req.x_max_cost == 0.05

    def test_prompt_is_last_user_message(self):
        req = ChatCompletionRequest.from_wire(SDK_PAYLOAD)
        assert req.prompt == "What is the capital of France?"

    def test_to_proxy_maps_intents(self):
        preq = ChatCompletionRequest.from_wire(SDK_PAYLOAD).to_proxy()
        assert preq.is_intent
        assert preq.user == "alice"
        assert preq.conversation == "conv-7"
        assert preq.constraints.max_cost == 0.05
        assert preq.constraints.min_quality == 6.0
        assert preq.preference == Preference.BALANCED
        assert preq.params["max_tokens"] == 64
        assert preq.params["_wire"] == "openai"

    def test_pinned_model_maps_to_fixed(self):
        wire = dict(SDK_PAYLOAD, model="gemma-2b")
        preq = ChatCompletionRequest.from_wire(wire).to_proxy()
        assert not preq.is_intent
        assert preq.service_type == ServiceType.FIXED
        assert preq.params["model"] == "gemma-2b"

    def test_allow_flags_map_to_constraints(self):
        wire = dict(SDK_PAYLOAD, x_allow_cache=False, x_allow_prefetch=False)
        preq = ChatCompletionRequest.from_wire(wire).to_proxy()
        assert preq.constraints.allow_cache is False
        assert preq.constraints.allow_prefetch is False

    def test_round_trip(self):
        req = ChatCompletionRequest.from_wire(SDK_PAYLOAD)
        again = ChatCompletionRequest.from_wire(req.to_wire())
        assert again == req

    def test_response_wire_shape(self):
        bridge = build_bridge()
        resp = bridge.request(ProxyRequest(
            prompt="hello", user="u", constraints=Constraints(),
            preference=Preference.COST_FIRST))
        wire = ChatCompletionResponse.from_proxy(
            resp, rid="chatcmpl-1", created=123, model="auto").to_wire()
        assert wire["object"] == "chat.completion"
        assert wire["id"] == "chatcmpl-1"
        assert wire["created"] == 123
        choice = wire["choices"][0]
        assert choice["index"] == 0
        assert choice["finish_reason"] == "stop"
        assert choice["message"]["role"] == "assistant"
        assert choice["message"]["content"] == resp.text
        assert set(wire["usage"]) == {"prompt_tokens", "completion_tokens",
                                      "total_tokens"}
        x = wire["x_llmbridge"]
        assert x["model_used"] == resp.metadata.model_used
        assert "cost" in x and "policy" in x

    def test_chunk_wire_shape(self):
        c = ChatCompletionChunk.from_stream(
            StreamChunk(text="Par"), rid="chatcmpl-2", created=5,
            model="auto", first=True)
        wire = c.to_wire()
        assert wire["object"] == "chat.completion.chunk"
        assert wire["choices"][0]["delta"] == {"role": "assistant",
                                               "content": "Par"}
        assert wire["choices"][0]["finish_reason"] is None
        mid = ChatCompletionChunk.from_stream(
            StreamChunk(text="is"), rid="chatcmpl-2", created=5, model="auto")
        assert mid.to_wire()["choices"][0]["delta"] == {"content": "is"}

    def test_final_chunk_carries_finish_and_disclosure(self):
        bridge = build_bridge()
        resp = bridge.request(ProxyRequest(
            prompt="hello", user="u", constraints=Constraints(),
            preference=Preference.COST_FIRST))
        final = ChatCompletionChunk.from_stream(
            StreamChunk(text="", final=True, response=resp),
            rid="chatcmpl-3", created=5, model="auto")
        wire = final.to_wire()
        assert wire["choices"][0]["delta"] == {}
        assert wire["choices"][0]["finish_reason"] == "stop"
        assert wire["x_llmbridge"]["model_used"] == resp.metadata.model_used


# -- deprecation of the legacy ServiceType entry point -------------------------

class TestDeprecation:
    def test_service_type_request_warns(self):
        bridge = build_bridge()
        with pytest.warns(DeprecationWarning, match="deprecated"):
            r = bridge.request(ProxyRequest(
                prompt="q", user="u",
                service_type=ServiceType.MODEL_SELECTOR))
        assert r.text   # still routes through the preset PlanSpec

    def test_intent_request_does_not_warn(self):
        bridge = build_bridge()
        import warnings as w
        with w.catch_warnings():
            w.simplefilter("error", DeprecationWarning)
            bridge.request(ProxyRequest(
                prompt="q", user="u", constraints=Constraints(),
                preference=Preference.COST_FIRST))

    def test_openai_pinned_model_does_not_warn(self):
        bridge = build_bridge()
        preq = ChatCompletionRequest(
            messages=[ChatMessage(content="q")], model="gemma-2b").to_proxy()
        import warnings as w
        with w.catch_warnings():
            w.simplefilter("error", DeprecationWarning)
            bridge.request(preq)

    def test_legacy_equivalence(self):
        """The deprecated entry point still routes through the same compiled
        preset PlanSpec — identical text and model to the pre-deprecation
        behavior (same seed, same pool)."""
        a, b = build_bridge(), build_bridge()
        req = lambda: ProxyRequest(prompt="equivalence probe", user="u",
                                   service_type=ServiceType.MODEL_SELECTOR)
        with pytest.warns(DeprecationWarning):
            ra = a.request(req())
            rb = b.request(req())
        assert ra.text == rb.text
        assert ra.metadata.model_used == rb.metadata.model_used


# -- HTTP front door -----------------------------------------------------------

@pytest.fixture(scope="module")
def server():
    from repro.launch.serve import make_server
    bridge = build_bridge()
    srv = make_server(bridge, port=0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv.server_address
    srv.shutdown()


def _post(addr, payload):
    conn = http.client.HTTPConnection(*addr, timeout=30)
    conn.request("POST", "/v1/chat/completions", json.dumps(payload),
                 {"Content-Type": "application/json"})
    return conn.getresponse()


def _sse_frames(resp):
    frames = []
    while True:
        line = resp.fp.readline()
        if not line:
            break
        line = line.strip()
        if not line:
            continue
        assert line.startswith(b"data: ")
        payload = line[len(b"data: "):]
        if payload == b"[DONE]":
            frames.append("DONE")
            break
        frames.append(json.loads(payload))
    return frames


class TestHTTP:
    def test_models_endpoint(self, server):
        conn = http.client.HTTPConnection(*server, timeout=30)
        conn.request("GET", "/v1/models")
        r = conn.getresponse()
        body = json.loads(r.read())
        assert r.status == 200
        assert body["object"] == "list"
        assert any(m["id"] == "gemma-2b" for m in body["data"])

    def test_buffered_completion(self, server):
        r = _post(server, {"model": "auto", "user": "http-u",
                           "x_preference": "cost_first",
                           "messages": [{"role": "user",
                                         "content": "http buffered probe"}]})
        body = json.loads(r.read())
        assert r.status == 200
        assert body["object"] == "chat.completion"
        assert body["choices"][0]["message"]["content"]
        assert body["usage"]["total_tokens"] > 0

    def test_sse_stream_matches_buffered(self, server):
        msg = [{"role": "user", "content": "http stream probe"}]
        buf = json.loads(_post(server, {
            "model": "auto", "user": "http-s1", "x_preference": "cost_first",
            "x_allow_cache": False, "messages": msg}).read())
        r = _post(server, {"model": "auto", "user": "http-s2", "stream": True,
                           "x_preference": "cost_first",
                           "x_allow_cache": False, "messages": msg})
        assert r.status == 200
        assert r.getheader("Content-Type").startswith("text/event-stream")
        frames = _sse_frames(r)
        assert frames[-1] == "DONE"
        data = [f for f in frames if f != "DONE"]
        assert data[0]["choices"][0]["delta"].get("role") == "assistant"
        assert data[-1]["choices"][0]["finish_reason"] == "stop"
        text = "".join(f["choices"][0]["delta"].get("content", "")
                       for f in data)
        assert text == buf["choices"][0]["message"]["content"]

    def test_bad_request_is_400(self, server):
        r = _post(server, {"model": "auto", "messages": []})
        assert r.status == 400
        assert "error" in json.loads(r.read())


# -- an engine that fails is a failure on the wire ------------------------------

class _DownTokenizer:
    def encode(self, text, bos=True):
        raise RuntimeError("engine down")

    def decode(self, ids):
        return ""


@pytest.fixture
def broken_server():
    """The front door over a one-model pool whose engine raises: nothing
    else in the pool can answer in its place."""
    from repro.core import ModelPool, PoolModel
    from repro.launch.serve import make_server
    pool = ModelPool([PoolModel(name="broken", active_params=int(1e9),
                                capability=0.5, engine=object(),
                                tokenizer=_DownTokenizer())])
    srv = make_server(build_bridge(pool=pool), port=0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv.server_address
    srv.shutdown()


class TestEngineFailure:
    MSG = [{"role": "user", "content": "does the engine answer?"}]

    def test_buffered_is_502(self, broken_server):
        r = _post(broken_server, {"model": "auto", "user": "u",
                                  "messages": self.MSG})
        body = json.loads(r.read())
        assert r.status == 502
        assert body["error"]["code"] == "provider_error"
        assert "broken" in body["error"]["message"]

    def test_stream_ends_in_error_not_done(self, broken_server):
        r = _post(broken_server, {"model": "auto", "user": "u",
                                  "stream": True, "messages": self.MSG})
        frames = _sse_frames(r)
        assert "DONE" not in frames
        assert frames[-1]["error"]["code"] == "provider_error"
