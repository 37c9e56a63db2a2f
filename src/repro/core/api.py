"""LLMBridge API v3: intent-based delegation, OpenAI compatibility, streaming.

The paper's interface idea is *delegation with transparency*: applications
hand the proxy a high-level intent, the proxy picks the low-level mechanisms
(model, context window, cache), discloses every choice it made, and the
application iterates.  Version 3 of the request plane has three faces:

* **Intents** (v2, the native surface) — a request carries
  :class:`Constraints` (``max_cost``, ``max_latency``, ``min_quality``,
  ``allow_cache``, ``allow_prefetch``) and a :class:`Preference`
  (cost-first / balanced / quality-first / latency-first).  The proxy's
  ``PolicyCompiler`` (``core/policy.py``) compiles the intent into a
  concrete ``PromptPipeline`` composition, and a per-user ``BudgetLedger``
  lets compiled plans degrade gracefully (cheaper route, tighter context-k,
  cache-only) as a budget depletes.
* **OpenAI compatibility** — :class:`ChatCompletionRequest` /
  :class:`ChatCompletionResponse` / :class:`ChatCompletionChunk` mirror the
  ``/v1/chat/completions`` wire schema, so existing OpenAI SDKs point at the
  proxy unchanged (``launch/serve.py`` serves the HTTP surface).  The intent
  API rides on ``x_``-prefixed extension fields (``x_max_cost``,
  ``x_preference``, ...); unknown wire fields are ignored, and responses
  disclose the proxy's decisions in an ``x_llmbridge`` extension block.
* **Streaming** — :class:`TokenStream` is the incremental token channel
  threaded through the serving stack (``LLMBridge.request_stream`` /
  ``submit_stream``): the engine yields per decode step (speculative rounds
  yield their accepted prefix as a burst), each delta arrives as a
  :class:`StreamChunk`, and the final chunk carries the full
  ``ProxyResponse`` — whose buffered text is bit-exact with the
  non-streamed path and still feeds semantic-cache insertion, judge scoring
  and the ledger settle.  ``Metadata.ttft`` / ``inter_token_p50`` disclose
  the realised streaming latency.
* **Presets** (v1, deprecated) — the seven :class:`ServiceType` values
  survive as *named presets* compiling through the same compiler path, but
  ``LLMBridge.request(service_type=...)`` now emits a ``DeprecationWarning``;
  state an intent (or speak OpenAI) instead.
* **Transparency** — :class:`Metadata` disclosures cover the compiled
  policy, budget tier, stage trajectory, per-stage :class:`StageRecord`
  entries, serving/speculation/provider telemetry and streaming latency;
  ``proxy.stats()`` aggregates them proxy-wide (the paper's Fig 6-style
  CDFs, live, plus a TTFT CDF under ``stats()["serving"]``).
* **Iteration** — ``proxy.regenerate`` walks the compiler-produced
  *escalation ladder*: each regeneration attempt is an alternate pipeline
  composition, so escalation composes with caching and batching.
"""
from __future__ import annotations

import dataclasses
import enum
import queue
import statistics
import threading
import time
from typing import Any, Dict, Iterator, List, Mapping, Optional, Sequence

from repro.core import telemetry


class ServiceType(str, enum.Enum):
    """v1 delegation presets (paper Table 2), kept as named intents.

    Deprecated as an entrypoint: ``LLMBridge.request(service_type=...)``
    warns and routes through the preset's compiled ``PlanSpec``."""
    FIXED = "fixed"
    QUALITY = "quality"
    COST = "cost"
    MODEL_SELECTOR = "model_selector"
    SMART_CONTEXT = "smart_context"
    SMART_CACHE = "smart_cache"
    # latency-centric (paper §5.1): answer immediately with the fastest
    # cheap model while prefetching a high-quality answer into the cache;
    # the "Get Better Answer" button (regenerate) serves it with zero wait.
    FAST_THEN_BETTER = "fast_then_better"


class Preference(str, enum.Enum):
    """Which axis the proxy should optimise when constraints leave slack."""
    COST_FIRST = "cost_first"
    BALANCED = "balanced"          # verification routing (paper §3.3)
    QUALITY_FIRST = "quality_first"
    LATENCY_FIRST = "latency_first"


@dataclasses.dataclass(frozen=True)
class Constraints:
    """Client-stated envelope the compiled pipeline must respect.

    ``max_cost`` is a hard per-request ceiling in cost units: the compiler
    only selects plans whose *pessimistic* estimate fits, so the realised
    usage never exceeds it.  ``max_latency`` filters plans by their modelled
    latency (best-effort; realised latency carries jitter).  ``min_quality``
    is a capability floor in [0, 1] applied to the routing candidates.
    ``allow_cache`` / ``allow_prefetch`` grant the middlebox permission to
    consult the semantic cache / spend budget on background prefetch.
    """
    max_cost: Optional[float] = None
    max_latency: Optional[float] = None
    min_quality: Optional[float] = None
    allow_cache: bool = True
    allow_prefetch: bool = True


@dataclasses.dataclass
class ProxyRequest:
    prompt: str
    user: str = "anon"
    conversation: str = "default"
    service_type: ServiceType = ServiceType.MODEL_SELECTOR
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)
    update_context: bool = True      # §3.4: some calls read but don't insert
    # benchmark plumbing: the planted workload query this prompt came from
    query: Optional[Any] = None
    # -- v2 intent fields: when either is set the request takes the
    # constraint-compilation path and ``service_type`` is ignored ----------
    constraints: Optional[Constraints] = None
    preference: Optional[Preference] = None
    # arrival timestamp, stamped by the admission front-end at enqueue —
    # ALWAYS the time.monotonic() domain (even when the controller runs on
    # a virtual clock).  ``Constraints.max_latency`` counts from HERE:
    # queue wait consumes the latency budget, so the decode-slot deadline
    # downstream is arrival-adjusted (a request that waited gets a tighter
    # decode budget).
    submitted_at: Optional[float] = None
    # durable identity: client-suppliable (HTTP `Idempotency-Key` /
    # `x-request-id`) or proxy-generated.  Keys the ledger WAL's holds and
    # settles and the idempotent-retry dedup window — re-sending a settled
    # id returns the recorded outcome instead of re-executing.
    request_id: Optional[str] = None

    @property
    def is_intent(self) -> bool:
        return self.constraints is not None or self.preference is not None


@dataclasses.dataclass
class Usage:
    input_tokens: int = 0
    output_tokens: int = 0
    extra_llm_input_tokens: int = 0   # verifier / smart-context / cache-LLM
    extra_llm_output_tokens: int = 0
    cost: float = 0.0                 # cost units (active-param-weighted)
    latency: float = 0.0              # seconds (modelled)

    def add(self, other: "Usage") -> "Usage":
        return Usage(
            self.input_tokens + other.input_tokens,
            self.output_tokens + other.output_tokens,
            self.extra_llm_input_tokens + other.extra_llm_input_tokens,
            self.extra_llm_output_tokens + other.extra_llm_output_tokens,
            self.cost + other.cost,
            self.latency + other.latency,
        )


@dataclasses.dataclass
class StageRecord:
    """One pipeline stage's disclosure: what it decided and what it cost.

    ``duration`` is the wall time in seconds of the stage's
    ``proxy.stage.<name>`` telemetry span (in batch mode, the stage's batch
    span divided evenly across its live requests);
    ``decision`` is the stage's one-token summary (``hit``/``miss``, routed
    model, context strategy, ...); ``cost_delta`` is the request-cost
    increase attributable to the stage.
    """
    name: str
    duration: float = 0.0
    decision: str = ""
    cost_delta: float = 0.0


@dataclasses.dataclass
class Metadata:
    """Transparency payload (paper §3.2 'Transparency')."""
    service_type: str = ""
    model_used: str = ""
    models_consulted: List[str] = dataclasses.field(default_factory=list)
    verifier_score: Optional[float] = None
    context_k: int = 0
    context_strategy: str = "none"
    context_decision_latency: float = 0.0
    cache_hit: bool = False
    cache_types: List[str] = dataclasses.field(default_factory=list)
    usage: Usage = dataclasses.field(default_factory=Usage)
    regeneration: int = 0
    # stage trajectory through the PromptPipeline (transparency + telemetry)
    pipeline_stages: List[str] = dataclasses.field(default_factory=list)
    # -- v2 disclosure ------------------------------------------------------
    policy: str = ""                 # compiled plan the proxy chose
    budget_tier: int = 0             # degradation level (0 = undegraded)
    budget_remaining: float = float("inf")
    stage_records: List[StageRecord] = dataclasses.field(default_factory=list)
    # -- admission disclosure (batch-forming front-end) ---------------------
    # BudgetLedger tier of the user at settle time (0 = fully funded;
    # >= the controller's yield_tier means the user defers under contention)
    ledger_tier: int = 0
    queue_wait: float = 0.0          # seconds spent in the admission queue
    batch_size: int = 0              # size of the formed batch (0 = direct)
    # -- speculative-decode disclosure (paged serving engine) ---------------
    # acceptance rate and draft/verify wall time of the serving batches the
    # answering model has decoded speculatively (None = plain decode)
    spec_acceptance: Optional[float] = None
    spec_draft_time: float = 0.0
    spec_verify_time: float = 0.0
    # -- provider-fleet disclosure (core/providers.py) ----------------------
    # the backend that actually answered (may differ from the routed model
    # after retry-against-healthy), how many attempts the request consumed,
    # and the per-attempt event trail: retries, backoffs, breaker
    # transitions, hedge fire/win/loss.  ``hedge_wasted_cost`` is the
    # cancelled hedge loser's spend — disclosed, never charged to the user.
    provider: str = ""
    provider_attempts: int = 0
    provider_events: List[str] = dataclasses.field(default_factory=list)
    hedge_wasted_cost: float = 0.0
    # -- streaming disclosure (request_stream / submit_stream) --------------
    # realised time-to-first-token and median inter-chunk gap (seconds,
    # wall-clock from stream creation); ``stream_cancelled`` means the
    # client dropped mid-stream — the slot was torn down and the ledger
    # settled only the tokens actually generated
    stream: bool = False
    stream_cancelled: bool = False
    ttft: Optional[float] = None
    inter_token_p50: Optional[float] = None
    # -- overload disclosure (core/overload.py) -----------------------------
    # brownout level at settle time ("" = controller disabled), why the
    # request was degraded/timed out ("" = it wasn't), and the suggested
    # client backoff when the proxy is under load (mirrors the HTTP
    # surface's Retry-After header)
    load_level: str = ""
    shed_reason: str = ""
    retry_after: Optional[float] = None
    # -- durability disclosure (core/durability.py) -------------------------
    # the request id the outcome is journaled under, and whether this
    # response was served from the idempotent-retry dedup window (a replay
    # costs nothing: the original settle already posted)
    request_id: str = ""
    idempotent_replay: bool = False


@dataclasses.dataclass
class ProxyResponse:
    text: str
    metadata: Metadata
    request: ProxyRequest
    # ground-truth quality (planted workloads only; never shown to "users")
    true_quality: Optional[float] = None
    # internal: cost units already posted to the BudgetLedger for this
    # response (async prefetch tops usage up after the response returns)
    _ledger_charged: float = dataclasses.field(default=0.0, repr=False)
    # internal: counter for the idempotence keys of incremental charges
    # (prefetch top-ups) posted against this response — key = rid, then
    # rid#x1, rid#x2, ... so WAL replay applies each top-up exactly once
    _charge_seq: int = dataclasses.field(default=0, repr=False)


# ---------------------------------------------------------------------------
# Streaming channel
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StreamChunk:
    """One incremental piece of a streamed response.

    ``text`` is the decoded delta (concatenating every chunk's text
    reproduces the buffered response bit-for-bit); ``token_ids`` are the
    engine tokens behind it (empty in SIM mode).  The terminal chunk has
    ``final=True``, empty text, and carries the full :class:`ProxyResponse`
    (metadata, ledger settle and cache insertion already done)."""
    text: str
    token_ids: List[int] = dataclasses.field(default_factory=list)
    final: bool = False
    response: Optional[ProxyResponse] = None
    # the chunk's place in its stream (0, 1, ...), which ``TokenStream``
    # assigns; -1 on the terminal chunk and chunks built elsewhere.  The
    # ``stream.emit`` and ``http.sse_write`` spans of a chunk carry it.
    seq: int = -1


class _StreamError:
    __slots__ = ("error",)

    def __init__(self, error: BaseException):
        self.error = error


class TokenStream:
    """Thread-safe producer/consumer channel for one streamed response.

    The pipeline's producer side calls :meth:`emit` per decode event and
    :meth:`close` once the response is finalized; the consumer iterates.
    ``emit`` returns ``False`` once the consumer cancelled (generator
    closed / client dropped), which the producer treats as a stop signal —
    the serving slot is torn down and only emitted tokens are charged.

    ``maxsize`` bounds the queue: a slow or gone consumer backpressures the
    producer instead of buffering unboundedly (0 = unbounded).  Timing is
    recorded per successful emit, feeding ``Metadata.ttft`` /
    ``inter_token_p50`` and the proxy-wide TTFT CDF.

    ``idle_timeout`` arms the abandoned-stream reaper: when no consumer
    has taken a chunk (or blocked in :meth:`wait`) for that many seconds,
    the next :meth:`emit` self-cancels and returns False — the producer
    tears the decode slot down exactly as on a client disconnect, pages
    release, and the ledger settles only the tokens actually emitted.  A
    ``submit_stream`` ticket whose ``chunks()`` is never consumed can
    therefore no longer pin decode slots forever (``None`` = never reap).
    """

    #: producer put() poll interval while checking the cancel flag
    _POLL_S = 0.05

    def __init__(self, maxsize: int = 0, idle_timeout: Optional[float] = None):
        self._q: "queue.Queue" = queue.Queue(maxsize=maxsize)
        self._cancel = threading.Event()
        self._finished = threading.Event()
        self._t0 = time.perf_counter()
        self.idle_timeout = idle_timeout
        self.cancel_reason = ""
        self._last_consumed = self._t0      # creation counts as activity
        self._waiters = 0                   # result()-style wait() blockers
        self._consume_lock = threading.Lock()
        self.arrivals: List[float] = []     # seconds since stream creation
        self.pieces: List[str] = []         # emitted text deltas, in order
        self.chunks_emitted = 0
        self.response: Optional[ProxyResponse] = None
        self.error: Optional[BaseException] = None

    # -- producer side -------------------------------------------------------
    def emit(self, text: str, token_ids: Sequence[int] = ()) -> bool:
        """Push one delta.  Returns False iff the consumer cancelled — the
        producer must stop decoding (the chunk may or may not have been
        delivered; it is not counted as emitted after a cancel)."""
        with telemetry.span("stream.emit", seq=self.chunks_emitted):
            return self._emit(text, token_ids)

    def _emit(self, text: str, token_ids: Sequence[int]) -> bool:
        if self._cancel.is_set():
            return False
        if (self.idle_timeout is not None and self._waiters == 0
                and time.perf_counter() - self._last_consumed
                > self.idle_timeout):
            # abandoned-stream reaper: nobody is iterating or waiting —
            # self-cancel so the producer releases its decode slot/pages
            self.cancel_reason = "idle"
            self._cancel.set()
            return False
        chunk = StreamChunk(text=text, token_ids=list(token_ids),
                            seq=self.chunks_emitted)
        while True:
            try:
                self._q.put(chunk, timeout=self._POLL_S)
                break
            except queue.Full:
                if self._cancel.is_set():
                    return False
        self.arrivals.append(time.perf_counter() - self._t0)
        self.pieces.append(text)
        self.chunks_emitted += 1
        return not self._cancel.is_set()

    def close(self, response: Optional[ProxyResponse] = None,
              error: Optional[BaseException] = None) -> None:
        """Terminal marker: the pipeline finished (or died).  Always lands,
        even against a full queue whose consumer is gone — after a cancel
        the buffered chunks are dropped to make room (nobody reads them)."""
        self.response = response
        self.error = error
        item = (_StreamError(error) if error is not None
                else StreamChunk(text="", final=True, response=response))
        while True:
            try:
                self._q.put(item, timeout=self._POLL_S)
                break
            except queue.Full:
                if self._cancel.is_set():
                    try:
                        while True:
                            self._q.get_nowait()
                    except queue.Empty:
                        pass
        self._finished.set()

    # -- consumer side -------------------------------------------------------
    def __iter__(self) -> Iterator[StreamChunk]:
        while True:
            item = self._q.get()
            self._last_consumed = time.perf_counter()
            if isinstance(item, _StreamError):
                raise item.error
            yield item
            if item.final:
                return

    def cancel(self, reason: str = "consumer") -> None:
        """Consumer dropped: unblock the producer and make further emits
        return False."""
        if not self._cancel.is_set():
            self.cancel_reason = reason
        self._cancel.set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the producer closed the stream (submit_stream
        tickets use this for ``result()``).  A blocked waiter counts as a
        live consumer: the idle reaper must not cancel decode out from
        under a caller that wants the final response."""
        with self._consume_lock:
            self._waiters += 1
        try:
            return self._finished.wait(timeout)
        finally:
            with self._consume_lock:
                self._waiters -= 1
            self._last_consumed = time.perf_counter()

    # -- telemetry -----------------------------------------------------------
    @property
    def cancelled(self) -> bool:
        return self._cancel.is_set()

    @property
    def text(self) -> str:
        """Everything emitted so far, concatenated (== the buffered response
        text once the stream completes uncancelled)."""
        return "".join(self.pieces)

    def ttft(self) -> Optional[float]:
        """Time-to-first-token: seconds from stream creation to the first
        delivered chunk."""
        return self.arrivals[0] if self.arrivals else None

    def inter_token_p50(self) -> Optional[float]:
        """Median gap between consecutive chunk deliveries."""
        if len(self.arrivals) < 2:
            return None
        gaps = [b - a for a, b in zip(self.arrivals, self.arrivals[1:])]
        return statistics.median(gaps)


# ---------------------------------------------------------------------------
# OpenAI-compatible wire schema (/v1/chat/completions)
# ---------------------------------------------------------------------------

#: "model" values that mean "let the proxy route" (the native mode)
AUTO_MODELS = ("", "auto", "llmbridge", "llmbridge-auto")


@dataclasses.dataclass
class ChatMessage:
    role: str = "user"
    content: str = ""

    def to_wire(self) -> Dict[str, str]:
        return {"role": self.role, "content": self.content}


@dataclasses.dataclass
class ChatCompletionRequest:
    """The OpenAI ``/v1/chat/completions`` request body, plus ``x_``
    extension fields that carry the intent API over the wire.

    ``from_wire`` ignores unknown fields (SDKs evolve; the proxy must not
    400 on fields it doesn't know) and ``to_proxy`` maps the result onto a
    native :class:`ProxyRequest`: extension fields become
    :class:`Constraints` / :class:`Preference`; a concrete ``model`` pins
    the route through the FIXED preset; ``max_tokens`` caps the decode."""
    messages: List[ChatMessage] = dataclasses.field(default_factory=list)
    model: str = "auto"
    stream: bool = False
    max_tokens: Optional[int] = None
    temperature: Optional[float] = None
    user: Optional[str] = None
    # -- x_ extensions: the intent API over the OpenAI wire ------------------
    x_max_cost: Optional[float] = None
    x_max_latency: Optional[float] = None
    x_min_quality: Optional[float] = None
    x_preference: Optional[str] = None      # a Preference value
    x_conversation: Optional[str] = None
    x_allow_cache: bool = True
    x_allow_prefetch: bool = True

    @classmethod
    def from_wire(cls, payload: Mapping[str, Any]) -> "ChatCompletionRequest":
        known = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in payload.items() if k in known}
        kw["messages"] = [
            m if isinstance(m, ChatMessage)
            else ChatMessage(role=str(m.get("role", "user")),
                             content=str(m.get("content", "")))
            for m in kw.get("messages", [])]
        return cls(**kw)

    def to_wire(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "messages": [m.to_wire() for m in self.messages],
            "model": self.model,
            "stream": self.stream,
        }
        for f in ("max_tokens", "temperature", "user", "x_max_cost",
                  "x_max_latency", "x_min_quality", "x_preference",
                  "x_conversation"):
            v = getattr(self, f)
            if v is not None:
                out[f] = v
        if not self.x_allow_cache:
            out["x_allow_cache"] = False
        if not self.x_allow_prefetch:
            out["x_allow_prefetch"] = False
        return out

    @property
    def prompt(self) -> str:
        """The prompt the proxy answers: the last user-role message (the
        conversation history lives in the proxy's own ContextManager,
        keyed by ``x_conversation``)."""
        for m in reversed(self.messages):
            if m.role == "user":
                return m.content
        return self.messages[-1].content if self.messages else ""

    def to_proxy(self) -> ProxyRequest:
        user = self.user or "anon"
        conversation = self.x_conversation or f"openai:{user}"
        params: Dict[str, Any] = {"_wire": "openai"}
        if self.max_tokens is not None:
            params["max_tokens"] = int(self.max_tokens)
        if self.model not in AUTO_MODELS:
            # explicit model pin: route through the FIXED preset
            params["model"] = self.model
            return ProxyRequest(prompt=self.prompt, user=user,
                                conversation=conversation,
                                service_type=ServiceType.FIXED,
                                params=params)
        constraints = Constraints(
            max_cost=self.x_max_cost, max_latency=self.x_max_latency,
            min_quality=self.x_min_quality,
            allow_cache=self.x_allow_cache,
            allow_prefetch=self.x_allow_prefetch)
        preference = (Preference(self.x_preference)
                      if self.x_preference is not None else None)
        return ProxyRequest(prompt=self.prompt, user=user,
                            conversation=conversation, params=params,
                            constraints=constraints, preference=preference)


def _x_llmbridge(md: Metadata) -> Dict[str, Any]:
    """The proxy's transparency disclosure on the OpenAI wire."""
    out: Dict[str, Any] = {
        "model_used": md.model_used,
        "policy": md.policy,
        "cost": md.usage.cost,
        "cache_hit": md.cache_hit,
        "budget_tier": md.budget_tier,
    }
    if md.ttft is not None:
        out["ttft"] = md.ttft
    if md.inter_token_p50 is not None:
        out["inter_token_p50"] = md.inter_token_p50
    if md.load_level:
        out["load_level"] = md.load_level
    if md.shed_reason:
        out["shed_reason"] = md.shed_reason
    if md.retry_after is not None:
        out["retry_after"] = md.retry_after
    if md.request_id:
        out["request_id"] = md.request_id
    if md.idempotent_replay:
        out["idempotent_replay"] = True
    return out


@dataclasses.dataclass
class ChatCompletionResponse:
    """Buffered (non-stream) response object: ``chat.completion``."""
    id: str
    created: int
    model: str
    response: ProxyResponse
    object: str = "chat.completion"

    def to_wire(self) -> Dict[str, Any]:
        md = self.response.metadata
        return {
            "id": self.id,
            "object": self.object,
            "created": self.created,
            "model": self.model,
            "choices": [{
                "index": 0,
                "message": {"role": "assistant", "content": self.response.text},
                "finish_reason": "stop",
            }],
            "usage": {
                "prompt_tokens": md.usage.input_tokens,
                "completion_tokens": md.usage.output_tokens,
                "total_tokens": md.usage.input_tokens + md.usage.output_tokens,
            },
            "x_llmbridge": _x_llmbridge(md),
        }

    @classmethod
    def from_proxy(cls, resp: ProxyResponse, *, rid: str, created: int,
                   model: str) -> "ChatCompletionResponse":
        return cls(id=rid, created=created,
                   model=resp.metadata.model_used or model, response=resp)


@dataclasses.dataclass
class ChatCompletionChunk:
    """One SSE frame of a streamed response: ``chat.completion.chunk``."""
    id: str
    created: int
    model: str
    delta: Dict[str, Any]
    finish_reason: Optional[str] = None
    x_llmbridge: Optional[Dict[str, Any]] = None
    object: str = "chat.completion.chunk"

    def to_wire(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "id": self.id,
            "object": self.object,
            "created": self.created,
            "model": self.model,
            "choices": [{
                "index": 0,
                "delta": self.delta,
                "finish_reason": self.finish_reason,
            }],
        }
        if self.x_llmbridge is not None:
            out["x_llmbridge"] = self.x_llmbridge
        return out

    @classmethod
    def from_stream(cls, chunk: StreamChunk, *, rid: str, created: int,
                    model: str, first: bool = False) -> "ChatCompletionChunk":
        if chunk.final:
            md = chunk.response.metadata if chunk.response is not None else None
            return cls(id=rid, created=created,
                       model=(md.model_used if md is not None else model),
                       delta={}, finish_reason="stop",
                       x_llmbridge=_x_llmbridge(md) if md is not None else None)
        delta: Dict[str, Any] = {"content": chunk.text}
        if first:
            delta["role"] = "assistant"
        return cls(id=rid, created=created, model=model, delta=delta)
