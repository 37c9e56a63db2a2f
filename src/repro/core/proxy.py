"""LLMBridge proxy orchestrator: compiled pipelines over a batched hot path.

The request plane is *intent-based* (API v2): a ``ProxyRequest`` either
names a v1 ``ServiceType`` preset or states ``Constraints`` + ``Preference``
and lets the ``PolicyCompiler`` (``core/policy.py``) pick the mechanisms.
Both compile to the same thing — a ``PromptPipeline`` of middlebox stages
(② ``CacheStage`` -> ③ ``ContextStage`` -> ``RouteStage`` -> ④
``ModelStage`` (paper Fig 2), with ``PrefetchStage`` appended for
latency-centric plans) plus an *escalation ladder*: alternate compositions
that ``regenerate`` walks, so iteration composes with caching and batching.

Two execution modes share the same stages:

* ``request``        — one request through its pipeline, stage by stage;
* ``request_batch``  — B in-flight requests executed stage-major, grouped by
  compiled pipeline: one embedder forward pass and one multi-query
  ``VectorStore.search`` (Pallas ``cache_topk``) answer the whole batch's
  cache lookups, and REAL-mode pool models — including verification's M1/M2
  legs — decode admitted requests in one continuous batch on the serving
  ``Scheduler``, whose admission serves latency-budgeted requests
  earliest-deadline-first.  Requests in a batch are concurrently in-flight:
  context writes commit after the batch completes, in submission order.

Cost governance: a per-user ``BudgetLedger`` meters every response; compiled
intent plans place a pessimistic hold first, so a constrained run can never
overdraw, and plans degrade monotonically as the budget depletes.

Fair admission: ``submit()``/``drain()`` front the proxy with the
``AdmissionController`` (``core/admission.py``) — per-user FIFO queues
(the paper's SQS discipline, §4), cross-user batch formation by rotating,
deadline- and budget-aware round-robin, holds placed at enqueue — so
single-request callers transparently share the batched hot path and heavy
users cannot monopolize it.

Transparency: responses carry the compiled policy name, budget tier, stage
trajectory and per-stage ``StageRecord``s; ``stats()`` aggregates per-stage
wall-time and hit/decision rates across both execution paths (the paper's
Fig 6-style CDFs, live), and ``stage_cdf`` exposes the raw curves.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import queue
import threading
import time
import uuid
import warnings
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import telemetry
from repro.core.api import (Metadata, ProxyRequest, ProxyResponse, ServiceType,
                            StreamChunk, TokenStream, Usage)
from repro.core.cache import SemanticCache
from repro.core.context_manager import (ContextManager, LastK, SmartContext,
                                        apply_filters)
from repro.core.model_adapter import ModelAdapter, ModelPool, PoolModel
from repro.core.judge import Judge
from repro.core.overload import LoadLevel, OverloadController
from repro.core.pipeline import PromptPipeline, RequestState
from repro.core.policy import BudgetLedger, CompiledPolicy, PolicyCompiler
from repro.core.workload import Workload


@dataclasses.dataclass
class ProxyConfig:
    verify_threshold: float = 8.0
    default_context_k: int = 5          # model_selector uses 5 previous msgs (§3.2)
    smart_context_k: int = 5
    cache_relevance: float = 0.60
    smart_context_accuracy: float = 0.90  # planted decider channel accuracy


def _new_request_id() -> str:
    """A fresh durable request identity (the WAL/dedup key for requests
    whose client supplied none)."""
    return f"req_{uuid.uuid4().hex[:16]}"


class _PrefetchWorker:
    """Single background worker draining prefetch jobs in submission order.

    The thread is started lazily on the first job and exits after
    ``IDLE_TIMEOUT`` seconds without work (a later job restarts it), so a
    process that builds many bridges does not accumulate parked threads.
    ``flush`` joins the queue and re-raises the first captured job error —
    the deterministic-test hook the async-prefetch satellite calls for."""

    IDLE_TIMEOUT = 1.0
    _STOP = object()

    def __init__(self):
        self._q: "queue.Queue" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._errors: List[BaseException] = []
        self._lock = threading.Lock()

    def submit(self, job) -> None:
        # enqueue under the lock: the worker's idle-exit also holds it, so
        # a job can never land between its emptiness check and its exit
        with self._lock:
            self._q.put(job)
            if self._thread is None:
                self._thread = threading.Thread(target=self._loop, daemon=True)
                self._thread.start()

    def _loop(self) -> None:
        while True:
            try:
                job = self._q.get(timeout=self.IDLE_TIMEOUT)
            except queue.Empty:
                with self._lock:
                    if self._q.empty():
                        self._thread = None
                        return
                continue
            if job is self._STOP:
                with self._lock:
                    self._thread = None
                self._q.task_done()
                return
            try:
                job()
            except BaseException as e:       # surfaced on flush()
                self._errors.append(e)
            finally:
                self._q.task_done()

    def flush(self, raise_errors: bool = True) -> None:
        self._q.join()
        if raise_errors and self._errors:
            raise self._errors.pop(0)

    def close(self) -> None:
        """Drain the queue, then stop and join the worker thread promptly
        (no idle-timeout wait) via a stop sentinel.  A later ``submit``
        restarts the worker, so close is safe to call between uses."""
        self._q.join()
        with self._lock:
            t = self._thread
            if t is None:
                return
            self._q.put(self._STOP)
        t.join()


def jsonable(obj):
    """Recursively make a stats/telemetry dict JSON-serializable: NaN and
    +/-inf (e.g. the ledger's unlimited default budget) become null, tuples
    become lists, keys become strings.  The benchmark JSON artifact
    exporters run ``proxy.stats()`` output through this."""
    if isinstance(obj, dict):
        return {str(k): jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [jsonable(v) for v in obj]
    if isinstance(obj, float) and (obj != obj
                                   or obj in (float("inf"), float("-inf"))):
        return None
    return obj


class ProxyStats:
    """Per-stage wall-time + decision aggregation for ``proxy.stats()``.

    Counts/totals/decisions are exact scalars; percentile/CDF material is
    kept in a bounded ring of the most recent ``WINDOW`` durations per
    stage so a long-lived proxy's memory stays flat."""

    WINDOW = 4096

    def __init__(self):
        self._paths: Dict[str, Dict[str, Any]] = {}
        # streaming latency rings: realised TTFTs and median inter-chunk
        # gaps of the most recent streamed responses (stats()["serving"])
        self._ttft: collections.deque = collections.deque(maxlen=self.WINDOW)
        self._inter: collections.deque = collections.deque(maxlen=self.WINDOW)
        self._streams = 0
        self._streams_cancelled = 0

    def record_stream(self, sink: TokenStream) -> None:
        self._streams += 1
        if sink.cancelled:
            self._streams_cancelled += 1
        t = sink.ttft()
        if t is not None:
            self._ttft.append(t)
        g = sink.inter_token_p50()
        if g is not None:
            self._inter.append(g)

    def stream_snapshot(self) -> Dict[str, Any]:
        t = np.asarray(self._ttft, dtype=np.float64)
        g = np.asarray(self._inter, dtype=np.float64)
        return {
            "streams": self._streams,
            "streams_cancelled": self._streams_cancelled,
            "ttft_cdf": sorted(float(x) for x in t),
            "ttft_p50_s": float(np.percentile(t, 50)) if t.size else 0.0,
            "ttft_p95_s": float(np.percentile(t, 95)) if t.size else 0.0,
            "inter_token_p50_s": (float(np.percentile(g, 50))
                                  if g.size else 0.0),
        }

    def record(self, path: str, state: RequestState) -> None:
        p = self._paths.setdefault(path, {"requests": 0, "stages": {}})
        p["requests"] += 1
        for rec in state.records:
            s = p["stages"].setdefault(
                rec.name, {"count": 0, "total_s": 0.0, "cost": 0.0,
                           "durations": collections.deque(maxlen=self.WINDOW),
                           "decisions": {}})
            s["count"] += 1
            s["total_s"] += rec.duration
            s["cost"] += rec.cost_delta
            s["durations"].append(rec.duration)
            if rec.decision:
                s["decisions"][rec.decision] = \
                    s["decisions"].get(rec.decision, 0) + 1

    def durations(self, path: str, stage: str) -> List[float]:
        return list(self._paths.get(path, {}).get("stages", {})
                    .get(stage, {}).get("durations", []))

    def snapshot(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for path, p in self._paths.items():
            stages = {}
            for name, s in p["stages"].items():
                d = np.asarray(s["durations"], dtype=np.float64)
                n = s["count"]
                stages[name] = {
                    "count": n,
                    "total_s": s["total_s"],
                    "mean_s": s["total_s"] / n if n else 0.0,
                    "p50_s": float(np.percentile(d, 50)) if d.size else 0.0,
                    "p95_s": float(np.percentile(d, 95)) if d.size else 0.0,
                    "cost": s["cost"],
                    "decisions": dict(s["decisions"]),
                    "decision_rates": {k: v / n for k, v
                                       in s["decisions"].items()},
                }
            out[path] = {"requests": p["requests"], "stages": stages}
        return out


class LLMBridge:
    def __init__(self, pool: ModelPool, context: ContextManager,
                 cache: SemanticCache, judge: Judge,
                 workload: Optional[Workload] = None,
                 config: ProxyConfig = ProxyConfig(), seed: int = 0,
                 ledger: Optional[BudgetLedger] = None,
                 durability=None):
        self.pool = pool
        self.adapter = ModelAdapter(pool, workload=workload, seed=seed)
        self.context = context
        self.cache = cache
        self.judge = judge
        self.workload = workload
        self.config = config
        self.rng = np.random.default_rng(seed + 1)
        # crash-safe durability (core/durability.py): a Durability facade
        # supplies the WAL-backed ledger, persists the semantic cache, and
        # backs the idempotent-retry dedup window
        self.durability = durability
        if durability is not None:
            if ledger is None:
                ledger = (durability.ledger if durability.ledger is not None
                          else durability.open_ledger())
            if cache.persist is None:
                durability.attach_cache(cache)
        self.ledger = ledger if ledger is not None else BudgetLedger()
        # the compiler: presets AND intents lower through the same path
        self.compiler = PolicyCompiler(config)
        self._preset_policies: Dict[ServiceType, CompiledPolicy] = {
            st: self.compiler.compile_service(st) for st in ServiceType}
        # back-compat dict view (mutate/extend to add or override policies)
        self.pipelines: Dict[ServiceType, PromptPipeline] = {
            st: pol.pipeline for st, pol in self._preset_policies.items()}
        # FAST_THEN_BETTER prefetched qualities, keyed by _better_key
        self._better_quality: Dict[str, Any] = {}
        self._prefetch = _PrefetchWorker()
        self._ledger_lock = threading.Lock()
        self._stats = ProxyStats()
        self._admission = None          # lazy AdmissionController (submit())
        # overload control ships disabled: library callers keep unbounded
        # acceptance unless they (or the HTTP front door) opt in via
        # enable_overload() — see core/overload.py
        self.overload = OverloadController(enabled=False)
        self.adapter.overload = self.overload

    # -- the SmartContext decider (planted channel or real small model) -------
    def _context_decider(self):
        acc = self.config.smart_context_accuracy

        def decide(prompt: str, messages, query=None) -> bool:
            if query is not None:
                truth = bool(query.needs_context)
                return truth if self.rng.random() < acc else not truth
            # fallback heuristic: pronouns/ellipsis suggest context need
            p = prompt.lower()
            return any(w in p.split() for w in ("it", "that", "they", "more", "why"))
        return decide

    # -- policy resolution -----------------------------------------------------
    def _policy_for(self, req: ProxyRequest) -> CompiledPolicy:
        if req.is_intent:
            return self.compiler.compile_intent(req, self)
        # presets have no candidate ladder to degrade along, so brownout
        # applies its floor directly: cache-only, then decline
        if (self.overload.enabled
                and self.overload.level >= LoadLevel.CACHE_PREFERRED):
            return self.compiler.compile_brownout(req, self)
        pol = self._preset_policies[req.service_type]
        pipe = self.pipelines.get(req.service_type, pol.pipeline)
        if pipe is not pol.pipeline:      # user override via the dict view
            pol = dataclasses.replace(pol, pipeline=pipe)
        return pol

    def _state_for(self, req: ProxyRequest) -> RequestState:
        """Compile ``req`` and stamp the overload-layer wall deadline: with
        the controller enabled and ``max_latency`` stated, the pipeline's
        stage watchdogs (and the decode step loop) enforce it absolutely
        from arrival, not per-stage."""
        state = RequestState(req=req, policy=self._policy_for(req))
        if (self.overload.enabled and req.constraints is not None
                and req.constraints.max_latency is not None):
            base = (req.submitted_at if req.submitted_at is not None
                    else time.monotonic())
            state.deadline_at = base + req.constraints.max_latency
        return state

    def _warn_legacy(self, req: ProxyRequest) -> None:
        """v1 deprecation: a non-intent request through a public entry point
        warns (the preset PlanSpecs still compile and route identically).
        Requests mapped from the OpenAI wire surface are v3, not v1 — a
        model pin legitimately rides the FIXED preset without warning."""
        if not req.is_intent and req.params.get("_wire") is None:
            warnings.warn(
                "LLMBridge.request(service_type=...) is deprecated: state "
                "Constraints/Preference (the intent API) or use the "
                "OpenAI-compatible surface; ServiceType presets keep "
                "routing through their compiled PlanSpecs for now.",
                DeprecationWarning, stacklevel=3)

    # -- durable identity + idempotent retries ---------------------------------
    def _crash_hit(self, name: str) -> None:
        if self.durability is not None:
            self.durability.crash.hit(name)

    def _prepare(self, req: ProxyRequest) -> Optional[ProxyResponse]:
        """Stamp the request's durable identity and consult the
        idempotent-retry window.  A client-supplied id that already settled
        returns the recorded outcome (the replay response — zero cost, no
        re-execution); a fresh id returns None and the request executes."""
        if req.request_id is None:
            req.request_id = _new_request_id()
            return None
        if self.durability is not None:
            outcome = self.durability.lookup(req.request_id)
            if outcome is not None:
                return self._dedup_response(req, outcome)
        return None

    def _dedup_response(self, req: ProxyRequest,
                        outcome: Dict[str, Any]) -> ProxyResponse:
        md = Metadata(
            model_used=outcome.get("model", ""),
            policy=outcome.get("policy", ""),
            cache_hit=bool(outcome.get("cache_hit", False)),
            context_strategy="idempotent_replay",
            request_id=req.request_id or "",
            idempotent_replay=True)
        md.budget_remaining = self.ledger.remaining(req.user)
        md.ledger_tier = self.ledger.tier(req.user)
        return ProxyResponse(text=outcome.get("text", ""), metadata=md,
                             request=req)

    # -- main entry ------------------------------------------------------------
    def request(self, req: ProxyRequest) -> ProxyResponse:
        self._warn_legacy(req)
        replay = self._prepare(req)
        if replay is not None:
            return replay
        state = self._state_for(req)
        try:
            state.policy.pipeline.run(self, state)
        except BaseException:
            self._release_hold(state)   # a failed request must not leak it
            raise
        return self._finalize(state, path="request")

    def request_stream(self, req: ProxyRequest, *,
                       buffer: int = 0) -> Iterator[StreamChunk]:
        """Execute ``req`` while yielding ``StreamChunk``s as tokens land.

        The pipeline runs on a worker thread with a ``TokenStream`` attached
        to the request state; the caller iterates chunks here.  The final
        chunk carries the full ``ProxyResponse`` (``chunk.final`` /
        ``chunk.response``) — full text is still buffered internally, so
        semantic-cache insertion, judge scoring and ledger settlement see
        exactly what ``request()`` would have, and the concatenated chunk
        text is bit-exact with the buffered path.  Closing the generator
        mid-stream cancels decode: the serving slot is torn down, pages are
        released, and the ledger settles only the tokens actually generated.
        ``buffer`` bounds the chunk queue (0 = unbounded); a bounded queue
        backpressures the decode loop against a slow consumer.
        """
        self._warn_legacy(req)
        replay = self._prepare(req)
        if replay is not None:
            # dropped-SSE retry: replay the recorded outcome as one chunk
            sink = TokenStream(maxsize=buffer)
            if replay.text:
                sink.emit(replay.text)
            replay.metadata.stream = True
            sink.close(response=replay)
            yield from sink
            return
        state = self._state_for(req)
        sink = TokenStream(maxsize=buffer)
        state.stream = sink
        # the worker's spans are children of the caller's (the front
        # door's ``http.request``) and serve the same request
        parent = telemetry.current()

        def work() -> None:
            try:
                with telemetry.handoff(parent, rid=req.request_id):
                    state.policy.pipeline.run(self, state)
                    resp = self._finalize(state, path="request_stream")
                sink.close(response=resp)
            except BaseException as e:   # surface to the consumer, don't leak
                self._release_hold(state)
                sink.close(error=e)

        t = threading.Thread(target=work, name="llmbridge-stream", daemon=True)
        t.start()
        try:
            yield from sink
        except GeneratorExit:
            sink.cancel()
            t.join()
            raise
        t.join()

    def request_batch(self, reqs: Sequence[ProxyRequest]) -> List[ProxyResponse]:
        """Execute B in-flight requests batch-first.

        Requests are grouped by compiled pipeline (order preserved within a
        group) and each group runs stage-major, so the cache stage issues
        ONE embedder call + ONE multi-query vector search for the group and
        REAL-mode models decode in one continuous batch.  Context appends
        commit after the batch, in submission order — a batch is a set of
        concurrently in-flight requests, so members do not observe each
        other's context writes.
        """
        out: List[Optional[ProxyResponse]] = [None] * len(reqs)
        live: List[Tuple[int, RequestState]] = []
        try:
            for i, r in enumerate(reqs):
                replay = self._prepare(r)
                if replay is not None:
                    out[i] = replay
                    continue
                live.append((i, self._state_for(r)))
        except BaseException:
            # a failed compile must not leak earlier requests' holds
            for _, s in live:
                self._release_hold(s)
            raise
        resps = self._run_states([s for _, s in live])
        for (i, _), resp in zip(live, resps):
            out[i] = resp
        return out

    def _run_states(self, states: Sequence[RequestState],
                    path: str = "request_batch") -> List[ProxyResponse]:
        """Batched execution over pre-compiled states: the shared engine
        under ``request_batch`` (compile here) and the admission front-end
        (compiles — and places ledger holds — at enqueue time)."""
        groups: Dict[int, Tuple[PromptPipeline, List[RequestState]]] = {}
        try:
            for st in states:
                pipe = st.policy.pipeline
                groups.setdefault(id(pipe), (pipe, []))[1].append(st)
            for pipe, group in groups.values():
                pipe.run_batch(self, group)
        except BaseException:
            # a failed batch must not leak any member's hold
            for s in states:
                self._release_hold(s)
            raise
        return [self._finalize(s, path=path) for s in states]

    def _finalize(self, state: RequestState, path: str = "request",
                  query_tokens: bool = True) -> ProxyResponse:
        """Shared epilogue of request/request_batch/regenerate: disclosure
        fields, ledger settle, stats, context append.  ``query_tokens=False``
        preserves the historical regenerate behaviour of appending context
        without the planted token count."""
        with telemetry.span("proxy.finalize"):
            return self._finalize_in_span(state, path, query_tokens)

    def _finalize_in_span(self, state: RequestState, path: str,
                          query_tokens: bool) -> ProxyResponse:
        self._crash_hit("proxy.finalize.pre")
        req, resp, policy = state.req, state.response, state.policy
        resp.metadata.request_id = req.request_id or ""
        resp.metadata.service_type = ("intent" if req.is_intent
                                      else req.service_type.value)
        resp.metadata.pipeline_stages = list(state.stages_run)
        resp.metadata.stage_records = list(state.records)
        if policy is not None:
            resp.metadata.policy = policy.name
            resp.metadata.budget_tier = policy.tier
        self._settle(state, resp)
        if (self.durability is not None and req.request_id
                and resp.metadata.model_used not in ("none", "timeout",
                                                     "error")
                and not resp.metadata.shed_reason):
            # only real answers enter the dedup window — a client retrying
            # a timeout/decline/provider error must re-execute, not replay
            self.durability.record_outcome(req.request_id, resp)
        resp.metadata.budget_remaining = self.ledger.remaining(req.user)
        resp.metadata.ledger_tier = self.ledger.tier(req.user)
        spec = self.adapter.serving_stats.get(resp.metadata.model_used)
        if spec and spec.get("enabled"):
            resp.metadata.spec_acceptance = spec["acceptance_rate"]
            resp.metadata.spec_draft_time = spec["draft_time"]
            resp.metadata.spec_verify_time = spec["verify_time"]
        if self.overload.enabled and not resp.metadata.load_level:
            resp.metadata.load_level = self.overload.level.label
        if state.stream is not None:
            sink = state.stream
            # paths that never touched the incremental channel (cache hits,
            # verification, declines) still deliver: one final full-text chunk
            if sink.chunks_emitted == 0 and resp.text:
                sink.emit(resp.text)
            resp.metadata.stream = True
            resp.metadata.stream_cancelled = sink.cancelled
            resp.metadata.ttft = sink.ttft()
            resp.metadata.inter_token_p50 = sink.inter_token_p50()
            self._stats.record_stream(sink)
            if self.overload.enabled and resp.metadata.ttft is not None:
                self.overload.observe("ttft", resp.metadata.ttft)
        self._stats.record(path, state)
        # declined/timed-out responses are policy boilerplate, not
        # conversation — they must not pollute future context windows
        if req.update_context and resp.metadata.context_strategy not in (
                "declined", "timeout"):
            toks = None
            if query_tokens and req.query is not None:
                toks = req.query.input_tokens + req.query.output_tokens
            self.context.append(req.conversation, req.prompt, resp.text, tokens=toks)
        return resp

    def _settle(self, state: RequestState, resp: ProxyResponse) -> None:
        """Release the compile-time hold and post the realised cost — the
        response usage plus any missed-cache consult spend (kept out of the
        response usage for v1 compatibility, but real money to the ledger;
        the compile-time cache reserve covers it)."""
        self._release_hold(state)
        rid = state.req.request_id
        if state.miss_usage.cost:
            self.ledger.charge(state.req.user, state.miss_usage.cost,
                               key=f"{rid}#consult" if rid else None)
        self._charge_response(resp)

    def _release_hold(self, state: RequestState) -> None:
        if state.policy is not None and state.policy.reserved:
            self.ledger.release(state.req.user, state.policy.reserved,
                                rid=state.req.request_id)
            state.policy.reserved = 0.0

    def _charge_response(self, resp: ProxyResponse) -> None:
        """Post ``resp``'s usage cost to the ledger exactly once, even when
        async prefetch tops the usage up after the response returned.  Each
        incremental charge carries its own idempotence key (rid, rid#x1,
        rid#x2, ...) so WAL replay after a crash also posts each exactly
        once."""
        with self._ledger_lock:
            delta = resp.metadata.usage.cost - resp._ledger_charged
            if delta:
                rid = resp.request.request_id
                key = None
                if rid:
                    key = (rid if resp._charge_seq == 0
                           else f"{rid}#x{resp._charge_seq}")
                self.ledger.charge(resp.request.user, delta, key=key)
                resp._charge_seq += 1
                resp._ledger_charged += delta

    # -- fair admission (batch-forming front-end) ------------------------------
    @property
    def admission(self):
        """The attached ``AdmissionController`` (created on first use with
        defaults; ``attach_admission`` installs a tuned one)."""
        if self._admission is None:
            from repro.core.admission import AdmissionController
            self._admission = AdmissionController(self)
        return self._admission

    def attach_admission(self, controller) -> None:
        """Install a configured ``AdmissionController`` (max_batch/max_wait/
        yield policy).  Refuses to drop queued work."""
        if self._admission is not None and self._admission.pending():
            raise RuntimeError("admission controller has queued requests")
        self._admission = controller

    # -- overload control (core/overload.py) -----------------------------------
    def enable_overload(self, **kwargs) -> OverloadController:
        """Switch on load-adaptive brownout + backpressure for this bridge.

        Replaces the default disabled controller with an enabled one
        (kwargs forward to ``OverloadController``) and registers the
        open-breaker tap.  Admission queue depth/wait and streaming TTFT are
        pushed by their owners; decode-engine occupancy is pushed by the
        adapter.  Returns the controller for tuning/inspection."""
        kwargs.setdefault("enabled", True)
        ov = OverloadController(**kwargs)

        def _breaker_fraction() -> float:
            per = self.providers.snapshot().get("providers", {}) or {}
            states = [p.get("state", "closed")
                      for p in per.values() if isinstance(p, dict)]
            if not states:
                return 0.0
            return sum(1 for s in states if s == "open") / len(states)

        ov.add_tap("breakers", _breaker_fraction)
        self.overload = ov
        self.adapter.overload = ov
        return ov

    def submit(self, req: ProxyRequest):
        """Enqueue ``req`` into its user's FIFO on the admission front-end
        and return a ``Ticket``.  The request's policy compiles now, so
        intent holds land on the ledger at enqueue time; the batched hot
        path executes it when ``drain()``/``pump()`` forms its batch."""
        self._warn_legacy(req)
        return self.admission.submit(req)

    def submit_stream(self, req: ProxyRequest):
        """Enqueue ``req`` for fair admission with a live token channel
        attached: the returned ``Ticket`` exposes ``chunks()`` (iterate
        deltas as the batch decodes) alongside ``result()``.  Streaming
        tickets do not block batch formation — their batch dispatches on a
        background worker, so ``max_wait`` is honored against first token
        rather than last."""
        self._warn_legacy(req)
        return self.admission.submit_stream(req)

    def drain(self) -> List[ProxyResponse]:
        """Form and dispatch batches until the admission queues are empty;
        responses in dispatch order."""
        return [t.result() for t in self.admission.drain()]

    # -- lifecycle -------------------------------------------------------------
    def begin_drain(self) -> None:
        """Graceful-drain entry (SIGTERM): pin the overload controller at
        SHED so the front door answers 503 + Retry-After while in-flight
        requests finish and settle their realized tokens."""
        self.overload.force_level(LoadLevel.SHED)

    def close(self) -> None:
        """Shut the bridge down cleanly: join the background prefetch
        worker and the admission dispatch worker (fixing the daemon-thread
        leak when one process builds many bridges), then flush the WAL
        journals and write final snapshots.  Idempotent."""
        try:
            self._prefetch.flush(raise_errors=False)
        finally:
            self._prefetch.close()
            if self._admission is not None:
                self._admission.close()
            if self.durability is not None:
                self.durability.flush()
                self.durability.close()

    def __enter__(self) -> "LLMBridge":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # -- telemetry -------------------------------------------------------------
    def flush_prefetch(self) -> None:
        """Join the background prefetch queue (deterministic-test hook)."""
        self._prefetch.flush()

    def stats(self) -> Dict[str, Any]:
        """Proxy-wide transparency aggregate: per-stage wall-time +
        hit/decision rates for both execution paths, cache counters, and
        the budget ledger (the paper's Fig 6-style telemetry, live)."""
        out = {
            "paths": self._stats.snapshot(),
            "cache": {
                "hits": self.cache.n_hits,
                "misses": self.cache.n_misses,
                "exact_hits": self.cache.n_exact_hits,
                "hit_rate": (self.cache.n_hits /
                             max(1, self.cache.n_hits + self.cache.n_misses)),
                # retrieval-index transparency: flat-vs-IVF dispatch counts,
                # probes + shortlist rows scored, and index build wall-time
                "index": self.cache.store.index_stats(),
            },
            "ledger": self.ledger.summary(),
            # per-model speculative-decode telemetry from the serving
            # substrate (acceptance rate, draft/verify wall time); empty
            # until an engine-backed model decodes a batch with a draft
            # ... plus the streaming surface: TTFT CDF + inter-token gaps
            # across every finished request_stream/submit_stream
            "serving": {"spec": {name: dict(s) for name, s in
                                 self.adapter.serving_stats.items()},
                        **self._stats.stream_snapshot()},
            # the reliability layer: per-provider health/breaker state plus
            # fleet-wide retry/hedge accounting (wasted hedge cost included)
            "providers": self.providers.snapshot(),
            # brownout/backpressure disclosure: current level, per-signal
            # pressure, shed counts, recent level transitions
            "overload": self.overload.snapshot(),
            # the process's span and compile counters (core/telemetry.py):
            # scheduler steps, live-slot steps, tokens, prefilled and
            # shared prompt tokens, backend compiles
            "telemetry": telemetry.counters(),
        }
        if self._admission is not None:
            out["admission"] = self._admission.stats()
        if self.durability is not None:
            # journal/snapshot/recovery disclosure (core/durability.py)
            out["durability"] = self.durability.stats()
        return out

    def stage_cdf(self, path: str, stage: str
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """(sorted wall-times, cumulative fraction) for one stage — plot it
        and you have the paper's Fig 6 latency CDF for that middlebox hop."""
        d = np.sort(np.asarray(self._stats.durations(path, stage)))
        if d.size == 0:
            return d, d
        return d, np.arange(1, d.size + 1) / d.size

    # -- stage primitives --------------------------------------------------------
    def _select_context(self, req: ProxyRequest, k: int, smart: bool):
        """Returns (messages, strategy_name, gate_usage, decision_latency)."""
        gate_usage = Usage()
        if k <= 0:
            return [], "none", gate_usage, 0.0
        if smart:
            decider_raw = self._context_decider()
            decider = lambda p, m: decider_raw(p, m, query=req.query)
            small = self.pool.cheapest()
            sc = SmartContext(decider, model=small)
            msgs = apply_filters([LastK(k), sc], self.context.history(req.conversation),
                                 req.prompt)
            return msgs, f"smart_context(k={k})", sc.last_usage, sc.last_usage.latency
        msgs = apply_filters(LastK(k), self.context.history(req.conversation), req.prompt)
        return msgs, f"last_k(k={k})", gate_usage, 0.0

    def _estimate_context_tokens(self, req: ProxyRequest, k: int) -> int:
        """Token count of the last-k window the compiled plan would attach —
        exact for non-smart plans (smart gating can only shrink it)."""
        if k <= 0:
            return 0
        msgs = apply_filters(LastK(k), self.context.history(req.conversation),
                             req.prompt)
        return ContextManager.token_count(msgs)

    def _has_context(self, req: ProxyRequest, msgs) -> bool:
        return len(msgs) > 0 or not (req.query is not None
                                     and req.query.needs_context)

    def _verification_triple(self, req: ProxyRequest
                             ) -> Tuple[PoolModel, PoolModel, PoolModel]:
        """(m1, m2, verifier) for this request, param overrides applied."""
        return self.adapter.resolve_triple(
            m1=self._param_model(req, "m1"), m2=self._param_model(req, "m2"),
            verifier=self._param_model(req, "verifier"))

    def _verify_threshold(self, req: ProxyRequest) -> float:
        return float(req.params.get("threshold", self.config.verify_threshold))

    def _resolve(self, req: ProxyRequest, model: Optional[PoolModel], msgs,
                 strategy: str, gate_usage: Usage, decision_latency: float,
                 *, verification: bool = False,
                 text_override: Optional[str] = None,
                 resolution_override=None, reserved: float = 0.0,
                 stream=None,
                 out_tokens_override: Optional[int] = None) -> ProxyResponse:
        from repro.core.model_adapter import Resolution
        from repro.core.providers import ProviderError
        ctx_tokens = ContextManager.token_count(msgs)
        has_ctx = self._has_context(req, msgs)
        out_override = req.params.get("max_tokens")
        out_tokens = int(out_override) if out_override else None
        if out_tokens_override is not None:
            # a wall-deadline-truncated decode charges what it generated
            out_tokens = out_tokens_override
        self._crash_hit("proxy.resolve.pre")
        try:
            if resolution_override is not None:
                res = resolution_override
            elif verification:
                res = self.adapter.verification_select(
                    req.prompt, threshold=self._verify_threshold(req),
                    judge=self.judge, context_tokens=ctx_tokens,
                    query=req.query, has_context=has_ctx,
                    m1=self._param_model(req, "m1"), m2=self._param_model(req, "m2"),
                    verifier=self._param_model(req, "verifier"))
            else:
                res = self.adapter.answer(
                    model, req.prompt, context_tokens=ctx_tokens,
                    query=req.query, has_context=has_ctx,
                    out_tokens=out_tokens,
                    text_override=text_override,
                    hedge=self._wants_hedge(req),
                    fallback=self._fallback_candidates(
                        req, ctx_tokens=ctx_tokens, reserved=reserved),
                    stream=stream)
        except ProviderError as e:
            # the structured terminal failure: every candidate exhausted.
            # The request resolves (the batch lives on) with a disclosed
            # error response — latency waited through is real, cost is zero.
            res = Resolution(
                text=f"[provider-error] {e}", model="error",
                usage=Usage(latency=e.latency), provider=e.provider,
                attempts=e.attempts, provider_events=list(e.events),
                models_consulted=[])
        usage = res.usage.add(gate_usage)
        md = Metadata(model_used=res.model, models_consulted=res.models_consulted,
                      verifier_score=res.verifier_score,
                      context_k=len(msgs), context_strategy=strategy,
                      context_decision_latency=decision_latency, usage=usage,
                      provider=res.provider, provider_attempts=res.attempts,
                      provider_events=list(res.provider_events),
                      hedge_wasted_cost=res.hedge_wasted_cost)
        return ProxyResponse(text=res.text, metadata=md, request=req,
                             true_quality=res.true_quality)

    # -- provider-fleet views ---------------------------------------------------
    @property
    def providers(self):
        """The reliability layer (``core/providers.py``): per-provider
        health, breakers and the chaos-injection surface."""
        return self.adapter.fleet

    def healthy_models(self, candidates: Optional[List[PoolModel]] = None
                       ) -> List[PoolModel]:
        """Pool candidates minus open-circuit providers (all of them when
        every circuit is open — degraded service beats none).  RouteStage
        and the PolicyCompiler's candidate ordering consult this."""
        return self.providers.healthy(candidates or self.pool.list())

    def _wants_hedge(self, req: ProxyRequest) -> bool:
        """Hedged requests are a latency-first privilege: the tail matters
        more than the duplicated spend (which is disclosed as wasted)."""
        from repro.core.api import Preference
        return req.preference == Preference.LATENCY_FIRST

    def _fallback_candidates(self, req: ProxyRequest, ctx_tokens: int = 0,
                             reserved: float = 0.0) -> List[PoolModel]:
        """Retry-against-healthy candidate set: the pool, min_quality
        honored best-effort (the fleet re-ranks by live health), filtered to
        what the request may still spend.  ``reserved`` is the compiled
        plan's ledger hold for this request: the affordability ceiling is
        remaining + reserved (and ``max_cost`` when stated), and the
        adapter's estimates are cost-exact, so a retry or hedge answering
        with a pricier model can never overdraw the ledger or breach the
        client's cost ceiling."""
        cands = self.pool.list()
        if req.constraints is not None and req.constraints.min_quality is not None:
            filtered = self.pool.filter(
                min_capability=req.constraints.min_quality)
            if filtered:
                cands = filtered
        allow = self.ledger.remaining(req.user) + reserved
        if req.constraints is not None and req.constraints.max_cost is not None:
            allow = min(allow, req.constraints.max_cost)
        if math.isfinite(allow):
            # an empty result is valid: execute() then retries the routed
            # primary only, and exhaustion surfaces as ProviderError
            cands = [m for m in cands if self.adapter.estimate_answer(
                m, req.prompt, context_tokens=ctx_tokens,
                query=req.query).cost <= allow + 1e-9]
        return cands

    def _param_model(self, req: ProxyRequest, key: str) -> Optional[PoolModel]:
        name = req.params.get(key)
        return self.pool.get(name) if name else None

    @staticmethod
    def _better_key(req: ProxyRequest) -> str:
        return f"__better__:{req.conversation}:{req.prompt}"

    def batch_request(self, prompts, models, *, user: str = "batch",
                      queries=None) -> Dict[str, List[ProxyResponse]]:
        """Batch-mode comparison interface (paper §5.2): submit a batch of
        prompts to several pool models at once; each model's batch runs
        through the batched execution engine."""
        out: Dict[str, List[ProxyResponse]] = {}
        queries = queries or [None] * len(prompts)
        for name in models:
            out[name] = self.request_batch([ProxyRequest(
                prompt=prompt, user=user, conversation=f"batch:{name}",
                service_type=ServiceType.FIXED, update_context=False,
                query=q, params={"model": name, "context_k": 0})
                for prompt, q in zip(prompts, queries)])
        return out

    def _try_cache(self, req: ProxyRequest) -> Optional[ProxyResponse]:
        hit_tuple = self.cache.smart_get(
            req.prompt, query=req.query, workload=self.workload,
            relevance_threshold=float(req.params.get(
                "cache_threshold", self.config.cache_relevance)))
        return self._cache_response(req, hit_tuple, self.cache.last_usage)

    def _cache_response(self, req: ProxyRequest, hit_tuple,
                        usage: Usage) -> Optional[ProxyResponse]:
        hit, text, types, tq = hit_tuple
        if not hit:
            return None
        md = Metadata(model_used=(self.cache.small_model.name
                                  if self.cache.small_model else "cache"),
                      cache_hit=True, cache_types=types, usage=usage,
                      context_strategy="cache")
        return ProxyResponse(text=text or "", metadata=md, request=req,
                             true_quality=tq)

    # -- iterative refinement -----------------------------------------------------
    def regenerate(self, resp: ProxyResponse,
                   service_type: Optional[ServiceType] = None) -> ProxyResponse:
        """Same service type / intent => walk the policy's escalation ladder
        (paper §3.2: regenerate = spend more); a different service type
        re-runs the request under the new policy.  Each ladder rung is a
        compiler-produced pipeline composition, so escalation composes with
        caching and batching instead of living in a per-type if/else."""
        req = resp.request
        # a regenerate is a new billable run: fresh durable identity, so its
        # WAL charges/holds never collide with the original's keys
        req.request_id = _new_request_id()
        if resp.metadata.context_strategy != "declined":
            # initial answer leaves context (§5.1); declines never entered it
            self.context.pop_last(req.conversation)
        if service_type is not None and (req.is_intent
                                         or service_type != req.service_type):
            # an explicit service type takes over: drop the intent fields,
            # otherwise _policy_for would re-take the constraint path
            new_req = dataclasses.replace(req, service_type=service_type,
                                          constraints=None, preference=None)
            out = self.request(new_req)
        else:
            attempt = resp.metadata.regeneration + 1
            if req.is_intent:
                # budget-checked escalation: better plans, same ceilings —
                # regenerate can never breach max_cost or overdraw the ledger
                policy = self.compiler.compile_intent(req, self, escalate=True)
                pipe = policy.pipeline
            else:
                policy = self._policy_for(req)
                pipe = policy.escalation(attempt)
            state = RequestState(req=req, policy=policy)
            try:
                pipe.run(self, state)
            except BaseException:
                self._release_hold(state)
                raise
            out = self._finalize(state, path="request", query_tokens=False)
        out.metadata.regeneration = resp.metadata.regeneration + 1
        return out
