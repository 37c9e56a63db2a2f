"""Composable prompt pipeline: the proxy request plane as declarative stages.

The paper frames LLMBridge as an HTTP-proxy analogue for prompts — a
middlebox whose value comes from *composing* caching, context and routing
functions per request.  This module makes that composition explicit:

* ``RequestState``   — per-request scratchpad threaded through the stages;
* ``Stage``          — one middlebox function (cache / context / route /
  model / prefetch / decline); each consumes and produces a ``RequestState``;
* ``PromptPipeline`` — an ordered stage list with single-request (``run``)
  and batch-first (``run_batch``) execution.  Both run every stage in a
  ``proxy.stage.<name>`` telemetry span and append a ``StageRecord``
  (name, the span's duration, decision, cost delta) to the state — the
  raw material for ``Metadata.stage_records`` and ``proxy.stats()``.

Pipelines are produced by the ``PolicyCompiler`` (``core/policy.py``): the
seven ``ServiceType`` presets and arbitrary ``Constraints``/``Preference``
intents compile into stage compositions through the same path.  Hand-rolled
compositions still work — e.g. a cache→route→verify chain is one line:

    bridge.pipelines[my_type] = PromptPipeline(
        [CacheStage(), ContextStage(default_k=5), ModelStage(verification=True)])

Batch execution is stage-major: a stage sees ALL in-flight requests of its
pipeline at once, which is what lets ``CacheStage`` embed every prompt in a
single embedder forward pass and answer the whole batch with one multi-query
``VectorStore.search`` (the Pallas ``cache_topk`` hot path), and lets
``ModelStage`` decode every REAL-mode request — including the M1/M2 legs of
verification routing — in one continuous batch on the serving ``Scheduler``.
Stages process requests in submission order, so per-generator RNG draw
sequences match the sequential path exactly.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from repro.core import telemetry
from repro.core.api import (Metadata, ProxyRequest, ProxyResponse, ServiceType,
                            StageRecord, Usage)
from repro.core.context_manager import ContextManager, Message
from repro.core.model_adapter import PoolModel


@dataclasses.dataclass
class RequestState:
    """Mutable per-request state consumed/produced by pipeline stages."""
    req: ProxyRequest
    model: Optional[PoolModel] = None
    messages: List[Message] = dataclasses.field(default_factory=list)
    strategy: str = "none"
    gate_usage: Usage = dataclasses.field(default_factory=Usage)
    decision_latency: float = 0.0
    text_override: Optional[str] = None    # batched REAL-mode decode result
    resolution_override: Optional[Any] = None  # batched verification result
    response: Optional[ProxyResponse] = None
    stages_run: List[str] = dataclasses.field(default_factory=list)
    records: List[StageRecord] = dataclasses.field(default_factory=list)
    policy: Optional[Any] = None           # CompiledPolicy that produced this
    # small-model relevance spend of a MISSED cache consult: kept out of the
    # response usage (v1-compatible disclosure) but metered to the ledger
    # and visible in the cache StageRecord's cost_delta
    miss_usage: Usage = dataclasses.field(default_factory=Usage)
    # per-stage disclosure scratch (e.g. the prefetch budget gate's verdict)
    notes: Dict[str, str] = dataclasses.field(default_factory=dict)
    # incremental token channel (core.api.TokenStream) attached by
    # request_stream/submit_stream: ModelStage threads it to the adapter so
    # deltas are emitted as they decode; None = buffered delivery
    stream: Optional[Any] = None
    # overload layer (core/overload.py): absolute wall deadline in the
    # time.monotonic domain, stamped by LLMBridge._state_for when the
    # controller is enabled and Constraints.max_latency is stated.  The
    # pipeline's stage watchdogs and the engine decode loop enforce it.
    deadline_at: Optional[float] = None
    # engine tokens actually decoded when a wall deadline truncated the
    # batch decode: settlement charges these, not the planted count
    realized_out: Optional[int] = None

    @property
    def resolved(self) -> bool:
        return self.response is not None

    def cost(self) -> float:
        """Cost accumulated so far (gate usage folds into the response
        usage at resolve time, so count one or the other, not both)."""
        base = self.miss_usage.cost
        if self.response is not None:
            return base + self.response.metadata.usage.cost
        return base + self.gate_usage.cost


class Stage:
    """One middlebox function. ``run`` handles a single request; ``run_batch``
    defaults to an in-order loop and is overridden by stages with a vectorized
    hot path (CacheStage, ModelStage)."""

    name = "stage"
    #: stages that post-process a resolved response (PrefetchStage) set False
    skip_if_resolved = True

    def run(self, proxy, state: RequestState) -> None:
        raise NotImplementedError

    def run_batch(self, proxy, states: Sequence[RequestState]) -> None:
        for st in states:
            if not (st.resolved and self.skip_if_resolved):
                self.run(proxy, st)

    def decision(self, state: RequestState) -> str:
        """One-token disclosure of what the stage did for ``state``
        (recorded in ``StageRecord.decision`` after the stage ran)."""
        return ""


class CacheStage(Stage):
    """Semantic-cache GET (paper §3.5).  A hit resolves the request and
    short-circuits the rest of the pipeline.  With ``opt_in=True`` the stage
    only consults the cache when ``params["cache"]`` is set and not "skip"
    (the FIXED service type's contract)."""

    name = "cache"

    def __init__(self, opt_in: bool = False):
        self.opt_in = opt_in

    def _enabled(self, req: ProxyRequest) -> bool:
        if self.opt_in:
            return req.params.get("cache", "skip") != "skip"
        return True

    def run(self, proxy, state: RequestState) -> None:
        if not self._enabled(state.req):
            return
        state.response = proxy._try_cache(state.req)
        if state.response is None:
            # a missed consult still spent the relevance decision — meter it
            state.miss_usage = state.miss_usage.add(proxy.cache.last_usage)

    def run_batch(self, proxy, states: Sequence[RequestState]) -> None:
        todo = [s for s in states if not s.resolved and self._enabled(s.req)]
        if not todo:
            return
        results, usages = proxy.cache.smart_get_batch(
            [s.req.prompt for s in todo],
            queries=[s.req.query for s in todo],
            workload=proxy.workload,
            relevance_thresholds=[float(s.req.params.get(
                "cache_threshold", proxy.config.cache_relevance)) for s in todo])
        for s, hit_tuple, usage in zip(todo, results, usages):
            s.response = proxy._cache_response(s.req, hit_tuple, usage)
            if s.response is None:
                s.miss_usage = s.miss_usage.add(usage)

    def decision(self, state: RequestState) -> str:
        if not self._enabled(state.req):
            return "skip"
        if state.response is not None and state.response.metadata.cache_hit:
            return "hit"
        return "miss"


class ContextStage(Stage):
    """Context selection (paper §3.4): last-k, optionally gated by the
    SmartContext decider.  ``default_k`` reads ``params["context_k"]`` with
    that default; ``k`` pins the window and ignores params.  ``scale``
    multiplies the resolved k and ``suffix`` tags the disclosed strategy —
    escalation-ladder pipelines use them for the paper's "regenerating uses
    more context" rule (§3.2)."""

    name = "context"

    def __init__(self, default_k: Optional[int] = None, k: Optional[int] = None,
                 smart: bool = False, scale: int = 1, suffix: str = ""):
        assert (default_k is None) != (k is None), "pass exactly one of default_k/k"
        self.default_k = default_k
        self.k = k
        self.smart = smart
        self.scale = scale
        self.suffix = suffix
        if smart:
            self.name = "context[smart]"

    def run(self, proxy, state: RequestState) -> None:
        req = state.req
        k = self.k if self.k is not None else int(
            req.params.get("context_k", self.default_k))
        k *= self.scale
        msgs, strat, gate, dlat = proxy._select_context(req, k, smart=self.smart)
        state.messages = msgs
        state.strategy = strat + self.suffix
        state.gate_usage = gate
        state.decision_latency = dlat

    def decision(self, state: RequestState) -> str:
        return state.strategy


class RouteStage(Stage):
    """Model routing over the pool (paper §3.3 filters).  ``select`` maps
    ``(proxy, req) -> PoolModel``; named constructors cover the standard
    policies."""

    name = "route"

    def __init__(self, select: Callable, label: str = "route"):
        self.select = select
        self.name = f"route[{label}]"

    def run(self, proxy, state: RequestState) -> None:
        state.model = self.select(proxy, state.req)

    def decision(self, state: RequestState) -> str:
        return state.model.name if state.model is not None else "none"

    @classmethod
    def fixed(cls) -> "RouteStage":
        return cls(lambda p, r: p.pool.get(r.params["model"]), "fixed")

    # the best/cheapest/mid selectors route over ``proxy.healthy_models()``:
    # an open-circuit provider drops out of rotation until its breaker
    # half-opens (when every circuit is open the full pool returns —
    # degraded service beats none)

    @classmethod
    def best(cls) -> "RouteStage":
        return cls(lambda p, r: p.pool.best(p.healthy_models()), "best")

    @classmethod
    def cheapest(cls) -> "RouteStage":
        return cls(lambda p, r: p.pool.cheapest(p.healthy_models()),
                   "cheapest")

    @classmethod
    def param_or_best(cls) -> "RouteStage":
        return cls(lambda p, r: p._param_model(r, "model")
                   or p.pool.best(p.healthy_models()), "param|best")

    @classmethod
    def param_or_cheapest(cls) -> "RouteStage":
        return cls(lambda p, r: p._param_model(r, "model")
                   or p.pool.cheapest(p.healthy_models()), "param|cheapest")

    @classmethod
    def mid(cls) -> "RouteStage":
        """Median-priced model — the COST preset's escalation step."""
        def select(p, r):
            ms = sorted(p.healthy_models(), key=lambda m: m.price_in)
            return ms[len(ms) // 2]
        return cls(select, "mid")

    @classmethod
    def m2_or_best(cls) -> "RouteStage":
        """Straight to the expensive model (§3.3) — MODEL_SELECTOR's
        escalation step."""
        return cls(lambda p, r: p._param_model(r, "m2")
                   or p.pool.best(p.healthy_models()), "m2|best")

    @classmethod
    def named(cls, name: str) -> "RouteStage":
        """Pin a specific pool model — compiled budget-aware plans pick the
        most capable affordable model at compile time."""
        return cls(lambda p, r: p.pool.get(name), name)


class ModelStage(Stage):
    """Resolve the request against the routed model (or the verification
    triple when ``verification=True``, paper §3.3).  In batch mode, REAL-mode
    pool models decode every request of the batch in one continuous batch via
    the serving Scheduler before the in-order accounting loop; verification
    routing batches the M1 leg and the M2 leg the same way."""

    name = "model"

    def __init__(self, verification: bool = False):
        self.verification = verification
        if verification:
            self.name = "model[verify]"

    def run(self, proxy, state: RequestState) -> None:
        # the incremental channel only engages for a plain model resolve:
        # verification must score the COMPLETE answer before anything is
        # served, and pre-batched overrides are already decoded — those
        # paths fall back to one final full-text chunk (proxy.request_stream)
        stream = (state.stream
                  if (state.stream is not None and not self.verification
                      and state.text_override is None
                      and state.resolution_override is None)
                  else None)
        state.response = proxy._resolve(
            state.req, state.model, state.messages, state.strategy,
            state.gate_usage, state.decision_latency,
            verification=self.verification, text_override=state.text_override,
            resolution_override=state.resolution_override,
            reserved=(state.policy.reserved if state.policy is not None
                      else 0.0),
            stream=stream, out_tokens_override=state.realized_out)
        if state.realized_out is not None:
            # wall deadline truncated the engine decode: partial text was
            # served and only the decoded tokens were charged — disclose it
            state.response.metadata.shed_reason = "decode_deadline"

    def run_batch(self, proxy, states: Sequence[RequestState]) -> None:
        todo = [s for s in states if not s.resolved]
        if self.verification:
            self._run_batch_verification(proxy, todo)
            return
        # streaming members skip the buffered continuous batch — their
        # run() decodes step-wise through the streaming Scheduler so a
        # live stream never blocks the batch's buffered members (and vice
        # versa: the buffered decode completes in one scheduler run)
        buffered = [s for s in todo if s.stream is None]
        realized: List[Optional[int]] = [None] * len(buffered)
        texts = proxy.adapter.generate_batch(
            [(s.model, s.req.prompt, s.req.query, _latency_budget(s.req),
              _ledger_tier(proxy, s.req), _wall_deadline(proxy, s))
             for s in buffered], realized=realized)
        for s, t, r in zip(buffered, texts, realized):
            if t is not None:
                s.text_override = t
            if r is not None:
                s.realized_out = r
        for s in todo:
            self.run(proxy, s)

    def _run_batch_verification(self, proxy, todo) -> None:
        """Batched M1 → verifier → M2 (satellite of the batch-first plan).

        Engine-backed M1 decodes run as ONE continuous batch, then the
        in-order verifier loop scores them, then the sub-threshold subset's
        M2 decodes run as a second continuous batch.  When no engine is
        involved (SIM mode) the plain in-order loop is kept so RNG draw
        sequences match the sequential path bit-for-bit.
        """
        triples = [proxy._verification_triple(s.req) for s in todo]
        if not any(m1.engine is not None or m2.engine is not None
                   for m1, m2, _ in triples):
            for s in todo:
                self.run(proxy, s)
            return
        m1_texts = proxy.adapter.generate_batch(
            [(m1, s.req.prompt, s.req.query, _latency_budget(s.req),
              _ledger_tier(proxy, s.req)) for s, (m1, _, _) in zip(todo, triples)])
        results: List = [None] * len(todo)
        pendings: List = [None] * len(todo)
        for i, (s, (m1, _, verifier), t1) in enumerate(
                zip(todo, triples, m1_texts)):
            ctx_tokens = ContextManager.token_count(s.messages)
            res, pending = proxy.adapter.verification_phase1(
                s.req.prompt, threshold=proxy._verify_threshold(s.req),
                judge=proxy.judge, m1=m1, verifier=verifier,
                context_tokens=ctx_tokens, query=s.req.query,
                has_context=proxy._has_context(s.req, s.messages),
                m1_text=t1)
            results[i], pendings[i] = res, pending
        need = [i for i in range(len(todo)) if results[i] is None]
        m2_texts = proxy.adapter.generate_batch(
            [(triples[i][1], todo[i].req.prompt, todo[i].req.query,
              _latency_budget(todo[i].req), _ledger_tier(proxy, todo[i].req))
             for i in need])
        for i, t2 in zip(need, m2_texts):
            s = todo[i]
            results[i] = proxy.adapter.verification_phase2(
                s.req.prompt, pendings[i], m2=triples[i][1],
                context_tokens=ContextManager.token_count(s.messages),
                query=s.req.query,
                has_context=proxy._has_context(s.req, s.messages),
                m2_text=t2)
        for s, res in zip(todo, results):
            s.resolution_override = res
            self.run(proxy, s)

    def decision(self, state: RequestState) -> str:
        if state.response is None:
            return "unresolved"
        return state.response.metadata.model_used


class PrefetchStage(Stage):
    """FAST_THEN_BETTER tail (paper §5.1): prefetch a high-quality answer
    into the exact-match cache; its cost is charged, its latency hidden.

    With ``background=True`` (the default) the high-quality answer is
    computed on the proxy's prefetch worker thread, so the user-facing path
    truly returns after ``ModelStage``; ``proxy.flush_prefetch()`` joins the
    queue (tests / the escalation ladder's serve-prefetched stage call it).
    The worker draws from ``adapter.background_rng`` so off-thread work
    never interleaves draws with the foreground request path.

    Budget governance: the stage places a ledger *hold* for the estimated
    prefetch spend BEFORE the background decode is queued — not charging
    after the fact — so a nearly-empty ledger cannot be overdrawn between
    the foreground settle and the background charge.  A compiled intent
    plan's own reserve (which already includes the prefetch leg) counts as
    slack, so one decode is never double-booked; when the hold does not fit,
    the prefetch is skipped and disclosed as ``skip(budget)``.
    """

    name = "prefetch"
    skip_if_resolved = False

    def __init__(self, background: bool = True):
        self.background = background

    def run(self, proxy, state: RequestState) -> None:
        req, quick, msgs = state.req, state.response, list(state.messages)
        best = proxy.pool.best()
        # provider-health gate (mirrors the budget gate below): background
        # work must not be fired at a provider whose breaker is open — the
        # decode would burn a probe slot or fail outright off-path
        if proxy.providers.breaker_open(best.name):
            state.notes["prefetch"] = "skip(provider_down)"
            return
        hold = proxy.adapter.estimate_answer(
            best, req.prompt,
            context_tokens=ContextManager.token_count(msgs),
            query=req.query).cost
        slack = state.policy.reserved if state.policy is not None else 0.0
        # the prefetch leg keys its own hold: on crash recovery a stranded
        # `rid#prefetch` hold is released independently of the foreground's
        prid = f"{req.request_id}#prefetch" if req.request_id else None
        if not proxy.ledger.try_hold(req.user, hold, slack=slack, rid=prid):
            state.notes["prefetch"] = "skip(budget)"
            return
        state.notes["prefetch"] = "queued" if self.background else "inline"
        if self.background:
            proxy._prefetch.submit(
                lambda: self._prefetch(proxy, req, quick, msgs, hold=hold))
        else:
            self._prefetch(proxy, req, quick, msgs, hold=hold)

    def _prefetch(self, proxy, req: ProxyRequest, quick: ProxyResponse,
                  msgs: List[Message], hold: float = 0.0) -> None:
        try:
            best = proxy.pool.best()
            ctx_tokens = ContextManager.token_count(msgs)
            better = proxy.adapter.answer(
                best, req.prompt, context_tokens=ctx_tokens, query=req.query,
                rng=proxy.adapter.background_rng if self.background else None)
            prid = f"{req.request_id}#prefetch" if req.request_id else None
            proxy.cache.put_exact(proxy._better_key(req), better.text,
                                  rid=prid)
            proxy._better_quality[proxy._better_key(req)] = better.true_quality
            # cost is accounted; latency is off the critical path
            with proxy._ledger_lock:
                quick.metadata.usage = quick.metadata.usage.add(
                    Usage(input_tokens=better.usage.input_tokens,
                          output_tokens=better.usage.output_tokens,
                          cost=better.usage.cost, latency=0.0))
                quick.metadata.models_consulted = (
                    quick.metadata.models_consulted + [f"prefetch:{best.name}"])
            proxy._charge_response(quick)
        finally:
            # the realised charge replaces the hold (charge first, then
            # release: remaining dips pessimistically, never optimistically)
            if hold:
                proxy.ledger.release(
                    req.user, hold,
                    rid=f"{req.request_id}#prefetch" if req.request_id
                    else None)

    def decision(self, state: RequestState) -> str:
        return state.notes.get("prefetch",
                               "queued" if self.background else "inline")


class ServePrefetchedStage(Stage):
    """Escalation-ladder head for latency-centric plans: serve the
    prefetched high-quality answer from the exact-match cache — zero extra
    model cost, zero wait (the paper's "Get Better Answer" button).  Falls
    through (leaves the state unresolved) when nothing was prefetched."""

    name = "serve_prefetched"

    def run(self, proxy, state: RequestState) -> None:
        key = proxy._better_key(state.req)
        text = proxy.cache.get_exact(key)
        if text is None:
            # only wait on the queue when this key might still be in flight,
            # and never let another request's failed prefetch poison this one
            # (its error stays stored for an explicit flush_prefetch())
            proxy._prefetch.flush(raise_errors=False)
            text = proxy.cache.get_exact(key)
        if text is None:
            return
        md = Metadata(model_used="cache:prefetched", cache_hit=True,
                      cache_types=["exact"], usage=Usage())
        state.response = ProxyResponse(
            text=text, metadata=md, request=state.req,
            true_quality=proxy._better_quality.get(key))

    def decision(self, state: RequestState) -> str:
        if (state.response is not None
                and state.response.metadata.model_used == "cache:prefetched"):
            return "served"
        return "fallthrough"


class DeclineStage(Stage):
    """Terminal stage of a fully depleted budget plan: answer without any
    model spend so the ledger is never overdrawn.  The response discloses
    the decline; ``regenerate`` (or a topped-up ledger) is the way out."""

    name = "decline"

    def run(self, proxy, state: RequestState) -> None:
        md = Metadata(model_used="none", context_strategy="declined")
        state.response = ProxyResponse(
            text="[budget-exhausted] request declined by policy; top up the "
                 "budget or relax constraints and regenerate.",
            metadata=md, request=state.req)

    def decision(self, state: RequestState) -> str:
        return "declined"


def _latency_budget(req: ProxyRequest) -> Optional[float]:
    """Remaining decode latency budget: ``Constraints.max_latency`` minus
    time already spent waiting since admission enqueue (arrival-adjusted —
    the deadline is absolute, queue wait consumes it).  Floored at 1ms so a
    blown deadline still decodes a minimal answer instead of going negative."""
    if req.constraints is None or req.constraints.max_latency is None:
        return None
    budget = req.constraints.max_latency
    if req.submitted_at is not None:
        budget -= max(0.0, time.monotonic() - req.submitted_at)
    return max(budget, 1e-3)


def _ledger_tier(proxy, req: ProxyRequest) -> int:
    return proxy.ledger.tier(req.user)


def _wall_deadline(proxy, state: RequestState) -> Optional[float]:
    """The absolute decode wall deadline the engine step loop enforces —
    only meaningful while the overload controller is enabled."""
    ov = getattr(proxy, "overload", None)
    if ov is None or not ov.enabled:
        return None
    return state.deadline_at


def _deadline_blown(proxy, state: RequestState) -> bool:
    ov = getattr(proxy, "overload", None)
    if (ov is None or not ov.enabled or state.deadline_at is None
            or state.resolved):
        return False
    return time.monotonic() >= state.deadline_at


def _resolve_timeout(proxy, state: RequestState, stage_name: str) -> None:
    """Stage-deadline watchdog fired: resolve ``state`` with a disclosed
    timeout response.  Realized spend so far (context gates, cache-miss
    consults) still settles through the normal epilogue; the compile-time
    hold releases there too — a timed-out request never charges for work
    that did not run."""
    ov = proxy.overload
    err = ov.shed(f"stage_deadline:{stage_name}")
    md = Metadata(model_used="timeout", context_strategy="timeout",
                  usage=state.gate_usage, load_level=ov.level.label,
                  shed_reason=err.reason, retry_after=err.retry_after)
    state.notes["timeout"] = stage_name
    state.response = ProxyResponse(
        text=f"[deadline-exceeded] latency budget spent before stage "
             f"'{stage_name}'; retry after {err.retry_after:.1f}s.",
        metadata=md, request=state.req)


class PromptPipeline:
    """An ordered stage composition with sequential and batch execution.

    Both modes run every stage in a ``proxy.stage.<name>`` span and append
    a ``StageRecord`` per live request — the span's wall time, the stage's
    decision, and the cost delta it caused — feeding
    ``Metadata.stage_records`` and ``proxy.stats()``.
    """

    def __init__(self, stages: Sequence[Stage]):
        self.stages = list(stages)

    def describe(self) -> str:
        return " -> ".join(s.name for s in self.stages)

    def run(self, proxy, state: RequestState) -> RequestState:
        for stage in self.stages:
            if state.resolved and stage.skip_if_resolved:
                continue
            # stage-deadline watchdog (core/overload.py): a blown wall
            # deadline resolves the request as a timeout instead of
            # starting more work it can no longer use
            if _deadline_blown(proxy, state):
                _resolve_timeout(proxy, state, stage.name)
                break
            cost_before = state.cost()
            with telemetry.span("proxy.stage." + stage.name,
                                rid=state.req.request_id or None) as sp:
                stage.run(proxy, state)
            state.stages_run.append(stage.name)
            state.records.append(StageRecord(
                name=stage.name, duration=sp.t1 - sp.t0,
                decision=stage.decision(state),
                cost_delta=state.cost() - cost_before))
        return state

    def run_batch(self, proxy, states: Sequence[RequestState]
                  ) -> Sequence[RequestState]:
        """Stage-major execution: each stage sees every still-live request,
        in submission order, enabling the batched cache/embedding/decode hot
        paths.  The stage's batch wall-time is attributed evenly across its
        live requests in their ``StageRecord``s."""
        for stage in self.stages:
            for s in states:
                if _deadline_blown(proxy, s):
                    _resolve_timeout(proxy, s, stage.name)
            # timed-out states are out of the batch for good — even for
            # post-resolve stages like PrefetchStage (skip_if_resolved
            # False), which must not spend on a request that timed out
            live = [s for s in states
                    if "timeout" not in s.notes
                    and not (s.resolved and stage.skip_if_resolved)]
            if not live:
                continue
            costs_before = [s.cost() for s in live]
            with telemetry.span("proxy.stage." + stage.name) as sp:
                stage.run_batch(proxy, live)
            share = (sp.t1 - sp.t0) / len(live)
            for s, cb in zip(live, costs_before):
                s.stages_run.append(stage.name)
                s.records.append(StageRecord(
                    name=stage.name, duration=share,
                    decision=stage.decision(s), cost_delta=s.cost() - cb))
        return states


def default_pipelines(config) -> Dict[ServiceType, PromptPipeline]:
    """The seven paper service types as compiled stage compositions.

    Back-compat shim: presets now compile through the PolicyCompiler (the
    same path Constraints/Preference intents take); this returns the
    compiled pipeline per ServiceType.
    """
    from repro.core.policy import PolicyCompiler
    compiler = PolicyCompiler(config)
    return {st: compiler.compile_service(st).pipeline for st in ServiceType}
