"""Model Adapter (paper §3.3): unified pool + filters + verification routing.

The pool maps onto this framework's own model zoo: every pool entry is one of
the assigned architectures with cost-per-token proportional to *active*
parameters (self-hosted economics, DESIGN.md §3) and a latency model whose
constants derive from the roofline terms.  Entries can carry a real Engine
(reduced configs; real generation) or run in SIM mode against the planted
workload (benchmarks at paper scale).

``verification_select`` is the paper's strategy: M1 answers every prompt, a
verifier scores it 1-10, M2 is consulted only below threshold t.  The
adapter's heuristic picks verifier/M1/M2 so that
cost(verifier) <= cost(M1) <= cost(M2) (§3.3).

Reliability layering (``core/providers.py``).  Quality/cost selection
assumes backends answer; production backends flake, stall and rate-limit.
The adapter therefore stacks three layers under every ``answer`` call:

1. **ProviderAdapter** — each ``PoolModel`` is registered with the
   ``ProviderFleet`` at construction; an injectable ``FaultSpec`` per
   provider models errors/timeouts/rate-limits/outages and the latency
   tail, from a per-provider seeded stream (chaos replays exactly).
2. **HealthTracker + CircuitBreaker** — every attempt (fleet-routed or
   passive via ``ProviderFleet.observe`` on the fast path) feeds an EWMA
   health score and a three-state breaker.  Open circuits are skipped by
   routing, by the ``PolicyCompiler``'s candidate ordering, and by the
   background prefetch worker.
3. **Routing policy** — with chaos active, ``answer`` delegates to
   ``ProviderFleet.execute``: bounded retry-against-healthy with backoff
   (candidates re-ranked by live health after every failure) and hedged
   requests for latency-first callers.  A raising REAL-mode backend is a
   provider failure like any other: it surfaces as a structured
   ``ProviderError`` (provider name + attempt count in ``Metadata``)
   instead of killing the batch.

Cost contract: the returned ``Resolution`` carries the cost of the attempt
that actually answered — failed attempts add latency only, hedge losers are
accounted in ``fleet.snapshot()`` — so the ``BudgetLedger`` settles against
the answering provider and retries/hedges can never double-charge.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core import telemetry
from repro.core.api import Usage
from repro.core.providers import ProviderError, ProviderFleet
from repro.core.workload import Query, Workload, capability_from_params

PRICE_IN_PER_1K_PER_BPARAM = 0.01     # cost units; relative scale is what matters
OUTPUT_PRICE_MULT = 5.0               # output tokens ~5x input (paper §2.2)


@dataclasses.dataclass
class PoolModel:
    name: str
    active_params: int
    capability: float                  # [0,1] planted quality anchor
    context_window: int = 8192
    generation_bonus: float = 0.0      # "newer generation" shift (paper §5.1)
    engine: Optional[Any] = None       # serving.Engine for REAL mode
    tokenizer: Optional[Any] = None
    # serving.Engine of the SMALL family sibling used as a speculative-decode
    # draft; batched decode runs the paged scheduler with a DraftEngine wrap
    # when set (the scheduler's compatibility gate still has the last word)
    draft_engine: Optional[Any] = None
    spec_k: int = 4                    # draft window when draft_engine is set
    base_latency: float = 0.5          # s, queueing + prefill floor
    serving_chips: int = 8             # v5e chips the pool serves this model on
    latency_jitter: float = 0.9        # lognormal sigma (paper's heavy p99.9 tail)

    @property
    def price_in(self) -> float:       # per 1k input tokens
        return PRICE_IN_PER_1K_PER_BPARAM * self.active_params / 1e9

    @property
    def price_out(self) -> float:
        return OUTPUT_PRICE_MULT * self.price_in

    @property
    def per_token_latency(self) -> float:
        # memory-bound decode: time/token ~ bytes(active params)/(HBM_bw x
        # chips in the serving slice). bf16 params, 819 GB/s v5e per chip.
        return max(2 * self.active_params / (819e9 * self.serving_chips), 2e-4)

    def effective_capability(self) -> float:
        return float(np.clip(self.capability + self.generation_bonus, 0.0, 0.99))

    def usage_for(self, in_tokens: int, out_tokens: int,
                  rng: Optional[np.random.Generator] = None) -> Usage:
        lat = self.base_latency + out_tokens * self.per_token_latency
        if rng is not None:
            lat *= float(rng.lognormal(0.0, self.latency_jitter))
        cost = in_tokens / 1e3 * self.price_in + out_tokens / 1e3 * self.price_out
        return Usage(input_tokens=in_tokens, output_tokens=out_tokens,
                     cost=cost, latency=lat)

    def estimate_usage(self, in_tokens: int, out_tokens: int) -> Usage:
        """Deterministic (jitter-free) cost/latency estimate for the same
        token counts ``usage_for`` would charge — what the PolicyCompiler
        queries when fitting plans inside ``Constraints``/ledger budgets.
        Cost is exact (the charged cost is deterministic); latency is the
        un-jittered model."""
        return self.usage_for(in_tokens, out_tokens, rng=None)


def pool_model_from_config(cfg, generation_bonus: float = 0.0, **kw) -> PoolModel:
    n = cfg.active_params()
    return PoolModel(name=cfg.name, active_params=n,
                     capability=capability_from_params(n),
                     generation_bonus=generation_bonus, **kw)


@dataclasses.dataclass
class Resolution:
    text: str
    model: str
    usage: Usage
    true_quality: Optional[float] = None
    models_consulted: List[str] = dataclasses.field(default_factory=list)
    verifier_score: Optional[float] = None
    # -- provider-fleet disclosure (core/providers.py) ----------------------
    provider: str = ""                 # backend that actually answered
    attempts: int = 1                  # 1 = first try; >1 = retried
    provider_events: List[str] = dataclasses.field(default_factory=list)
    hedge_wasted_cost: float = 0.0     # cancelled hedge loser's spend


class ModelPool:
    def __init__(self, models: Optional[List[PoolModel]] = None):
        self._models: Dict[str, PoolModel] = {}
        for m in models or []:
            self.add(m)

    def add(self, m: PoolModel) -> None:
        self._models[m.name] = m

    def get(self, name: str) -> PoolModel:
        return self._models[name]

    def list(self) -> List[PoolModel]:
        return list(self._models.values())

    # filter interface (paper Fig 2: attribute filters over the pool)
    def filter(self, *, max_price_in: Optional[float] = None,
               min_capability: Optional[float] = None,
               min_context: Optional[int] = None,
               names: Optional[List[str]] = None) -> List[PoolModel]:
        out = []
        for m in self._models.values():
            if max_price_in is not None and m.price_in > max_price_in:
                continue
            if min_capability is not None and m.effective_capability() < min_capability:
                continue
            if min_context is not None and m.context_window < min_context:
                continue
            if names is not None and m.name not in names:
                continue
            out.append(m)
        return out

    def cheapest(self, candidates: Optional[List[PoolModel]] = None) -> PoolModel:
        return min(candidates or self.list(), key=lambda m: m.price_in)

    def best(self, candidates: Optional[List[PoolModel]] = None) -> PoolModel:
        return max(candidates or self.list(), key=lambda m: m.effective_capability())

    def pick_triple(self) -> Tuple[PoolModel, PoolModel, PoolModel]:
        """(verifier, M1, M2) with price(verifier) <= price(M1) <= price(M2)."""
        ms = sorted(self.list(), key=lambda m: m.price_in)
        assert len(ms) >= 2, "need at least two models for verification routing"
        verifier = ms[0]
        m1 = ms[min(1, len(ms) - 2)]
        m2 = ms[-1]
        return verifier, m1, m2


class ModelAdapter:
    #: decode cap for engine-backed generation (reduced CPU configs keep the
    #: test suite fast).  BOTH the buffered and the streamed paths honor the
    #: same cap, so streamed output stays bit-exact with ``request()``;
    #: benchmarks raise it per-instance for long-output sweeps.
    max_engine_tokens = 32

    def __init__(self, pool: ModelPool, workload: Optional[Workload] = None,
                 seed: int = 0, fleet: Optional[ProviderFleet] = None):
        self.pool = pool
        self.workload = workload
        self.rng = np.random.default_rng(seed)
        # dedicated generator for off-critical-path work (async prefetch):
        # background threads must not interleave draws with the foreground
        # request path, both for thread-safety and for reproducibility
        self.background_rng = np.random.default_rng(seed + 1000)
        # per-model serving telemetry, accumulated over every scheduler the
        # model's requests built: prefilled and shared prompt tokens, peak
        # live slots, and speculative-decode totals when it drafts
        # (proxy.stats()["serving"] and Metadata.spec_* read this)
        self.serving_stats: Dict[str, Dict[str, Any]] = {}
        self._serving_lock = threading.Lock()   # streams finish on threads
        # the reliability layer: every pool model is a registered provider.
        # With no FaultSpec injected the fleet is a passive health tap (zero
        # extra RNG draws, bit-identical behaviour); chaos specs or
        # always_route switch answer() onto fleet routing.
        self.fleet = fleet if fleet is not None else ProviderFleet(
            seed=seed + 2000)
        for m in pool.list():
            if m.name not in self.fleet.adapters:
                self.fleet.register(m)
        # overload controller (core/overload.py), attached by the owning
        # LLMBridge: engine-batch decodes feed it PagePool occupancy, and a
        # request's wall deadline cancels its decode mid-batch
        self.overload = None

    # -- answering ------------------------------------------------------------
    def answer(self, model: PoolModel, prompt: str, *,
               context_tokens: int = 0,
               query: Optional[Query] = None,
               has_context: bool = True,
               cached_facts: bool = False,
               out_tokens: Optional[int] = None,
               text_override: Optional[str] = None,
               rng: Optional[np.random.Generator] = None,
               hedge: bool = False,
               fallback: Optional[List[PoolModel]] = None,
               stream=None) -> Resolution:
        """Answer ``prompt`` with ``model`` (SIM template or REAL engine).

        When the provider fleet is routing (chaos injected or
        ``always_route``) and no pre-batched ``text_override`` is present,
        the call goes through ``ProviderFleet.execute``: the answering model
        may be a healthier ``fallback`` candidate, and ``hedge=True``
        (latency-first plans) races the p95-tail against the
        next-healthiest provider.  Exhausted fleets raise ``ProviderError``.

        ``stream`` (a ``core.api.TokenStream``) switches generation onto the
        incremental path: engine-backed models decode step-wise through the
        streaming Scheduler and each delta is emitted as it lands; SIM
        models chunk their templated text.  Streamed text is bit-exact with
        the buffered path (same greedy decode, same token cap).  A cancelled
        stream stops decoding and charges only the emitted tokens.
        Streaming bypasses fleet *routing* (chunks already delivered cannot
        be unsent by a retry) but still feeds the passive health tap.
        """
        rng = rng if rng is not None else self.rng
        prompt_tokens = query.input_tokens if query is not None else _count_tokens(prompt)
        in_tokens = prompt_tokens + context_tokens
        # explicit 0 is a valid charge (a wall-deadline cancel before the
        # first decode step) — only None falls back to the planted default
        if out_tokens is None:
            out_tokens = _default_out_tokens(prompt_tokens, query)

        def run(m: PoolModel) -> Resolution:
            charged_out = out_tokens
            if text_override is not None:
                text = text_override
            elif stream is not None:
                text, charged_out = self._stream_generate(
                    m, prompt, out_tokens, stream)
            elif m.engine is not None and m.tokenizer is not None:
                text = self._guarded_real_generate(m, prompt, out_tokens)
            else:
                text = (f"[{m.name}] response({_count_tokens(prompt)}t "
                        f"prompt): {prompt[:64]}")
            tq = None
            if query is not None and self.workload is not None:
                tq = self.workload.quality(
                    query, m.effective_capability(),
                    has_context=has_context, cached_facts=cached_facts,
                    rng=rng)
            usage = m.usage_for(in_tokens, charged_out, rng=rng)
            return Resolution(text=text, model=m.name, usage=usage,
                              true_quality=tq, models_consulted=[m.name],
                              provider=m.name)

        if text_override is None and stream is None and self.fleet.routing_enabled:
            res = self.fleet.execute(
                model, fallback if fallback is not None else self.pool.list(),
                run, lambda m: self.estimate_answer(
                    m, prompt, context_tokens=context_tokens, query=query,
                    out_tokens=out_tokens),
                hedge=hedge)
            res.models_consulted = [res.model]
            return res

        try:
            res = run(model)
        except ProviderError:
            self.fleet.observe(model.name, False, 0.0, kind="exception")
            raise
        # passive health tap: the fast path still feeds the trackers (no
        # extra RNG draws, so legacy draw sequences stay bit-identical)
        self.fleet.observe(model.name, True, res.usage.latency)
        return res

    # -- cost/latency estimation (the compiler's oracle) -----------------------
    def estimate_answer(self, model: PoolModel, prompt: str, *,
                        context_tokens: int = 0,
                        query: Optional[Query] = None,
                        out_tokens: Optional[int] = None) -> Usage:
        """Deterministic estimate of what ``answer`` would charge for the
        same inputs — cost-exact, latency un-jittered."""
        prompt_tokens = (query.input_tokens if query is not None
                         else _count_tokens(prompt))
        out_tokens = out_tokens or _default_out_tokens(prompt_tokens, query)
        return model.estimate_usage(prompt_tokens + context_tokens, out_tokens)

    def estimate_verification(self, prompt: str, *,
                              m1: Optional[PoolModel] = None,
                              m2: Optional[PoolModel] = None,
                              verifier: Optional[PoolModel] = None,
                              context_tokens: int = 0,
                              query: Optional[Query] = None) -> Usage:
        """Worst-case (M2 consulted) estimate of ``verification_select``."""
        v, d1, d2 = self.pool.pick_triple()
        m1, m2, verifier = m1 or d1, m2 or d2, verifier or v
        u1 = self.estimate_answer(m1, prompt, context_tokens=context_tokens,
                                  query=query)
        vin = u1.input_tokens + u1.output_tokens
        vu = verifier.estimate_usage(vin, 4)
        u2 = self.estimate_answer(m2, prompt, context_tokens=context_tokens,
                                  query=query)
        return u1.add(Usage(extra_llm_input_tokens=vin,
                            extra_llm_output_tokens=4,
                            cost=vu.cost, latency=vu.latency)).add(u2)

    def _real_generate(self, model: PoolModel, prompt: str, out_tokens: int) -> str:
        """One request through the same Scheduler as the batched path, so a
        request answered alone decodes exactly as it would in a batch."""
        texts, _ = self._real_generate_batch(model, [prompt], [out_tokens])
        return texts[0]

    @staticmethod
    def _scheduler(model: PoolModel, n_slots: int):
        """The serving Scheduler for an engine-backed model: the paged KV
        cache (and its paged attention kernels) for attention-only
        families, speculative when the model carries a draft engine; the
        dense cache for recurrent families, which have no paged layout."""
        from repro.models.transformer import PAGED_FAMILIES
        from repro.serving.scheduler import Scheduler
        with telemetry.span("adapter.scheduler_build"):
            if model.engine.cfg.family not in PAGED_FAMILIES:
                return Scheduler(model.engine, n_slots=n_slots)
            draft = None
            if model.draft_engine is not None:
                from repro.serving.engine import DraftEngine
                draft = DraftEngine(model.draft_engine, n_slots=n_slots,
                                    max_len=model.engine.max_len)
            return Scheduler(model.engine, n_slots=n_slots, paged=True,
                             draft=draft, spec_k=model.spec_k)

    @staticmethod
    def _prompt_ids(model: PoolModel, prompt: str):
        """The prompt's last tokens, leaving the engine room to decode."""
        import jax.numpy as jnp
        with telemetry.span("adapter.tokenize"):
            ids = model.tokenizer.encode(prompt)
            keep = min(64, model.engine.max_len - 1)
            return jnp.asarray(ids[-keep:], jnp.int32)

    # -- streaming generation (the incremental token channel) ------------------
    def _stream_generate(self, model: PoolModel, prompt: str,
                         out_tokens: int, stream) -> Tuple[str, int]:
        """Generate while emitting deltas into ``stream``.  Returns
        ``(full_text, charged_out_tokens)``: a completed stream charges the
        same ``out_tokens`` the buffered path would; a cancelled stream
        charges only the tokens actually generated."""
        if model.engine is not None and model.tokenizer is not None:
            try:
                return self._stream_real_generate(model, prompt, out_tokens,
                                                  stream)
            except ProviderError:
                raise
            except Exception as e:
                raise ProviderError(provider=model.name, attempts=1,
                                    kind=f"exception({type(e).__name__})",
                                    cause=e) from e
        return self._stream_sim(model, prompt, out_tokens, stream)

    def _stream_real_generate(self, model: PoolModel, prompt: str,
                              out_tokens: int, stream) -> Tuple[str, int]:
        """Step-wise engine decode through the streaming Scheduler —
        configured exactly like the buffered batch path (``_scheduler``),
        so the emitted token sequence is bit-exact with ``request()``'s
        text.  The text delta per event is a prefix diff of the full decode
        (byte-level tokenizers make per-token decode non-concatenative).  A
        trailing U+FFFD may be a multi-byte character whose bytes are still
        arriving, so it is held back until a later token settles it and the
        deltas concatenate to exactly the buffered text."""
        from repro.serving.scheduler import Request
        cap = min(out_tokens, self.max_engine_tokens)
        ids = self._prompt_ids(model, prompt)
        sched = self._scheduler(model, n_slots=1)
        sched.submit(Request(rid=0, user="__stream__", prompt=ids,
                             max_new=cap))
        emitted: List[int] = []
        text = full = ""
        cancelled = False
        while sched.pending() and not cancelled:
            for _req, new_toks, _done in sched.step_stream():
                emitted.extend(new_toks)
                with telemetry.span("adapter.detokenize"):
                    full = model.tokenizer.decode(emitted)
                stable = full.rstrip("\ufffd")
                delta, text = stable[len(text):], stable
                if not stream.emit(delta, token_ids=new_toks):
                    cancelled = True
                    sched.cancel(0)
                    break
        if not cancelled and len(full) > len(text):
            stream.emit(full[len(text):])
            text = full
        self._note_scheduler(model, sched)
        return text, (len(emitted) if cancelled else out_tokens)

    def _stream_sim(self, model: PoolModel, prompt: str, out_tokens: int,
                    stream) -> Tuple[str, int]:
        """SIM-mode streaming: the templated text arrives in fixed-size
        chunks, each mapped to a share of the modelled output tokens so a
        cancelled SIM stream still settles proportionally."""
        text = (f"[{model.name}] response({_count_tokens(prompt)}t "
                f"prompt): {prompt[:64]}")
        chunk = 8
        pieces = [text[i:i + chunk] for i in range(0, len(text), chunk)] or [""]
        per_piece = max(1, out_tokens // len(pieces))
        sent = ""
        for i, piece in enumerate(pieces):
            if not stream.emit(piece):
                return sent, min(out_tokens, (i + 1) * per_piece)
            sent += piece
        return sent, out_tokens

    def _guarded_real_generate(self, model: PoolModel, prompt: str,
                               out_tokens: int) -> str:
        """REAL-mode exception boundary: a raising backend (engine or
        tokenizer) surfaces as a structured ``ProviderError`` — under fleet
        routing it becomes one failed attempt (retried against healthy
        providers); on the fast path it reaches the caller with provider
        name + attempt count instead of a raw stack unwind."""
        try:
            return self._real_generate(model, prompt, out_tokens)
        except Exception as e:
            raise ProviderError(provider=model.name, attempts=1,
                                kind=f"exception({type(e).__name__})",
                                cause=e) from e

    # -- batched decode (the serving substrate) --------------------------------
    def generate_batch(self, items,
                       realized: Optional[List[Optional[int]]] = None
                       ) -> List[Optional[str]]:
        """items: ``[(model, prompt, query)]`` with optional trailing
        ``deadline``, ``tier`` and ``wall`` elements.  Engine-backed models
        decode ALL their prompts in one continuous batch on the serving
        Scheduler; SIM-mode entries return None (their text is templated in
        ``answer``).  A non-None deadline (seconds of latency budget) is
        handed to the Scheduler, whose admission serves tight-budget
        requests first; a non-zero ``tier`` (BudgetLedger depletion level)
        makes the request yield decode slots to funded traffic under
        contention.  A non-None ``wall`` (absolute ``time.monotonic``
        deadline, from the overload layer's stage budgeting) cancels the
        row's decode mid-batch via ``Scheduler.cancel`` when blown — pages
        release, the partial text is returned, and ``realized`` (a caller
        list the same length as ``items``) records the engine tokens
        actually decoded so settlement charges only those.
        """
        out: List[Optional[str]] = [None] * len(items)
        groups: Dict[str, Tuple[PoolModel, List[tuple]]] = {}
        for i, item in enumerate(items):
            model, prompt, query = item[0], item[1], item[2]
            deadline = item[3] if len(item) > 3 else None
            tier = item[4] if len(item) > 4 else 0
            wall = item[5] if len(item) > 5 else None
            if model is None or model.engine is None or model.tokenizer is None:
                continue
            prompt_tokens = (query.input_tokens if query is not None
                             else _count_tokens(prompt))
            out_tokens = _default_out_tokens(prompt_tokens, query)
            groups.setdefault(model.name, (model, []))[1].append(
                (i, prompt, out_tokens, deadline, tier, wall))
        for model, rows in groups.values():
            try:
                texts, cut = self._real_generate_batch(
                    model, [r[1] for r in rows], [r[2] for r in rows],
                    deadlines=[r[3] for r in rows], tiers=[r[4] for r in rows],
                    walls=[r[5] for r in rows])
            except Exception:
                # one model's raising backend must not kill the whole batch:
                # record the provider failure (feeds health + breaker) and
                # leave these rows un-overridden — answer() retries them
                # per-request through the fleet's exception boundary
                self.fleet.observe(model.name, False, 0.0, kind="exception")
                continue
            for row, text, n in zip(rows, texts, cut):
                out[row[0]] = text
                if realized is not None and n is not None:
                    realized[row[0]] = n
        return out

    def _real_generate_batch(self, model: PoolModel, prompts: List[str],
                             out_tokens: List[int],
                             deadlines: Optional[List[Optional[float]]] = None,
                             tiers: Optional[List[int]] = None,
                             walls: Optional[List[Optional[float]]] = None
                             ) -> Tuple[List[str], List[Optional[int]]]:
        """Continuous-batch decode: every prompt gets a Scheduler slot (one
        synthetic user per request so admission is concurrent, not per-user
        FIFO-serialized) and the whole batch shares the decode steps.  A
        request with a latency budget is admitted earliest-deadline-first and
        has its decode length trimmed to what the budget affords; a depleted
        budget tier weighs against the request in the slot-refill order.
        Returns ``(texts, realized)``: realized[i] is the decoded token
        count when row i's wall deadline truncated it, else None."""
        from repro.serving.scheduler import Request
        deadlines = deadlines or [None] * len(prompts)
        tiers = tiers or [0] * len(prompts)
        walls = walls or [None] * len(prompts)
        ids = [self._prompt_ids(model, prompt) for prompt in prompts]
        sched = self._scheduler(model, n_slots=min(len(prompts), 8))
        for i, (prompt_ids, ot, dl, tier) in enumerate(
                zip(ids, out_tokens, deadlines, tiers)):
            if dl is not None:
                affordable = int((dl - model.base_latency) /
                                 model.per_token_latency)
                ot = max(1, min(ot, affordable))
            sched.submit(Request(rid=i, user=f"__batch__{i}",
                                 prompt=prompt_ids,
                                 max_new=min(ot, self.max_engine_tokens),
                                 deadline=dl, tier=tier))
        cancelled: set = set()
        if any(w is not None for w in walls):
            # wall-deadline watchdog loop: blown rows cancel mid-batch
            # (slot torn down, pages released, partial generated retained)
            # instead of decoding tokens their caller can no longer use
            for _ in range(10_000):
                if sched.pending() == 0:
                    break
                now = time.monotonic()
                for i, w in enumerate(walls):
                    if w is not None and i not in cancelled and now >= w:
                        sched.cancel(i)
                        cancelled.add(i)
                if sched.pending() == 0:
                    break
                sched.step()
                self._observe_occupancy(sched)
            done = sched.finished
        else:
            done = sched.run_to_completion()
            self._observe_occupancy(sched)
        self._note_scheduler(model, sched)
        texts = {r.rid: model.tokenizer.decode(r.generated) for r in done}
        # a rid missing from finished was cancelled while still queued:
        # nothing decoded, nothing to charge
        out_texts = [texts.get(i, "") for i in range(len(prompts))]
        lens = {r.rid: len(r.generated) for r in done}
        realized = [lens.get(i, 0) if i in cancelled else None
                    for i in range(len(prompts))]
        return out_texts, realized

    def _observe_occupancy(self, sched) -> None:
        """Feed the overload monitor the engine's memory/slot pressure:
        PagePool occupancy when paged, live-slot fraction otherwise."""
        ov = self.overload
        if ov is None or not ov.enabled:
            return
        pool = getattr(sched, "pool", None)
        if pool is not None:
            ov.observe("pages", pool.used() / max(1, pool.n_pages))
        else:
            live = sum(1 for s in sched.slots if s is not None)
            ov.observe("pages", live / max(1, len(sched.slots)))

    def _note_scheduler(self, model: PoolModel, sched) -> None:
        """Fold a finished scheduler's counters (and, when it drafted, its
        speculation summary) into the model's running totals: each request
        builds its own scheduler, whose counters would die with it."""
        with self._serving_lock:
            agg = self.serving_stats.setdefault(model.name, {
                "schedulers": 0, "prefill_tokens": 0, "shared_tokens": 0,
                "peak_live": 0})
            agg["schedulers"] += 1
            agg["prefill_tokens"] += sched.prefill_tokens
            agg["shared_tokens"] += sched.shared_tokens
            agg["peak_live"] = max(agg["peak_live"], sched.peak_live)
            if model.draft_engine is None:
                return
            summary = sched.spec_summary()
            for key in ("rounds", "proposed", "accepted", "emitted",
                        "draft_time", "verify_time"):
                agg[key] = agg.get(key, 0) + summary[key]
            agg["enabled"] = summary["enabled"]
            agg["disabled_reason"] = summary["disabled_reason"]
            agg["acceptance_rate"] = (agg["accepted"] / agg["proposed"]
                                      if agg["proposed"] else 0.0)
            agg["tokens_per_round"] = (agg["emitted"] / agg["rounds"]
                                       if agg["rounds"] else 0.0)

    # -- verification-based selection (paper §3.3) -----------------------------
    def resolve_triple(self, m1: Optional[PoolModel] = None,
                       m2: Optional[PoolModel] = None,
                       verifier: Optional[PoolModel] = None
                       ) -> Tuple[PoolModel, PoolModel, PoolModel]:
        """(m1, m2, verifier) with explicit overrides applied over the pool
        heuristic — the same resolution the verification phases use."""
        v, d1, d2 = self.pool.pick_triple()
        return m1 or d1, m2 or d2, verifier or v

    def verification_phase1(self, prompt: str, *, threshold: float,
                            judge, m1: PoolModel, verifier: PoolModel,
                            context_tokens: int = 0,
                            query: Optional[Query] = None,
                            has_context: bool = True,
                            m1_text: Optional[str] = None
                            ) -> Tuple[Optional[Resolution], Optional[tuple]]:
        """M1 answers, the verifier scores.  Returns ``(resolution, None)``
        when the score clears the threshold, else ``(None, pending)`` where
        ``pending`` carries what phase 2 needs to consult M2.  ``m1_text``
        injects a pre-batched engine decode (the batch hot path)."""
        r1 = self.answer(m1, prompt, context_tokens=context_tokens,
                         query=query, has_context=has_context,
                         text_override=m1_text)
        score = judge.score(r1, query=query) if judge is not None else 10.0
        # verifier call: reads prompt+answer, emits a 1-10 token
        vin = r1.usage.input_tokens + r1.usage.output_tokens
        vusage = verifier.usage_for(vin, 4, rng=self.rng)
        vusage = Usage(extra_llm_input_tokens=vin, extra_llm_output_tokens=4,
                       cost=vusage.cost, latency=vusage.latency)

        if score >= threshold:
            out = dataclasses.replace(r1, usage=r1.usage.add(vusage),
                                      verifier_score=score)
            out.models_consulted = [m1.name, f"verifier:{verifier.name}"]
            return out, None
        return None, (r1, vusage, score, m1.name, verifier.name)

    def verification_phase2(self, prompt: str, pending: tuple, *,
                            m2: PoolModel, context_tokens: int = 0,
                            query: Optional[Query] = None,
                            has_context: bool = True,
                            m2_text: Optional[str] = None) -> Resolution:
        """Consult M2 for a sub-threshold phase-1 result."""
        r1, vusage, score, m1_name, v_name = pending
        r2 = self.answer(m2, prompt, context_tokens=context_tokens,
                         query=query, has_context=has_context,
                         text_override=m2_text)
        usage = r1.usage.add(vusage).add(r2.usage)
        return Resolution(text=r2.text, model=m2.name, usage=usage,
                          true_quality=r2.true_quality,
                          models_consulted=[m1_name, f"verifier:{v_name}",
                                            m2.name],
                          verifier_score=score)

    def verification_select(self, prompt: str, *, threshold: float = 8.0,
                            judge=None,
                            m1: Optional[PoolModel] = None,
                            m2: Optional[PoolModel] = None,
                            verifier: Optional[PoolModel] = None,
                            context_tokens: int = 0,
                            query: Optional[Query] = None,
                            has_context: bool = True) -> Resolution:
        m1, m2, verifier = self.resolve_triple(m1, m2, verifier)
        done, pending = self.verification_phase1(
            prompt, threshold=threshold, judge=judge, m1=m1, verifier=verifier,
            context_tokens=context_tokens, query=query, has_context=has_context)
        if done is not None:
            return done
        return self.verification_phase2(
            prompt, pending, m2=m2, context_tokens=context_tokens,
            query=query, has_context=has_context)


def _default_out_tokens(prompt_tokens: int, query: Optional[Query]) -> int:
    """Shared by the sequential and batched answer paths so both decode the
    same length; a zero planted budget falls through to the 3x heuristic."""
    out = query.output_tokens if query is not None else 0
    return out or int(prompt_tokens * 3)


def _count_tokens(text: str) -> int:
    # ~1.3 tokens per word (paper §2.2)
    return max(1, int(round(len(text.split()) * 1.3)))
