"""PolicyCompiler: constraints → compiled pipelines (LLMBridge API v2).

The paper's bidirectional contract (§3.2) asks applications to *delegate*
cost/quality trade-offs.  v1 hard-coded the delegation vocabulary as the
``ServiceType`` enum; this module replaces the enum-as-dispatch-key with a
compiler:

* ``PlanSpec``       — a declarative description of one middlebox plan
  (cache mode, context window, route, verification, prefetch).  Frozen and
  hashable, so compiled pipelines are shared across requests with the same
  plan (batch grouping keeps working).
* ``PolicyCompiler`` — compiles a ``PlanSpec`` into a ``PromptPipeline``.
  The seven v1 service types are *named preset specs* (``PRESET_SPECS``)
  and their regeneration behaviours are *escalation-ladder specs*
  (``ESCALATION_SPECS``); ``Constraints``/``Preference`` intents are
  lowered to a ``PlanSpec`` by candidate-plan selection against the
  adapter's cost/latency estimators and the request's remaining budget.
* ``BudgetLedger``   — per-user metering of ``Usage`` across requests.
  Compiled intent plans place a pessimistic *hold* before running and
  settle to the realised cost afterwards, so a constrained run can never
  overdraw; as the budget depletes the compiler degrades plans
  monotonically (cheaper route → tighter context-k → cache-only →
  decline).  Degradation is sticky per user until ``top_up``/``set_budget``.
* ``CompiledPolicy`` — what the proxy executes: the pipeline, its
  escalation ladder (alternate compositions per regeneration attempt — the
  paper's "regenerate = spend more" rule expressed as composition, not
  if/else), and the disclosure fields for ``Metadata`` v2.
"""
from __future__ import annotations

import dataclasses
import math
import threading
from typing import Dict, List, Optional, Tuple

from repro.core.api import Constraints, Preference, ProxyRequest, ServiceType
from repro.core.model_adapter import PoolModel
from repro.core.pipeline import (CacheStage, ContextStage, DeclineStage,
                                 ModelStage, PrefetchStage, PromptPipeline,
                                 RouteStage, ServePrefetchedStage)


@dataclasses.dataclass(frozen=True)
class PlanSpec:
    """Declarative middlebox plan; the compiler's intermediate form."""
    label: str
    cache: str = "off"                      # off | on | opt_in
    route: str = "none"                     # none|fixed|best|cheapest|mid|...
    context_k: Optional[int] = None         # pinned window
    context_default_k: Optional[int] = None  # params-overridable window
    context_scale: int = 1
    context_suffix: str = ""
    context_smart: bool = False
    verification: bool = False
    prefetch: bool = False
    serve_prefetched: bool = False
    decline: bool = False
    route_first: bool = False               # FIXED resolves its model first

    @property
    def has_context(self) -> bool:
        return self.context_k is not None or self.context_default_k is not None


# -- the seven v1 service types as named preset specs ---------------------------
def preset_specs(config) -> Dict[ServiceType, PlanSpec]:
    return {
        ServiceType.FIXED: PlanSpec(
            "fixed", route="fixed", route_first=True, cache="opt_in",
            context_default_k=0),
        ServiceType.QUALITY: PlanSpec(
            "quality", route="best", context_default_k=50),
        ServiceType.COST: PlanSpec("cost", route="cheapest"),
        ServiceType.MODEL_SELECTOR: PlanSpec(
            "model_selector", verification=True,
            context_default_k=config.default_context_k),
        ServiceType.SMART_CONTEXT: PlanSpec(
            "smart_context", route="param_or_best", context_smart=True,
            context_default_k=config.smart_context_k),
        ServiceType.SMART_CACHE: PlanSpec(
            "smart_cache", cache="on", route="param_or_cheapest",
            context_k=1),
        ServiceType.FAST_THEN_BETTER: PlanSpec(
            "fast_then_better", route="cheapest", context_k=1, prefetch=True),
    }


def escalation_specs(config) -> Dict[ServiceType, PlanSpec]:
    """Per-preset regeneration plans (paper §3.2: same type ⇒ escalate)."""
    best50 = PlanSpec("regen:best", route="best", context_k=50)
    return {
        ServiceType.FIXED: best50,
        ServiceType.QUALITY: best50,
        ServiceType.COST: PlanSpec("regen:mid", route="mid"),
        ServiceType.MODEL_SELECTOR: PlanSpec(
            "regen:m2", route="m2_or_best",
            context_default_k=config.default_context_k),
        ServiceType.SMART_CONTEXT: PlanSpec(
            "regen:more_context", route="param_or_best",
            context_default_k=config.smart_context_k, context_scale=2,
            context_suffix="+regen"),
        ServiceType.SMART_CACHE: PlanSpec(
            "regen:bypass_cache", route="best",
            context_k=config.default_context_k),
        ServiceType.FAST_THEN_BETTER: dataclasses.replace(
            best50, label="regen:prefetched", serve_prefetched=True),
    }


class BudgetLedger:
    """Per-user/session cost metering with hold/settle semantics.

    ``hold`` reserves a pessimistic estimate before a compiled plan runs;
    ``charge``/``release`` settle it to the realised cost, so concurrent
    in-flight requests cannot jointly overdraw.  ``tier`` maps the fraction
    of budget remaining to a degradation level; the level a user has reached
    ratchets (monotone degradation) until ``top_up``/``set_budget`` resets.
    """

    #: fraction-remaining thresholds for degradation tiers 1, 2, 3
    TIER_THRESHOLDS = (0.5, 0.25, 0.1)

    def __init__(self, default_budget: float = math.inf):
        self.default_budget = default_budget
        self._budgets: Dict[str, float] = {}
        self._spent: Dict[str, float] = {}
        self._held: Dict[str, float] = {}
        self._degradation: Dict[str, int] = {}
        # the background prefetch worker charges concurrently with the
        # foreground path; mutations must not lose updates
        self._lock = threading.Lock()

    def set_budget(self, user: str, amount: float) -> None:
        with self._lock:
            self._budgets[user] = amount
            self._degradation.pop(user, None)

    def top_up(self, user: str, amount: float) -> None:
        with self._lock:
            self._budgets[user] = self._budgets.get(
                user, self.default_budget) + amount
            self._degradation.pop(user, None)

    def budget(self, user: str) -> float:
        return self._budgets.get(user, self.default_budget)

    def spent(self, user: str) -> float:
        return self._spent.get(user, 0.0)

    def remaining(self, user: str) -> float:
        return self.budget(user) - self.spent(user) - self._held.get(user, 0.0)

    def hold(self, user: str, amount: float,
             rid: Optional[str] = None) -> None:
        """``rid`` keys the hold for durable ledgers (ignored here): a hold
        whose settle never lands is released by name on crash recovery."""
        with self._lock:
            self._held[user] = self._held.get(user, 0.0) + amount

    def try_hold(self, user: str, amount: float, slack: float = 0.0,
                 rid: Optional[str] = None) -> bool:
        """Place a hold only if the remaining budget covers it; atomic with
        the remaining-balance check, so concurrent holders cannot jointly
        overdraw.  ``slack`` credits budget already held for this same work
        (e.g. a compiled plan's reserve that includes the prefetch leg), so
        the gate does not double-book one decode."""
        with self._lock:
            remaining = (self._budgets.get(user, self.default_budget)
                         - self._spent.get(user, 0.0)
                         - self._held.get(user, 0.0))
            if remaining + slack < amount - 1e-9:
                return False
            self._held[user] = self._held.get(user, 0.0) + amount
            return True

    def release(self, user: str, amount: float,
                rid: Optional[str] = None) -> None:
        with self._lock:
            self._held[user] = self._held.get(user, 0.0) - amount

    def charge(self, user: str, cost: float,
               key: Optional[str] = None) -> bool:
        """Post realized cost.  ``key`` is an idempotence key honored by
        durable ledgers (exactly-once settlement across crash/replay);
        the in-memory ledger always posts and returns True."""
        with self._lock:
            self._spent[user] = self._spent.get(user, 0.0) + cost
            return True

    def fraction_remaining(self, user: str) -> float:
        b = self.budget(user)
        if not math.isfinite(b) or b <= 0:
            return 1.0 if b > 0 else 0.0
        return max(0.0, self.remaining(user)) / b

    def tier(self, user: str) -> int:
        f = self.fraction_remaining(user)
        t = 0
        for i, thresh in enumerate(self.TIER_THRESHOLDS):
            if f <= thresh:
                t = i + 1
        return max(t, self._degradation.get(user, 0))

    def note_degradation(self, user: str, level: int) -> None:
        with self._lock:
            if math.isfinite(self._budgets.get(user, self.default_budget)):
                self._degradation[user] = max(
                    self._degradation.get(user, 0), level)

    def summary(self) -> Dict[str, Dict[str, float]]:
        users = set(self._budgets) | set(self._spent)
        return {u: {"budget": self.budget(u), "spent": self.spent(u),
                    "remaining": self.remaining(u), "tier": self.tier(u)}
                for u in sorted(users)}


@dataclasses.dataclass
class CompiledPolicy:
    """A compiled plan: pipeline + escalation ladder + disclosure fields."""
    name: str
    pipeline: PromptPipeline
    ladder: List[PromptPipeline] = dataclasses.field(default_factory=list)
    tier: int = 0
    reserved: float = 0.0        # ledger hold to release at settle time

    def escalation(self, attempt: int) -> PromptPipeline:
        """Pipeline for regeneration attempt ``attempt`` (1-based);
        attempts past the ladder reuse its last rung."""
        if not self.ladder:
            return self.pipeline
        return self.ladder[min(attempt, len(self.ladder)) - 1]


class PolicyCompiler:
    """Compiles service-type presets and Constraints/Preference intents
    into ``CompiledPolicy`` objects through a single ``PlanSpec`` path."""

    def __init__(self, config):
        self.config = config
        self._pipelines: Dict[PlanSpec, PromptPipeline] = {}
        self._presets = preset_specs(config)
        self._escalations = escalation_specs(config)

    # -- spec -> pipeline (the single compilation path) ------------------------
    def compile_spec(self, spec: PlanSpec) -> PromptPipeline:
        """Lower a PlanSpec to stages.  Memoized per spec so equal plans
        share one pipeline object (request_batch groups by pipeline)."""
        if spec in self._pipelines:
            return self._pipelines[spec]
        stages: List = []
        if spec.serve_prefetched:
            stages.append(ServePrefetchedStage())
        route = self._route_stage(spec.route)
        if spec.route_first and route is not None:
            stages.append(route)
        if spec.cache != "off":
            stages.append(CacheStage(opt_in=(spec.cache == "opt_in")))
        if spec.has_context:
            stages.append(ContextStage(
                default_k=spec.context_default_k, k=spec.context_k,
                smart=spec.context_smart, scale=spec.context_scale,
                suffix=spec.context_suffix))
        if route is not None and not spec.route_first:
            stages.append(route)
        if spec.decline:
            stages.append(DeclineStage())
        else:
            stages.append(ModelStage(verification=spec.verification))
            if spec.prefetch:
                stages.append(PrefetchStage())
        pipe = PromptPipeline(stages)
        self._pipelines[spec] = pipe
        return pipe

    def _route_stage(self, route: str) -> Optional[RouteStage]:
        if route == "none":
            return None
        if route.startswith("name:"):
            return RouteStage.named(route[len("name:"):])
        return {
            "fixed": RouteStage.fixed, "best": RouteStage.best,
            "cheapest": RouteStage.cheapest, "mid": RouteStage.mid,
            "param_or_best": RouteStage.param_or_best,
            "param_or_cheapest": RouteStage.param_or_cheapest,
            "m2_or_best": RouteStage.m2_or_best,
        }[route]()

    # -- presets ---------------------------------------------------------------
    def compile_service(self, service_type: ServiceType) -> CompiledPolicy:
        spec = self._presets[service_type]
        esc = self._escalations[service_type]
        return CompiledPolicy(name=service_type.value,
                              pipeline=self.compile_spec(spec),
                              ladder=[self.compile_spec(esc)])

    def compile_brownout(self, req: ProxyRequest, proxy) -> CompiledPolicy:
        """Preset requests under a CACHE_PREFERRED/SHED brownout: presets
        have no candidate ladder to tighten, so the floor applies directly —
        answer from cache if the hit is free-tier, decline otherwise.  No
        ledger hold is placed (cache-only consults charge at settle)."""
        from repro.core.overload import LoadLevel
        if proxy.overload.level >= LoadLevel.SHED:
            spec = PlanSpec("brownout:declined", decline=True)
        else:
            spec = PlanSpec("brownout:cache_only", cache="on", decline=True)
        return CompiledPolicy(name=f"brownout:{spec.label}",
                              pipeline=self.compile_spec(spec))

    # -- intents ---------------------------------------------------------------
    def compile_intent(self, req: ProxyRequest, proxy,
                       escalate: bool = False) -> CompiledPolicy:
        """Lower (Constraints, Preference) to the most capable plan that
        fits ``min(remaining ledger budget, max_cost)``, degrading down the
        preference's candidate list; place the ledger hold.

        With ``escalate=True`` (a regenerate attempt) the candidate list is
        the escalation ladder — better plans than the primary — selected
        under the SAME budget fit, so iteration can never breach
        ``max_cost`` or overdraw the ledger either.
        """
        from repro.core.overload import LoadLevel
        cons = req.constraints if req.constraints is not None else Constraints()
        pref = req.preference if req.preference is not None else Preference.BALANCED
        ledger: BudgetLedger = proxy.ledger
        user = req.user
        ov = getattr(proxy, "overload", None)
        brown = (ov.level if ov is not None and ov.enabled
                 else LoadLevel.NORMAL)

        if escalate:
            candidates = self._escalation_plans(pref, cons, req, proxy)
            base_start = 0  # an explicit pay-more request skips the ratchet
        else:
            candidates = self._candidate_plans(pref, cons, req, proxy)
            # degradation saturates at the list's cheapest plan: a short
            # list (COST_FIRST has one candidate) is already maximally
            # degraded, and decline is reserved for true unaffordability
            base_start = min(ledger.tier(user), len(candidates) - 1)
        # brownout rides the SAME monotone ladder budget depletion walks:
        # DEGRADE advances the start one rung (cheaper route / tighter
        # context); CACHE_PREFERRED/SHED floor to cache-only/decline below.
        # base_start stays ledger-only — transient load must not feed the
        # sticky per-user degradation ratchet.
        bump = 1 if brown == LoadLevel.DEGRADE else 0
        start = min(base_start + bump, len(candidates) - 1)
        ledger_budget = ledger.remaining(user)
        budget = min(ledger_budget,
                     cons.max_cost if cons.max_cost is not None else math.inf)

        # reserve for the cache consult if the client allows caching
        cache_bound = 0.0
        use_cache = cons.allow_cache and not escalate
        if use_cache:
            out_tokens = (req.query.output_tokens
                          if req.query is not None else 64) or 64
            cache_bound = proxy.cache.consult_cost_bound(req.prompt, out_tokens)
            if cache_bound > budget:
                use_cache, cache_bound = False, 0.0

        def first_affordable(limit: float,
                             s: int = start) -> Tuple[Optional[Tuple], int]:
            for j, (spec, est_cost, est_lat) in enumerate(candidates[s:]):
                if est_cost > limit - cache_bound:
                    continue
                if cons.max_latency is not None and est_lat > cons.max_latency:
                    continue
                return (spec, est_cost), s + j
            return None, len(candidates)

        if brown >= LoadLevel.SHED:
            # brownout floor: no model spend, no cache consult spend
            use_cache, cache_bound = False, 0.0
            chosen, level = ((PlanSpec("brownout:declined", decline=True),
                              0.0), len(candidates))
        elif brown >= LoadLevel.CACHE_PREFERRED:
            spec = (PlanSpec("brownout:cache_only", cache="on", decline=True)
                    if use_cache
                    else PlanSpec("brownout:declined", decline=True))
            chosen, level = (spec, 0.0), len(candidates)
        else:
            chosen, level = first_affordable(budget)
            if chosen is None:
                if use_cache:
                    chosen = (PlanSpec("cache_only", cache="on",
                                       decline=True), 0.0)
                elif (escalate and pref == Preference.LATENCY_FIRST
                      and cons.allow_prefetch):
                    # a prefetched answer is already paid for — serve it
                    # free before declining
                    chosen = (PlanSpec("regen:prefetched_only",
                                       serve_prefetched=True,
                                       decline=True), 0.0)
                else:
                    chosen = (PlanSpec("declined", decline=True), 0.0)
        spec, est_cost = chosen
        if use_cache and spec.cache == "off":
            spec = dataclasses.replace(spec, cache="on",
                                       label=spec.label + "+cache")

        hold = est_cost + cache_bound
        ledger.hold(user, hold, rid=req.request_id)
        if not escalate:
            # the ratchet tracks what the *budget* can afford — a request
            # whose own max_cost/max_latency was the binding constraint must
            # not degrade the user's future unconstrained requests (and the
            # brownout bump, being transient, is excluded via base_start)
            _, ledger_level = first_affordable(ledger_budget, base_start)
            ledger.note_degradation(user, ledger_level)

        return CompiledPolicy(
            name=f"intent:{pref.value}/{spec.label}",
            pipeline=self.compile_spec(spec), tier=level, reserved=hold)

    def _escalation_plans(self, pref: Preference, cons: Constraints,
                          req: ProxyRequest, proxy
                          ) -> List[Tuple[PlanSpec, float, float]]:
        """Regeneration candidates, most→least capable (paper §3.2:
        regenerate = spend more), budget-fitted like primary plans.  For a
        prefetching latency-first intent the chosen plan is headed by
        serve_prefetched, which can only lower the realised cost."""
        plans = self._candidate_plans(Preference.QUALITY_FIRST, cons, req,
                                      proxy)
        out = []
        for spec, est_cost, est_lat in plans:
            spec = dataclasses.replace(spec, label="regen:" + spec.label)
            if pref == Preference.LATENCY_FIRST and cons.allow_prefetch:
                spec = dataclasses.replace(spec, serve_prefetched=True)
            out.append((spec, est_cost, est_lat))
        return out

    def _candidate_plans(self, pref: Preference, cons: Constraints,
                         req: ProxyRequest, proxy
                         ) -> List[Tuple[PlanSpec, float, float]]:
        """Ordered (most→least capable) candidate specs with deterministic
        cost/latency estimates; index = degradation level.

        Provider health flows in here: open-circuit providers are dropped
        from the eligible set (compiled plans and escalation ladders skip
        them), and capability ties break toward the healthier provider —
        so a flapping best-tier backend loses the ``best`` slot to an
        equally-capable healthy sibling while its breaker is open."""
        pool = proxy.pool
        eligible = pool.list()
        if cons.min_quality is not None:
            filtered = pool.filter(min_capability=cons.min_quality)
            eligible = filtered or eligible     # best-effort floor
        eligible = proxy.healthy_models(eligible)
        health = proxy.providers.health_score
        best = max(eligible, key=lambda m: (m.effective_capability(),
                                            health(m.name)))
        cheapest = min(eligible, key=lambda m: (m.price_in, -health(m.name)))
        mids = sorted(eligible, key=lambda m: m.price_in)
        mid = mids[len(mids) // 2]
        cfg_k = self.config.default_context_k

        def single(label: str, model: PoolModel, k: int,
                   prefetch: bool = False) -> Tuple[PlanSpec, float, float]:
            spec = PlanSpec(label, route=f"name:{model.name}",
                            context_k=k if k > 0 else None,
                            prefetch=prefetch)
            est = self._estimate_single(model, k, req, proxy)
            cost, lat = est.cost, est.latency
            if prefetch:
                # charged, but off the latency critical path (paper §5.1)
                cost += self._estimate_single(pool.best(), k, req, proxy).cost
            return spec, cost, lat

        def verify(label: str, k: int) -> Tuple[PlanSpec, float, float]:
            spec = PlanSpec(label, verification=True,
                            context_k=k if k > 0 else None)
            ctx = proxy._estimate_context_tokens(req, k)
            est = proxy.adapter.estimate_verification(
                req.prompt, context_tokens=ctx, query=req.query,
                m1=proxy._param_model(req, "m1"),
                m2=proxy._param_model(req, "m2"),
                verifier=proxy._param_model(req, "verifier"))
            return spec, est.cost, est.latency

        if pref == Preference.QUALITY_FIRST:
            return [single("best,k=50", best, 50),
                    single(f"best,k={cfg_k}", best, cfg_k),
                    single(f"mid,k={cfg_k}", mid, cfg_k),
                    single("cheapest,k=0", cheapest, 0)]
        if pref == Preference.BALANCED:
            # verification escalates M1 -> M2: a one-model pool has no M2
            out = ([verify(f"verify,k={cfg_k}", cfg_k)]
                   if len(pool.list()) >= 2 else [])
            return out + [single(f"mid,k={cfg_k}", mid, cfg_k),
                          single("cheapest,k=0", cheapest, 0)]
        if pref == Preference.LATENCY_FIRST:
            out = []
            if cons.allow_prefetch:
                out.append(single("fast+prefetch,k=1", cheapest, 1,
                                  prefetch=True))
            out += [single("fast,k=1", cheapest, 1),
                    single("fast,k=0", cheapest, 0)]
            return out
        # COST_FIRST
        return [single("cheapest,k=0", cheapest, 0)]

    def _estimate_single(self, model: PoolModel, k: int, req: ProxyRequest,
                         proxy):
        ctx = proxy._estimate_context_tokens(req, k)
        return proxy.adapter.estimate_answer(model, req.prompt,
                                             context_tokens=ctx,
                                             query=req.query)
