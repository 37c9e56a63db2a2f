"""Policy layer: PolicyCompiler preset equivalence against the PR 1
hand-built pipelines, intent compilation (Constraints/Preference), budget
ledger degradation, per-stage telemetry via proxy.stats(), deadline-aware
scheduler admission, and batched verification routing.

(No hypothesis dependency on purpose: this module must run even when the
property-based modules are skipped at collection; the max_cost property
tests live in test_policy_properties.py.)
"""
import types

import numpy as np
import pytest

from repro.core import (BudgetLedger, CacheStage, Constraints, ContextManager,
                        ContextStage, Judge, LLMBridge, ModelPool, ModelStage,
                        PoolModel, Preference,
                        PrefetchStage, PromptPipeline, ProxyConfig,
                        ProxyRequest, RouteStage, SemanticCache, ServiceType,
                        Workload, WorkloadConfig, WorkloadEmbedder,
                        build_bridge)
from test_pipeline import (_assert_responses_equal, _one_req_per_conversation,
                           _populate_cache)


@pytest.fixture(scope="module")
def workload():
    return Workload(WorkloadConfig(n_conversations=6, turns_per_conversation=12,
                                   seed=7))


# -- compiler preset equivalence ------------------------------------------------
def _pr1_pipelines(config):
    """The PR 1 hand-built stage compositions, preserved verbatim as the
    equivalence oracle for the compiler's preset specs."""
    return {
        ServiceType.FIXED: PromptPipeline([
            RouteStage.fixed(), CacheStage(opt_in=True),
            ContextStage(default_k=0), ModelStage()]),
        ServiceType.QUALITY: PromptPipeline([
            ContextStage(default_k=50), RouteStage.best(), ModelStage()]),
        ServiceType.COST: PromptPipeline([
            RouteStage.cheapest(), ModelStage()]),
        ServiceType.MODEL_SELECTOR: PromptPipeline([
            ContextStage(default_k=config.default_context_k),
            ModelStage(verification=True)]),
        ServiceType.SMART_CONTEXT: PromptPipeline([
            ContextStage(default_k=config.smart_context_k, smart=True),
            RouteStage.param_or_best(), ModelStage()]),
        ServiceType.SMART_CACHE: PromptPipeline([
            CacheStage(), ContextStage(k=1),
            RouteStage.param_or_cheapest(), ModelStage()]),
        ServiceType.FAST_THEN_BETTER: PromptPipeline([
            ContextStage(k=1), RouteStage.cheapest(), ModelStage(),
            PrefetchStage()]),
    }


SERVICE_PARAMS = {
    ServiceType.FIXED: {"model": "gemma3-27b", "context_k": 2, "cache": "on"},
}


def test_compiled_presets_match_pr1_trajectories(workload):
    bridge = build_bridge(workload=workload, seed=0)
    oracle = _pr1_pipelines(bridge.config)
    for st in ServiceType:
        assert bridge.pipelines[st].describe() == oracle[st].describe()


@pytest.mark.parametrize("st", list(ServiceType))
def test_compiled_presets_match_pr1_responses(workload, st):
    """Each ServiceType compiled via PolicyCompiler produces byte-identical
    responses and pipeline_stages trajectories to the PR 1 hand-built
    pipelines on the planted workload."""
    compiled = build_bridge(workload=workload, seed=0)
    manual = build_bridge(workload=workload, seed=0)
    manual.pipelines.update(_pr1_pipelines(manual.config))
    _populate_cache(compiled, workload)
    _populate_cache(manual, workload)
    for q in workload.queries[:10]:
        req = ProxyRequest(prompt=q.text, conversation=q.conversation,
                           service_type=st, query=q,
                           params=dict(SERVICE_PARAMS.get(st, {})))
        rc = compiled.request(req)
        compiled.flush_prefetch()
        rm = manual.request(req)
        manual.flush_prefetch()
        _assert_responses_equal(rc, rm)
        assert rc.metadata.pipeline_stages == rm.metadata.pipeline_stages


def test_service_enum_is_a_shim_not_a_dispatch_key(workload):
    """All seven presets route through the compiler: the pipelines dict is a
    view over compiled policies, and every policy carries a ladder."""
    bridge = build_bridge(workload=workload, seed=0)
    assert set(bridge._preset_policies) == set(ServiceType)
    for st, pol in bridge._preset_policies.items():
        assert pol.pipeline is bridge.pipelines[st]
        assert pol.name == st.value and pol.ladder
    # the compiler memoizes by PlanSpec: recompiling yields the same object
    compiler = bridge.compiler
    for st in ServiceType:
        assert compiler.compile_service(st).pipeline is bridge.pipelines[st]


def test_escalation_ladders_replace_if_else(workload):
    """Regeneration is a compiler-produced pipeline composition per preset."""
    bridge = build_bridge(workload=workload, seed=0)
    lad = {st: bridge._preset_policies[st].escalation(1).describe()
           for st in ServiceType}
    assert lad[ServiceType.COST] == "route[mid] -> model"
    assert lad[ServiceType.MODEL_SELECTOR] == "context -> route[m2|best] -> model"
    assert lad[ServiceType.SMART_CONTEXT].startswith("context")
    assert lad[ServiceType.FAST_THEN_BETTER].startswith("serve_prefetched")


def test_fast_then_better_regenerate_serves_prefetched(workload):
    bridge = build_bridge(workload=workload, seed=0)
    q = workload.queries[3]
    r = bridge.request(ProxyRequest(prompt=q.text, conversation=q.conversation,
                                    service_type=ServiceType.FAST_THEN_BETTER,
                                    query=q))
    better = bridge.regenerate(r)   # ladder head flushes the prefetch queue
    assert better.metadata.cache_hit and better.metadata.usage.cost == 0.0
    assert better.metadata.model_used == "cache:prefetched"
    assert better.metadata.pipeline_stages[0] == "serve_prefetched"


# -- intent compilation ---------------------------------------------------------
def test_preference_routing(workload):
    bridge = build_bridge(workload=workload, seed=0)
    q = workload.queries[0]

    def ask(pref, **cons):
        return bridge.request(ProxyRequest(
            prompt=q.text, conversation=q.conversation, query=q,
            update_context=False, preference=pref,
            constraints=Constraints(allow_cache=False, **cons)))

    cost = ask(Preference.COST_FIRST)
    assert cost.metadata.model_used == bridge.pool.cheapest().name
    assert cost.metadata.policy.startswith("intent:cost_first")
    assert cost.metadata.service_type == "intent"

    qual = ask(Preference.QUALITY_FIRST)
    assert qual.metadata.model_used == bridge.pool.best().name

    bal = ask(Preference.BALANCED)
    assert bal.metadata.verifier_score is not None

    fast = ask(Preference.LATENCY_FIRST)
    bridge.flush_prefetch()
    assert fast.metadata.model_used == bridge.pool.cheapest().name
    assert any(m.startswith("prefetch:")
               for m in fast.metadata.models_consulted)

    no_pf = ask(Preference.LATENCY_FIRST, allow_prefetch=False)
    bridge.flush_prefetch()
    assert not any(m.startswith("prefetch:")
                   for m in no_pf.metadata.models_consulted)


def test_stage_records_disclose_every_stage(workload):
    bridge = build_bridge(workload=workload, seed=0)
    q = workload.queries[1]
    r = bridge.request(ProxyRequest(prompt=q.text, conversation=q.conversation,
                                    query=q, preference=Preference.QUALITY_FIRST,
                                    constraints=Constraints(allow_cache=False)))
    recs = r.metadata.stage_records
    assert [x.name for x in recs] == r.metadata.pipeline_stages
    assert all(x.duration >= 0.0 for x in recs)
    model_rec = next(x for x in recs if x.name == "model")
    assert model_rec.decision == r.metadata.model_used
    assert np.isclose(model_rec.cost_delta, r.metadata.usage.cost)


def test_max_cost_is_a_hard_ceiling(workload):
    bridge = build_bridge(workload=workload, seed=0)
    for q in workload.queries[:8]:
        cap = 0.05
        r = bridge.request(ProxyRequest(
            prompt=q.text, conversation=q.conversation, query=q,
            update_context=False,
            constraints=Constraints(max_cost=cap, allow_cache=False)))
        assert r.metadata.usage.cost <= cap + 1e-12


def test_unaffordable_request_declines_at_zero_cost(workload):
    bridge = build_bridge(workload=workload, seed=0)
    q = workload.queries[0]
    r = bridge.request(ProxyRequest(
        prompt=q.text, conversation=q.conversation, query=q,
        constraints=Constraints(max_cost=1e-9, allow_cache=False)))
    assert r.metadata.usage.cost == 0.0
    assert r.metadata.model_used == "none"
    assert r.metadata.pipeline_stages == ["decline"]


def test_min_quality_filters_routing_candidates(workload):
    bridge = build_bridge(workload=workload, seed=0)
    q = workload.queries[0]
    floor = 0.7
    r = bridge.request(ProxyRequest(
        prompt=q.text, conversation=q.conversation, query=q,
        update_context=False, preference=Preference.COST_FIRST,
        constraints=Constraints(min_quality=floor, allow_cache=False)))
    m = bridge.pool.get(r.metadata.model_used)
    assert m.effective_capability() >= floor


def test_intent_regenerate_respects_cost_ceiling_and_ledger(workload):
    """Regeneration compiles through the same budget fit as the primary
    plan: neither max_cost nor the ledger can be breached by escalation."""
    bridge = build_bridge(workload=workload, seed=0)
    bridge.ledger.set_budget("v", 0.05)
    q = workload.queries[0]
    r = bridge.request(ProxyRequest(
        prompt=q.text, conversation=q.conversation, query=q, user="v",
        preference=Preference.COST_FIRST,
        constraints=Constraints(max_cost=0.01, allow_cache=False)))
    assert r.metadata.usage.cost <= 0.01 + 1e-12
    r2 = bridge.regenerate(r)
    assert r2.metadata.usage.cost <= 0.01 + 1e-12
    assert bridge.ledger.remaining("v") >= -1e-12
    assert bridge.ledger.spent("v") <= 0.05 + 1e-12


def test_infeasible_constraints_do_not_ratchet_degradation(workload):
    """A request whose own max_cost is the binding constraint must not
    degrade the user's future unconstrained requests (the ratchet tracks
    budget depletion, not per-request infeasibility)."""
    bridge = build_bridge(workload=workload, seed=0)
    bridge.ledger.set_budget("w", 100.0)
    q = workload.queries[0]
    r = bridge.request(ProxyRequest(
        prompt=q.text, conversation=q.conversation, query=q, user="w",
        preference=Preference.QUALITY_FIRST,
        constraints=Constraints(max_cost=1e-9, allow_cache=False)))
    assert r.metadata.model_used == "none"          # declined, cost 0
    r2 = bridge.request(ProxyRequest(
        prompt=q.text, conversation=q.conversation, query=q, user="w",
        update_context=False, preference=Preference.QUALITY_FIRST,
        constraints=Constraints(allow_cache=False)))
    assert r2.metadata.budget_tier == 0             # budget barely touched
    assert r2.metadata.model_used == bridge.pool.best().name


def test_intent_regenerate_escalates(workload):
    bridge = build_bridge(workload=workload, seed=0)
    q = workload.queries[2]
    r = bridge.request(ProxyRequest(
        prompt=q.text, conversation=q.conversation, query=q,
        preference=Preference.COST_FIRST,
        constraints=Constraints(allow_cache=False)))
    r2 = bridge.regenerate(r)
    assert r2.metadata.regeneration == 1
    assert r2.metadata.model_used == bridge.pool.best().name


def test_cache_miss_consult_cost_is_metered(workload):
    """A missed semantic-cache consult still spent the small-model relevance
    call: the ledger and the cache StageRecord see it, even though the
    response usage stays v1-compatible."""
    bridge = build_bridge(workload=workload, seed=0)
    _populate_cache(bridge, workload)
    q = workload.queries[0]
    r = bridge.request(ProxyRequest(
        prompt=q.text, conversation=q.conversation, query=q, user="m",
        update_context=False, preference=Preference.COST_FIRST,
        constraints=Constraints(allow_cache=True),
        params={"cache_threshold": 1.1}))   # force a miss past any score
    assert not r.metadata.cache_hit
    cache_rec = next(x for x in r.metadata.stage_records if x.name == "cache")
    assert cache_rec.decision == "miss" and cache_rec.cost_delta > 0.0
    assert bridge.ledger.spent("m") == pytest.approx(
        r.metadata.usage.cost + cache_rec.cost_delta)


def test_regenerate_intent_with_explicit_service_type(workload):
    """An explicit service type on regenerate takes over from the intent
    (the docstring contract: re-run under the new policy)."""
    bridge = build_bridge(workload=workload, seed=0)
    q = workload.queries[1]
    r = bridge.request(ProxyRequest(
        prompt=q.text, conversation=q.conversation, query=q,
        preference=Preference.COST_FIRST,
        constraints=Constraints(allow_cache=False)))
    r2 = bridge.regenerate(r, ServiceType.QUALITY)
    assert r2.metadata.service_type == "quality"
    assert r2.metadata.model_used == bridge.pool.best().name
    assert r2.metadata.regeneration == 1


def test_depleted_latency_first_regen_serves_prefetched(workload):
    """A budget-depleted latency-first user still gets the already-paid-for
    prefetched answer on regenerate instead of a decline."""
    bridge = build_bridge(workload=workload, seed=0)
    bridge.ledger.set_budget("p", 1.0)
    q = workload.queries[2]
    r = bridge.request(ProxyRequest(
        prompt=q.text, conversation=q.conversation, query=q, user="p",
        preference=Preference.LATENCY_FIRST,
        constraints=Constraints(allow_cache=False)))
    bridge.flush_prefetch()
    bridge.ledger.charge("p", bridge.ledger.remaining("p"))   # deplete
    better = bridge.regenerate(r)
    assert better.metadata.model_used == "cache:prefetched"
    assert better.metadata.usage.cost == 0.0


def test_declined_responses_stay_out_of_context(workload):
    bridge = build_bridge(workload=workload, seed=0)
    q = workload.queries[0]
    before = len(bridge.context.history(q.conversation))
    r = bridge.request(ProxyRequest(
        prompt=q.text, conversation=q.conversation, query=q,
        constraints=Constraints(max_cost=1e-9, allow_cache=False)))
    assert r.metadata.model_used == "none"
    assert len(bridge.context.history(q.conversation)) == before
    r2 = bridge.regenerate(r)     # must not pop an entry never appended
    assert r2.metadata.regeneration == 1


def test_batch_compile_failure_releases_holds(workload):
    """A later request's failing compile must not leak earlier requests'
    ledger holds."""
    bridge = build_bridge(workload=workload, seed=0)
    bridge.ledger.set_budget("h", 10.0)
    good = ProxyRequest(prompt=workload.queries[0].text, conversation="c0",
                        query=workload.queries[0], user="h",
                        preference=Preference.QUALITY_FIRST,
                        constraints=Constraints(allow_cache=False))
    bad = ProxyRequest(prompt=workload.queries[1].text, conversation="c1",
                       query=workload.queries[1], user="h",
                       preference=Preference.BALANCED,
                       constraints=Constraints(allow_cache=False),
                       params={"m1": "no-such-model"})
    with pytest.raises(KeyError):
        bridge.request_batch([good, bad])
    assert bridge.ledger.remaining("h") == pytest.approx(10.0)


# -- budget ledger --------------------------------------------------------------
def test_budget_ledger_hold_settle():
    led = BudgetLedger()
    led.set_budget("u", 10.0)
    led.hold("u", 4.0)
    assert led.remaining("u") == 6.0
    led.release("u", 4.0)
    led.charge("u", 3.0)
    assert led.remaining("u") == 7.0 and led.spent("u") == 3.0
    assert led.tier("u") == 0
    led.charge("u", 6.5)                       # 0.5/10 remaining
    assert led.tier("u") == 3
    led.note_degradation("u", 2)
    led.top_up("u", 90.0)                      # reset clears the ratchet
    assert led.tier("u") == 0


def test_budget_constrained_run_degrades_monotonically(workload):
    """The acceptance invariant: a ledger-constrained planted run stays
    under its cost budget while quality degrades monotonically (tier is
    non-decreasing, routed capability non-increasing)."""
    bridge = build_bridge(workload=workload, seed=0)
    budget = 4.0
    bridge.ledger.set_budget("u", budget)
    tiers, caps, total = [], [], 0.0
    for q in workload.queries[:20]:
        r = bridge.request(ProxyRequest(
            prompt=q.text, conversation=q.conversation, query=q, user="u",
            update_context=False, preference=Preference.QUALITY_FIRST,
            constraints=Constraints(allow_cache=False)))
        tiers.append(r.metadata.budget_tier)
        total += r.metadata.usage.cost
        if r.metadata.model_used != "none":
            caps.append(bridge.pool.get(
                r.metadata.model_used).effective_capability())
    assert total <= budget + 1e-9
    assert bridge.ledger.spent("u") <= budget + 1e-9
    assert tiers == sorted(tiers), "degradation must be monotone"
    assert len(set(tiers)) >= 3, "run should traverse several tiers"
    assert all(a >= b - 1e-12 for a, b in zip(caps, caps[1:])), \
        "routed capability must be non-increasing as the budget depletes"
    # depleted runs settle on the cheapest plan (or further, into
    # cache-only/decline) and the ledger never goes negative
    assert tiers[-1] >= 3 and bridge.stats()["ledger"]["u"]["remaining"] >= 0


# -- stats endpoint -------------------------------------------------------------
def test_stats_reports_both_paths(workload):
    bridge = build_bridge(workload=workload, seed=0)
    _populate_cache(bridge, workload)
    reqs = _one_req_per_conversation(workload, ServiceType.SMART_CACHE)
    for r in reqs[:3]:
        bridge.request(r)
    bridge.request_batch(reqs[3:])
    s = bridge.stats()
    for path in ("request", "request_batch"):
        assert path in s["paths"]
        stages = s["paths"][path]["stages"]
        assert "cache" in stages
        cache = stages["cache"]
        assert cache["count"] > 0 and cache["total_s"] >= 0.0
        assert set(cache["decisions"]) <= {"hit", "miss", "skip"}
        assert sum(cache["decision_rates"].values()) == pytest.approx(1.0)
    assert s["cache"]["hits"] + s["cache"]["misses"] > 0
    d, f = bridge.stage_cdf("request", "cache")
    assert len(d) == len(f) and (len(f) == 0 or f[-1] == pytest.approx(1.0))


# -- scheduler latency budgets --------------------------------------------------
class _StubEngine:
    max_len = 16

    def new_cache(self, batch, max_len):
        return {}


def test_scheduler_admits_earliest_deadline_first():
    import jax.numpy as jnp
    from repro.serving.scheduler import Request, Scheduler

    sch = Scheduler(_StubEngine(), n_slots=1)
    for user, dl in (("a", None), ("b", 0.5), ("c", 0.1)):
        sch.submit(Request(rid=hash(user), user=user,
                           prompt=jnp.zeros((2,), jnp.int32), deadline=dl))
    order = []
    for _ in range(3):
        req = sch._next_request()
        order.append(req.user)
        sch.user_inflight[req.user] = False
    assert order == ["c", "b", "a"], "tightest latency budget admits first"


# -- batched verification routing ----------------------------------------------
class _FakeTokenizer:
    def encode(self, text, bos=True):
        return [ord(c) % 49 + 1 for c in text][:12] or [1]

    def decode(self, ids):
        return "tok:" + ",".join(map(str, ids))


class _FakeEngine:
    """Counts batched-cache creations (slot pool + one per admitted prefill
    group) and prefill calls: a batched Scheduler refill must admit the whole
    continuous batch with ONE prefill.  Its family has no paged KV layout,
    so the adapter schedules it on the dense per-slot cache."""
    max_len = 64
    cfg = types.SimpleNamespace(family="ssm")

    def __init__(self):
        self.batch_caches = 0
        self.generate_calls = 0
        self.prefill_calls = 0

    def new_cache(self, batch, max_len):
        if batch > 1:
            self.batch_caches += 1
        return {}

    def prefill(self, toks, cache):
        import jax.numpy as jnp
        self.prefill_calls += 1
        logits = jnp.zeros((toks.shape[0], toks.shape[1], 50)).at[:, :, 7].set(1.0)
        return logits, cache

    def decode(self, toks, positions, cache):
        import jax.numpy as jnp
        logits = jnp.zeros((toks.shape[0], 1, 50)).at[:, :, 7].set(1.0)
        return logits, cache

    def generate(self, toks, max_new=32):
        import jax.numpy as jnp
        self.generate_calls += 1
        tail = jnp.full((toks.shape[0], max_new), 7, jnp.int32)
        return jnp.concatenate([toks, tail], axis=1)


def _engine_bridge():
    tok = _FakeTokenizer()
    e_small, e_big = _FakeEngine(), _FakeEngine()
    pool = ModelPool([
        PoolModel(name="fake-small", active_params=int(1e9), capability=0.4,
                  engine=e_small, tokenizer=tok),
        PoolModel(name="fake-big", active_params=int(20e9), capability=0.8,
                  engine=e_big, tokenizer=tok)])
    emb = WorkloadEmbedder(dim=16)
    bridge = LLMBridge(pool, ContextManager(), SemanticCache(emb, dim=16),
                       Judge(mode="planted"), config=ProxyConfig(), seed=0)
    return bridge, e_small, e_big


def test_request_batch_batches_verification_decodes():
    """M1 decodes for the whole batch run as ONE continuous batch; the
    sub-threshold subset's M2 decodes run as a second one (threshold 11
    forces every request to consult M2)."""
    bridge, e_small, e_big = _engine_bridge()
    reqs = [ProxyRequest(prompt=f"question number {i} about things",
                         conversation=f"c{i}", update_context=False,
                         service_type=ServiceType.MODEL_SELECTOR,
                         params={"threshold": 11.0}) for i in range(3)]
    out = bridge.request_batch(reqs)
    # one scheduler per consulted model: slot pool + ONE admitted group each
    assert e_small.batch_caches == 2 and e_big.batch_caches == 2
    assert e_small.prefill_calls == 1 and e_big.prefill_calls == 1
    assert e_small.generate_calls == 0 and e_big.generate_calls == 0
    for r in out:
        assert r.metadata.model_used == "fake-big"
        assert len(r.metadata.models_consulted) == 3
        assert r.metadata.verifier_score is not None
        assert r.text.startswith("tok:")


def test_request_batch_skips_m2_batch_when_verified():
    bridge, e_small, e_big = _engine_bridge()
    reqs = [ProxyRequest(prompt=f"easy question {i}", conversation=f"c{i}",
                         update_context=False,
                         service_type=ServiceType.MODEL_SELECTOR)
            for i in range(3)]
    out = bridge.request_batch(reqs)   # planted judge scores 10 >= 8
    assert e_small.prefill_calls == 1 and e_big.prefill_calls == 0
    assert e_small.batch_caches == 2 and e_big.batch_caches == 0
    assert all(r.metadata.model_used == "fake-small" for r in out)
