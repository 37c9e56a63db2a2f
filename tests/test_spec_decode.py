"""Speculative decoding on the paged engine: greedy spec output must be
bit-exact with non-speculative greedy decoding (the verifier's argmax is
the only token source — proposals only decide how many rows are consumed),
under real drafts, oracle drafts with controlled acceptance, EOS landing
mid-window, and incompatible drafts degrading to plain decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import init_model
from repro.serving.engine import DraftEngine, Engine, OracleDraftEngine
from repro.serving.scheduler import Request, Scheduler

MAX_LEN = 64


@pytest.fixture(scope="module")
def engine():
    cfg = configs.get_reduced("qwen2-1.5b")
    return Engine(cfg, init_model(cfg, jax.random.PRNGKey(0)),
                  max_len=MAX_LEN)


@pytest.fixture(scope="module")
def small_engine(engine):
    """A genuinely smaller family sibling: same name/vocab (so the spec
    gate accepts the pair), one layer, independent weights — acceptance is
    whatever the tiny model earns, not 1.0 by construction."""
    cfg = dataclasses.replace(engine.cfg, n_layers=1)
    return Engine(cfg, init_model(cfg, jax.random.PRNGKey(7)),
                  max_len=MAX_LEN + DraftEngine.HEADROOM)


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    # lengths straddle page boundaries for page_size=4 (and 8/16)
    return [jnp.asarray(rng.integers(3, 90, n).tolist(), jnp.int32)
            for n in (9, 17, 33, 5)]


def _run(engine, prompts, max_new=12, eos=None, **sched_kw):
    sch = Scheduler(engine, n_slots=len(prompts), paged=True, page_size=4,
                    **sched_kw)
    for i, p in enumerate(prompts):
        sch.submit(Request(rid=i, user=f"u{i}", prompt=p, max_new=max_new,
                           eos_id=-1 if eos is None else eos))
    done = sch.run_to_completion()
    return sch, {r.rid: list(r.generated) for r in done}


def test_spec_self_draft_bit_exact_across_page_boundaries(engine):
    """Self-draft (draft == verifier weights): high acceptance, many tokens
    per round, and output identical to the plain paged greedy loop even as
    every slot crosses page_size=4 boundaries mid-window."""
    _, base = _run(engine, _prompts())
    draft = DraftEngine(engine, n_slots=4, max_len=MAX_LEN)
    sch, out = _run(engine, _prompts(), draft=draft, spec_k=4)
    assert sch.spec_stats["enabled"]
    assert out == base
    s = sch.spec_summary()
    assert s["rounds"] > 0 and s["emitted"] > s["rounds"], \
        "speculation never emitted more than one token per round"
    sch.pool.check()


def test_spec_real_small_draft_bit_exact(engine, small_engine):
    """A one-layer independent-weights draft mostly disagrees with the
    verifier; output must STILL be bit-exact — acceptance only sets speed."""
    _, base = _run(engine, _prompts(seed=1))
    draft = DraftEngine(small_engine, n_slots=4, max_len=MAX_LEN)
    sch, out = _run(engine, _prompts(seed=1), draft=draft, spec_k=4)
    assert sch.spec_stats["enabled"]
    assert out == base
    sch.pool.check()


def test_spec_oracle_mixed_acceptance_bit_exact(engine, small_engine):
    """Controlled acceptance ~0.5: rounds mix full accepts, partial
    accepts, and total rejections; every path must emit the verifier's
    tokens."""
    _, base = _run(engine, _prompts(seed=2))
    draft = OracleDraftEngine(small_engine, n_slots=4, max_len=MAX_LEN,
                              continuations=base, accept_p=0.5, seed=3)
    sch, out = _run(engine, _prompts(seed=2), draft=draft, spec_k=4)
    assert out == base
    s = sch.spec_summary()
    assert 0.0 < s["acceptance_rate"] < 1.0, \
        f"oracle acceptance degenerate: {s['acceptance_rate']}"
    sch.pool.check()


def test_spec_eos_inside_draft_window(engine):
    """EOS emitted mid-verify-window: the request stops exactly where the
    plain loop stops (tokens after EOS in the same round are discarded)."""
    _, base = _run(engine, _prompts(seed=4), max_new=12)
    # make some baseline token an EOS that lands strictly inside a k=4
    # window (generation index 5 -> round 2 of the self-draft run)
    eos = base[0][5]
    _, base_eos = _run(engine, _prompts(seed=4), max_new=12, eos=eos)
    draft = DraftEngine(engine, n_slots=4, max_len=MAX_LEN)
    sch, out = _run(engine, _prompts(seed=4), max_new=12, eos=eos,
                    draft=draft, spec_k=4)
    assert out == base_eos
    assert any(len(v) < 12 for v in out.values()), "EOS never fired"
    sch.pool.check()


def test_spec_disabled_for_incompatible_draft(engine):
    """Different token family -> the gate refuses the pair, records why,
    and the scheduler produces plain-decode output (never wrong tokens)."""
    cfg = configs.get_reduced("gemma-2b")
    other = Engine(cfg, init_model(cfg, jax.random.PRNGKey(1)),
                   max_len=MAX_LEN)
    draft = DraftEngine(other, n_slots=4, max_len=MAX_LEN)
    _, base = _run(engine, _prompts(seed=5))
    sch, out = _run(engine, _prompts(seed=5), draft=draft, spec_k=4)
    assert not sch.spec_stats["enabled"]
    assert "not token-compatible" in sch.spec_stats["disabled_reason"]
    assert sch.spec_stats["rounds"] == 0
    assert out == base


def test_spec_gate_rejects_sampling_and_dense(engine):
    from repro.serving.sampler import SamplerConfig
    draft = DraftEngine(engine, n_slots=2, max_len=MAX_LEN)
    sch = Scheduler(engine, n_slots=2, paged=True, page_size=4,
                    sampler=SamplerConfig(temperature=0.8), draft=draft)
    assert sch.draft is None and "greedy" in sch.spec_stats["disabled_reason"]
    sch = Scheduler(engine, n_slots=2, draft=draft)   # dense cache
    assert sch.draft is None and "paged" in sch.spec_stats["disabled_reason"]
    sch = Scheduler(engine, n_slots=4, paged=True, page_size=4, draft=draft)
    assert sch.draft is None and "slots" in sch.spec_stats["disabled_reason"]


def test_adapter_generate_batch_spec_wiring(engine, small_engine):
    """PoolModel.draft_engine routes batched decode through the paged
    scheduler with a draft: text identical to the plain path, telemetry
    accumulated in ModelAdapter.serving_stats (what proxy.stats() and
    Metadata.spec_* disclose)."""
    from repro.core import ModelPool, PoolModel
    from repro.core.model_adapter import ModelAdapter
    from repro.data.tokenizer import ByteTokenizer
    tok = ByteTokenizer()

    def mk(draft):
        return PoolModel(name="qwen2-1.5b", active_params=int(1.5e9),
                         capability=0.5, engine=engine, tokenizer=tok,
                         draft_engine=draft)

    adapter = ModelAdapter(ModelPool())
    prompts = ["hello world", "the quick brown fox", "prompt-centric"]
    plain = adapter.generate_batch([(mk(None), p, None) for p in prompts])
    # a plain decode leaves no speculation totals, only the scheduler's
    s = adapter.serving_stats["qwen2-1.5b"]
    assert "rounds" not in s and "enabled" not in s
    assert s["schedulers"] == 1 and s["prefill_tokens"] > 0
    spec = adapter.generate_batch([(mk(small_engine), p, None)
                                   for p in prompts])
    assert spec == plain
    s = adapter.serving_stats["qwen2-1.5b"]
    assert s["enabled"] and s["rounds"] > 0 and s["emitted"] > 0


def test_adapter_schedules_attention_families_paged(engine):
    """Every engine-backed adapter path decodes on the paged cache (and its
    paged attention kernels) when the family has one; recurrent families
    keep the dense per-slot cache."""
    from repro.core import PoolModel
    from repro.core.model_adapter import ModelAdapter
    from repro.data.tokenizer import ByteTokenizer
    cfg = configs.get_reduced("xlstm-350m")
    recurrent = Engine(cfg, init_model(cfg, jax.random.PRNGKey(0)),
                       max_len=32)
    for eng, paged in ((engine, True), (recurrent, False)):
        m = PoolModel(name=eng.cfg.name, active_params=int(1e9),
                      capability=0.5, engine=eng, tokenizer=ByteTokenizer())
        assert ModelAdapter._scheduler(m, n_slots=2).paged is paged


def test_draft_engine_rejects_cursorless_family():
    """Recurrent drafts have no dense KV cursor to rewind -> constructor
    refuses instead of silently corrupting proposals."""
    cfg = configs.get_reduced("xlstm-350m")
    eng = Engine(cfg, init_model(cfg, jax.random.PRNGKey(0)), max_len=32)
    with pytest.raises(ValueError, match="attention-family"):
        DraftEngine(eng, n_slots=2, max_len=32)
