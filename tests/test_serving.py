"""Serving substrate: engine generation, continuous-batching scheduler,
per-user FIFO discipline, slot cache surgery, paged KV cache with
copy-on-write prefix sharing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import init_model
from repro.serving import kv_cache
from repro.serving.engine import Engine, generate_scan
from repro.serving.sampler import SamplerConfig, sample
from repro.serving.scheduler import Request, Scheduler


@pytest.fixture(scope="module")
def engine():
    cfg = configs.get_reduced("qwen2-1.5b")
    params = init_model(cfg, jax.random.PRNGKey(0))
    return Engine(cfg, params, max_len=64)


def _prompts_with_overlap(n, shared_len, tail_len, seed=0):
    """n prompts sharing a ``shared_len``-token prefix with distinct tails."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(3, 90, shared_len).tolist()
    return [jnp.asarray(shared + rng.integers(3, 90, tail_len).tolist(),
                        jnp.int32) for _ in range(n)]


def test_generate_shapes(engine):
    prompt = jnp.arange(6, dtype=jnp.int32)[None, :] + 3
    out = engine.generate(prompt, max_new=5)
    assert out.shape == (1, 5)
    assert bool((out >= 0).all())


def test_generate_scan_matches_loop(engine):
    prompt = jnp.asarray([[3, 4, 5, 6, 7]], jnp.int32)
    loop = engine.generate(prompt, max_new=6)
    cache = engine.new_cache(1, 64)
    scan = generate_scan(engine.params, engine.cfg, prompt, 6, cache)
    np.testing.assert_array_equal(np.asarray(loop), np.asarray(scan))


def test_scheduler_fifo_per_user(engine):
    sch = Scheduler(engine, n_slots=3)
    for i in range(7):
        sch.submit(Request(rid=i, user=f"u{i % 2}", max_new=4,
                           prompt=jnp.arange(4 + i, dtype=jnp.int32) + 3))
    done = sch.run_to_completion()
    assert len(done) == 7
    for user in ("u0", "u1"):
        rids = [r.rid for r in done if r.user == user]
        assert rids == sorted(rids), "per-user FIFO violated"


def test_scheduler_tier_weighted_refill(engine):
    """Budget-aware decode slots: with one slot contended, a depleted-tier
    head yields to funded users, regardless of submit order."""
    sch = Scheduler(engine, n_slots=1, starvation_s=60.0)
    for rid, (user, tier) in enumerate([("rich", 0), ("poor", 3),
                                        ("rich2", 0)]):
        sch.submit(Request(rid=rid, user=user, tier=tier, max_new=2,
                           prompt=jnp.arange(4, dtype=jnp.int32) + 3))
    done = sch.run_to_completion()
    assert [r.rid for r in done] == [0, 2, 1], "depleted head did not yield"


def test_scheduler_tier_starvation_guard(engine):
    """The aged depleted head regains full priority: with starvation_s=0 it
    is already 'aged', so plain rotation order is preserved."""
    sch = Scheduler(engine, n_slots=1, starvation_s=0.0)
    for rid, (user, tier) in enumerate([("rich", 0), ("poor", 3),
                                        ("rich2", 0)]):
        sch.submit(Request(rid=rid, user=user, tier=tier, max_new=2,
                           prompt=jnp.arange(4, dtype=jnp.int32) + 3))
    done = sch.run_to_completion()
    assert [r.rid for r in done] == [0, 1, 2], "starvation guard inactive"


def test_scheduler_tier_weighs_into_edf(engine):
    """Among deadlined heads, each depletion tier costs tier_penalty seconds
    of effective deadline slack."""
    sch = Scheduler(engine, n_slots=1, tier_penalty=10.0, starvation_s=60.0)
    # poor's deadline is nominally tighter, but 3 tiers * 10s of penalty
    # push its effective deadline past rich's
    sch.submit(Request(rid=0, user="poor", tier=3, deadline=5.0, max_new=2,
                       prompt=jnp.arange(4, dtype=jnp.int32) + 3))
    sch.submit(Request(rid=1, user="rich", tier=0, deadline=8.0, max_new=2,
                       prompt=jnp.arange(4, dtype=jnp.int32) + 3))
    done = sch.run_to_completion()
    assert [r.rid for r in done] == [1, 0]


def test_scheduler_batches_multiple_users(engine):
    sch = Scheduler(engine, n_slots=4)
    for i in range(4):
        sch.submit(Request(rid=i, user=f"u{i}", max_new=3,
                           prompt=jnp.arange(5, dtype=jnp.int32) + 3))
    sch.step()
    live = sum(1 for s in sch.slots if s is not None)
    assert live >= 3   # concurrent decode slots in use


def test_batched_admit_exact_vs_single_request(engine):
    """A mixed-length refill is ONE padded prefill call, and greedy decode
    matches per-request generation bit-for-bit (right-padding is dead KV
    under the causal mask once the write cursor is rewound)."""
    prompts = [jnp.arange(4 + i, dtype=jnp.int32) + 3 for i in range(5)]
    sch = Scheduler(engine, n_slots=5)
    for i, p in enumerate(prompts):
        sch.submit(Request(rid=i, user=f"u{i}", prompt=p, max_new=6))
    calls0 = engine.n_prefill_calls
    done = sch.run_to_completion()
    assert engine.n_prefill_calls - calls0 == 1, "refill must batch prefill"
    assert len(done) == 5
    for r in done:
        ref = engine.generate(prompts[r.rid][None, :], max_new=6)[0]
        assert r.generated == [int(t) for t in np.asarray(ref)]


def test_batched_admit_exact_hybrid_family():
    """Recurrent-state caches can't absorb pad tokens: admission groups by
    prompt length and stays exact."""
    cfg = configs.get_reduced("zamba2-7b")
    from repro.models import init_model as _init
    eng = Engine(cfg, _init(cfg, jax.random.PRNGKey(0)), max_len=64)
    prompts = [jnp.arange(l, dtype=jnp.int32) + 3 for l in (5, 7, 5, 7)]
    sch = Scheduler(eng, n_slots=4)
    for i, p in enumerate(prompts):
        sch.submit(Request(rid=i, user=f"v{i}", prompt=p, max_new=4))
    calls0 = eng.n_prefill_calls
    done = sch.run_to_completion()
    assert eng.n_prefill_calls - calls0 == 2   # one per length group
    for r in done:
        ref = eng.generate(prompts[r.rid][None, :], max_new=4)[0]
        assert r.generated == [int(t) for t in np.asarray(ref)]


def test_insert_slots_multi(engine):
    """insert_slots writes a B=k cache into k slots in one scatter per leaf,
    equivalent to k insert_slot calls."""
    big = engine.new_cache(4, 32)
    multi = engine.new_cache(2, 32)
    multi = jax.tree.map(lambda a: a + 1 if a.dtype != jnp.int32 else a, multi)
    merged = kv_cache.insert_slots(big, multi, [1, 3])
    seq = big
    for i, slot in enumerate([1, 3]):
        one = jax.tree.map(lambda a: a[:, i:i + 1], multi)
        seq = kv_cache.insert_slot(seq, one, slot)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), merged, seq)


def test_slot_insert_and_reset(engine):
    big = engine.new_cache(4, 32)
    single = engine.new_cache(1, 32)
    single = jax.tree.map(lambda a: a + 1 if a.dtype != jnp.int32 else a, single)
    merged = kv_cache.insert_slot(big, single, 2)
    k = merged["kv"]["k"]
    assert float(jnp.abs(k[:, 2]).sum()) > 0
    assert float(jnp.abs(k[:, 0]).sum()) == 0
    back = kv_cache.reset_slot(merged, 2)
    assert float(jnp.abs(back["kv"]["k"][:, 2]).sum()) == 0


def test_reset_slots_matches_sequential(engine):
    """reset_slots zeroes k slots in one masked pass per leaf, equivalent to
    k reset_slot calls."""
    big = engine.new_cache(5, 32)
    big = jax.tree.map(lambda a: a + 2 if a.dtype != jnp.int32 else a, big)
    batched = kv_cache.reset_slots(big, [0, 2, 4])
    seq = big
    for slot in [0, 2, 4]:
        seq = kv_cache.reset_slot(seq, slot)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), batched, seq)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), kv_cache.reset_slots(big, []), big)


def test_generate_eos_polling_matches_per_step_sync(engine):
    """The decode loop polls the done mask every DONE_POLL_EVERY steps
    instead of forcing a host round-trip per token; the trimmed output is
    bit-identical to the per-step-sync loop."""
    prompt = jnp.arange(6, dtype=jnp.int32)[None, :] + 3
    full = engine.generate(prompt, max_new=12)
    eos = int(full[0, 3])       # fires mid-stream, off the poll boundary
    # reference: the per-step-sync semantics, replicated inline
    cache = engine.new_cache(1, 64)
    logits, cache = engine.prefill(prompt, cache)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    key = jax.random.PRNGKey(0)
    out_ref, done = [], jnp.zeros((1,), bool)
    for i in range(12):
        out_ref.append(tok)
        key, sub = jax.random.split(key)
        logits, cache = engine.decode(
            tok[:, None], jnp.full((1, 1), 6 + i, jnp.int32), cache)
        tok = sample(logits[:, -1], sub, SamplerConfig())
        done = done | (tok == eos)
        if bool(done.all()):
            break
    syncs0 = engine.n_host_syncs
    out_new = engine.generate(prompt, max_new=12, eos_id=eos)
    np.testing.assert_array_equal(
        np.asarray(jnp.stack(out_ref, axis=1)), np.asarray(out_new))
    assert engine.n_host_syncs - syncs0 <= -(-12 // 8), "per-token host sync"
    # EOS-free sampling can never exit early: zero syncs
    syncs0 = engine.n_host_syncs
    engine.generate(prompt, max_new=4)
    assert engine.n_host_syncs == syncs0


# --------------------------------------------------------------------------
# paged KV cache: copy-on-write prefix sharing, page-budgeted admission
# --------------------------------------------------------------------------
def test_paged_bit_exact_vs_unshared_and_dense(engine):
    """Trie-shared decoding is bit-exact vs the unshared paged path AND the
    dense per-request path, across an admission group with 2/3 prefix
    overlap (intra-refill wave sharing included)."""
    prompts = _prompts_with_overlap(6, shared_len=32, tail_len=6)
    refs = [engine.generate(p[None, :], max_new=5)[0] for p in prompts]
    outs, scheds = {}, {}
    for pc in (False, True):
        sch = Scheduler(engine, n_slots=6, paged=True, page_size=16,
                        prefix_cache=pc)
        for i, p in enumerate(prompts):
            sch.submit(Request(rid=i, user=f"u{pc}{i}", prompt=p, max_new=5))
        done = sch.run_to_completion()
        assert len(done) == 6
        outs[pc] = {r.rid: r.generated for r in done}
        scheds[pc] = sch
        sch.pool.check()
    for i in range(6):
        ref = [int(t) for t in np.asarray(refs[i])]
        assert outs[False][i] == ref, "unshared paged != dense"
        assert outs[True][i] == ref, "shared paged != unshared"
    # sharing must actually have happened, and cut prefill work
    assert scheds[True].shared_tokens >= 5 * 32
    assert scheds[True].prefill_tokens < scheds[False].prefill_tokens / 2


def test_paged_full_prompt_match_cow(engine):
    """A prompt fully covered by cached pages reruns only its last token;
    the write into the shared boundary page goes through a copy-on-write
    fork and stays bit-exact."""
    prompt = _prompts_with_overlap(1, shared_len=32, tail_len=0)[0]
    ref = [int(t) for t in np.asarray(engine.generate(prompt[None, :],
                                                      max_new=4)[0])]
    sch = Scheduler(engine, n_slots=2, paged=True, page_size=16)
    sch.submit(Request(rid=0, user="a", prompt=prompt, max_new=4))
    sch.run_to_completion()
    sch.submit(Request(rid=1, user="b", prompt=prompt, max_new=4))
    sch.run_to_completion()
    got = {r.rid: r.generated for r in sch.finished}
    assert got[0] == ref and got[1] == ref
    assert sch.pool.n_cow >= 1, "full-page match must exercise COW"
    assert sch.shared_tokens >= 31
    sch.pool.check()


def test_paged_trie_pages_bit_identical_to_fresh_prefill(engine):
    """The physical pages a trie hit maps a request onto hold bit-identical
    KV to pages prefilled from scratch for the same prompt."""
    prompt = _prompts_with_overlap(1, shared_len=16, tail_len=8, seed=5)[0]
    sch = Scheduler(engine, n_slots=2, paged=True, page_size=16)
    sch.submit(Request(rid=0, user="a", prompt=prompt, max_new=3))
    sch.run_to_completion()
    sch.submit(Request(rid=1, user="b", prompt=prompt, max_new=3))
    sch.step()                                    # admits rid=1 via the trie
    slot = next(s.slot for s in sch.slots if s is not None and s.rid == 1)
    assert sch.shared_tokens >= 16
    shared_page = int(sch._tables[slot, 0])

    fresh = Scheduler(engine, n_slots=1, paged=True, page_size=16,
                      prefix_cache=False)
    fresh.submit(Request(rid=2, user="c", prompt=prompt, max_new=3))
    fresh.step()
    fslot = next(s.slot for s in fresh.slots if s is not None)
    fresh_page = int(fresh._tables[fslot, 0])
    for leaf in ("k_pages", "v_pages"):
        np.testing.assert_array_equal(
            np.asarray(sch.cache["paged"][leaf][:, shared_page]),
            np.asarray(fresh.cache["paged"][leaf][:, fresh_page]))
    sch.run_to_completion()
    fresh.run_to_completion()


def test_paged_equal_hbm_concurrency_and_prefill_savings(engine):
    """At the SAME HBM budget (4 dense slots x max_len=64 == 16+1 pages of
    16), page-budgeted admission sustains >= 2x the concurrent slots and,
    with >= 0.5 prefix overlap, well under half the prefill tokens — with
    bit-exact outputs."""
    prompts = _prompts_with_overlap(12, shared_len=16, tail_len=5, seed=2)
    dense = Scheduler(engine, n_slots=4)
    paged = Scheduler(engine, n_slots=12, paged=True, page_size=16,
                      n_pages=4 * 4 + 1)
    for sch, tag in ((dense, "d"), (paged, "p")):
        for i, p in enumerate(prompts):
            sch.submit(Request(rid=i, user=f"{tag}{i}", prompt=p, max_new=4))
        assert len(sch.run_to_completion()) == 12
    assert paged.peak_live >= 2 * dense.peak_live
    assert paged.prefill_tokens < dense.prefill_tokens / 2
    gd = {r.rid: r.generated for r in dense.finished}
    gp = {r.rid: r.generated for r in paged.finished}
    assert gd == gp
    paged.pool.check()


def test_paged_lazy_decode_page_allocation(engine):
    """Decode pages are mapped the step the cursor crosses a page boundary,
    not reserved up front at admission."""
    prompt = jnp.arange(10, dtype=jnp.int32) + 3
    sch = Scheduler(engine, n_slots=1, paged=True, page_size=16)
    sch.submit(Request(rid=0, user="a", prompt=prompt, max_new=12))
    sch.step()                       # admit + first decode (pos 10 -> 11)
    assert sch._tables[0, 0] >= 0
    assert sch._tables[0, 1] == -1, "decode page mapped eagerly"
    while sch.slots[0] is not None and sch.slots[0].pos < 17:
        sch.step()
    assert sch._tables[0, 1] >= 0, "page not mapped at boundary"
    done = sch.run_to_completion()
    ref = engine.generate(prompt[None, :], max_new=12)[0]
    assert done[0].generated == [int(t) for t in np.asarray(ref)]


def test_paged_eviction_under_pressure(engine):
    """Cold trie-retained prefix pages are LRU-evicted when the pool runs
    dry; serving stays correct throughout."""
    rng = np.random.default_rng(7)
    prompts = [jnp.asarray(rng.integers(3, 90, 16), jnp.int32)
               for _ in range(8)]
    sch = Scheduler(engine, n_slots=2, paged=True, page_size=8,
                    n_pages=2 * 8 + 1)
    for i, p in enumerate(prompts):        # one user: strictly sequential
        sch.submit(Request(rid=i, user="solo", prompt=p, max_new=4))
    done = sch.run_to_completion()
    assert len(done) == 8
    assert sch.pool.n_evictions > 0, "pressure never evicted trie pages"
    for r in done:
        ref = engine.generate(prompts[r.rid][None, :], max_new=4)[0]
        assert r.generated == [int(t) for t in np.asarray(ref)]
    sch.pool.check()


def test_paged_moe_family():
    """The paged cache path plumbs through the MoE stack (incl. the grok
    score softcap).  MoE outputs are only compared step-wise: capacity-
    factor token drops make generations batch-composition-dependent, so no
    generation-level exactness is claimed for this family (the dense
    scheduler has the same property)."""
    cfg = configs.get_reduced("grok-1-314b")
    eng = Engine(cfg, init_model(cfg, jax.random.PRNGKey(0)), max_len=64)
    prompts = _prompts_with_overlap(3, shared_len=16, tail_len=4, seed=3)
    sch = Scheduler(eng, n_slots=3, paged=True, page_size=16)
    for i, p in enumerate(prompts):
        sch.submit(Request(rid=i, user=f"m{i}", prompt=p, max_new=4))
    done = sch.run_to_completion()
    assert len(done) == 3 and sch.shared_tokens >= 2 * 16
    assert all(len(r.generated) == 4 for r in done)
    sch.pool.check()


def test_paged_decode_step_bit_exact_vs_dense_moe():
    """One decode step on the MoE family: paged attention (softcap included)
    == dense attention, bit for bit, given identical cache contents."""
    cfg = configs.get_reduced("grok-1-314b")
    eng = Engine(cfg, init_model(cfg, jax.random.PRNGKey(0)), max_len=64)
    prompt = jnp.arange(8, dtype=jnp.int32)[None, :] + 3
    dense = eng.new_cache(1, 64)
    logits, dense = eng.prefill(prompt, dense)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    paged = eng.new_paged_cache(1, 8, 16, 4)
    L = dense["kv"]["k"].shape[0]
    kp = np.asarray(paged["paged"]["k_pages"]).copy()
    vp = np.asarray(paged["paged"]["v_pages"]).copy()
    kp[:, 1, :8] = np.asarray(dense["kv"]["k"][:, 0, :8])
    vp[:, 1, :8] = np.asarray(dense["kv"]["v"][:, 0, :8])
    tbl = np.full((1, 4), -1, np.int32)
    tbl[0, 0] = 1
    paged["paged"].update(
        k_pages=jnp.asarray(kp), v_pages=jnp.asarray(vp),
        table=jnp.broadcast_to(jnp.asarray(tbl)[None], (L, 1, 4)),
        pos=jnp.full((L, 1), 8, jnp.int32))
    positions = jnp.full((1, 1), 8, jnp.int32)
    ld, _ = eng.decode(tok[:, None], positions, dense)
    lp, _ = eng.decode(tok[:, None], positions, paged)
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))


def test_paged_oversize_requests_bounded(engine):
    """A decode budget overflowing max_len is capped at admission (the page
    table is max_pages wide; no mid-decode IndexError), and a prompt that
    cannot decode at all is rejected up front."""
    sch = Scheduler(engine, n_slots=2, paged=True, page_size=8)  # max_len 64
    sch.submit(Request(rid=0, user="a", max_new=16,
                       prompt=jnp.arange(60, dtype=jnp.int32) + 3))
    done = sch.run_to_completion()
    assert len(done) == 1 and len(done[0].generated) == 4   # capped at 64-60
    sch.pool.check()
    # rejection happens at submit, before any queue/inflight state mutates
    with pytest.raises(ValueError, match="cannot decode"):
        sch.submit(Request(rid=1, user="a", max_new=1,
                           prompt=jnp.arange(64, dtype=jnp.int32) + 3))
    assert sch.pending() == 0 and not sch.user_inflight["a"]
    # the scheduler still serves subsequent traffic
    sch.submit(Request(rid=2, user="a", max_new=2,
                       prompt=jnp.arange(6, dtype=jnp.int32) + 3))
    assert len(sch.run_to_completion()) == 2


def test_paged_pool_infeasible_request_not_stranded(engine):
    """A request the pool can NEVER fit raises — but only after queue and
    in-flight state are restored, so nothing is silently dropped."""
    sch = Scheduler(engine, n_slots=2, paged=True, page_size=16, n_pages=3)
    sch.submit(Request(rid=0, user="u", max_new=32,
                       prompt=jnp.arange(20, dtype=jnp.int32) + 3))
    with pytest.raises(ValueError, match="can never free"):
        sch.step()
    assert sch.pending() == 1 and not sch.user_inflight["u"]
    sch.pool.check()


def test_paged_multi_token_prefill_matches_stepwise(engine):
    """Multi-token prefill straight into a paged cache (the paged
    flash-prefill path) agrees with feeding the same tokens one decode
    step at a time — including across a page boundary (page_size=4,
    prompt length 8 spans two pages)."""
    prompt = jnp.arange(8, dtype=jnp.int32)[None, :] + 3
    tbl = np.full((1, 4), -1, np.int32)
    tbl[0, 0], tbl[0, 1] = 1, 2          # avoid trash page 0

    def fresh():
        cache = engine.new_paged_cache(1, 8, 4, 4)
        L = cache["paged"]["pos"].shape[0]
        cache["paged"].update(
            table=jnp.broadcast_to(jnp.asarray(tbl)[None], (L, 1, 4)),
            pos=jnp.zeros((L, 1), jnp.int32))
        return cache

    lp, cp = engine.prefill(prompt, fresh())
    cache = fresh()
    rows = []
    for j in range(8):
        lj, cache = engine.decode(prompt[:, j:j + 1],
                                  jnp.full((1, 1), j, jnp.int32), cache)
        rows.append(np.asarray(lj[:, 0]))
    np.testing.assert_allclose(np.asarray(lp[0]), np.stack(rows, 1)[0],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.argmax(np.asarray(lp[0]), -1),
                                  np.argmax(np.stack(rows, 1)[0], -1))
    # both paths advanced the cursors identically and wrote the same pages
    np.testing.assert_array_equal(np.asarray(cp["paged"]["pos"]),
                                  np.asarray(cache["paged"]["pos"]))
    np.testing.assert_allclose(np.asarray(cp["paged"]["k_pages"][:, 1:3]),
                               np.asarray(cache["paged"]["k_pages"][:, 1:3]),
                               rtol=1e-6, atol=1e-6)


def test_paged_rejects_recurrent_family():
    cfg = configs.get_reduced("zamba2-7b")
    eng = Engine(cfg, init_model(cfg, jax.random.PRNGKey(0)), max_len=64)
    with pytest.raises(ValueError):
        Scheduler(eng, n_slots=2, paged=True)


def test_sampler_greedy_and_topk():
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]])
    assert int(sample(logits, jax.random.PRNGKey(0), SamplerConfig())[0]) == 1
    sc = SamplerConfig(temperature=1.0, top_k=2)
    draws = {int(sample(logits, jax.random.PRNGKey(i), sc)[0]) for i in range(40)}
    assert draws <= {1, 2}, "top-k truncation leaked"


# --------------------------------------------------------------------------
# the plain decode step's host side: one decode call, no eager glue
# --------------------------------------------------------------------------
@pytest.mark.parametrize("paged,n_slots", [(False, 1), (False, 4),
                                           (True, 1), (True, 4)])
def test_greedy_scheduler_bit_identical_to_generate(engine, paged, n_slots):
    """The step feeds the compiled greedy pick straight back as the next
    tokens: every answer equals ``Engine.generate``'s, with slots finishing
    at different steps and being re-admitted, and (paged, 8-token pages)
    decodes crossing two and three page boundaries."""
    rng = np.random.default_rng(11)
    lens, max_new = (5, 9, 3, 12, 7, 6), (20, 4, 13, 17, 9, 21)
    prompts = [jnp.asarray(rng.integers(3, 90, l), jnp.int32) for l in lens]
    kw = dict(paged=True, page_size=8) if paged else {}
    sch = Scheduler(engine, n_slots=n_slots, **kw)
    for i, (p, m) in enumerate(zip(prompts, max_new)):
        sch.submit(Request(rid=i, user=f"g{i}", prompt=p, max_new=m))
    done = sch.run_to_completion()
    assert len(done) == 6
    for r in done:
        ref = engine.generate(prompts[r.rid][None, :], max_new=max_new[r.rid])
        assert r.generated == [int(t) for t in np.asarray(ref[0])]
    if paged:
        sch.pool.check()


@pytest.mark.parametrize("paged", [False, True])
def test_sampled_scheduler_matches_split_and_sample(engine, paged):
    """With a temperature the step keeps the eager key split and draw: the
    tokens equal ``split`` + ``sample(logits[:, -1])`` run by hand from the
    same seed."""
    sc = SamplerConfig(temperature=0.8, top_k=20)
    prompt = jnp.arange(9, dtype=jnp.int32) + 3
    kw = dict(paged=True, page_size=8) if paged else {}
    sch = Scheduler(engine, n_slots=1, sampler=sc, seed=5, **kw)
    sch.submit(Request(rid=0, user="t", prompt=prompt, max_new=12))
    got = sch.run_to_completion()[0].generated

    cache = engine.new_cache(1, 64)
    logits, cache = engine.prefill(prompt[None, :], cache)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    key, want = jax.random.PRNGKey(5), []
    for i in range(12):
        want.append(int(tok[0]))
        key, sub = jax.random.split(key)
        logits, cache = engine.decode(
            tok[:, None], jnp.full((1, 1), 9 + i, jnp.int32), cache)
        tok = sample(logits[:, -1], sub, sc)
    assert got == want


class _Recording:
    """Stands in for a module in the scheduler's namespace and records every
    name the scheduler reads from it."""

    def __init__(self, module, seen):
        self._module, self._seen = module, seen

    def __getattr__(self, name):
        self._seen.append(name)
        return getattr(self._module, name)


def test_steady_state_step_is_one_decode_call(engine, monkeypatch):
    """A step with no admission, page crossing or teardown makes exactly one
    ``Engine.decode`` call and no eager ``jnp``, ``jax.random`` or ``.at[]``
    op: positions go up as a fresh host array, and the compiled greedy
    pick of the previous step is the next step's tokens."""
    from repro.serving import scheduler as sched_mod
    sch = Scheduler(engine, n_slots=2, paged=True, page_size=16)
    for i in range(2):
        sch.submit(Request(rid=i, user=f"s{i}", max_new=12,
                           prompt=jnp.arange(5 + i, dtype=jnp.int32) + 3))
    sch.step()                                   # admits both, first decode
    sch.step()
    assert all(s is not None and s.pos % 16 for s in sch.slots)

    calls, seen, at = [], [], []
    decode = engine.decode

    def counting_decode(*a):
        calls.append(a)
        return decode(*a)
    monkeypatch.setattr(engine, "decode", counting_decode)
    monkeypatch.setattr(sched_mod, "jnp", _Recording(jnp, seen))
    monkeypatch.setattr(sched_mod, "jax", _Recording(jax, seen))
    array_type = type(sch.tokens)
    at_prop = array_type.at
    monkeypatch.setattr(array_type, "at", property(
        lambda x: at.append(1) or at_prop.fget(x)))
    want = [[s.pos] for s in sch.slots]
    sch.step()
    assert len(calls) == 1 and calls[0][0].shape == (2, 1)
    np.testing.assert_array_equal(np.asarray(calls[0][1]), want)
    assert seen == [] and at == []


def test_page_crossings_and_teardown_patch_the_device_tables(engine,
                                                             monkeypatch):
    """The device page tables follow the host tables through every lazy
    mapping and teardown, each a single call of the fixed-shape patch
    program (which compiles once for the scheduler's shapes)."""
    from repro.serving import scheduler as sched_mod
    patches = []
    update = sched_mod.page_table_update

    def counting_update(*a):
        patches.append(a[2].shape)
        return update(*a)
    monkeypatch.setattr(sched_mod, "page_table_update", counting_update)
    sch = Scheduler(engine, n_slots=3, paged=True, page_size=4)
    for i in range(5):
        sch.submit(Request(rid=i, user=f"p{i}", max_new=6 + 3 * i,
                           prompt=jnp.arange(3 + i, dtype=jnp.int32) + 3))
    while sch.pending():
        n0 = len(patches)
        done = sch.step()
        assert len(patches) - n0 <= 1 + int(bool(done))  # map + teardown
        table = np.asarray(sch.cache["paged"]["table"])
        assert (table == sch._tables[None]).all()
        for r in done:
            assert (np.asarray(sch.cache["paged"]["pos"])[:, r.slot] == 0).all()
    assert len(sch.finished) == 5 and patches
    assert set(patches) == {(3, 3)}
    sch.pool.check()
