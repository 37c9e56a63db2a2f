"""The traffic generator: reproducible from its seed, true to the mix's
declared parameters, and the same work for every seed."""
import collections

import numpy as np
import pytest

from bench import common, traffic

MIXES = ("classroom-stream", "whatsapp-cached")
SEED = 2 ** 31 + 12345          # seeds run past 32 signed bits


def _objects(mix, seed):
    m = dict(mix)
    if m.get("cache_fill"):        # a smaller fill keeps the test fast
        m["cache_fill"] = dict(m["cache_fill"], objects=500)
    return traffic.cache_objects(m, seed)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = common.load_json("traffic", name)
    objs = _objects(mix, SEED)
    a = traffic.schedule(mix, 2.0, 30, SEED, objs)
    b = traffic.schedule(mix, 2.0, 30, SEED, _objects(mix, SEED))
    assert [(r.due, r.body, r.repeat_of) for r in a] == \
        [(r.due, r.body, r.repeat_of) for r in b]
    c = traffic.schedule(mix, 2.0, 30, SEED + 1, objs)
    assert [r.body for r in a] != [r.body for r in c]


@pytest.mark.parametrize("name", MIXES)
def test_schedule_matches_declared_parameters(name):
    mix = common.load_json("traffic", name)
    objs = _objects(mix, SEED)
    rate, seconds = 2.0, 30
    reqs = traffic.schedule(mix, rate, seconds, SEED, objs)
    assert len(reqs) == traffic.n_requests(rate, seconds)
    due = [r.due for r in reqs]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < seconds
    lo, hi = mix["question_bytes"]
    mt = mix["max_tokens"]
    for r in reqs:
        assert r.stream == mix["stream"] == r.body["stream"]
        assert mt["min"] <= r.max_tokens <= mt["max"]
        assert r.body["messages"][-1] == {"role": "user", "content": r.prompt}
        if r.repeat_of is None:
            assert lo <= len(r.prompt.encode()) <= hi
        else:
            assert r.prompt == objs[r.repeat_of]["question"]
        if mix["courses"]:
            sysmsg = r.body["messages"][0]
            assert sysmsg["role"] == "system"
            assert len(sysmsg["content"]) == mix["system_bytes"]
    share = sum(r.repeat_of is not None for r in reqs) / len(reqs)
    assert share == pytest.approx(mix["repeat_share"], abs=1 / len(reqs))
    sessions = collections.Counter(r.body["x_conversation"] for r in reqs)
    assert max(sessions.values()) <= mix["turns"][1]
    if not mix["repeat_share"]:
        assert len({r.prompt for r in reqs}) == len(reqs)    # unique


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    mix = common.load_json("traffic", name)
    work = []
    for seed in (1, 2, SEED):
        reqs = traffic.schedule(mix, 2.0, 30, seed, _objects(mix, seed))
        gaps = np.diff([r.due for r in reqs])
        work.append((sorted(r.max_tokens for r in reqs),
                     sum(r.repeat_of is not None for r in reqs),
                     np.round(np.sort(gaps), 9).tolist()))
    assert work[0][:2] == work[1][:2] == work[2][:2]


def test_cache_objects_are_unique_and_identifiable():
    mix = common.load_json("traffic", "whatsapp-cached")
    objs = _objects(mix, SEED)
    assert len({o["question"] for o in objs}) == len(objs)
    lo, hi = mix["cache_fill"]["answer_bytes"]
    for o in objs:
        assert lo <= len(o["answer"]) <= hi and len(o["answer"]) > 96
        assert o["answer"].startswith(f"A{o['id']}:")


def test_zipf_popularity():
    p = traffic.zipf_ranks(14700, 1.0)
    assert p.sum() == pytest.approx(1.0)
    assert p[0] / p[9] == pytest.approx(10.0)


@pytest.mark.parametrize("name", MIXES)
def test_warmup_covers_every_engine_prompt_length(name):
    mix = common.load_json("traffic", name)
    w = traffic.warmup(mix, SEED)
    lo, hi = mix["question_bytes"]
    lengths = {len(r.prompt.encode()) for r in w}
    assert lengths == set(range(lo, min(hi, 64) + 1))
    assert all(r.body["x_allow_cache"] is False for r in w)
    assert max(r.max_tokens for r in w) == traffic.WARM_TOKENS
