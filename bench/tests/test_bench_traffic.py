"""The traffic generators: deterministic in the seed, and the same work
for every seed in another order."""

import numpy as np
import pytest

from bench.harness import Layout
from bench.loops import closed_loop, open_loop, sizes

LAYOUT = Layout()
BIG_SEED = 2 ** 33 + 12345


def _traffic(name):
    return LAYOUT.json("traffic", name)


def _reqs(plan):
    """A plan as a list: a closed loop's first three rounds."""
    if isinstance(plan, closed_loop.Deck):
        return [q for r in range(3) for q in plan.round(r)]
    return plan


def _key(reqs):
    reqs = _reqs(reqs)
    return [(r["id"], r["prompt"].tolist(), r["max_new"],
             r.get("due"), r.get("client")) for r in reqs]


@pytest.mark.parametrize("name,mod", [("prompt_heavy", closed_loop),
                                      ("chat_short", open_loop),
                                      ("decode_long", closed_loop)])
def test_plan_is_deterministic_in_the_seed(name, mod):
    t = _traffic(name)
    a = mod.plan(t, BIG_SEED, 20.0, 1000)
    b = mod.plan(t, BIG_SEED, 20.0, 1000)
    c = mod.plan(t, BIG_SEED + 2 ** 32, 20.0, 1000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)          # the high 32 bits count


@pytest.mark.parametrize("name,mod", [("prompt_heavy", closed_loop),
                                      ("chat_short", open_loop),
                                      ("decode_long", closed_loop)])
def test_every_seed_offers_the_same_work(name, mod):
    t = _traffic(name)
    a = _reqs(mod.plan(t, 1, 30.0, 1000))
    b = _reqs(mod.plan(t, 2, 30.0, 1000))
    assert len(a) == len(b)
    # the same (prompt, output) pairs, not only the same lengths of each
    assert sorted((len(r["prompt"]), r["max_new"]) for r in a) == \
        sorted((len(r["prompt"]), r["max_new"]) for r in b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]


def test_open_loop_gaps_keep_the_rate_and_the_window():
    t = _traffic("chat_short")
    reqs = open_loop.plan(t, 5, 50.0, 100)
    assert len(reqs) == round(t["rate"] * 50.0)
    due = np.array([r["due"] for r in reqs])
    assert due[0] == 0.0 and (np.diff(due) >= 0).all() and due[-1] < 50.0
    g = sizes.gaps(t["arrival"], 200, 4.0)
    assert g.mean() == pytest.approx(0.25)
    assert g.std() / g.mean() == pytest.approx(2.0, rel=0.25)   # cv 2


def test_lengths_stay_in_their_clip():
    for name in ("prompt_heavy", "chat_short", "decode_long"):
        t = _traffic(name)
        for key in ("prompt", "output"):
            q = sizes.quantiles(t[key], 64)
            assert q.min() >= t[key]["lo"] and q.max() <= t[key]["hi"]
        ctx = t["prompt"]["hi"] + t["output"]["hi"]
        assert ctx <= t["engine"]["max_context"]


def test_closed_loop_rounds_deal_one_multiset():
    for name in ("decode_long", "prompt_heavy"):
        t = _traffic(name)
        deck = closed_loop.plan(t, 9, 10.0, 100)
        R = t.get("deck", t["clients"])
        dealt = [deck.take() for _ in range(2 * R)]
        assert [r["id"] for r in dealt] == list(range(2 * R))
        r0 = sorted(r["max_new"] for r in dealt if r["round"] == 0)
        r1 = sorted(r["max_new"] for r in dealt if r["round"] == 1)
        assert r0 == r1 and len(r0) == R


def test_repeat_replaces_a_request_with_one_of_the_same_lengths():
    t = _traffic("decode_long")
    assert t["repeat"]
    deck = closed_loop.plan(t, BIG_SEED, 10.0, 100)
    first = [deck.take() for _ in range(t["clients"])]
    done = first[3]
    again = deck.take(done["id"])
    assert again["id"] == t["clients"]
    assert (len(again["prompt"]), again["max_new"]) == \
        (len(done["prompt"]), done["max_new"])
    assert again["prompt"].tolist() != done["prompt"].tolist()
    other = closed_loop.plan(t, BIG_SEED, 10.0, 100)
    [other.take() for _ in range(t["clients"])]
    assert other.take(done["id"])["prompt"].tolist() == \
        again["prompt"].tolist()
