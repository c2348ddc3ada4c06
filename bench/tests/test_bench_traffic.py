"""The seeded open-loop traffic: the same seed gives the same requests,
sizes follow the stated medians and clips, every seed offers the same work."""
import json
import statistics

import _paths  # noqa: F401
import pytest

from bench.run import BENCH, load_module

GEN = load_module("traffic", "open_loop_serve")


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def draw(name, seed, rate=5.0, seconds=40.0):
    return GEN.generate(mix(name), rate_per_s=rate, seconds=seconds,
                        seed=seed, vocab=1000)


def test_same_seed_same_requests_and_large_seeds_work():
    seed = 2**31 + 977
    a, b = draw("chat_tiers", seed), draw("chat_tiers", seed)
    assert [(x.due_s, x.prompt, x.max_new, x.tier) for x in a] == \
        [(x.due_s, x.prompt, x.max_new, x.tier) for x in b]
    c = draw("chat_tiers", seed + 1)
    assert [x.prompt for x in a] != [x.prompt for x in c]
    # the order of arrivals is not sorted by size
    assert [len(x.prompt) for x in a] != sorted(len(x.prompt) for x in a)


def test_every_seed_offers_the_same_schedule():
    a, b = draw("chat_tiers", 1), draw("chat_tiers", 2)
    for key in (lambda x: x.due_s, lambda x: len(x.prompt), lambda x: x.max_new,
                lambda x: x.tier):
        assert list(map(key, a)) == list(map(key, b))
    assert [x.prompt for x in a] != [x.prompt for x in b]


@pytest.mark.parametrize("name,plo,phi,pmed,olo,ohi", [
    ("chat_tiers", 32, 1024, 256, 16, 512),
])
def test_lengths_follow_stated_medians_and_clips(name, plo, phi, pmed, olo, ohi):
    arr = draw(name, 7, rate=10.0, seconds=100.0)
    plens = [len(x.prompt) for x in arr]
    outs = [x.max_new for x in arr]
    assert min(plens) >= plo and max(plens) <= phi
    assert min(outs) >= olo and max(outs) <= ohi
    assert abs(statistics.median(plens) - pmed) <= 0.02 * pmed
    assert all(1 <= t < 1000 for x in arr for t in x.prompt)


def test_arrivals_fill_the_window_at_the_rate():
    arr = draw("chat_tiers", 3, rate=5.0, seconds=40.0)
    assert len(arr) == 200
    due = [x.due_s for x in arr]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 40.0


def test_tier_shares():
    arr = draw("chat_tiers", 4, rate=10.0, seconds=100.0)
    counts = [sum(1 for x in arr if x.tier == t) for t in range(3)]
    assert counts == [500, 300, 200]
