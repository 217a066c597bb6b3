"""The traffic generator: the same seed gives the same requests, another seed
the same work in another order."""

import numpy as np

from lib import common, traffic

MIX = common.read_json(common.BENCH / "workloads" / "gpt2-xl.serve-chat-surge.json")["traffic"]


def _key(requests):
    return [(r["due_s"], r["max_tokens"], r["prompt"].tobytes()) for r in requests]


def test_same_seed_same_requests():
    a = traffic.schedule(MIX, 2147483659, 30, 50257)
    b = traffic.schedule(MIX, 2147483659, 30, 50257)
    assert _key(a) == _key(b)
    assert len(a) > 5


def test_other_seed_same_work_other_order():
    mix = dict(MIX, rate_per_s=3.0)
    a = traffic.schedule(mix, 1, 40, 50257)
    b = traffic.schedule(mix, 2, 40, 50257)
    assert _key(a) != _key(b)
    assert abs(len(a) - len(b)) <= 2
    # the multisets of lengths are the same quantile points (up to the few
    # requests whose arrival fell beyond the window)
    la, lb = sorted(len(r["prompt"]) for r in a), sorted(len(r["prompt"]) for r in b)
    assert abs(np.median(la) - np.median(lb)) <= 8
    assert abs(np.median(la) - 192) <= 12


def test_lengths_respect_clip_and_rate():
    mix = dict(MIX, rate_per_s=2.5, initial_burst=0)
    reqs = traffic.schedule(mix, 7, 40, 50257)
    assert all(16 <= len(r["prompt"]) <= 768 and 8 <= r["max_tokens"] <= 192 for r in reqs)
    assert all(0 <= r["due_s"] < 40 for r in reqs)
    assert 90 <= len(reqs) <= 100
    assert all(r["prompt"].min() >= 0 and r["prompt"].max() < 50257 for r in reqs)


def test_gamma_gaps_have_the_stated_mean_and_burstiness():
    g = traffic.gaps({"arrivals": "gamma", "cv": 3.0, "rate_per_s": 2.0}, 400)
    assert abs(g.mean() - 0.5) < 1e-9
    assert 2.0 < g.std() / g.mean() < 3.2
    p = traffic.gaps({"arrivals": "poisson", "rate_per_s": 2.0}, 400)
    assert 0.9 < p.std() / p.mean() < 1.05


def test_shared_prefix_groups():
    mix = dict(MIX, rate_per_s=3.0, shared_prefix={"tokens": 64, "groups": 2})
    reqs = [r for r in traffic.schedule(mix, 3, 20, 50257) if len(r["prompt"]) >= 64]
    heads = {r["prompt"][:64].tobytes() for r in reqs}
    assert len(heads) == 2


def test_initial_burst_is_due_at_the_first_instant():
    mix = dict(MIX, rate_per_s=2.0, initial_burst=8)
    reqs = traffic.schedule(mix, 5, 50, 50257)
    assert [r["due_s"] for r in reqs[:8]] == [0.0] * 8
    assert reqs[8]["due_s"] > 0.0
    assert 100 <= len(reqs) <= 108
