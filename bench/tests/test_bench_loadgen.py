"""The traffic generator: deterministic per seed, the same work on
every seed, and the distributions the mix files state."""

import json
import math
import os
from collections import Counter
from statistics import NormalDist

import pytest

from bench import loadgen

MIXES = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")
BIG_SEED = 2 ** 40 + 12345


def _mix(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


def _draw(spec, seed, n):
    t = loadgen.Traffic(spec, seed, vocab=1000)
    return [t.next_request() for _ in range(n)], t


@pytest.mark.parametrize("name", ["docqa-shared", "agent-shared",
                                  "chat-short"])
def test_same_seed_same_requests(name):
    spec = _mix(name)
    a, ta = _draw(spec, BIG_SEED, 40)
    b, tb = _draw(spec, BIG_SEED, 40)
    assert a == b
    assert ta.shared == tb.shared and len(ta.shared) == spec["shared_prefix"]
    c, _ = _draw(spec, BIG_SEED + 1, 40)
    assert a != c


@pytest.mark.parametrize("name", ["docqa-shared", "agent-shared",
                                  "chat-short"])
def test_every_seed_gets_the_same_sizes(name):
    spec = _mix(name)
    k = spec["strata"]
    sizes = []
    for seed in (1, 99, BIG_SEED):
        reqs, t = _draw(spec, seed, k)
        s = len(t.shared)
        sizes.append((Counter(len(p) - s for p, _ in reqs),
                      Counter(o for _, o in reqs)))
    assert sizes[0] == sizes[1] == sizes[2]


def test_strata_follow_the_stated_distributions():
    uni = {"dist": "uniform", "min": 32, "max": 96}
    vals = loadgen.strata_values(uni, 65)
    assert vals == sorted(vals) and vals[0] == 32 and vals[-1] == 96
    assert abs(sum(vals) / len(vals) - 64) < 1
    logu = {"dist": "loguniform", "min": 128, "max": 512}
    vals = loadgen.strata_values(logu, 64)
    assert 250 <= vals[32] <= 260          # geometric middle 256
    logn = {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32,
            "max": 1024}
    vals = loadgen.strata_values(logn, 64)
    assert vals[31] <= 256 <= vals[32]
    u = 0.9
    want = round(256 * math.exp(0.8 * NormalDist().inv_cdf(u)))
    assert loadgen.quantile(logn, u) == min(want, 1024)
    assert max(vals) == 1024 and min(vals) >= 32


def test_open_loop_arrivals_are_poisson_strata():
    spec = _mix("chat-short")
    t = loadgen.Traffic(spec, BIG_SEED, vocab=1000)
    due = t.arrivals(200.0)
    assert due == sorted(due) and due[-1] < 200.0
    # one full cycle of strata has mean gap 1/rate to within the strata
    gaps = [b - a for a, b in zip([0.0] + due, due)][:spec["strata"]]
    mean = sum(gaps) / len(gaps)
    assert abs(mean * spec["rate_per_s"] - 1) < 0.05
    t2 = loadgen.Traffic(spec, BIG_SEED, vocab=1000)
    assert t2.arrivals(200.0) == due


def test_geometry_check():
    for name in ("docqa-shared", "agent-shared", "chat-short"):
        loadgen.check_geometry(_mix(name), 64)
    bad = dict(_mix("docqa-shared"))
    bad["engine"] = dict(bad["engine"], max_len=3200)
    with pytest.raises(ValueError):
        loadgen.check_geometry(bad, 64)
