"""The generator: what the seed may and may not change."""

import json
import os

import pytest

from harness import loadgen

TRAFFIC = os.path.join(os.path.dirname(__file__), "..", "traffic")
REHEARSE = os.path.join(os.path.dirname(__file__), "rehearse", "traffic")


def _all_traffic():
    out = []
    for d in (TRAFFIC, REHEARSE):
        for name in sorted(os.listdir(d)):
            out.append(os.path.join(d, name))
    return out


@pytest.mark.parametrize("path", _all_traffic(),
                         ids=lambda p: "/".join(p.split(os.sep)[-2:]))
def test_two_seeds_offer_the_same_tokens(path):
    with open(path) as f:
        traffic = json.load(f)
    a = loadgen.plan(traffic, 7, 20.0, 32000)
    b = loadgen.plan(traffic, 3000000019, 20.0, 32000)
    assert loadgen.offered_tokens(a) == loadgen.offered_tokens(b)
    assert loadgen.offered_tokens(a)["requests"] > 0
    assert json.dumps(a) != json.dumps(b)       # the seed changed something
    again = loadgen.plan(traffic, 7, 20.0, 32000)
    assert json.dumps(a) == json.dumps(again)   # and nothing else did


def test_open_requests_fall_inside_ramp_and_window():
    with open(os.path.join(REHEARSE, "open.json")) as f:
        traffic = json.load(f)
    p = loadgen.plan(traffic, 1, 30.0, 1000)
    due = [r["due"] for r in p["requests"]]
    assert min(due) >= -traffic["ramp_s"] and max(due) < 30.0
    in_window = [r for r in p["requests"] if r["due"] >= 0]
    assert len(in_window) == round(traffic["streams"][0]["rate"] * 30.0)
    lens = sorted(len(r["prompt"]) for r in in_window)
    spec = traffic["streams"][0]["prompt"]
    assert lens[0] >= spec["min"] and lens[-1] <= spec["max"]


def test_shared_groups_keep_their_distance_and_share_a_prefix():
    with open(os.path.join(REHEARSE, "docs.json")) as f:
        traffic = json.load(f)
    p = loadgen.plan(traffic, 5, 60.0, 32000)
    reqs = [r for r in p["requests"] if r["due"] >= 0]
    first = [r for r in reqs if r["class"] == "shared_first"]
    later = [r for r in reqs if r["class"] == "shared_later"]
    assert len(later) == 3 * len(first) > 0
    heads = [tuple(r["prompt"][:64]) for r in reqs]
    assert len(set(heads)) == len(first)
    gap = traffic["streams"][0]["shared"]["min_gap"]
    for i, h in enumerate(heads[:-gap]):       # the greedy repair's reach
        assert h not in heads[max(0, i - gap):i]


def test_arrivals_keep_count_and_range():
    import numpy as np
    ts = loadgen.arrivals(50, 2.0, 10.0, np.random.default_rng(0))
    assert len(ts) == 50 and ts == sorted(ts)
    assert 2.0 <= ts[0] and ts[-1] < 12.0


def test_quantiles_are_a_fixed_multiset():
    d = {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32,
         "max": 2048}
    xs = loadgen.quantiles(d, 40)
    assert xs == sorted(xs) and xs == loadgen.quantiles(d, 40)
    assert xs[0] >= 32 and xs[-1] <= 2048
    assert abs(sorted(xs)[20] - 256) < 40


def test_blocks_give_every_stretch_the_same_mix():
    import numpy as np
    xs = list(range(64))
    out = loadgen._in_blocks(xs, 8, np.random.default_rng(3))
    assert sorted(out) == xs
    for i in range(0, 64, 8):                 # one value from each eighth
        assert sorted(x // 8 for x in out[i:i + 8]) == list(range(8))
    with open(os.path.join(TRAFFIC, "longgen.json")) as f:
        traffic = json.load(f)
    p = loadgen.plan(traffic, 11, 51.0, 32000)
    lens = [len(r["prompt"]) for r in p["requests"]]
    per = len(lens) // 8
    stratum = {x: i // per for i, x in enumerate(sorted(lens))}
    for i in range(0, len(lens) - 7, 8):
        assert sorted(stratum[x] for x in lens[i:i + 8]) == list(range(8))
