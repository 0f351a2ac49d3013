"""The seeded traffic generator: one seed, one schedule; every seed the
same sizes (a closed loop: the same client scripts), and an open loop the
same gaps; lengths at the stated quantiles."""

import json
import math
import os

import numpy as np
import pytest

from perfbench import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: an open-loop mix: lognormal prompts and outputs, Poisson arrivals
OPEN = {"loop": "open", "rate_per_s": 1.5,
        "prompt_len": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                       "min": 16, "max": 512},
        "output_len": {"dist": "lognormal", "median": 64, "sigma": 0.8,
                       "min": 8, "max": 256},
        "greedy_share": 0.25, "temperature": 0.8, "top_k": 40}
CLIENTS = 8


def _mix(name):
    if name == "open":
        return OPEN
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _gen(m, seed, vocab=49155):
    clients = CLIENTS if m["loop"] == "closed" else None
    return traffic.generate(m, seed, 51, vocab, clients)


def _key(reqs):
    return [(r.prompt.tolist(), r.max_new, r.greedy, r.seed, r.arrival)
            for r in reqs]


def _scripts(reqs):
    """Each client's requests in turn, as (prompt, output, greedy)."""
    return sorted(tuple((r.prompt.size, r.max_new, r.greedy)
                        for r in reqs[c::CLIENTS]) for c in range(CLIENTS))


@pytest.mark.parametrize("mix", ["decode", "open"])
def test_one_seed_gives_one_schedule(mix):
    m = _mix(mix)
    a, b, c = (_gen(m, s) for s in (2**33 + 17, 2**33 + 17, 2**33 + 18))
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("mix", ["decode", "open"])
def test_every_seed_offers_the_same_sizes_in_another_order(mix):
    m = _mix(mix)
    runs = [_gen(m, s, 1000) for s in (1, 2, 3**20)]
    sizes = [sorted((r.prompt.size, r.max_new, r.greedy) for r in reqs)
             for reqs in runs]
    assert sizes[1] == sizes[0] and sizes[2] == sizes[0]
    assert [r.prompt.size for r in runs[0]] != \
        [r.prompt.size for r in runs[1]]
    if m["loop"] == "open":
        # the gaps, the one after the last arrival included, sum to the
        # window: the same set of them for every seed
        gaps = [sorted(np.diff([r.arrival for r in reqs] + [51.0]))
                for reqs in runs]
        for g in gaps[1:]:
            np.testing.assert_allclose(g, gaps[0], rtol=1e-9)


def test_a_closed_loop_deals_every_seed_the_same_client_scripts():
    m = _mix("decode")
    runs = [_gen(m, s) for s in (5, 6, 2**40 + 9)]
    assert _scripts(runs[1]) == _scripts(runs[0])
    assert _scripts(runs[2]) == _scripts(runs[0])
    # every round spans the lengths: its shortest and longest lie in the
    # pool's lowest and highest quarter
    total = sorted(r.prompt.size + r.max_new for r in runs[0])
    q1, q3 = total[len(total) // 4], total[3 * len(total) // 4]
    for k in range(0, len(runs[0]), CLIENTS):
        rnd = [r.prompt.size + r.max_new for r in runs[0][k:k + CLIENTS]]
        assert min(rnd) <= q1 and max(rnd) >= q3


def test_a_pool_that_does_not_deal_into_whole_rounds_is_refused():
    with pytest.raises(ValueError, match="whole rounds"):
        traffic.generate(_mix("decode"), 1, 51, 1000, clients=5)


def test_open_loop_arrivals_fill_the_window_at_the_stated_rate():
    reqs = _gen(OPEN, 5, 1000)
    assert len(reqs) == round(OPEN["rate_per_s"] * 51)
    arr = [r.arrival for r in reqs]
    assert arr[0] == 0.0 and arr == sorted(arr) and arr[-1] < 51


def test_lognormal_lengths_match_the_stated_parameters():
    for key in ("prompt_len", "output_len"):
        spec = OPEN[key]
        q = traffic.quantiles(spec, 10_001)
        assert q == sorted(q)
        assert q[0] >= spec["min"] and q[-1] <= spec["max"]
        assert np.median(q) == spec["median"]
        # unclipped quartiles: median * exp(+-0.6745 sigma)
        for u, z in ((0.25, -0.6745), (0.75, 0.6745)):
            want = spec["median"] * math.exp(z * spec["sigma"])
            assert q[int(u * 10_000)] == pytest.approx(want, abs=1.0)
        assert q[-1] == spec["max"]


def test_decode_lengths_are_uniform_over_the_stated_ranges():
    m = _mix("decode")
    for key in ("prompt_len", "output_len"):
        lo, hi = m[key]["min"], m[key]["max"]
        assert traffic.quantiles(m[key], hi - lo + 1) == \
            list(range(lo, hi + 1))


def test_the_longest_request_is_greedy_and_a_quarter_are():
    for mix in ("decode", "open"):
        m = _mix(mix)
        reqs = _gen(m, 11, 1000)
        share = sum(r.greedy for r in reqs) / len(reqs)
        assert share == pytest.approx(m["greedy_share"], abs=0.02)
        assert all(0 <= t < 1000 for r in reqs for t in r.prompt)
        if m["loop"] == "open":
            longest = max(reqs, key=lambda r: r.prompt.size + r.max_new)
            assert longest.greedy
        else:
            # a quarter of the clients send greedy requests, every round
            for k in range(0, len(reqs), CLIENTS):
                assert sum(r.greedy for r in reqs[k:k + CLIENTS]) == \
                    CLIENTS // 4
