"""The seeded traffic generator: same seed, same schedule; every seed the
same sizes; lengths inside their clips with medians where the mix says."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import cell  # noqa: E402
import traffic  # noqa: E402

# every mix named in BENCHMARK.json, and those kept for a later cell
MIXES = sorted({w["traffic"] for w in cell.benchmark()["workloads"]}
               | {p.stem for p in traffic.TRAFFIC_DIR.glob("*.json")})
SEED = 2**31 + 12345          # seeds may exceed 32 signed bits


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = traffic.load_mix(name)
    a = traffic.schedule(mix, SEED, 30, 32000)
    b = traffic.schedule(mix, SEED, 30, 32000)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)
    c = traffic.schedule(mix, SEED + 1, 30, 32000)
    if mix.get("order", "seed") == "seed":
        assert [len(x.prompt) for x in a] != [len(x.prompt) for x in c]
    else:
        # one order for every seed: the seed picks the prompt tokens
        assert [(len(x.prompt), x.max_new_tokens, x.due_s) for x in a] == \
            [(len(x.prompt), x.max_new_tokens, x.due_s) for x in c]
        assert any(not np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_serves_the_same_sizes(name):
    mix = traffic.load_mix(name)
    a = traffic.schedule(mix, SEED, 30, 32000)
    b = traffic.schedule(mix, 7, 30, 32000)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt)
                                                     for x in b)
    assert sorted(x.max_new_tokens for x in a) == sorted(x.max_new_tokens
                                                        for x in b)
    assert a[-1].due_s == pytest.approx(b[-1].due_s)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_within_clips(name):
    mix = traffic.load_mix(name)
    s = traffic.schedule(mix, SEED, 30, 32000)
    for key, got in (("prompt", [len(x.prompt) for x in s]),
                     ("output", [x.max_new_tokens for x in s])):
        d = mix[key]
        assert d["min"] <= min(got) and max(got) <= d["max"]
        mid = d["median"] if d["dist"] == "lognormal" else \
            (d["min"] + d["max"]) / 2
        assert abs(np.median(got) - mid) <= 0.02 * mid + 1
    assert all(0 <= int(x.prompt.max()) < 32000 for x in s)


def test_poisson_rate_and_backlog():
    mix = {"arrival": "poisson", "rate_per_s": 3.0, "preroll_s": 5,
           "prompt": {"dist": "uniform", "min": 4, "max": 8},
           "output": {"dist": "uniform", "min": 4, "max": 8}}
    s = traffic.schedule(mix, SEED, 60, 100)
    due = np.array([x.due_s for x in s])
    assert due[0] > 0 and np.all(np.diff(due) >= 0)
    # the schedule spans preroll + window with slack at the stated rate
    assert due[-1] > 65
    assert len(s) / due[-1] == pytest.approx(3.0, rel=0.05)
    back = dict(mix, arrival="backlog", requests=17)
    assert [x.due_s for x in traffic.schedule(back, SEED, 60, 100)] == \
        [0.0] * 17


def test_blocks_hold_the_same_pairs():
    mix = {"arrival": "backlog", "requests": 50, "block": 8, "preroll_s": 5,
           "prompt": {"dist": "lognormal", "median": 64, "sigma": 0.8,
                      "min": 8, "max": 512},
           "output": {"dist": "uniform", "min": 4, "max": 300}}
    a = traffic.schedule(mix, SEED, 60, 100)
    b = traffic.schedule(mix, 7, 60, 100)
    assert len(a) == len(b) == 56          # rounded up to whole blocks
    pairs = [[(len(x.prompt), x.max_new_tokens) for x in s[i:i + 8]]
             for s in (a, b) for i in range(0, 56, 8)]
    # every block of either seed is one set of pairs, in its own order
    assert all(sorted(p) == sorted(pairs[0]) for p in pairs)
    assert len(set(pairs[0])) == 8
    assert pairs[0] != pairs[7] or pairs[1] != pairs[8]


@pytest.mark.parametrize("arrival", ["backlog", "poisson"])
def test_a_fixed_order_is_the_same_work_for_every_seed(arrival):
    mix = {"arrival": arrival, "requests": 48, "rate_per_s": 2.0,
           "preroll_s": 5, "block": 8, "order": "fixed",
           "prompt": {"dist": "lognormal", "median": 64, "sigma": 0.8,
                      "min": 8, "max": 512},
           "output": {"dist": "uniform", "min": 4, "max": 300}}
    a, b = (traffic.schedule(mix, s, 20, 100) for s in (SEED, 7))
    assert [(len(x.prompt), x.max_new_tokens, x.due_s) for x in a] == \
        [(len(x.prompt), x.max_new_tokens, x.due_s) for x in b]
    seeded = traffic.schedule(dict(mix, order="seed"), SEED, 20, 100)
    assert sorted(len(x.prompt) for x in seeded) == \
        sorted(len(x.prompt) for x in a)
    with pytest.raises(ValueError):
        traffic.schedule(dict(mix, order="sorted"), SEED, 20, 100)
