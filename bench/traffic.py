"""Seeded traffic: one general generator driven by a mix file.

A mix (``bench/traffic/<mix>.json``) gives the arrival process, the length
distributions and the pre-roll:

  arrival     "poisson" (open loop at ``rate_per_s``, in wall-clock seconds)
              or "backlog" (``requests`` requests, all due when the pre-roll
              starts: an offline batch larger than a run can finish)
  preroll_s   seconds of traffic before the measured window opens, so the
              batch is in steady state when the window starts
  prompt,     {"dist": "lognormal", "median", "sigma", "min", "max"} or
  output      {"dist": "uniform", "min", "max"}; lengths are clipped to
              [min, max]
  greedy      every request decodes greedily (the comparison that decides
              ``correct`` holds only for greedy tokens)
  block       optional: lengths come in blocks of this many requests, each
              block the same set of (prompt, output) pairs
  order       "seed" (the default): the seed orders the lengths; "fixed":
              one order for every seed, which then picks only the prompt
              tokens

Lengths and inter-arrival gaps are stratified: the i-th of n draws is the
distribution's quantile at (i + 1/2) / n.  So every seed serves the same
multiset of sizes and gaps, and the seed only orders them and picks the
prompt tokens.  Runs with different seeds then do the same work, and their
spread is the system's, not the draw's.

Where a run serves only the head of the schedule, as it does of a backlog,
the whole multiset is not enough: the head's sizes would still differ from
seed to seed.  ``block`` makes every block of requests hold the same pairs,
so any whole number of blocks is the same set of sizes.  The pairing of
prompt and output quantiles within a block is fixed, not drawn from the
seed.  Where even the order inside a block changes the work (which request
takes a slot as another frees it sets the decode bucket and the prefill
ticks of a backlog), ``order: fixed`` keeps one order for every seed.
"""
from __future__ import annotations

import dataclasses
import json
import math
import statistics
from pathlib import Path
from typing import List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
# requests past the window's end that a Poisson schedule carries, as a share
# of the expected count: the window never runs out of arrivals
_SLACK = 0.25


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float          # offset from the pre-roll's start
    prompt: np.ndarray    # int32 token ids
    max_new_tokens: int


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    mix = json.loads(path.read_text())
    if mix["arrival"] not in ("poisson", "backlog"):
        raise ValueError(f"{name}: unknown arrival {mix['arrival']!r}")
    return mix


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """The n stratified draws of a length distribution, clipped, as ints."""
    p = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in p])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + p * (dist["max"] + 1 - dist["min"])
        x = np.floor(x)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def request_count(mix: dict, seconds: float) -> int:
    if mix["arrival"] == "backlog":
        n = int(mix["requests"])
    else:
        span = mix["preroll_s"] + seconds
        n = int(math.ceil(mix["rate_per_s"] * span * (1 + _SLACK))) + 8
    b = int(mix.get("block", 1))
    return -(-n // b) * b        # whole blocks only


def _lengths(mix: dict, n: int, rng) -> tuple:
    """Prompt and output lengths of n requests, in the mix's order."""
    order = mix.get("order", "seed")
    if order not in ("seed", "fixed"):
        raise ValueError(f"unknown order {order!r}")
    fixed = np.random.default_rng(0)      # the same for every seed
    orng = rng if order == "seed" else fixed
    if "block" not in mix:
        return (orng.permutation(_quantiles(mix["prompt"], n)),
                orng.permutation(_quantiles(mix["output"], n)))
    b = int(mix["block"])
    prompts = _quantiles(mix["prompt"], b)
    outputs = _quantiles(mix["output"], b)[fixed.permutation(b)]
    idx = np.concatenate([orng.permutation(b) for _ in range(n // b)])
    return prompts[idx], outputs[idx]


def schedule(mix: dict, seed: int, seconds: float,
             vocab: int) -> List[Arrival]:
    """Arrivals in due order, from the mix and the seed alone."""
    n = request_count(mix, seconds)
    rng = np.random.default_rng(seed)
    prompts, outputs = _lengths(mix, n, rng)
    if mix["arrival"] == "backlog":
        due = np.zeros(n)
    else:
        u = (np.arange(n) + 0.5) / n
        grng = (rng if mix.get("order", "seed") == "seed"
                else np.random.default_rng(1))
        gaps = grng.permutation(-np.log1p(-u) / mix["rate_per_s"])
        due = np.cumsum(gaps)
    return [Arrival(float(d), rng.integers(0, vocab, int(p), dtype=np.int32),
                    int(o))
            for d, p, o in zip(due, prompts, outputs)]
