"""Open-loop serving traffic from a mix file and a seed.

A mix file (``bench/traffic/<mix>.json``) of ``"kind": "serve"`` gives:

* ``rate``: mean arrivals per second (Poisson);
* ``prompt`` / ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal length, clipped;
* ``greedy_share``: the share of requests decoded greedily; the rest sample
  at ``temperature``;
* ``lead_in_s``: seconds of the same traffic before the window opens.

Every seed gets the same multiset of lengths, gaps and decoding modes
(quantiles of the distributions) in the lead-in and in the window apart,
in an order drawn from the seed that keeps every stretch of 16 requests
balanced across the distribution, and its own prompt tokens: seeds change
the order of the work, not its amount.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Planned:
    rid: int
    due: float               # seconds after the lead-in started
    prompt: List[int]
    max_new_tokens: int
    greedy: bool
    sample_seed: int


STRATA = 16


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def balanced_order(values: np.ndarray, rng) -> np.ndarray:
    """``values`` in an order drawn from ``rng`` in which every run of
    ``STRATA`` consecutive entries holds one value from each of the
    ``STRATA`` quantile bands: the whole multiset is the same for every
    seed, and so is the mix of any stretch of it."""
    v = np.sort(np.asarray(values))
    bands = [rng.permutation(b) for b in np.array_split(v, STRATA)]
    out = []
    for j in range(max(len(b) for b in bands)):
        block = [b[j] for b in bands if j < len(b)]
        out.extend(rng.permutation(block))
    return np.asarray(out, dtype=v.dtype)


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def arrival_times(rate: float, start: float, length: float, rng
                  ) -> np.ndarray:
    """``round(rate * length)`` due times in ``[start, start + length)``:
    exponential gaps at fixed quantiles, in a balanced order, scaled to
    the segment."""
    n = max(1, int(round(rate * length)))
    gaps = balanced_order(-np.log(1.0 - _quantiles(n)), rng)
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return start + t * (length / gaps.sum())


def _segment(mix: dict, start: float, length: float, rng) -> List[tuple]:
    due = arrival_times(mix["rate"], start, length, rng)
    n = len(due)
    prompts = balanced_order(lognormal_lengths(mix["prompt"], n), rng)
    outputs = balanced_order(lognormal_lengths(mix["output"], n), rng)
    greedy = balanced_order(
        np.arange(n) < int(math.ceil(mix.get("greedy_share", 1.0) * n)), rng)
    return list(zip(due, prompts, outputs, greedy))


def plan(mix: dict, seed: int, seconds: float, vocab: int
         ) -> List[Planned]:
    """The requests due from the start of the lead-in to the window's
    end, in due order. The lead-in and the window are planned apart, so
    the window's own work is the same for every seed."""
    rng = np.random.default_rng(seed)
    lead = window_start(mix)
    rows = (_segment(mix, 0.0, lead, rng) if lead > 0 else []) + \
        _segment(mix, lead, seconds, rng)
    out = []
    for i, (due, plen, olen, greedy) in enumerate(rows):
        toks = rng.integers(1, vocab, int(plen)).tolist()
        out.append(Planned(rid=i, due=float(due), prompt=toks,
                           max_new_tokens=int(olen), greedy=bool(greedy),
                           sample_seed=int(rng.integers(0, 2 ** 31))))
    return out


def window_start(mix: dict) -> float:
    return float(mix.get("lead_in_s", 0.0))
