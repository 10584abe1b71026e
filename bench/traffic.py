"""The one request generator that every serving mix's data file drives.

A mix (``bench/traffic/<name>.json``) gives an arrival process and a
length distribution for prompts and outputs.  The same seed gives the same
requests.  Different seeds get the same set of sizes and the same set of
inter-arrival gaps, in another order, and other token ids: the seed moves
which request comes when, not how much work the window holds.  The order is
spread: every ``GROUP`` consecutive requests hold one value of each of
``GROUP`` strata of the sorted set, so every stretch of the window carries
the same mix of work, whatever the seed.

Lengths: ``{"dist": "lognormal", "median": m, "sigma": s, "min": a,
"max": b}`` (the set is the distribution's quantiles at (i + 1/2) / n,
clipped to [a, b]) or ``{"dist": "fixed", "value": v}``.

Arrivals: open loop, ``rate`` requests per second over the window
(``n = round(rate * seconds)``), gaps at the exponential distribution's
quantiles.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


GROUP = 10   # requests in a group that holds one value of each stratum


@dataclasses.dataclass
class Spec:
    prompt: np.ndarray        # (prompt_len,) int32
    max_tokens: int
    due_s: float              # when it is due to be sent, window-relative


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_set(n: int, spec: dict) -> np.ndarray:
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def spread_order(rs: np.random.RandomState, values) -> np.ndarray:
    """``values`` in an order drawn from ``rs`` in which every ``GROUP``
    consecutive ones hold one value of each of ``GROUP`` strata of the
    sorted values."""
    v = np.sort(np.asarray(values))
    strata = [rs.permutation(s)
              for s in np.array_split(np.arange(len(v)), GROUP)]
    order = []
    for j in range(len(strata[0])):
        order.extend(rs.permutation([s[j] for s in strata if j < len(s)]))
    return v[np.asarray(order, np.int64)]


def arrival_times(rs: np.random.RandomState, seconds: float,
                  spec: dict) -> np.ndarray:
    n = max(1, int(round(spec["rate"] * seconds)))
    gaps = -np.log1p(-_quantiles(n)) / spec["rate"]
    t = np.cumsum(spread_order(rs, gaps))
    t *= seconds * (1.0 - 0.5 / n) / t[-1]   # the last one falls in the window
    return t


def requests(seed: int, seconds: float, mix: dict, vocab: int) -> List[Spec]:
    rs = np.random.RandomState(seed % 2 ** 32)
    due = arrival_times(rs, seconds, mix["arrivals"])
    n = len(due)
    plens = spread_order(rs, length_set(n, mix["prompt"]))
    outs = spread_order(rs, length_set(n, mix["output"]))
    return [Spec(prompt=rs.randint(0, vocab, size=int(p)).astype(np.int32),
                 max_tokens=int(o), due_s=float(t))
            for p, o, t in zip(plens, outs, due)]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the values at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])
