"""Open-loop serving traffic at a fixed rate: one fixed schedule of arrivals,
prompt and output lengths and tiers, the same for every seed.

The schedule is not a random draw. Arrival gaps sit at evenly spaced
quantiles of the exponential distribution (the gaps of a Poisson process at
the rate), scaled so the arrivals span the window; sizes sit at evenly spaced
quantiles of their distributions, tiers at their shares. The mix's
``schedule_seed`` puts them in one order, and the run's seed draws only the
prompt tokens (and, elsewhere, the weights). A window holds a few dozen
requests at most, and with a random draw per seed the sizes and order alone
moved a run's numbers by tens of percent; so every seed offers the same work
in the same order, and two runs differ by the system, not by the draw.

The mix file (``bench/traffic/<mix>.json``) names this generator under
``"generator"`` and gives:

- ``prompt`` and ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}`` (whole tokens,
  both ends included);
- ``tiers``: ``{"keeps": [...], "shares": [...]}``, the HPA keep ratio of
  each tier (largest first) and the share of requests that ask for it;
- ``schedule_seed``: the seed of the order of arrivals.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Arrival:
    due_s: float          # seconds after the window opens
    prompt: list[int]
    max_new: int          # the request emits exactly this many tokens
    tier: int             # index into the mix's keeps (0 = largest)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` whole lengths at evenly spaced quantiles of ``spec``."""
    q = _quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        x = lo + q * (hi + 1 - lo)
        x = np.floor(x)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def tier_counts(shares: list[float], n: int) -> list[int]:
    """Requests per tier: shares of ``n`` by largest remainder."""
    raw = np.asarray(shares, np.float64) / float(sum(shares)) * n
    counts = np.floor(raw).astype(np.int64)
    for i in np.argsort(-(raw - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def generate(mix: dict, *, rate_per_s: float, seconds: float, seed: int,
             vocab: int) -> list[Arrival]:
    """The arrivals due in a window of ``seconds`` at ``rate_per_s``."""
    n = max(1, int(round(rate_per_s * seconds)))
    order = np.random.default_rng(int(mix["schedule_seed"]))
    rng = np.random.default_rng(seed)
    # exponential gaps at evenly spaced quantiles, scaled so the n arrivals
    # span the window exactly: the first is due at 0, none at or past its end
    gaps = -np.log1p(-_quantiles(n))
    gaps = gaps / gaps.sum() * seconds
    gaps = order.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plens = order.permutation(lengths(mix["prompt"], n))
    olens = order.permutation(lengths(mix["output"], n))
    tiers = np.repeat(np.arange(len(mix["tiers"]["keeps"])),
                      tier_counts(mix["tiers"]["shares"], n))
    tiers = order.permutation(tiers)
    return [
        Arrival(float(due[i]), rng.integers(1, vocab, size=int(plens[i])).tolist(),
                int(olens[i]), int(tiers[i]))
        for i in range(n)
    ]
