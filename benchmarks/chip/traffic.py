"""The one traffic generator: a mix file of parameters -> timed requests.

A mix (``traffic/<name>.json``) names an arrival process, a prompt-length
and an output-length distribution (``uniform`` over [min, max], or
``lognormal`` by its ``median`` or ``mean`` and ``sigma``, clipped to
[min, max]), and the lead-in before the measured window. The cell (``cells/<name>.json``) gives the rate. Every seed gets the
same work: the gaps between arrivals and the lengths are the quantiles of
their distributions at evenly spaced probabilities, and the seed draws only
their order and the prompt token ids. So two seeds differ in which request
meets which, not in how much there is to do.

Arrival process ``poisson``: the gaps are exponential with mean 1/rate (the
open-loop Poisson process of ``repro.workloads.arrivals.PoissonArrivals``,
sampled at its quantiles). Within the window the gaps are scaled so that
exactly ``round(rate * seconds)`` requests are due in it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np

# token ids below this are never drawn (0 pads chunks; keep ids clear of it)
MIN_TOKEN_ID = 2


@dataclass
class Item:
    """One request as the generator makes it: due time (seconds from the
    start of the session), prompt token ids, tokens to generate, and whether
    it is due inside the measured window."""

    rid: int
    due: float
    prompt: List[int]
    n_out: int
    counted: bool


def _quantile(dist: Dict, q: float) -> float:
    kind = dist["dist"]
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        return lo + q * (hi + 1 - lo) - 0.5
    if kind == "lognormal":
        sigma = dist["sigma"]
        # given by its median, or by its mean (median = mean / e^(sigma^2 / 2))
        med = dist["median"] if "median" in dist else dist["mean"] * math.exp(-sigma * sigma / 2)
        return math.exp(math.log(med) + sigma * NormalDist().inv_cdf(q))
    raise ValueError(f"unknown length distribution {kind!r}")


def lengths(dist: Dict, n: int) -> List[int]:
    """n lengths at the distribution's quantiles (i + 0.5) / n, clipped to
    [min, max] and rounded."""
    lo, hi = dist["min"], dist["max"]
    return [
        int(min(hi, max(lo, round(_quantile(dist, (i + 0.5) / n))))) for i in range(n)
    ]


def gaps(n: int, rate: float) -> np.ndarray:
    """n exponential gaps of mean 1/rate at their quantiles (i + 0.5) / n."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def generate(mix: Dict, rate: float, seconds: float, tail_s: float, seed: int,
             vocab: int) -> List[Item]:
    """Requests for a lead-in of ``mix['lead_in_s']``, a window of `seconds`
    and `tail_s` after it, at `rate` requests per second."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    rng = np.random.default_rng(seed)
    lead = float(mix["lead_in_s"])
    out: List[Item] = []
    t0 = 0.0
    for span, counted in ((lead, False), (float(seconds), True), (float(tail_s), False)):
        n = max(1, round(rate * span))
        g = rng.permutation(gaps(n, rate))
        if counted:
            g *= span / g.sum()
        due = t0 + np.cumsum(g) - (g[0] if counted else 0.0)
        ins = rng.permutation(lengths(mix["prompt"], n))
        outs = rng.permutation(lengths(mix["output"], n))
        for d, n_in, n_out in zip(due, ins, outs, strict=True):
            if not counted and d >= t0 + span:
                continue
            prompt = rng.integers(MIN_TOKEN_ID, vocab, int(n_in)).tolist()
            out.append(Item(len(out), float(d), prompt, int(n_out), counted))
        t0 += span
    return out
