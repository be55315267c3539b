"""Traffic: one general generator, driven by a mix's data file.

A mix (``traffic/<name>.json``) states:

    loop        "open": independent users at ``rate_per_s`` requests/s,
                Poisson on the host clock; "closed": ``clients`` callers,
                each sending its next request when the last one finishes.
    prompt      length distribution of prompts (tokens)
    output      distribution of the generation budget (max_new_tokens)
    check       how many finished requests the correctness check samples
    source      the published length statistics the distributions follow

A distribution is ``{"dist": "lognormal", "mean", "sigma", "clip": [lo,
hi]}``: a log-normal with that mean and log-space standard deviation,
clipped to [lo, hi].

Every seed gets the same work in the same order: prompt lengths, output
budgets and inter-arrival gaps are the distributions' quantiles at
(i + 0.5) / N, each put in one fixed order (``ORDER_SEED``), and the seed
draws only the token ids. The schedule is the same from run to run, so a
tail such as a p95 over a few tens of requests does not move with the
order in which the seed would have sent them.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List

import numpy as np

CLOSED_LOOP_DEPTH = 32        # requests queued per client in a closed loop
ORDER_SEED = 0x0DE2           # the one order of every mix's sizes and gaps


@dataclass
class Req:
    prompt: np.ndarray
    max_new: int
    due: float = 0.0            # seconds after the window opens (open loop)


@dataclass
class Schedule:
    loop: str
    requests: List[Req] = field(default_factory=list)   # open: by due time
    clients: List[List[Req]] = field(default_factory=list)  # closed


def quantile_lengths(spec: dict, u: np.ndarray) -> np.ndarray:
    """Lengths at quantiles ``u`` (each in (0, 1)) of the distribution."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    s = spec["sigma"]
    mu = math.log(spec["mean"]) - s * s / 2
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    lo, hi = spec["clip"]
    return np.clip(np.round(np.exp(mu + s * z)), lo, hi).astype(np.int64)


def build(mix: dict, seed: int, seconds: float, vocab: int) -> Schedule:
    """The requests of one run of ``mix``: the same sizes in the same order
    for every seed, with the seed's token ids."""
    if mix["loop"] == "open":
        n = max(1, int(round(mix["rate_per_s"] * seconds)))
    elif mix["loop"] == "closed":
        n = mix["clients"] * CLOSED_LOOP_DEPTH
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    order = np.random.default_rng(ORDER_SEED)
    strata = (np.arange(n) + 0.5) / n
    plen = quantile_lengths(mix["prompt"], order.permutation(strata))
    mnew = quantile_lengths(mix["output"], order.permutation(strata))
    rng = np.random.default_rng(seed & (2**64 - 1))
    reqs = [Req(rng.integers(0, vocab, int(p)).astype(np.int32), int(m))
            for p, m in zip(plen, mnew)]
    if mix["loop"] == "open":
        gaps = -np.log1p(-order.permutation(strata)) / mix["rate_per_s"]
        gaps *= seconds / gaps.sum()          # the last gap ends the window
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        for r, t in zip(reqs, due):
            r.due = float(t)
        return Schedule("open", requests=reqs)
    c = mix["clients"]
    return Schedule("closed", clients=[reqs[k::c] for k in range(c)])


def longest_prompt(mix: dict) -> int:
    """The longest prompt the mix can send (warm-up covers its chunks)."""
    return int(quantile_lengths(mix["prompt"], np.array([1 - 1e-12]))[0])
