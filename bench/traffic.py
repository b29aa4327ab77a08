"""The one traffic generator: request sizes and arrival times from a mix file.

The draws follow ``src/repro/runtime/workload.py`` (lognormal lengths clipped
to bounds, Poisson or bursty arrivals), with two changes.  Time is in wall
seconds, not virtual decode steps.  And every distribution is drawn
*stratified*: the ``n`` values of a size are the distribution's quantiles at
``(i + 0.5) / n``, and the seed only permutes them (and draws the token ids).
So every seed offers the same work in another order, and a run-to-run spread
is not a difference in the work.

A mix file holds:

- ``loop``: ``"closed"`` (the queue never empties) or ``"open"`` (requests
  fall due on a schedule whatever the server does);
- open loop only: ``arrival`` (``"poisson"`` or ``"bursty"``), ``rate_per_s``
  (mean requests per second), ``burst`` (arrivals per burst), ``preroll_s``
  (seconds of schedule served before the window opens);
- ``prompt``: a list of parts whose lengths add up (e.g. image tiles plus a
  question), each a distribution;
- ``gen``: the distribution of tokens to generate;
- ``requests`` (closed loop): how many requests the queue holds in all.

A distribution is ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
``{"dist": "uniform", "min", "max"}`` (whole numbers, both ends in) or
``{"dist": "choice", "values", "weights"?}``.  A prompt part with
``"shared_pool": K`` takes its tokens from one of ``K`` sequences drawn once
per run (a shared system prompt or history): requests that draw the same
one share that part as a prefix, up to the shorter length.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass
class Draw:
    rid: int
    prompt: np.ndarray  # int32 token ids
    max_new: int
    due: float  # seconds after the schedule's origin (0 in a closed loop)


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def quantile_values(spec: Dict[str, Any], n: int) -> np.ndarray:
    """The ``n`` stratified values of one length distribution (unpermuted)."""
    u = _grid(n)
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    elif kind == "uniform":
        v = spec["min"] + np.floor(u * (spec["max"] - spec["min"] + 1))
    elif kind == "choice":
        w = np.asarray(spec.get("weights", [1.0] * len(spec["values"])),
                       float)
        idx = np.searchsorted(np.cumsum(w) / w.sum(), u)
        v = np.asarray(spec["values"])[idx]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo = spec.get("min", -math.inf)
    hi = spec.get("max", math.inf)
    return np.clip(v, lo, hi).astype(np.int64)


def length_bounds(spec: Dict[str, Any]):
    """(least, largest) value a distribution can give."""
    if spec["dist"] == "choice":
        return min(spec["values"]), max(spec["values"])
    return spec["min"], spec["max"]


def prompt_bounds(mix: Dict[str, Any]):
    parts = [length_bounds(p) for p in mix["prompt"]]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)


def max_positions(mix: Dict[str, Any]) -> int:
    """The most K/V positions one request of the mix can hold."""
    return prompt_bounds(mix)[1] + length_bounds(mix["gen"])[1]


def request_count(mix: Dict[str, Any], seconds: float) -> int:
    if mix["loop"] == "closed":
        return int(mix["requests"])
    span = mix["preroll_s"] + seconds
    burst = int(mix.get("burst", 1))
    # 10% more than the span holds on average: the schedule outlasts the
    # window whatever order the seed gives the gaps
    groups = math.ceil(1.1 * mix["rate_per_s"] * span / burst) + 1
    return groups * burst


def arrival_times(mix: Dict[str, Any], n: int, rng) -> np.ndarray:
    """Due times in seconds from the origin: exponential gaps at the mix's
    mean rate (stratified, permuted), one gap before each burst."""
    if mix["loop"] == "closed":
        return np.zeros(n)
    burst = int(mix.get("burst", 1))
    if mix["arrival"] == "poisson" and burst != 1:
        raise ValueError("a poisson mix has burst 1")
    groups = -(-n // burst)
    mean_gap = burst / mix["rate_per_s"]
    gaps = rng.permutation(-mean_gap * np.log1p(-_grid(groups)))
    return np.repeat(np.cumsum(gaps), burst)[:n]


def generate(mix: Dict[str, Any], vocab: int, seed: int,
             seconds: float) -> List[Draw]:
    """The run's requests, in due order, entirely determined by ``seed``."""
    rng = np.random.default_rng(seed)
    n = request_count(mix, seconds)
    lens = [rng.permutation(quantile_values(p, n)) for p in mix["prompt"]]
    gen = rng.permutation(quantile_values(mix["gen"], n))
    due = arrival_times(mix, n, rng)
    pools = []
    for p in mix["prompt"]:
        k = p.get("shared_pool")
        if k is None:
            pools.append(None)
            continue
        seqs = rng.integers(0, vocab, (k, length_bounds(p)[1]))
        pools.append((seqs, rng.permutation(np.arange(n) % k)))
    draws = []
    for i in range(n):
        prompt = rng.integers(0, vocab, int(sum(ln[i] for ln in lens)),
                              dtype=np.int64).astype(np.int32)
        at = 0
        for j, pool in enumerate(pools):
            ln = int(lens[j][i])
            if pool is not None:
                prompt[at: at + ln] = pool[0][pool[1][i], :ln]
            at += ln
        draws.append(Draw(rid=i, prompt=prompt, max_new=int(gen[i]),
                          due=float(due[i])))
    return draws
