"""End-to-end metrics from a host-clock timeline of token deliveries.

Each request carries the time it fell due (open loop) and its deliveries:
``(t, n, a)`` says ``n`` of its tokens reached the host at ``t`` from the
engine call that began at ``a`` (a prefill's first token, or a decode
chunk's block).  The window is ``[t0, t1]``.

- ``tok_s``: tokens generated inside the window over its length; a call's
  tokens are spread evenly over the call that produced them, so a chunk
  that straddles an end of the window counts for its share of the window
  (a rate that steps with whole chunks would swing by one chunk's tokens).
- ``ttft``: for every request due inside the window, its first delivery less
  its due time; one still waiting when the window closes counts with its
  wait so far, ``t1 - due``, so a stall cannot hide.
- ``tpot``: for every request with two or more deliveries inside the window,
  (last - first delivery) over the tokens delivered after the first: the
  stream rate a listener feels, whatever the chunking.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Stamp:
    rid: int
    due: Optional[float] = None  # host clock; None in a closed loop
    deliveries: List[Tuple[float, int, float]] = dataclasses.field(
        default_factory=list)


def p90(values) -> Optional[float]:
    return float(np.percentile(values, 90)) if len(values) else None


def tok_s(stamps: List[Stamp], t0: float, t1: float) -> float:
    n = 0.0
    for s in stamps:
        for t, k, a in s.deliveries:
            if t > a:
                n += k * max(0.0, min(t, t1) - max(a, t0)) / (t - a)
            elif t0 <= t <= t1:
                n += k
    return n / (t1 - t0)


def ttfts(stamps: List[Stamp], t0: float, t1: float) -> List[float]:
    out = []
    for s in stamps:
        if s.due is None or not (t0 <= s.due < t1):
            continue
        first = s.deliveries[0][0] if s.deliveries else None
        out.append((first if first is not None and first <= t1 else t1)
                   - s.due)
    return out


def tpots(stamps: List[Stamp], t0: float, t1: float) -> List[float]:
    out = []
    for s in stamps:
        inside = [(t, k) for t, k, _ in s.deliveries if t0 <= t <= t1]
        if len(inside) < 2:
            continue
        after = sum(k for _, k in inside[1:])
        out.append((inside[-1][0] - inside[0][0]) / after)
    return out


def summarize(stamps: List[Stamp], t0: float, t1: float) -> Dict[str, float]:
    """Every end-to-end number of a window, with its sample counts."""
    tt, tp = ttfts(stamps, t0, t1), tpots(stamps, t0, t1)
    return {
        "tok_s": tok_s(stamps, t0, t1),
        "ttft_p90_ms": None if not tt else 1e3 * p90(tt),
        "tpot_p90_ms": None if not tp else 1e3 * p90(tp),
        "ttft_p50_ms": None if not tt else 1e3 * float(np.median(tt)),
        "tpot_p50_ms": None if not tp else 1e3 * float(np.median(tp)),
        "ttft_samples": len(tt),
        "tpot_samples": len(tp),
    }
