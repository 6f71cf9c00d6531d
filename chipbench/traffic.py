"""The one traffic generator: a mix's parameters and a seed in, a
schedule out.

Every seed gets the same multiset of request sizes and inter-arrival gaps
(stratified quantiles of the mix's distributions), in its own order, so
seeds change which request comes when, never how much work a run holds.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float       # offset from the window's start
    windows: int       # 30-s audio windows, the request's batch
    slot: int          # which held input of that size it reads


def rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one purpose (``stream``) of one seed; any
    non-negative integer seed, however large."""
    return np.random.default_rng([stream, seed])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def request_windows(mix: Dict, n: int) -> np.ndarray:
    """Windows per request for ``n`` requests: lognormal audio durations
    at stratified quantiles, cut into ``window_s`` windows."""
    dur = mix["duration_s"]
    if dur["dist"] != "lognormal":
        raise ValueError(f"unknown duration distribution {dur['dist']!r}")
    z = np.array([statistics.NormalDist().inv_cdf(u) for u in _quantiles(n)])
    seconds = np.exp(math.log(dur["median"]) + dur["sigma"] * z)
    return np.clip(np.ceil(seconds / mix["window_s"]), 1,
                   mix["max_windows"]).astype(int)


def open_loop_schedule(mix: Dict, seed: int, seconds: float
                       ) -> List[Request]:
    """Requests due in ``[0, seconds)`` at the mix's mean rate: exactly
    ``round(rate * seconds)`` of them whatever the seed."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    n = max(1, round(mix["rate_per_s"] * seconds))
    gaps = -np.log1p(-_quantiles(n))
    gaps *= seconds / gaps.sum()
    g = rng(seed, 1)
    gaps = g.permutation(gaps)
    windows = g.permutation(request_windows(mix, n))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    held = mix["inputs_held"]
    return [Request(i, float(due[i]), int(windows[i]), i % held)
            for i in range(n)]


def checked_sample(schedule: List[Request], k: int, seed: int) -> List[int]:
    """Indices of the requests whose answers are compared: ``k`` drawn
    from the seed, always with a longest one among them."""
    g = rng(seed, 2)
    k = min(k, len(schedule))
    picked = set(g.choice(len(schedule), size=k, replace=False).tolist())
    longest = max(schedule, key=lambda r: (r.windows, -r.index)).index
    if longest not in picked:
        picked.discard(max(picked))
        picked.add(longest)
    return sorted(picked)
