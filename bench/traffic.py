"""The one traffic generator: a traffic file of parameters in, a request
schedule out, all from ``--seed``.

Every seed gets the same amount of work: the window holds exactly
``round(rate * seconds)`` requests, their seed counts are the same
multiset (the law's shares, rounded by largest remainder) in a seeded
order, and the arrival times are a Poisson process conditioned on that
count (sorted uniform times). Seeds draw which nodes they ask for.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Schedule:
    arrival_s: np.ndarray          # [n] offsets from the window start
    seeds: list[list[int]]         # [n] node ids per request


def rng_for(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), *map(ord, stream)])


def count_law(spec: dict) -> tuple[np.ndarray, np.ndarray]:
    """(values, probabilities) of the seeds-per-request law."""
    k = np.arange(spec["min"], spec["max"] + 1)
    if spec["law"] != "inverse":        # P(k) ~ 1/k
        raise ValueError(f"unknown seeds_per_request law {spec['law']!r}")
    p = 1.0 / k
    return k, p / p.sum()


def exact_counts(n: int, spec: dict) -> np.ndarray:
    """The ``n`` seed counts: each value's share of ``n`` rounded by
    largest remainder, so every seed asks for the same total."""
    k, p = count_law(spec)
    raw = n * p
    base = np.floor(raw).astype(np.int64)
    extra = n - int(base.sum())
    base[np.argsort(-(raw - base), kind="stable")[:extra]] += 1
    return np.repeat(k, base)


class Popularity:
    """Which nodes requests ask for: a law over node ranks, mapped to
    node ids through a seeded permutation."""

    def __init__(self, spec: dict, n_nodes: int, rng: np.random.Generator):
        ranks = np.arange(1, n_nodes + 1, dtype=np.float64)
        if spec["law"] != "zipf":
            raise ValueError(f"unknown popularity law {spec['law']!r}")
        p = ranks ** -float(spec["s"])
        self.cdf = np.cumsum(p / p.sum())
        self.perm = rng.permutation(n_nodes)

    def draw(self, rng: np.random.Generator, k: int) -> list[int]:
        """``k`` distinct node ids."""
        out: list[int] = []
        while len(out) < k:
            r = np.searchsorted(self.cdf, rng.random(2 * k), side="right")
            for v in self.perm[np.minimum(r, len(self.perm) - 1)]:
                if int(v) not in out:
                    out.append(int(v))
                    if len(out) == k:
                        break
        return out


def schedule(traffic: dict, n_nodes: int, seed: int, seconds: float,
             stream: str = "window") -> Schedule:
    """The requests of one window (``stream="window"``) or of the warm-up
    (``stream="warmup"``, ``traffic["warmup_requests"]`` of them, all due
    at once)."""
    rng = rng_for(seed, stream)
    pop = Popularity(traffic["popularity"], n_nodes, rng_for(seed, "nodes"))
    if stream == "warmup":
        n = int(traffic["warmup_requests"])
        arrival = np.zeros(n)
    else:
        arr = traffic["arrivals"]
        if arr["process"] != "poisson":
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        n = int(round(arr["rate_per_s"] * seconds))
        arrival = np.sort(rng.uniform(0.0, seconds, n))
    counts = rng.permutation(exact_counts(n, traffic["seeds_per_request"]))
    return Schedule(arrival, [pop.draw(rng, int(c)) for c in counts])
