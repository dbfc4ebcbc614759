"""Length and arrival draws that give every seed the same work.

Lengths: the n values are the distribution's quantiles at (k + 0.5) / n
and a seed only orders them, so two seeds send the same work in another
order and the spread between runs measures the system, not the draw.
The closed loop lays them out by rounds (`stratified`), the same for
every seed: each round of its callers holds one value from each band of
ranks, and the seed orders the callers' sequences. Arrivals of the open
loop are a Poisson process with its count fixed (`poisson_arrivals`)."""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n values of the distribution `spec` (sorted), rounded to whole
    numbers and clipped to [min, max] where the spec gives them.

    spec: {"dist": "lognormal", "median", "sigma"} or
          {"dist": "uniform", "min", "max"}, plus optional "min"/"max"."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        vals = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    vals = np.rint(vals).astype(np.int64)
    return np.clip(vals, spec.get("min", 1), spec.get("max", vals.max()))


def poisson_arrivals(n: int, seconds: float,
                     rng: np.random.Generator) -> np.ndarray:
    """The arrival times of a Poisson process over [0, seconds) given
    that it has n arrivals there: n independent uniform times, sorted.
    The gaps are the process's own, bursts and lulls included; only the
    count is fixed, so that every seed sends the same work."""
    return np.sort(rng.uniform(0.0, seconds, n))


def stratified(values, block: int, rng: np.random.Generator) -> np.ndarray:
    """`values` in an order drawn by `rng` in which the entries
    [i * block, (i + 1) * block) hold one value from each of `block`
    bands of ranks (the last run may be shorter)."""
    bands = [rng.permutation(b)
             for b in np.array_split(np.sort(np.asarray(values)), block)]
    out = []
    for i in range(max(len(b) for b in bands)):
        run = [b[i] for b in bands if i < len(b)]
        out.extend(rng.permutation(run))
    return np.asarray(out)


def rng_streams(seed: int, k: int) -> list[np.random.Generator]:
    """k independent generators from one seed of any size."""
    return [np.random.default_rng(s)
            for s in np.random.SeedSequence(seed).spawn(k)]
