"""Length and gap sets shared by the loop kinds.

Every seed gets the same requests and gaps, in another order: each
length set is the distribution's quantiles at ``(i + 0.5) / n``, prompt
and output lengths are paired the same way for every seed, and only the
permutation and the prompt tokens come from the seed.  So two seeds
offer the same work, and their runs differ by arrival order alone.
"""

from __future__ import annotations

import numpy as np
from scipy import stats


def rng(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...); any whole seed."""
    return np.random.default_rng([int(seed) % 2 ** 64, *stream])


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths spread over the distribution ``spec`` (clipped to
    ``[lo, hi]``, whole numbers), in ascending order."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "uniform":
        x = spec["lo"] + u * (spec["hi"] - spec["lo"] + 1)
    elif kind == "lognormal":
        x = stats.lognorm.ppf(u, spec["sigma"], scale=spec["median"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), spec["lo"], spec["hi"]).astype(np.int64)


def requests(traffic: dict, n: int) -> "tuple[np.ndarray, np.ndarray]":
    """``n`` (prompt, output) length pairs: the two quantile sets joined
    by one fixed pairing, the same for every seed, so that a seed orders
    the requests and never changes what they are."""
    p = quantiles(traffic["prompt"], n)
    o = quantiles(traffic["output"], n)
    return p, o[np.random.default_rng(0).permutation(n)]


def gaps(spec: dict, n: int, rate: float) -> np.ndarray:
    """``n`` inter-arrival gaps (seconds) with mean exactly ``1/rate``,
    spread over a Poisson process's exponential gaps or over Gamma gaps
    of coefficient of variation ``cv``; ascending."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "poisson":
        g = stats.expon.ppf(u)
    elif kind == "gamma":
        shape = 1.0 / spec["cv"] ** 2
        g = stats.gamma.ppf(u, shape)
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    return g / g.mean() / rate


def prompt(r: np.random.Generator, length: int, vocab: int) -> np.ndarray:
    return r.integers(0, vocab, int(length)).astype(np.int32)
