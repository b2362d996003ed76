"""Multisets from distributions: values at the quantiles (i + 1/2)/n, so that
every run of a cell draws the very same set and ``--seed`` only orders it."""

from __future__ import annotations

import numpy as np


def quantile_values(spec: dict, n: int) -> np.ndarray:
    """``n`` values at the quantiles (i + 1/2)/n of ``spec``'s distribution,
    truncated to [min, max] (the quantiles are taken of the truncated
    distribution, so no mass piles up at the ends).

    spec: {"dist": "lognormal", "median", "sigma"} | {"dist": "gamma",
    "shape"} (unit mean) | {"dist": "uniform"} | {"dist": "fixed", "value"};
    optional "min", "max", "round_to"."""
    from scipy import stats

    kind = spec["dist"]
    if kind == "fixed":
        return np.full(n, spec["value"])
    if kind == "lognormal":
        d = stats.lognorm(s=spec["sigma"], scale=spec["median"])
    elif kind == "gamma":
        d = stats.gamma(a=spec["shape"], scale=1.0 / spec["shape"])
    elif kind == "uniform":
        d = stats.uniform(loc=spec["min"], scale=spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo = d.cdf(spec["min"]) if "min" in spec else 0.0
    hi = d.cdf(spec["max"]) if "max" in spec else 1.0
    q = lo + (np.arange(n) + 0.5) / n * (hi - lo)
    vals = d.ppf(q)
    if "round_to" in spec:
        r = spec["round_to"]
        vals = np.round(vals / r) * r
        if "min" in spec:
            vals = np.maximum(vals, spec["min"])
        if "max" in spec:
            vals = np.minimum(vals, spec["max"])
        vals = vals.astype(np.int64)
    return vals


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])
