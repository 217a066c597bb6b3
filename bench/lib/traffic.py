"""The one general traffic generator: a mix's parameters in, a request
schedule out.

A mix (the ``traffic`` object of a cell file) gives the arrival process, its
rate, and the distributions of prompt and output length.  Every seed gets the
SAME multiset of inter-arrival gaps and of (prompt, output) lengths - the
quantile points of the stated distributions - in another order, so two seeds
offer the same work and differ only in how it falls; the token ids are drawn
uniformly over the vocabulary from the seed.

``arrivals``: ``"poisson"`` (exponential gaps) or ``"gamma"`` with a
coefficient of variation ``cv`` (bursty for cv > 1; cv = 1 is poisson).
Lengths: ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max":
b}`` or ``{"dist": "fixed", "value": v}`` or ``{"dist": "uniform", "min": a,
"max": b}``.  ``shared_prefix``: ``{"tokens": n, "groups": g}`` makes the
first ``n`` tokens of every prompt one of ``g`` seeded prefixes.
``initial_burst``: that many further requests due at the window's first
instant (a service that is already loaded when the window opens).
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def _gamma_ppf(q, shape, iters=60):
    """Quantiles of gamma(shape, scale=1) by bisection on the regularised
    lower incomplete gamma function (series), no scipy."""
    def cdf(x):
        if x <= 0:
            return 0.0
        term = total = 1.0 / shape
        for k in range(1, 400):
            term *= x / (shape + k)
            total += term
            if term < 1e-14 * total:
                break
        return min(1.0, total * math.exp(-x + shape * math.log(x) - math.lgamma(shape)))

    out = []
    for p in q:
        lo, hi = 0.0, max(10.0, shape * 20.0 + 50.0)
        for _ in range(iters):
            mid = (lo + hi) / 2
            lo, hi = (mid, hi) if cdf(mid) < p else (lo, mid)
        out.append((lo + hi) / 2)
    return np.asarray(out)


def gaps(mix, n):
    """``n`` inter-arrival gaps (seconds) with mean ``1 / rate``: the quantile
    points of the arrival process's gap distribution."""
    q = _quantiles(n)
    kind = mix.get("arrivals", "poisson")
    if kind == "poisson":
        g = -np.log1p(-q)
    elif kind == "gamma":
        shape = 1.0 / float(mix["cv"]) ** 2
        g = _gamma_ppf(q, shape) / shape
    else:
        raise ValueError(f"unknown arrivals {kind!r}")
    return g / g.mean() / float(mix["rate_per_s"])


def lengths(spec, n):
    """``n`` lengths: the quantile points of the stated distribution, clipped."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]))
    q = _quantiles(n)
    if dist == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(p)) for p in q])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif dist == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def schedule(mix, seed, seconds, vocab_size):
    """The requests due in a window of ``seconds``: a list of ``{"due_s",
    "prompt" (int32 array), "max_tokens"}`` in order of arrival."""
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    burst = int(mix.get("initial_burst", 0))
    rng = np.random.default_rng(seed)
    due = np.cumsum(rng.permutation(gaps(mix, n)))
    due -= due[0] * rng.random()                     # the first arrival falls inside its gap
    due = np.concatenate([np.zeros(burst), due])
    n += burst
    prompts = rng.permutation(lengths(mix["prompt_tokens"], n))
    outputs = rng.permutation(lengths(mix["output_tokens"], n))
    shared = mix.get("shared_prefix")
    prefixes = None
    if shared:
        prefixes = rng.integers(0, vocab_size, (int(shared["groups"]), int(shared["tokens"])))
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab_size, (int(prompts[i]),)).astype(np.int32)
        if prefixes is not None:
            k = min(len(prompt), prefixes.shape[1])
            prompt[:k] = prefixes[int(rng.integers(0, len(prefixes)))][:k]
        out.append({"due_s": float(due[i]), "prompt": prompt, "max_tokens": int(outputs[i])})
    return [r for r in out if r["due_s"] < seconds]
