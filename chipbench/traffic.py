"""Open-loop traffic from a mix file and a seed.

A mix (``chipbench/traffic/<mix>.json``) states an arrival process, its
rate, the distributions of prompt and output lengths, and ``fill``: how
many requests are due at once when the schedule starts, so that the
slots are busy before the window opens although requests live longer
than the ramp.  The schedule is the distributions' quantiles at
``(i + 0.5) / n`` -- inter-arrival gaps, prompt and output lengths --
each put in an order that ``--seed`` draws, as are the prompts' token
ids.  So every seed offers the same set of work, in its own order.

Distributions (each a dict with ``"dist"``):

* ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
* ``{"dist": "uniform", "min": a, "max": b}`` (integers, both ends in)
* ``{"dist": "fixed", "value": v}``

Arrivals: ``{"process": "poisson", "rate_per_s": r}`` (exponential gaps).
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request of the schedule: due ``due_s`` seconds after the
    schedule starts."""

    idx: int
    due_s: float
    prompt: np.ndarray          # (prompt_len,) int32
    max_new_tokens: int


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` midpoint quantiles of a length distribution, as ints."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "fixed":
        vals = np.full(n, float(spec["value"]))
    elif kind == "uniform":
        lo, hi = spec["min"], spec["max"]
        vals = lo + u * (hi - lo + 1) - 0.5
    elif kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    vals = np.rint(vals)
    if "min" in spec:
        vals = np.maximum(vals, spec["min"])
    if "max" in spec:
        vals = np.minimum(vals, spec["max"])
    return vals.astype(np.int64)


def gaps(arrivals: dict, n: int) -> np.ndarray:
    """The ``n`` midpoint quantiles of the inter-arrival distribution."""
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    rate = float(arrivals["rate_per_s"])
    if not rate > 0:
        raise ValueError(f"rate_per_s must be > 0, got {rate}")
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def length_cap(spec: dict) -> int:
    """The longest length a distribution can give."""
    if spec["dist"] == "fixed":
        return int(spec["value"])
    return int(spec["max"])


def n_requests(mix: dict, horizon_s: float) -> int:
    """Requests needed to cover ``horizon_s`` seconds at the mix's rate."""
    return max(1, math.ceil(float(mix["arrivals"]["rate_per_s"]) * horizon_s))


def schedule(mix: dict, *, seed: int, horizon_s: float,
             vocab: int) -> list[Arrival]:
    """The mix's arrival schedule over ``horizon_s`` seconds, in the
    seed's order and with the seed's prompt tokens."""
    n = n_requests(mix, horizon_s)
    fill = int(mix.get("fill", 0))
    rng = np.random.default_rng(int(seed) % 2**64)
    dt = rng.permutation(gaps(mix["arrivals"], n))
    plen = rng.permutation(quantiles(mix["prompt_len"], fill + n))
    olen = rng.permutation(quantiles(mix["output_len"], fill + n))
    # the fill, then the first arrival, are due at 0
    due = np.concatenate([np.zeros(fill), np.cumsum(dt) - dt[0]])
    out = []
    for i in range(fill + n):
        toks = rng.integers(0, vocab, int(plen[i]), dtype=np.int32)
        out.append(Arrival(i, float(due[i]), toks, int(olen[i])))
    return out
