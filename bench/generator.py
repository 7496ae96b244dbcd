"""The one request generator: an open-loop mix described by a traffic file.

Every seed gets the same schedule: the same sizes and inter-arrival gaps
(quantiles of the stated distributions), dealt out in the order the
traffic file's ``schedule_seed`` draws.  The run's seed draws the token ids
and modality features.  So runs differ in content, not in work: near a
serving system's capacity the tail of the time to first token is set by
which long requests overlap, and a schedule dealt anew for each seed moved
it severalfold between seeds.

A traffic file holds::

    {"kind": "open_loop", "rate_per_s": 4.0,
     "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.75,
                    "min": 32, "max": 736},
     "output_len": {"dist": "fixed", "value": 1}, "schedule_seed": 1}

``dist`` is ``lognormal`` (median, sigma), ``uniform`` (min, max) or
``fixed`` (value); ``min``/``max`` clip.  Arrivals are Poisson at
``rate_per_s``.
"""
from __future__ import annotations

import math
import statistics

import numpy as np


def _quantiles(spec: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "fixed":
        x = np.full(n, float(spec["value"]))
    elif kind == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(p) for p in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = spec.get("min", -np.inf), spec.get("max", np.inf)
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def length_range(spec: dict) -> tuple:
    if spec["dist"] == "fixed":
        return int(spec["value"]), int(spec["value"])
    return int(spec["min"]), int(spec["max"])


def n_requests(traffic: dict, seconds: float) -> int:
    """Requests made for a window: the window's expected count and a tenth
    more, so the mix never runs dry before the window closes."""
    return int(math.ceil(traffic["rate_per_s"] * seconds * 1.1)) + 8


def requests(traffic: dict, seed: int, seconds: float, vocab: int,
             n_modalities: int, modality_dim: int) -> dict:
    """Arrays of one run's requests, in order of due time: ``due`` (s from
    the window's start), ``prompt_len``, ``max_new``, ``tokens`` (list of
    int32 arrays), ``feats`` (n, M, F) float32."""
    if traffic["kind"] != "open_loop":
        raise ValueError(f"not an open-loop mix: {traffic['kind']!r}")
    n = n_requests(traffic, seconds)
    order = np.random.default_rng(traffic["schedule_seed"])
    plen = order.permutation(_quantiles(traffic["prompt_len"], n))
    onew = order.permutation(_quantiles(traffic["output_len"], n))
    u = (np.arange(n) + 0.5) / n
    due = np.cumsum(order.permutation(-np.log1p(-u) / traffic["rate_per_s"]))
    rng = np.random.default_rng(seed)
    toks = [rng.integers(2, vocab, size=int(s)).astype(np.int32)
            for s in plen]
    feats = rng.normal(size=(n, n_modalities, modality_dim)) \
        .astype(np.float32)
    return {"due": due, "prompt_len": plen, "max_new": onew,
            "tokens": toks, "feats": feats}
