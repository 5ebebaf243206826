"""Traffic from a mix's parameters and a seed.

One generator serves every mix; a mix is a data file
(``bench/traffic/<name>.json``) with these keys:

- ``kind``: ``"server"`` (open loop, arrivals over ``[0, seconds)``)
  or ``"offline"`` (every request due at t=0);
- ``rate_per_s``: the offered rate (server), or the requests per
  second of window that an offline run enqueues (``R``), so that
  ``N = round(R * seconds)`` either way;
- ``prompt`` and ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal length distribution, clipped;
- ``library``: how many lengths each library holds (32).

Lengths come from a fixed library: the ``library`` evenly spaced
quantiles of each distribution, the same under every seed (the way
MLPerf loadgen's performance sample set works), so that set-up can
compile every prompt length a run will send and later runs find them
in the compile cache.  The seed picks the token ids, the order in which
library entries are sent, the pairing of prompt and output lengths and
the order of the inter-arrival gaps.  Requests come in blocks of one
library's length; each block holds every prompt length and every
output length once, and a last, partial block the same evenly spread
subset under every seed, so every seed sends the same lengths.  The
gaps are
the ``N`` evenly spaced quantiles of an exponential distribution at
the offered rate, in an order drawn from the seed: Poisson arrivals
with the count and span fixed.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of a run's schedule."""

    qid: int
    prompt: np.ndarray     # (S,) int32 token ids
    max_new_tokens: int
    arrival_s: float


def quantile_lengths(dist: dict, n: int) -> list[int]:
    """The ``n`` evenly spaced quantiles ``(i + 0.5) / n`` of a clipped
    lognormal: ``median * exp(sigma * z)``, rounded and clipped to
    ``[min, max]``."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = round(dist["median"] * math.exp(dist["sigma"] * z))
        out.append(int(min(max(v, dist["min"]), dist["max"])))
    return out


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, round(mix["rate_per_s"] * seconds))


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """The ``n`` evenly spaced quantiles of Exp(rate), ascending."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def arrivals(mix: dict, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    """Due times (seconds from the first): all 0 offline; otherwise the
    exponential gaps in a seeded order, scaled to span ``[0, seconds)``
    with the first arrival at 0."""
    n = n_requests(mix, seconds)
    if mix["kind"] == "offline":
        return np.zeros(n)
    if mix["kind"] != "server":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    gaps = rng.permutation(exponential_gaps(n, mix["rate_per_s"]))
    t = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    # the last gap is the one after the last arrival: the run's arrivals
    # then cover [0, seconds) at the offered rate on average
    return t * (seconds / gaps.sum())


def schedule(mix: dict, seconds: float, seed: int, vocab: int
             ) -> list[Planned]:
    """The requests of one run, in arrival order."""
    rng = np.random.default_rng(seed)
    lib = mix["library"]
    prompts = quantile_lengths(mix["prompt"], lib)
    outputs = quantile_lengths(mix["output"], lib)
    due = arrivals(mix, seconds, rng)
    n = len(due)
    plan = []
    for start in range(0, n, lib):
        k = min(lib, n - start)
        entries = spread_entries(lib, k)
        pp = rng.permutation(entries)
        po = rng.permutation(entries)
        for j in range(k):
            plan.append(Planned(
                qid=start + j,
                prompt=rng.integers(0, vocab, prompts[pp[j]],
                                    dtype=np.int32),
                max_new_tokens=outputs[po[j]],
                arrival_s=float(due[start + j])))
    return plan


def spread_entries(lib: int, k: int) -> np.ndarray:
    """``k`` of the ``lib`` library entries, evenly spread over the
    quantiles and the same under every seed: all of them for a whole
    block, and for the last, partial block of a run a fixed subset, so
    that every seed sends the same lengths."""
    return np.floor((np.arange(k) + 0.5) * lib / k).astype(int)


def warm_up_requests(mix: dict, vocab: int) -> list[Planned]:
    """One request per distinct prompt length of the library, due at
    once, each decoding two tokens: compiles every prefill program the
    run will use and the decode chunk."""
    lengths = sorted(set(quantile_lengths(mix["prompt"], mix["library"])))
    rng = np.random.default_rng(0)
    return [Planned(qid=10 ** 9 + i,
                    prompt=rng.integers(0, vocab, s, dtype=np.int32),
                    max_new_tokens=2, arrival_s=0.0)
            for i, s in enumerate(lengths)]
