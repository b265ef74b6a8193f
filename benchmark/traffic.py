"""The one general traffic generator.  A traffic mix is a JSON file of
parameters under ``benchmark/traffic/``; nothing here knows a cell by name.

    {"route": "stream" | "predict",
     "loop": "closed" | "open-poisson",
     "clients": 64,                  closed loop: callers that each wait
     "rate_per_s": 8.0,              open loop: fixed offered rate
     "lead_in_s": 6.0,               load offered before the window, uncounted
     "drain_s": 20.0,                longest wait for in-flight requests
     "prompt_len": {"dist": ...},    stream route
     "output_len": {"dist": ...},
     "temperature": 0.0,
     "rows": {"dist": ...},          predict route: rows per request
     "pool": 512}                    how many distinct requests are drawn

Distributions: ``fixed`` (value), ``uniform`` (min, max, whole numbers),
``lognormal`` (median, sigma, min, max: clipped), ``choice`` (values,
weights).

Steadiness: every seed gets the SAME sizes and arrival gaps — the
distribution's quantiles at (i + 0.5) / n, not n random draws — in the SAME
order (one fixed shuffle), with other token ids (and other weights).  Two
seeds then offer the same work at the same instants, and a metric's spread
over seeds is the system's, not the sample's.  (With the order drawn from
the seed, one seed in six moved the chat cell's 95th-percentile TTFT by a
fifth, run after run: a tail depends on which long prompts arrive
together.  PERF.md, Findings, PR 23.)
"""

from __future__ import annotations

import math
import statistics

import numpy as np


ORDER_SEED = 0xA221  # the one order of sizes and gaps every run gets


def quantile_fn(spec: dict):
    """Inverse CDF of a length distribution, u in (0, 1) -> whole number."""
    kind = spec["dist"]
    if kind == "fixed":
        v = int(spec["value"])
        return lambda u: v
    if kind == "uniform":
        lo, hi = int(spec["min"]), int(spec["max"])
        return lambda u: min(hi, lo + int(u * (hi - lo + 1)))
    if kind == "lognormal":
        mu, sigma = math.log(spec["median"]), float(spec["sigma"])
        lo, hi = int(spec["min"]), int(spec["max"])
        nd = statistics.NormalDist()
        return lambda u: int(
            min(hi, max(lo, round(math.exp(mu + sigma * nd.inv_cdf(u)))))
        )
    if kind == "choice":
        values = [int(v) for v in spec["values"]]
        w = np.asarray(spec["weights"], float)
        cum = np.cumsum(w / w.sum())
        return lambda u: values[min(int(np.searchsorted(cum, u)), len(values) - 1)]
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(spec: dict, n: int) -> list[int]:
    """The distribution's n quantiles at (i + 0.5) / n, in rising order."""
    q = quantile_fn(spec)
    return [q((i + 0.5) / n) for i in range(n)]


def mean_of(spec: dict, n: int = 4096) -> float:
    return float(np.mean(stratified(spec, n)))


def exponential_gaps(rate_per_s: float, n: int) -> list[float]:
    """n gaps with the exponential distribution's quantiles: a Poisson
    process's marginal gaps, as a fixed multiset whose sum is n / rate."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate_per_s for i in range(n)]
    scale = (n / rate_per_s) / sum(gaps)
    return [g * scale for g in gaps]


def make_requests(mix: dict, seed: int, vocab: int, n: int) -> list[dict]:
    """n request specs.  Stream route: ``tokens`` (unique random ids in
    [1, vocab), from the seed) and ``max_new``; predict route: ``rows``.
    Sizes come in one fixed order whatever the seed."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    order = np.random.default_rng(ORDER_SEED)
    if mix["route"] == "stream":
        plen = stratified(mix["prompt_len"], n)
        olen = stratified(mix["output_len"], n)
        order.shuffle(plen)
        order.shuffle(olen)
        return [
            {"tokens": rng.integers(1, vocab, size=p).tolist(), "max_new": o}
            for p, o in zip(plen, olen)
        ]
    if mix["route"] == "predict":
        rows = stratified(mix["rows"], n)
        order.shuffle(rows)
        return [{"rows": r} for r in rows]
    raise ValueError(f"unknown route {mix['route']!r}")


def due_times(mix: dict, horizon_s: float) -> list[float]:
    """Open loop: the instants, from 0, at which requests are due; enough to
    cover ``horizon_s``.  The same times for every seed."""
    rate = float(mix["rate_per_s"])
    n = int(math.ceil(rate * horizon_s))
    gaps = exponential_gaps(rate, n)
    np.random.default_rng(ORDER_SEED + 1).shuffle(gaps)
    t, out = 0.0, []
    for g in gaps:
        t += g
        out.append(t)
    return out
