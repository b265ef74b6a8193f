"""The one general traffic generator.  A traffic mix is a JSON file of
parameters under ``benchmark/traffic/``; nothing here knows a cell by name.

    {"route": "stream" | "predict",
     "loop": "closed" | "open",      ("open-poisson": open, Poisson arrivals)
     "clients": 64,                  closed loop: callers that each wait
     "rate_per_s": 8.0,              open loop: fixed offered rate
     "arrivals": {"process": "poisson" | "gamma",   open loop: the gaps'
                  "cv": 3.0},        distribution; cv (gamma only) is their
                                     coefficient of variation, shape 1/cv^2
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


def _gamma_p(a: float, x: float) -> float:
    """The regularised lower incomplete gamma function P(a, x), a > 0: the
    gamma distribution's CDF at x for shape a and scale 1.  The series below
    a + 1, the continued fraction (modified Lentz) above it."""
    if x <= 0.0:
        return 0.0
    front = math.exp(a * math.log(x) - x - math.lgamma(a))
    if x < a + 1.0:
        term = total = 1.0 / a
        k = a
        while abs(term) > abs(total) * 1e-17:
            k += 1.0
            term *= x / k
            total += term
        return front * total
    tiny = 1e-300
    b = x + 1.0 - a
    c, d = 1.0 / tiny, 1.0 / b
    h = d
    for i in range(1, 10000):
        an = -i * (i - a)
        b += 2.0
        d = an * d + b
        d = 1.0 / (d if abs(d) > tiny else tiny)
        c = b + an / c
        c = c if abs(c) > tiny else tiny
        h *= d * c
        if abs(d * c - 1.0) < 1e-16:
            break
    return 1.0 - front * h


def gamma_quantile(shape: float, u: float) -> float:
    """Inverse of the gamma distribution's CDF (shape ``shape``, scale 1) at
    u in (0, 1), in the harness's own arithmetic (it imports no scipy): a
    bracket, then bisection on log x until the ends agree to 1e-15."""
    if not (shape > 0.0 and 0.0 < u < 1.0):
        raise ValueError(f"gamma_quantile({shape}, {u})")
    # P(a, x) <= x^a / Gamma(a + 1), and equals it to a relative x below
    log_lo = (math.log(u) + math.lgamma(shape + 1.0)) / shape
    if log_lo < -40.0:
        return math.exp(log_lo)
    lo = math.exp(log_lo)
    hi = max(2.0 * lo, 1.0)
    while _gamma_p(shape, hi) < u:
        lo, hi = hi, 2.0 * hi
    for _ in range(200):
        mid = math.sqrt(lo * hi)
        if _gamma_p(shape, mid) < u:
            lo = mid
        else:
            hi = mid
        if hi - lo <= 1e-15 * hi:
            break
    return math.sqrt(lo * hi)


def gamma_gaps(rate_per_s: float, cv: float, n: int) -> list[float]:
    """n gaps with the quantiles of a gamma distribution whose coefficient
    of variation is ``cv`` (shape 1 / cv^2; cv 1 is the exponential, above 1
    arrivals bunch), as a fixed multiset whose sum is n / rate.  The
    multiset's own cv is under the distribution's by the tail beyond the
    last quantile: 3 % at n = 288 for cv 3, under 1 % from n = 5,000."""
    shape = 1.0 / (float(cv) * float(cv))
    gaps = [gamma_quantile(shape, (i + 0.5) / n) for i in range(n)]
    scale = (n / rate_per_s) / sum(gaps)
    return [g * scale for g in gaps]


def open_loop(mix: dict) -> bool:
    """Whether requests arrive on a schedule (True) or from callers that
    each wait for a reply (False)."""
    if mix["loop"] in ("open", "open-poisson"):
        return True
    if mix["loop"] == "closed":
        return False
    raise ValueError(f"unknown loop {mix['loop']!r}")


def arrival_gaps(mix: dict, n: int) -> list[float]:
    """The n gaps of an open loop's arrival process, in rising order.
    ``"loop": "open-poisson"`` is ``"open"`` with Poisson arrivals."""
    rate = float(mix["rate_per_s"])
    arrivals = mix.get("arrivals", {"process": "poisson"})
    if mix["loop"] == "open-poisson" or arrivals["process"] == "poisson":
        return exponential_gaps(rate, n)
    if arrivals["process"] == "gamma":
        return gamma_gaps(rate, float(arrivals["cv"]), n)
    raise ValueError(f"unknown arrival process {arrivals['process']!r}")


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
    n = int(math.ceil(float(mix["rate_per_s"]) * horizon_s))
    gaps = arrival_gaps(mix, n)
    np.random.default_rng(ORDER_SEED + 1).shuffle(gaps)
    t, out = 0.0, []
    for g in gaps:
        t += g
        out.append(t)
    return out
