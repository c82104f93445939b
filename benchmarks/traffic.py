"""The one general traffic generator: ``traffic/<mix>.json`` -> a schedule.

A mix is data (loop kind, rate or client count, length distributions,
sharing by all requests or by the asks of a session, arrival shape); this
module turns any mix into requests.  It never
imports JAX: the load generator child runs it.

Steadiness rule (PERF.md §2): the SIZES of a run do not depend on
``--seed``.  Lengths and inter-arrival gaps are the mix's own fixed
stratified sample (the (i + 0.5) / n quantiles of each distribution), so
every seed offers exactly the same multiset of prompt lengths, output
lengths and gaps; the seed decides their ORDER and the token ids.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np


def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse normal CDF (Acklam's rational approximation, relative error
    about 1e-9: more than enough, lengths are rounded to whole tokens)."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    p = np.asarray(p, float)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                 + a[5]) * q
                / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4])
                   * r + 1))
    for mask, sign in ((lo, 1.0), (hi, -1.0)):
        pp = p[mask] if sign > 0 else 1 - p[mask]
        q = np.sqrt(-2 * np.log(pp))
        out[mask] = sign * (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q
                             + c[4]) * q + c[5]) / (
            (((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    return out


def quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """The (i + 0.5) / n quantiles of a length distribution, as whole
    numbers clipped to [min, max]."""
    p = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * _norm_ppf(p))
    elif kind == "loguniform":
        x = np.exp(np.log(dist["min"])
                   + p * (np.log(dist["max"]) - np.log(dist["min"])))
    elif kind == "uniform":
        x = dist["min"] + p * (dist["max"] - dist["min"])
    elif kind == "fixed":
        x = np.full(n, dist["value"], float)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo = dist.get("min", dist.get("value", 1))
    hi = dist.get("max", dist.get("value", 1 << 30))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def gap_quantiles(arrivals: Dict[str, Any], rate: float, n: int
                  ) -> np.ndarray:
    """n inter-arrival gaps with mean 1 / rate: the stratified sample of
    the arrival process's gap distribution, rescaled to sum to n / rate
    exactly so that every seed's schedule spans the same time."""
    p = (np.arange(n) + 0.5) / n
    kind = arrivals.get("kind", "poisson")
    if kind == "poisson":
        g = -np.log1p(-p)
    elif kind == "uniform":
        g = np.ones(n)
    elif kind == "gamma":
        # Gamma gaps with coefficient of variation cv (cv 1 = Poisson,
        # > 1 = bursts): quantiles by bisection on the regularised
        # incomplete gamma function, no SciPy needed.
        shape = 1.0 / float(arrivals["cv"]) ** 2
        g = np.array([_gamma_ppf(shape, float(q)) for q in p])
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    return g * (n / rate) / g.sum()


def _gamma_cdf(shape: float, x: float) -> float:
    if x <= 0:
        return 0.0
    term = total = 1.0 / shape
    for k in range(1, 2000):
        term *= x / (shape + k)
        total += term
        if term < total * 1e-12:
            break
    return total * math.exp(-x + shape * math.log(x) - math.lgamma(shape))


def _gamma_ppf(shape: float, p: float) -> float:
    lo, hi = 0.0, max(10.0, shape * 10)
    while _gamma_cdf(shape, hi) < p:
        hi *= 2
    for _ in range(80):
        mid = (lo + hi) / 2
        if _gamma_cdf(shape, mid) < p:
            lo = mid
        else:
            hi = mid
    return (lo + hi) / 2


def _stream(seed: int, what: str) -> np.random.Generator:
    """An independent stream of the run's seed.  ``seed`` may be anything
    up to a little over 2**31; SeedSequence takes any non-negative int."""
    tag = int.from_bytes(what.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng(np.random.SeedSequence([int(seed), tag]))


def resolve_mix(mix: Dict[str, Any], rehearse: bool = False
                ) -> Dict[str, Any]:
    """The mix as run: with ``rehearse`` its tiny CPU preset overlaid."""
    out = dict(mix)
    if rehearse:
        out.update(mix.get("rehearsal", {}))
    return out


def build_schedule(mix: Dict[str, Any], seed: int, seconds: float,
                   phase: str, rate: float = None) -> Dict[str, Any]:
    """The requests of one phase (``"warmup"`` or ``"window"``).

    Open loop: ``n = round(rate * seconds)`` arrivals with due times (s from
    the phase's start).  Closed loop: an endless-enough list that the
    clients take from in order.  Each request: {"due", "own_tokens",
    "max_tokens"}; the prompt is the mix's shared prefix (if any) followed
    by ``own_tokens`` fresh token ids, made by :func:`prompt_ids`.

    ``sessions`` (open loop): an arrival is a SESSION, not a request: a
    document of ``prefix_tokens`` that is asked ``asks`` times, each ask a
    request of its own (fresh ``own_tokens`` after the document), the asks
    ``ask_gap_s`` apart on average (exponential).  Its requests also carry
    {"session", "session_tokens"}.  An ask that would fall past the phase's
    end wraps round to its beginning, as the late ask of a session that
    began before it: every seed plays the same requests and the phase's
    load is even from its first second."""
    # The order of sizes and gaps: the run's seed, unless the mix pins it
    # (``order_seed``: every seed then plays the SAME schedule and differs
    # only in token ids and weights; for a mix whose tails swing with the
    # order more than a bound could cover).
    rng = _stream(mix.get("order_seed", seed), "order-" + phase)
    sessions = mix.get("sessions")
    if mix["loop"] == "open":
        rate = float(rate if rate is not None else mix["rate_rps"])
        n = max(1, int(round(rate * seconds)))
        gaps = gap_quantiles(mix.get("arrivals", {}), rate, n)
        gaps = gaps[rng.permutation(n)]
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    elif mix["loop"] == "closed" and not sessions:
        n = int(mix["closed_list_len"])
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop kind {mix['loop']!r}"
                         + (" with sessions" if sessions else ""))
    extra: List[Dict[str, int]] = [{} for _ in range(n)]
    if sessions:
        asks = int(sessions["asks"])
        docs = quantiles(sessions["prefix_tokens"], n)[rng.permutation(n)]
        waits = gap_quantiles({"kind": "poisson"},
                              1.0 / float(sessions["ask_gap_s"]),
                              n * (asks - 1))[rng.permutation(n * (asks - 1))]
        offsets = np.concatenate(
            [np.zeros((n, 1)), np.cumsum(waits.reshape(n, asks - 1), 1)], 1)
        due = ((due[:, None] + offsets) % seconds).reshape(-1)
        extra = [{"session": s, "session_tokens": int(docs[s])}
                 for s in range(n) for _ in range(asks)]
        n *= asks
    own = quantiles(mix["prompt_tokens"], n)[rng.permutation(n)]
    out = quantiles(mix["output_tokens"], n)[rng.permutation(n)]
    requests = [{"due": float(d), "own_tokens": int(p), "max_tokens": int(o),
                 **x} for d, p, o, x in zip(due, own, out, extra)]
    if sessions:
        requests.sort(key=lambda r: r["due"])
    return {"loop": mix["loop"], "clients": int(mix.get("clients", 0)),
            "requests": requests}


def shared_prefix(mix: Dict[str, Any], seed: int, vocab: int) -> List[int]:
    n = int(mix.get("shared_prefix_tokens", 0))
    return _stream(seed, "prefix").integers(1, vocab, size=n).tolist()


def prompt_ids(prefix: List[int], req: Dict[str, Any], seed: int,
               phase: str, index: int, vocab: int) -> List[int]:
    """Token ids of one request: the shared prefix, then its session's
    document (if the mix has sessions; the same ids for every ask of the
    session), then fresh ids from the request's own stream (so no two
    requests share anything else)."""
    which = 0 if phase == "window" else 1
    doc: List[int] = []
    if "session" in req:
        doc = np.random.default_rng(np.random.SeedSequence(
            [int(seed), 0x646F63, which, int(req["session"])])).integers(
            1, vocab, size=req["session_tokens"]).tolist()
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed), 0x70726F6D, which, int(index)]))
    return prefix + doc + rng.integers(
        1, vocab, size=req["own_tokens"]).tolist()
