"""Metric arithmetic over the load generator's records.  No JAX.

The records file: a header line {"window": [t0, t1], "epoch0", "loop"} and
one line per request as loadgen.py wrote it (times on the child's clock).

  - time to first token runs from the request's DUE time (open loop; a
    stall that delays the generator is charged to the requests behind it)
    or from its send (closed loop: due == sent);
  - a failed or refused request counts in ``failed`` and, in a percentile,
    as worse than any request that succeeded;
  - gaps between output tokens are pooled over all tokens of all measured
    requests; a frame that carries n tokens counts n gaps of
    (interval since the previous frame / n);
  - output tokens per second counts every token that arrived inside the
    window, whichever request it belongs to, over the window's length.
"""

from __future__ import annotations

import json
import math
import re
from typing import Any, Dict, List, Optional, Tuple


def load_records(path: str) -> Tuple[Dict[str, Any], List[Dict[str, Any]]]:
    with open(path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    return lines[0], lines[1:]


def percentile(values: List[float], p: float,
               weights: Optional[List[float]] = None) -> float:
    """Nearest-rank percentile (the smallest value with at least p % of the
    weight at or below it); +inf sorts last."""
    if not values:
        return math.nan
    order = sorted(range(len(values)), key=values.__getitem__)
    w = weights or [1.0] * len(values)
    need = p / 100.0 * sum(w)
    acc = 0.0
    for i in order:
        acc += w[i]
        if acc >= need - 1e-12:
            return values[i]
    return values[order[-1]]


def failed(rec: Dict[str, Any], loop: str) -> bool:
    if rec["status"] != 200 or rec["error"]:
        return True
    if rec["done"]:
        return rec["n_tokens"] != rec["max_tokens"]
    # Not finished: a closed loop's own cut at the window's end is no
    # failure once the first token is in; anything else is.
    return not (loop == "closed" and rec["cancelled"]
                and rec["first"] is not None)


def measured(records: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    return [r for r in records if r["phase"] == "window"]


def ttfts_ms(header, records) -> Tuple[List[float], int]:
    """Per measured request, ms from due time to first token; +inf for a
    failed one.  Returns (values, number failed)."""
    out, bad = [], 0
    for r in measured(records):
        if failed(r, header["loop"]) or r["first"] is None:
            bad += 1
            out.append(math.inf)
        else:
            out.append((r["first"] - r["due"]) * 1e3)
    return out, bad


def gaps_ms(header, records) -> Tuple[List[float], List[float]]:
    """(gap per token in ms, number of tokens it stands for).  Open loop:
    every frame of a measured request.  Closed loop: every frame that
    arrived inside the window, of any request (steady state)."""
    t0, t1 = header["window"]
    closed = header["loop"] == "closed"
    vals, wts = [], []
    for r in (records if closed else measured(records)):
        fr = r["frames"]
        for (ta, _), (tb, n) in zip(fr, fr[1:]):
            if closed and not (t0 <= tb < t1):
                continue
            vals.append((tb - ta) / n * 1e3)
            wts.append(float(n))
    return vals, wts


def tokens_in_window(header, records) -> int:
    t0, t1 = header["window"]
    return sum(n for r in records for t, n in r["frames"] if t0 <= t < t1)


def lateness_ms(records) -> List[float]:
    return [(r["sent"] - r["due"]) * 1e3 for r in measured(records)
            if r["sent"] is not None]


def _finite(value: float, header, records) -> float:
    """A percentile that landed on a failed request: report it as worse
    than any that succeeded (the whole window plus the wait after it)."""
    if math.isfinite(value):
        return value
    t0, _ = header["window"]
    last = max((r["end"] or t0) for r in records) if records else t0
    return (last - t0) * 1e3


E2E_NAME = re.compile(r"^(ttft|itl)_p(\d+(?:\.\d+)?)_ms$")


def end_to_end(header, records, names) -> Dict[str, float]:
    """The end-to-end metrics asked for, by name: ``ttft_p<N>_ms`` and
    ``itl_p<N>_ms`` for any percentile N, and ``out_tok_s`` (``setup_s`` is
    run.py's: it is not in the records).  An unknown name is an error."""
    t0, t1 = header["window"]
    tt, _ = ttfts_ms(header, records)
    gv, gw = gaps_ms(header, records)
    out = {}
    for name in names:
        m = E2E_NAME.match(name)
        if name == "out_tok_s":
            out[name] = tokens_in_window(header, records) / (t1 - t0)
        elif m and m.group(1) == "ttft":
            out[name] = _finite(percentile(tt, float(m.group(2))), header,
                                records)
        elif m:
            out[name] = percentile(gv, float(m.group(2)), gw)
        else:
            raise KeyError(f"clientmetrics knows no end-to-end metric "
                           f"{name!r}")
    return out


def counts(header, records) -> Dict[str, int]:
    m = measured(records)
    return {"attempted": len(m),
            "failed": sum(failed(r, header["loop"]) for r in m)}


def spread(values: List[float]) -> float:
    """Distance between the first and third quartile (Python's
    statistics.quantiles, n=4) as a share of the median."""
    import statistics
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
