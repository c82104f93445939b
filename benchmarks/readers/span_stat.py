"""A statistic over the program's spans of one name.

args: span (name), value ("dur_ms" or an attribute's name), stat ("median",
"mean", "p95", ...), where (optional: {attribute: [op, number]} with op one
of "==", ">", ">=", "<"; all must hold).
"""

import statistics

OPS = {"==": lambda a, b: a == b, ">": lambda a, b: a > b,
       ">=": lambda a, b: a >= b, "<": lambda a, b: a < b}


def read(ctx, span, value, stat, where=None):
    vals = []
    for s in ctx["spans"]:
        if s["name"] != span:
            continue
        attrs = s.get("attrs", {})
        if any(k not in attrs or not OPS[op](attrs[k], ref)
               for k, (op, ref) in (where or {}).items()):
            continue
        v = s["dur"] * 1e3 if value == "dur_ms" else attrs.get(value)
        if v is not None:
            vals.append(float(v))
    if not vals:
        return None
    if stat == "median":
        return statistics.median(vals)
    if stat == "mean":
        return statistics.fmean(vals)
    if stat.startswith("p"):
        import clientmetrics
        return clientmetrics.percentile(vals, float(stat[1:]))
    raise ValueError(f"unknown stat {stat!r}")
