"""How late the load generator sent its open-loop requests (sent - due).

args: stat ("p95", "p50", "max").
"""

import clientmetrics


def read(ctx, stat):
    if ctx["header"]["loop"] != "open":
        return None
    late = clientmetrics.lateness_ms(ctx["records"])
    if not late:
        return None
    if stat == "max":
        return max(late)
    return clientmetrics.percentile(late, float(stat[1:]))
