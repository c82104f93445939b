"""The gap between output tokens as the client saw it, pooled over all tokens
exactly as the end-to-end ``itl_p<N>_ms`` pools them (clientmetrics.gaps_ms).

args: stat ("p95", "p50", ...).  For a cell where the end-to-end metric's
runs spread too widely for a bound, so that it is read per layer instead.
"""

import clientmetrics


def read(ctx, stat):
    vals, wts = clientmetrics.gaps_ms(ctx["header"], ctx["records"])
    if not vals:
        return None
    return clientmetrics.percentile(vals, float(stat[1:]), wts)
