"""100 * (increase of one counter) / (increase of another) over the window.

args: numerator, denominator (metric names as ``/metrics`` prints them).
"""


def read(ctx, numerator, denominator):
    before, after = ctx["counters"]["before"], ctx["counters"]["after"]
    if numerator not in after or denominator not in after:
        return None
    den = after[denominator] - before.get(denominator, 0.0)
    if den <= 0:
        return None
    return 100.0 * (after[numerator] - before.get(numerator, 0.0)) / den
