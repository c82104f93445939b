"""Share of the traced slice in which no operation ran on the device:
100 * (1 - busy_s / window_s), from the profiler's trace.  No args.
"""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
