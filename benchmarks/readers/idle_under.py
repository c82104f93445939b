"""Share of the traced slice in which no operation ran on the device AND the
engine thread was inside one phase of its loop, from the profiler's trace.

The program wraps the phases of an engine-loop iteration in
``jax.profiler.TraceAnnotation("llmd.<phase>")`` (schedule, build, dispatch,
fetch, post); they land on the ``/host:CPU`` plane, on the device plane's
clock.  ``shares`` intersects the gaps between a chip's ``XLA Ops`` events
with those intervals: {phase: % of the slice}, with "none" for idle time
inside no phase (between two iterations, or the loop asleep) and outside the
first-to-last device event.  The values add up to the idle share that
``tracereduce.reduce_trace`` gives for the same trace and window.

args: phase ("schedule", "build", "dispatch", "post", or "other": all idle
time under none of these four, so that the five add up to the idle share).

``ctx`` carries no path to the trace: ``read`` takes the newest ``trace/``
under ``.bench_out/`` (run.py clears its own and writes exactly one before
the readers run).  None where the trace has no device plane (a CPU
rehearsal) or no ``llmd.*`` event (a program without the annotations).
"""

import functools
import glob
import os

import tracereduce

NAMED = ("schedule", "build", "dispatch", "post")
PREFIX = "llmd."
LOOP_THREAD = PREFIX + "emit"        # the event loop's, not the engine's
BENCH_OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".bench_out")


def _gaps(ops):
    """The idle intervals between the merged (start, end) of ``ops``."""
    out, end = [], None
    for s, e in sorted(ops):
        if end is not None and s > end:
            out.append((end, s))
        end = e if end is None else max(end, e)
    return out


def attribute(chips, phases, window_ns=0):
    """``chips``: one list of (start, end) device operations per chip;
    ``phases``: (start, end, name) of the engine thread, not overlapping;
    all in ns on one clock.  {name or "none": % of the window idle there}."""
    phases = sorted(phases)
    span_ns = (max(e for ops in chips for _, e in ops)
               - min(s for ops in chips for s, _ in ops))
    window_ns = max(span_ns, window_ns)
    idle = {"none": 0.0}
    for ops in chips:
        i = 0
        for g0, g1 in _gaps(ops):
            while i < len(phases) and phases[i][1] <= g0:
                i += 1
            under, j = 0, i
            while j < len(phases) and phases[j][0] < g1:
                p0, p1, name = phases[j]
                cut = min(p1, g1) - max(p0, g0)
                idle[name] = idle.get(name, 0.0) + cut
                under += cut
                j += 1
            idle["none"] += (g1 - g0) - under
        # the slice outside this chip's first-to-last operation
        idle["none"] += window_ns - (max(e for _, e in ops)
                                     - min(s for s, _ in ops))
    return {k: 100.0 * v / len(chips) / window_ns for k, v in idle.items()}


@functools.lru_cache(maxsize=2)
def shares(path, window_s=None):
    """``attribute`` of the trace at ``path``; None without a device plane
    that ran anything, or without ``llmd.*`` events."""
    from jax.profiler import ProfileData
    chips, phases = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name.startswith(tracereduce.DEVICE_PLANE_PREFIX):
                if line.name == tracereduce.OPS_LINE:
                    ops = [(int(ev.start_ns),
                            int(ev.start_ns) + int(ev.duration_ns))
                           for ev in line.events]
                    if ops:
                        chips.append(ops)
            else:
                phases.extend(
                    (int(ev.start_ns), int(ev.start_ns)
                     + int(ev.duration_ns), ev.name[len(PREFIX):])
                    for ev in line.events
                    if ev.name.startswith(PREFIX) and ev.name != LOOP_THREAD)
    if not chips or not phases:
        return None
    return attribute(chips, phases, (window_s or 0.0) * 1e9)


def newest_xplane():
    dirs = glob.glob(os.path.join(BENCH_OUT, "*", "trace"))
    return (tracereduce.find_xplane(max(dirs, key=os.path.getmtime))
            if dirs else None)


def read(ctx, phase):
    if not ctx["trace"]:
        return None
    path = newest_xplane()
    got = shares(path, ctx["trace"]["window_s"]) if path else None
    if got is None:
        return None
    if phase == "other":
        return sum(v for k, v in got.items() if k not in NAMED)
    return got.get(phase, 0.0)
