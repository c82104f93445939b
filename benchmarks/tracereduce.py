"""From a ``jax.profiler`` trace (``.xplane.pb``) to device busy time.

Read with ``jax.profiler.ProfileData`` and nothing else.  A TPU trace has
one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops`` line holds one
event per device operation and whose ``XLA Modules`` line one event per
launched program; host threads are other planes and are not read here.

  busy_s   union of the ``XLA Ops`` intervals of a chip, averaged over the
           chips that ran anything
  window_s from the first to the last event on any device line of the trace
           unless the caller knows the traced slice's length better
  device_ops  the operations that took most device time, by name
  idle_gaps   the idle time between programs, grouped by the program that
              followed the gap (what the host was preparing); naming the
              host's activity needs its spans on this clock: tracing issue
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def short_name(hlo: str) -> str:
    """``%name.7 = bf16[8,128]{...} custom-call(...)`` -> ``name bf16[8,128]``:
    the trace prints an operation as its whole HLO line; the instruction's
    name without its numeric suffix and its result shape tell the kernel
    and its bucket apart, and instances of one kernel add up."""
    head, _, rest = hlo.partition(" = ")
    name = head.lstrip("%").rsplit(".", 1)
    name = name[0] if len(name) == 2 and name[1].isdigit() else ".".join(name)
    shape = rest.split("{", 1)[0].split(" ", 1)[0].lstrip("(") if rest else ""
    return (name + (" " + shape if shape else ""))[:120]


def _union(intervals: List[Tuple[int, int]]) -> int:
    total, end = 0, -1
    for s, e in sorted(intervals):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _self_times(events: List[Tuple[int, int, str]]
                ) -> List[Tuple[str, int]]:
    """Self time of each event of one line: its duration less the events
    nested inside it (a ``while`` holds the operations of its body on the
    same line), so that the ranking names leaf operations once."""
    out: List[Tuple[str, int]] = []
    stack: List[List[Any]] = []          # [end, name, self_ns]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, n, ns = stack.pop()
            out.append((n, ns))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    out.extend((n, ns) for _, n, ns in stack)
    return out


def reduce_trace(path: str, window_s: Optional[float] = None,
                 top: int = 10) -> Optional[Dict[str, Any]]:
    """None where the trace has no device plane (a CPU rehearsal)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    busy_ns: List[int] = []
    op_ns: Dict[str, int] = defaultdict(int)
    gap_ns: Dict[str, int] = defaultdict(int)
    first, last = None, None
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        ops: List[Tuple[int, int]] = []
        modules: List[Tuple[int, int, str]] = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                named = []
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    ops.append((s, e))
                    named.append((s, e, ev.name))
                for name, ns in _self_times(named):
                    op_ns[short_name(name)] += ns
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    s = int(ev.start_ns)
                    modules.append((s, s + int(ev.duration_ns), ev.name))
        if not ops:
            continue
        busy_ns.append(_union(ops))
        lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
        first = lo if first is None else min(first, lo)
        last = hi if last is None else max(last, hi)
        modules.sort()
        for (_, e0, _), (s1, _, name) in zip(modules, modules[1:]):
            if s1 > e0:
                gap_ns["before " + name.split("(")[0]] += s1 - e0
    if not busy_ns:
        return None
    span_s = (last - first) / 1e9
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    window = span_s if window_s is None else max(window_s, span_s)

    def ranked(d: Dict[str, int]) -> List[List[Any]]:
        return [[k, v / 1e9 / len(busy_ns)] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_s, "window_s": window, "span_s": span_s,
            "chips": len(busy_ns),
            "breakdown": {"device_ops": ranked(op_ns),
                          "idle_gaps": ranked(gap_ns)}}


def describe(path: str) -> List[str]:
    """Planes and lines of a trace with their event counts: look at one
    trace by hand before trusting the reduction."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.append(f"{plane.name} | {line.name} | "
                       f"{sum(1 for _ in line.events)} events")
    return out
