"""Device time by PART of the step program, from the traced slice.

The program names every operation of a step program with a
``jax.named_scope("llmd.<part>")`` (llm_d_tpu/ops/parts.py); the profiler
carries the scope in the ``tf_op`` stat of the operation's metadata record,
which ``xplanemeta.py`` reads.  Here: the SELF time (``tracereduce.
_self_times``: a ``while`` holds its body's operations on the same line) of
the slice's ``XLA Ops`` events, grouped by the INNERMOST ``llmd.*`` scope of
each operation; an operation under none is ``unscoped``.  The time is the
part's, whatever kernel or fusion implements it: no operation's name is
looked at.

args: group, one of GROUPS.  ``read`` returns the group's % of the chip's
busy time (the sum of all self times); the six add up to 100.  A group with
no operation in the slice (``state`` in a stack without a mixer, ``experts``
in a dense one) returns None and is left out of the line, never reported as
0.  None as well where there is no device plane (a CPU rehearsal) or no
operation under any scope (a program without them: the parent of PR 36).

``python3 benchmarks/readers/device_parts.py <xplane.pb> [n]`` prints the
fine table: seconds and % by scope, and under each scope its n (5) largest
operations with their ``source``, so that an unnamed fusion has a file and
a line.
"""

import functools
import os
import re
import sys
from collections import defaultdict

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import tracereduce
import xplanemeta

UNSCOPED = "unscoped"
# The program's vocabulary (llm_d_tpu/ops/parts.py), by group.
GROUPS = {
    "experts": ("llmd.experts",),
    "attention": ("llmd.attn.decode", "llmd.attn.prefill"),
    "state": ("llmd.ssm.state",),
    "head": ("llmd.head", "llmd.sample"),
    "dense": ("llmd.embed", "llmd.attn.proj", "llmd.router", "llmd.shared",
              "llmd.mlp", "llmd.ssm.proj"),
    "other": ("llmd.tiles", "llmd.scan", UNSCOPED),
}
_SCOPE = re.compile(r"(?:^|/)(llmd\.[a-z_.]+)(?=/|:|$)")


def scope_of(tf_op):
    """The innermost ``llmd.*`` scope of a name stack, or ``unscoped``."""
    found = _SCOPE.findall(tf_op or "")
    return found[-1] if found else UNSCOPED


@functools.lru_cache(maxsize=2)
def self_times(path):
    """Per chip that ran anything: ({record id: self time in ps}, {record
    id: the record}) of its ``XLA Ops`` line."""
    chips = []
    for plane in xplanemeta.read(path, (tracereduce.OPS_LINE,)):
        events = plane["lines"].get(tracereduce.OPS_LINE)
        if not events or not plane["name"].startswith(
                tracereduce.DEVICE_PLANE_PREFIX):
            continue
        by_op = defaultdict(int)
        for key, ps in tracereduce._self_times(events):
            by_op[key] += ps
        chips.append((dict(by_op), plane["ops"]))
    return chips


def by_scope(path):
    """{scope: seconds of self time}, a mean over the chips; None without a
    device plane that ran anything."""
    chips = self_times(path)
    if not chips:
        return None
    out = defaultdict(float)
    for by_op, ops in chips:
        for key, ps in by_op.items():
            out[scope_of(ops.get(key, {}).get("tf_op"))] += ps
    return {scope: ps / 1e12 / len(chips) for scope, ps in out.items()}


def scoped_seconds(path, scopes):
    """Seconds under ``scopes`` in the trace; None where the program names
    no operation at all (or nothing ran)."""
    times = by_scope(path)
    if not times or set(times) <= {UNSCOPED}:
        return None
    return sum(times.get(s, 0.0) for s in scopes)


def read(ctx, group):
    if not ctx["trace"]:
        return None
    from readers.idle_under import newest_xplane
    path = newest_xplane()
    if path is None:
        return None
    seconds = scoped_seconds(path, GROUPS[group])
    if not seconds:
        return None
    return 100.0 * seconds / sum(by_scope(path).values())


def table(path, largest=5):
    """The fine table, as lines of text."""
    chips = self_times(path)
    times = by_scope(path)
    if not times:
        return ["no device plane that ran anything"]
    busy = sum(times.values())
    group_of = {s: g for g, scopes in GROUPS.items() for s in scopes}
    ops = defaultdict(lambda: defaultdict(int))
    for by_op, records in chips:
        for key, ps in by_op.items():
            rec = records.get(key, {})
            what = (tracereduce.short_name(rec.get("name", "?")),
                    rec.get("source", ""))
            ops[scope_of(rec.get("tf_op"))][what] += ps
    out = [f"busy {busy:.4f} s on {len(chips)} chip(s)"]
    for g, scopes in GROUPS.items():
        share = 100.0 * sum(times.get(s, 0.0) for s in scopes) / busy
        out.append(f"{g:10s} {share:6.2f} %")
    for scope, s in sorted(times.items(), key=lambda kv: -kv[1]):
        out.append(f"{scope:18s} {s:9.4f} s {100.0 * s / busy:6.2f} %  "
                   f"[{group_of.get(scope, 'NOT IN ANY GROUP')}]")
        top = sorted(ops[scope].items(), key=lambda kv: -kv[1])[:largest]
        for (name, source), ps in top:
            out.append(f"    {ps / 1e12 / len(chips):9.4f} s  {name}  "
                       f"{source}")
    return out


if __name__ == "__main__":
    print("\n".join(table(sys.argv[1], *map(int, sys.argv[2:3]))))
