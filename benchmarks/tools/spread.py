#!/usr/bin/env python3
"""From saved result lines (measure_sets.py) to medians, spreads and bounds.

    python3 benchmarks/tools/spread.py chiprun_out/sets-<workload>.jsonl

Per metric and set: the median and the spread, (Q3 - Q1) / median with
``statistics.quantiles(values, n=4)``.  The bound to write is about five
times the WIDER of the two sets' spreads, never under 1 %.  ``setup_s`` is
shown without its set's first run (the one that compiles).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from clientmetrics import spread  # noqa: E402


def main(path: str) -> int:
    rows = [json.loads(x) for x in open(path) if x.strip()]
    sets = defaultdict(lambda: defaultdict(list))
    for i, r in enumerate(rows):
        if r.get("rc") != 0 or r.get("trace"):
            continue
        for name, m in r["metrics"].items():
            sets[name][r["set"]].append(m["value"])
    for r in rows:
        if r.get("trace") and r.get("rc") == 0:
            print("traced:", json.dumps(
                {k: round(v["value"], 4) for k, v in r["metrics"].items()}),
                "idle share %.1f %%" % (100 * (
                    1 - r["device"]["busy_s"] / r["device"]["window_s"])))
    bad = [r for r in rows if r.get("rc") != 0 or not r.get("correct")]
    print(f"{len(rows)} runs, {len(bad)} failed or not correct")
    for name, by_set in sorted(sets.items()):
        widest = 0.0
        for s, vals in sorted(by_set.items()):
            if name == "setup_s" and len(vals) > 1 and s == min(by_set):
                vals = vals[1:]          # the first run of all compiled
            if len(vals) < 3:
                print(f"{name} set {s}: {vals} (too few for quartiles)")
                continue
            sp = spread(vals)
            widest = max(widest, sp)
            print(f"{name} set {s}: n {len(vals)} median "
                  f"{statistics.median(vals):.4f} min {min(vals):.4f} max "
                  f"{max(vals):.4f} spread {100 * sp:.2f} %")
        print(f"{name}: widest spread {100 * widest:.2f} % -> bound about "
              f"{max(0.01, 5 * widest):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
