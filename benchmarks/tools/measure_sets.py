#!/usr/bin/env python3
"""Run a cell's sets of runs one after the other and save each result line.

    python3 benchmarks/tools/measure_sets.py --workload <name> --seconds <s> \
        [--sets 2] [--runs 6] [--seed-base 1000] [--traced 1]

The same seeds in every set (seed-base + a large offset + i), each run a new
process, as the driver makes them.  This tool never touches JAX: the chip is
each child's in turn.  Result lines go to
``chiprun_out/sets-<workload>.jsonl`` (one JSON object per run, with ``set``,
``seed`` and ``rc`` added) and the spreads are printed with ``spread.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed-base", type=int, default=1000)
    ap.add_argument("--traced", type=int, default=1,
                    help="traced runs after the sets (their own seeds)")
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir,
                        f"sets-{args.workload}.jsonl")
    plan = [(s, 2**31 + args.seed_base + i, 0)
            for s in range(args.sets) for i in range(args.runs)]
    plan += [(-1, 2**31 + args.seed_base + 500 + i, 1)
             for i in range(args.traced)]
    with open(path, "a") as f:
        for set_no, seed, trace in plan:
            t0 = time.time()
            p = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
                 "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(args.seconds), "--trace", str(trace)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                cwd=ROOT)
            lines = p.stdout.strip().splitlines()
            try:
                row = json.loads(lines[-1]) if lines else {}
            except ValueError:
                row = {}
            row.update(set=set_no, seed=seed, trace=trace, rc=p.returncode,
                       wall_s=time.time() - t0)
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(f"set {set_no} seed {seed} trace {trace}: rc "
                  f"{p.returncode}, {row['wall_s']:.0f}s wall, correct "
                  f"{row.get('correct')}", flush=True)
            for x in p.stderr.splitlines():
                if any(k in x for k in ("warm-up steps", "window done",
                                        "Compiling", "a step of",
                                        "NOT CORRECT", "reference:",
                                        "vs plain", "decode vs")):
                    print("   |" + x[:400], flush=True)
            if p.returncode != 0 or not row.get("correct"):
                tail = [x for x in p.stderr.splitlines()
                        if not x.startswith(("WARNING", "I0000", "W0000"))]
                print("\n".join(tail[-25:]), flush=True)
    subprocess.run([sys.executable, os.path.join(HERE, "spread.py"), path])
    return 0


if __name__ == "__main__":
    sys.exit(main())
