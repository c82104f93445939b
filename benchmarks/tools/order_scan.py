#!/usr/bin/env python3
"""Which pinned order of a sessions mix gives tails a bound can hold.  Host only.

    python3 benchmarks/tools/order_scan.py --mix docqa-trinity --orders 0:500
    python3 benchmarks/tools/order_scan.py --mix docqa-trinity --orders 319 --show

A mix with ``order_seed`` plays ONE trace whatever ``--seed`` is, so where in
the sorted list of its requests' times to first token the median (nearest
rank) falls is a property of the order.  In ``trinity-mini.docqa`` a
window's 36 requests are of two kinds: warm asks that meet no prefill chunk
(30-75 ms, set by the host's timing and by where in a decode step they
arrive: 10-20 % from run to run) and requests behind a document's chunks
(0.4-4 s, set by device time: about 1 %).  An order whose rank N/2 is the
edge of the first kind cannot hold a 10 % bound (order 23: PERF.md section 6,
PR 27).  This tool plays ``traffic.build_schedule`` through a model of
``engine/scheduler.py`` (decodes first, then chunks in flight, then the
queue in arrival order, 2,048 tokens a step; a later ask of a document hits
what earlier asks have computed, to the block) with a step time in five
parts, fitted to the two recorded plays of the cell at 0.25 and 0.28
documents/s (``chiprun_out/r1/plays-*``, PR 27: mean |log(model / measured)|
0.084 over 96 requests; isolated cold asks 0.057).  Each order is played
``--trials`` times with every request's arrival late by U(0, 15) ms and all
step times scaled by N(1, 0.3 %), under a set of model variants (all step
times -10..+15 %, the full layers' and the decode rows' cost apart): the
order's score is the widest quartile spread of either tail in any variant.
The scores of the best dozen orders differ by less than two scans of one
order do (16 trials).  The model is a design aid, not a measurement: what
the chip then reads is in PERF.md.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                     # benchmarks/

import traffic  # noqa: E402

# ms; fitted as the docstring says.  ``win``: one layer's prefill kernel over
# the padded [S, 2048] grid, + ``win_s`` a row of S; ``full_k``: a full
# layer's extra per 1k keys of context under a 2,048-query chunk; ``drow`` /
# ``dctx``: a decode row riding a mixed step (per row, per 1k of its
# context); ``f<S>``: the attention terms' factor at sequence bucket S.
FIT = {"dec": 11.7, "head": 8.0, "host": 3.0, "http": 6.0, "moe_tok": 0.01555,
       "win": 12.556, "win_s": 0.1701, "full_k": 5.3426, "drow": 0.7374,
       "dctx": 0.4332, "f8": 1.0, "f16": 0.8732, "f32": 0.6707, "f64": 0.6978}
DEVICE = ("win", "full_k", "moe_tok", "drow", "dctx")
VARIANTS = ([{k: 1 + p / 100 for k in DEVICE} for p in (-10, -5, 0, 5, 10, 15)]
            + [{"full_k": 0.85}, {"full_k": 1.3}, {"drow": 0.5, "dctx": 0.5},
               {"drow": 2.0, "dctx": 2.0}, {"dec": 1.15}, {"http": 3.0}])
BUDGET, BLOCK, MAX_SEQS, FAST_MS = 2048, 32, 64, 150.0


def bucket(n: int, lo: int, hi: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return min(b, hi)


def step_ms(rows, p) -> float:
    """rows: (new tokens, tokens already computed) of each row in the step."""
    if max(n for n, _ in rows) == 1:
        return p["dec"] + 0.05 * len(rows) + p["host"]
    s = bucket(len(rows), 8, MAX_SEQS)
    frac = bucket(max(n for n, _ in rows), 16, BUDGET) / BUDGET
    tokens = bucket(sum(n for n, _ in rows), 16, BUDGET)
    full = sum(p["full_k"] * (c + n / 2) / 1e3 * n / BUDGET if n > 1
               else (p["drow"] + p["dctx"] * c / 1e3) * frac for n, c in rows)
    attn = 8 * (p["win"] + p["win_s"] * s) * frac + 2 * full
    return attn * p[f"f{s}"] + p["moe_tok"] * tokens + p["head"] + p["host"]


def schedule(mix, rate: float, seconds: float):
    reqs = []
    for phase, secs, t0 in (("warmup", float(mix["warmup_seconds"]), 0.0),
                            ("window", seconds, float(mix["warmup_seconds"]))):
        for r in traffic.build_schedule(mix, 1, secs, phase, rate)["requests"]:
            reqs.append({"phase": phase, "due": t0 + r["due"],
                         "doc": (phase, r["session"]), "out": r["max_tokens"],
                         "doc_len": r["session_tokens"],
                         "len": r["session_tokens"] + r["own_tokens"]})
    return reqs


def play(reqs, p, rnd=None):
    """Times to first token (ms) of the window's requests, and the most
    rows ever in flight."""
    for r in reqs:
        late = rnd.uniform(0, 0.015) if rnd else 0.0
        r.update(done=0, gen=0, first=None, seen=False,
                 arrive=r["due"] + p["http"] / 1e3 + late)
    todo = sorted(reqs, key=lambda r: r["arrive"])
    waiting, running, cached = [], [], {}
    t, k, most = 0.0, 0, 0
    while k < len(todo) or waiting or running:
        while k < len(todo) and todo[k]["arrive"] <= t:
            waiting.append(todo[k])
            k += 1
        if not waiting and not running:
            t = todo[k]["arrive"]
            continue
        budget, rows = BUDGET, []
        for r in running:
            if r["done"] >= r["len"]:
                rows.append((r, 1))
                budget -= 1
        for r in running:
            if r["done"] < r["len"] and budget > 0:
                rows.append((r, min(r["len"] - r["done"], budget)))
                budget -= rows[-1][1]
        while waiting and budget > 0 and len(running) < MAX_SEQS:
            r = waiting.pop(0)
            if not r["seen"]:
                r["seen"] = True
                r["done"] = r["hit"] = (
                    min(cached.get(r["doc"], 0), r["doc_len"])
                    // BLOCK * BLOCK)
            running.append(r)
            rows.append((r, min(r["len"] - r["done"], budget)))
            budget -= rows[-1][1]
        most = max(most, len(running))
        t += step_ms([(n, r["done"]) for r, n in rows], p) / 1e3
        for r, n in rows:
            if r["done"] < r["len"]:
                r["done"] += n
                cached[r["doc"]] = max(cached.get(r["doc"], 0),
                                       min(r["done"], r["doc_len"]))
                if r["done"] >= r["len"]:
                    r["gen"], r["first"] = 1, t
            else:
                r["gen"] += 1
            if r["gen"] >= r["out"]:
                running.remove(r)
    return [(r["first"] - r["due"]) * 1e3 for r in reqs
            if r["phase"] == "window"], most


def nearest_rank(values, pct: float) -> float:
    v = sorted(values)
    return v[max(0, math.ceil(pct / 100 * len(v) - 1e-12) - 1)]


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def score(mix, order: int, rate: float, seconds: float, trials: int):
    """(widest quartile spread of p50 or p95 over the variants, medians of
    p50 and p95 and the count of fast requests under the fit, most rows)."""
    reqs = schedule(dict(mix, order_seed=order), rate, seconds)
    worst, base = 0.0, None
    for i, variant in enumerate(VARIANTS):
        p = {k: v * variant.get(k, 1.0) for k, v in FIT.items()}
        rnd = random.Random(1000 * order + i)
        p50, p95, fast, most = [], [], [], 0
        for _ in range(trials):
            q = dict(p)
            scale = 1 + rnd.gauss(0, 0.003)
            for key in DEVICE:
                q[key] *= scale
            tt, m = play(reqs, q, rnd)
            p50.append(nearest_rank(tt, 50))
            p95.append(nearest_rank(tt, 95))
            fast.append(sum(x < FAST_MS for x in tt))
            most = max(most, m)
        worst = max(worst, spread(p50), spread(p95))
        if all(v == 1.0 for v in variant.values()):
            base = (statistics.median(p50), statistics.median(p95),
                    min(fast), max(fast), most)
    return (worst,) + base


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mix", required=True)
    ap.add_argument("--orders", default="0:500",
                    help="a:b (half open) or a comma-separated list")
    ap.add_argument("--trials", type=int, default=16)
    ap.add_argument("--show", action="store_true",
                    help="print each order's trace under the fit")
    args = ap.parse_args()
    bench = os.path.dirname(HERE)
    with open(os.path.join(bench, "traffic", args.mix + ".json")) as f:
        mix = json.load(f)
    with open(os.path.join(os.path.dirname(bench), "BENCHMARK.json")) as f:
        seconds = float(json.load(f)["run_seconds"])
    rate = float(mix["rate_rps"])
    if ":" in args.orders:
        lo, hi = args.orders.split(":")
        orders = range(int(lo), int(hi))
    else:
        orders = [int(o) for o in args.orders.split(",")]
    rows = []
    for order in orders:
        if args.show:
            reqs = schedule(dict(mix, order_seed=order), rate, seconds)
            tt, most = play(reqs, FIT)
            for r in reqs:
                print(f"  {r['phase']:6s} due {r['due']:6.2f} document "
                      f"{r['doc'][1]} tokens {r['len']:5d} cached "
                      f"{r['hit']:5d} ttft "
                      f"{(r['first'] - r['due']) * 1e3:8.1f}")
            print(f"order {order}: sorted ttft ms "
                  f"{[round(x) for x in sorted(tt)]}; most rows in flight "
                  f"{most}")
        rows.append((score(mix, order, rate, seconds, args.trials),
                     order))
    print("order  worst_spread_%  ttft_p50_ms  ttft_p95_ms  fast  rows")
    for (worst, p50, p95, f0, f1, most), order in sorted(rows):
        print(f"{order:5d}  {worst * 100:14.2f}  {p50:11.1f}  {p95:11.1f}  "
              f"{f0}-{f1}  {most}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
