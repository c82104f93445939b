#!/usr/bin/env python3
"""Rehearse the benchmark on the CPU: no chip, no device number comes out.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py [--cell <workload>]

Checks, in order:
  1. the metric arithmetic on a hand-made record file (time to first token
     from the DUE time, pooled gaps with a multi-token frame, a failed
     request counted as a miss, tokens per second over the window);
  2. the traffic generator: every seed offers the same multiset of sizes
     and gaps, in another order; sessions (documents asked several times);
  3. the warm-up plan: every planned step lands in its own cell of the
     doubling grid, which today is one of the engine's bucket triples;
  4. the trace reduction on the small recorded ``.xplane.pb`` beside this
     file (recorded on a v5e by this harness, PR 23);
  5. ``BENCHMARK.json`` against the data files it names;
  6. one cell end to end at its tiny preset with ``--rehearse`` (both
     ``--trace`` modes), the last line held to the contract's keys and
     every metric prefixed ``cpu_rehearsal.``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import clientmetrics as cm  # noqa: E402
import traffic  # noqa: E402


def check(cond, what):
    if not cond:
        raise SystemExit(f"FAILED: {what}")
    print(f"ok: {what}")


def close(a, b, tol=1e-6):
    return abs(a - b) <= tol


def rec(**kw):
    base = {"phase": "window", "i": 0, "due": 0.0, "sent": 0.0,
            "first": None, "frames": [], "done": True, "cancelled": False,
            "status": 200, "error": None, "max_tokens": 0,
            "prompt_tokens": 8, "n_tokens": 0, "end": 1.0}
    base.update(kw)
    base["n_tokens"] = sum(n for _, n in base["frames"])
    return base


def arithmetic() -> None:
    header = {"window": [10.0, 20.0], "epoch0": 0.0, "loop": "open"}
    records = [
        # due 10.0, sent late at 10.2, first token 10.5: TTFT 500 ms.
        rec(i=0, due=10.0, sent=10.2, first=10.5, max_tokens=4,
            frames=[[10.5, 1], [10.6, 1], [10.9, 2]], end=10.9),
        # on time; TTFT 100 ms; one gap of 50 ms.
        rec(i=1, due=11.0, sent=11.0, first=11.1, max_tokens=2,
            frames=[[11.1, 1], [11.15, 1]], end=11.15),
        # refused: counts as failed and as worse than any other.
        rec(i=2, due=12.0, sent=12.0, status=503, done=False,
            error="draining", max_tokens=4, end=12.01),
        # a warm-up request: never measured.
        rec(i=0, phase="warmup", due=5.0, sent=5.0, first=5.1,
            max_tokens=1, frames=[[5.1, 1]], end=5.1),
    ]
    tt, bad = cm.ttfts_ms(header, records)
    check(bad == 1 and close(tt[0], 500.0) and close(tt[1], 100.0)
          and math.isinf(tt[2]),
          "time to first token runs from the due time; a refused request "
          "is +inf")
    check(cm.counts(header, records) == {"attempted": 3, "failed": 1},
          "attempted / failed count the measured requests only")
    gv, gw = cm.gaps_ms(header, records)
    # request 0: 100 ms x1, then a 2-token frame after 300 ms = 150 ms x2;
    # request 1: 50 ms x1.
    check(all(close(g, want) and w == n for (g, w), (want, n) in zip(
        sorted(zip(gv, gw)), [(50.0, 1.0), (100.0, 1.0), (150.0, 2.0)])),
          "a frame that carries n tokens counts n gaps of interval / n")
    m = cm.end_to_end(header, records, ["ttft_p50_ms", "ttft_p95_ms",
                                        "itl_p95_ms", "out_tok_s"])
    check(close(m["ttft_p50_ms"], 500.0),
          "median of (100, 500, failed) is 500")
    check(m["ttft_p95_ms"] > 500.0 and math.isfinite(m["ttft_p95_ms"]),
          "a percentile that lands on a failed request is worse than any "
          "that succeeded, and finite")
    check(close(m["itl_p95_ms"], 150.0), "pooled 95th percentile gap")
    from readers import client_gap
    check(client_gap.read({"header": header, "records": records}, "p95")
          == m["itl_p95_ms"],
          "the client_gap reader is the end-to-end gap arithmetic")
    check(close(m["out_tok_s"], 6 / 10.0),
          "tokens per second: every token inside the window over its length")
    check(close(cm.percentile([1, 2, 3, 4], 50), 2)
          and close(cm.percentile([1, 2, 3, 4], 95), 4),
          "nearest-rank percentile")
    late = cm.lateness_ms(records)
    check(close(max(late), 200.0), "generator lateness is sent - due")
    # closed loop: a request cut by the window's end is no failure once
    # its first token is in; gaps count only frames inside the window.
    hc = dict(header, loop="closed")
    cut = rec(i=3, due=19.0, sent=19.0, first=19.2, max_tokens=50,
              done=False, cancelled=True, frames=[[19.2, 1], [20.5, 1]])
    check(not cm.failed(cut, "closed") and cm.failed(cut, "open"),
          "a closed loop's own cut is not a failure; a drain timeout is")
    gv, _ = cm.gaps_ms(hc, [cut])
    check(gv == [], "closed loop: a frame after the window is not counted")
    check(close(cm.spread([10, 11, 12, 13, 14, 15]), 3.5 / 12.5),
          "spread is (Q3 - Q1) / median with statistics.quantiles")


def generator() -> None:
    for name in sorted(os.listdir(os.path.join(HERE, "traffic"))):
        with open(os.path.join(HERE, "traffic", name)) as f:
            mix = json.load(f)
        a = traffic.build_schedule(mix, 1, 30, "window")["requests"]
        b = traffic.build_schedule(mix, 2**31 + 77, 30, "window")["requests"]
        same = all(sorted(r[k] for r in a) == sorted(r[k] for r in b)
                   for k in ("own_tokens", "max_tokens"))
        reordered = ([r["own_tokens"] for r in a]
                     != [r["own_tokens"] for r in b])
        pinned = "order_seed" in mix
        check(same and reordered != pinned and len(a) == len(b),
              f"traffic/{name}: two seeds, the same sizes in "
              + ("the mix's pinned order" if pinned else "another order"))
        if mix["loop"] == "open":
            def gaps(reqs):
                return sorted(round(y["due"] - x["due"], 9)
                              for x, y in zip(reqs, reqs[1:]))
            check(a[-1]["due"] < 30 and len(a) == round(
                mix["rate_rps"] * 30),
                f"traffic/{name}: rate x seconds requests, all due in 30 s")
            # The last gap runs past the window and is not in the list.
            check(len(set(gaps(a)) ^ set(gaps(b))) <= 2,
                  f"traffic/{name}: the same gaps in another order")
        req = {"own_tokens": 5}
        p1 = traffic.prompt_ids([1, 2], req, 7, "window", 3, 100)
        check(p1 == traffic.prompt_ids([1, 2], req, 7, "window", 3, 100)
              and p1 != traffic.prompt_ids([1, 2], req, 8, "window", 3, 100),
              f"traffic/{name}: token ids are a function of the seed")
    # Sessions (documents asked several times) are data too: the docqa
    # mixes of PERF.md's Open questions need no new code.
    mix = {"loop": "open", "rate_rps": 0.5,
           "prompt_tokens": {"dist": "loguniform", "min": 32, "max": 128},
           "output_tokens": {"dist": "loguniform", "min": 32, "max": 128},
           "sessions": {"asks": 4, "ask_gap_s": 5.0, "prefix_tokens": {
               "dist": "loguniform", "min": 8192, "max": 16384}}}
    a = traffic.build_schedule(mix, 1, 40, "window")["requests"]
    b = traffic.build_schedule(mix, 2, 40, "window")["requests"]
    by = {}
    for r in a:
        by.setdefault(r["session"], []).append(r)
    check(len(a) == len(b) == 80 and len(by) == 20
          and all(len(v) == 4 and len({r["session_tokens"] for r in v}) == 1
                  for v in by.values())
          and all(0 <= r["due"] < 40 for r in a)
          and a == sorted(a, key=lambda r: r["due"])
          and sorted(r["session_tokens"] for r in a)
          == sorted(r["session_tokens"] for r in b),
          "sessions: rate x seconds documents, each asked 4 times inside "
          "the window, the same documents for every seed")
    ids = [traffic.prompt_ids([9], r, 7, "window", i, 1000)
           for i, r in enumerate(by[0])]
    n = by[0][0]["session_tokens"]
    check(all(p[:1 + n] == ids[0][:1 + n] for p in ids)
          and len({tuple(p[1 + n:]) for p in ids}) == 4
          and traffic.prompt_ids([9], by[1][0], 7, "window", 0, 1000)[1:9]
          != ids[0][1:9],
          "sessions: the asks of one document share it and nothing else; "
          "another document shares nothing")


def warm_plan() -> None:
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(HERE, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    for max_prompt in (4352, 512, 40):
        plan = run.plan_warm_steps(16, 2048, 8, 64, max_prompt)
        bad = 0
        for it in plan:
            n, lens = it["n_dec"], it["lens"]
            got = (run.bucket_of(n + sum(lens), 16, 2048),
                   run.bucket_of(n + len(lens), 8, 64),
                   run.bucket_of(max(lens), 16, 2048)
                   if lens and max(lens) > 1 else 1)
            bad += got != tuple(it["shape"])
        check(bad == 0 and len({tuple(i["shape"]) for i in plan})
              == len(plan),
              f"warm-up plan for prompts <= {max_prompt}: {len(plan)} "
              f"steps, each in its own bucket triple")


def trace_reduction() -> None:
    import tracereduce
    path = os.path.join(HERE, "testdata", "v5e_slice.xplane.pb")
    check(os.path.exists(path), "the recorded trace is beside the script")
    tr = tracereduce.reduce_trace(path)
    check(tr is not None and tr["chips"] == 1,
          "the recorded trace has one device plane with operations")
    check(0 < tr["busy_s"] <= tr["window_s"],
          f"0 < busy {tr['busy_s']:.4f}s <= window {tr['window_s']:.4f}s")
    ops = tr["breakdown"]["device_ops"]
    check(0 < len(ops) <= 10 and all(s >= 0 for _, s in ops)
          and sum(s for _, s in ops) <= tr["busy_s"] * 1.0001,
          "top operations: self times, at most ten, within the busy time")
    check(sorted(tracereduce._self_times(
        [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"), (50, 60, "c")]))
        == [("a", 20), ("b", 40), ("c", 10), ("while", 30)],
        "self time leaves out nested operations")
    check(tracereduce._union([(0, 10), (5, 20), (30, 40)]) == 30,
          "busy time is a union of intervals")


def benchmark_json() -> None:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        print("skipped: no BENCHMARK.json yet")
        return
    with open(path) as f:
        b = json.load(f)
    e2e = {m["name"] for m in b["end_to_end"]}
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        check(conf["reduced"] == c["reduced"] and conf["source"]
              == c["source"], f"config {c['name']}: file agrees on source "
              f"and reduced")
    for w in b["workloads"]:
        check(os.path.exists(os.path.join(
            HERE, "traffic", w["traffic"] + ".json")),
            f"cell {w['name']}: its mix has a file")
    for m in b["per_layer"]:
        with open(os.path.join(HERE, "layer_metrics",
                               m["name"] + ".json")) as f:
            d = json.load(f)
        check(all(d[k] == m[k] for k in ("unit", "better", "layer", "moves",
                                          "source")) and m["moves"] in e2e,
              f"layer metric {m['name']}: file and entry agree")
        moved = next(x for x in b["end_to_end"] if x["name"] == m["moves"])
        cells = set(m.get("workloads", [w["name"] for w in b["workloads"]]))
        check("workloads" not in moved or cells <= set(moved["workloads"]),
              f"layer metric {m['name']}: {m['moves']} is reported in "
              f"every cell where it is")
        check(os.path.exists(os.path.join(
            HERE, "readers", d["reader"] + ".py")),
            f"layer metric {m['name']}: reader {d['reader']} exists")


def end_to_end(cell: str) -> None:
    for trace in (0, 1):
        out = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload",
             cell, "--seed", str(2**31 + 12345), "--seconds", "5",
             "--trace", str(trace), "--rehearse"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        check(out.returncode == 0, f"{cell} --trace {trace}: exit 0")
        last = json.loads(out.stdout.strip().splitlines()[-1])
        check({"correct", "attempted", "failed", "metrics", "device"}
              <= set(last), "the last line has the contract's keys")
        check(last["correct"] is True and last["failed"] == 0
              and last["attempted"] > 0,
              f"correct, {last['attempted']} attempted, none failed")
        check(last["metrics"] and all(
            k.startswith("cpu_rehearsal.") and set(v) == {"value", "unit"}
            and math.isfinite(v["value"])
            for k, v in last["metrics"].items()),
            "every metric is prefixed cpu_rehearsal. and finite: "
            + ", ".join(sorted(last["metrics"])))
        check(last["device"]["platform"] == "cpu",
              "the device is named as JAX reports it")
    with tempfile.TemporaryDirectory() as d:
        out = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload",
             cell, "--seed", "1", "--seconds", "5", "--trace", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=d)
    check(out.returncode != 0 and not out.stdout.strip(),
          "without --rehearse and without a TPU the run fails and prints "
          "no result")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="kanana2.batch")
    ap.add_argument("--skip-run", action="store_true")
    args = ap.parse_args()
    arithmetic()
    generator()
    warm_plan()
    trace_reduction()
    benchmark_json()
    if not args.skip_run:
        end_to_end(args.cell)
    print("rehearsal passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
