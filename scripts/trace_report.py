#!/usr/bin/env python
"""llmd-trace report: trace JSONL -> waterfalls + per-phase attribution.

The analysis half of ``llm_d_tpu/utils/tracing.py``: feed it the JSONL a
component exported (``Tracer.export_jsonl`` / ``export_all_jsonl``) or a
``/debug/traces`` scrape, get

  - **per-request waterfalls**: the span tree laid out on one timeline,
    indented by parent/child depth — where a slow request actually
    spent its life (queue vs schedule vs prefill vs KV wire vs decode,
    retries and resume attempts inline);
  - **aggregate per-phase attribution**: p50/p99 per phase (optionally
    per SLO class) over every trace in the file — the decomposition
    ROADMAP item 2's PD TTFT bench metric consumes, and what
    ``generate_load.py --trace-export`` appends to its load report;
  - **TTFT decomposition**: for each trace, measured TTFT (root start
    -> the relay/server ``first_token`` event) split into the phase
    spans that precede it, plus the residual no phase claims
    (``other``: HTTP hops, serialization).  The chaos acceptance bar
    (tests/test_tracing.py) pins decomposed ~= measured within 5%.

Examples::

  python scripts/trace_report.py trace.jsonl                 # summary
  python scripts/trace_report.py trace.jsonl --by-class      # per SLO class
  python scripts/trace_report.py trace.jsonl --waterfalls 3  # slowest 3
  python scripts/trace_report.py trace.jsonl --trace <id>    # one request
  python scripts/trace_report.py trace.jsonl --json          # machine form

Zero dependencies beyond stdlib — usable on any scrape from any pod.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Iterable, List, Optional, Tuple

# Phases that make up TTFT (everything before the first token; "decode"
# and post-first-token "resume" legs are TPOT territory).  Mirrors
# llm_d_tpu.utils.tracing.PHASES without importing the package, so the
# report runs against a bare JSONL scrape on any machine.
TTFT_PHASES = ("queue", "schedule", "prefill", "transfer", "first_decode")
ALL_PHASES = TTFT_PHASES + ("decode", "resume")


# ---------------------------------------------------------------------------
# loading / indexing
# ---------------------------------------------------------------------------

def load_trace_lines(lines: Iterable[str]) -> List[Dict[str, Any]]:
    """Parse JSONL, skipping blank/garbled lines (a truncated scrape
    must not kill the report) and deduping by (trace, span) id — the
    /debug/traces endpoint returns every component ring in the process,
    and a multi-URL scrape of one process would double-collect."""
    spans: List[Dict[str, Any]] = []
    seen: set = set()
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if not isinstance(d, dict) or "trace" not in d or "span" not in d:
            continue
        key = (d["trace"], d["span"])
        if key in seen:
            continue
        seen.add(key)
        spans.append(d)
    return spans


def load_trace_file(path: str) -> List[Dict[str, Any]]:
    with open(path) as f:
        return load_trace_lines(f)


def group_traces(spans: Iterable[Dict[str, Any]]
                 ) -> Dict[str, List[Dict[str, Any]]]:
    """trace id -> spans sorted by start timestamp."""
    out: Dict[str, List[Dict[str, Any]]] = {}
    for s in spans:
        out.setdefault(s["trace"], []).append(s)
    for tid in out:
        out[tid].sort(key=lambda s: (s.get("ts") or 0.0, s["span"]))
    return out


def find_orphans(trace_spans: List[Dict[str, Any]]
                 ) -> List[Dict[str, Any]]:
    """Spans whose parent id is absent from the trace (roots excepted).
    A connected tree has none — the chaos acceptance bar asserts zero
    orphans across a kill+resume, proving the failover chain stayed
    causally linked under the original trace id."""
    ids = {s["span"] for s in trace_spans}
    return [s for s in trace_spans
            if s.get("parent") and s["parent"] not in ids]


def _depth(span: Dict[str, Any], by_id: Dict[str, Dict[str, Any]]) -> int:
    d, cur, hops = 0, span, 0
    while cur.get("parent") and cur["parent"] in by_id and hops < 64:
        cur = by_id[cur["parent"]]
        d += 1
        hops += 1
    return d


# ---------------------------------------------------------------------------
# TTFT decomposition
# ---------------------------------------------------------------------------

def first_token_ts(trace_spans: List[Dict[str, Any]]) -> Optional[float]:
    """Earliest ``first_token`` event timestamp in the trace (stamped by
    the streaming relays and the sim/engine prefill boundary)."""
    best: Optional[float] = None
    for s in trace_spans:
        for ev in s.get("events") or ():
            if ev.get("name") == "first_token" and ev.get("ts") is not None:
                if best is None or ev["ts"] < best:
                    best = ev["ts"]
    return best


def ttft_decomposition(trace_spans: List[Dict[str, Any]]
                       ) -> Optional[Dict[str, Any]]:
    """One trace's TTFT split by phase.

    measured = first_token event - root span start.  Each TTFT-phase
    span contributes its duration clamped to the pre-first-token window;
    the residual no phase claims is ``other`` (HTTP hops, json, relay
    scheduling).  Returns None when the trace has no root or no
    first_token mark (non-streaming scrape without server spans)."""
    if not trace_spans:
        return None
    root = min(trace_spans, key=lambda s: s.get("ts") or float("inf"))
    t_first = first_token_ts(trace_spans)
    if t_first is None or root.get("ts") is None:
        return None
    t0 = root["ts"]
    measured = max(0.0, t_first - t0)
    phases: Dict[str, float] = {}
    for s in trace_spans:
        phase = (s.get("attrs") or {}).get("phase")
        if phase not in TTFT_PHASES:
            continue
        ts, dur = s.get("ts"), s.get("dur")
        if ts is None or dur is None or ts > t_first:
            continue
        phases[phase] = phases.get(phase, 0.0) \
            + max(0.0, min(ts + dur, t_first) - max(ts, t0))
    attributed = sum(phases.values())
    return {
        "trace": root["trace"],
        "request_id": (root.get("attrs") or {}).get("request_id"),
        "criticality": (root.get("attrs") or {}).get("criticality"),
        "measured_ttft_s": round(measured, 6),
        "phases_s": {p: round(v, 6) for p, v in phases.items()},
        "attributed_s": round(attributed, 6),
        "other_s": round(max(0.0, measured - attributed), 6),
    }


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------

def percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[idx]


def phase_attribution(spans: Iterable[Dict[str, Any]],
                      by_class: bool = False
                      ) -> Dict[str, Dict[str, Dict[str, float]]]:
    """Aggregate per-phase p50/p99 over every phase span in the input.

    Returns {class: {phase: {n, p50_s, p99_s, total_s}}}; with
    ``by_class=False`` everything lands under class ``"all"``.  The SLO
    class is read from the span's own attrs, falling back to its
    trace root's — component spans (engine/sim) usually carry it, event
    spans may not."""
    traces = group_traces(spans)
    root_class: Dict[str, Optional[str]] = {}
    for tid, tspans in traces.items():
        # Earliest span; a child recorded at its parent's own timestamp
        # must not win the tie (file order is not stable).
        root = min(tspans, key=lambda s: (s.get("ts") or float("inf"),
                                          bool(s.get("parent"))))
        root_class[tid] = (root.get("attrs") or {}).get("criticality")
    buckets: Dict[str, Dict[str, List[float]]] = {}
    for tid, tspans in traces.items():
        for s in tspans:
            attrs = s.get("attrs") or {}
            phase = attrs.get("phase")
            if phase not in ALL_PHASES or s.get("dur") is None:
                continue
            cls = "all"
            if by_class:
                cls = (attrs.get("criticality")
                       or root_class.get(tid) or "unknown")
            buckets.setdefault(cls, {}).setdefault(
                phase, []).append(float(s["dur"]))
    out: Dict[str, Dict[str, Dict[str, float]]] = {}
    for cls, phases in sorted(buckets.items()):
        out[cls] = {}
        for phase in ALL_PHASES:
            vals = sorted(phases.get(phase, ()))
            if not vals:
                continue
            out[cls][phase] = {
                "n": len(vals),
                "p50_s": round(percentile(vals, 0.5), 6),
                "p99_s": round(percentile(vals, 0.99), 6),
                "total_s": round(sum(vals), 6),
            }
    return out


def render_attribution(table: Dict[str, Dict[str, Dict[str, float]]]
                       ) -> str:
    lines = [f"{'class':<12} {'phase':<14} {'n':>6} {'p50 ms':>10} "
             f"{'p99 ms':>10}"]
    for cls, phases in table.items():
        for phase, row in phases.items():
            lines.append(
                f"{cls:<12} {phase:<14} {row['n']:>6} "
                f"{row['p50_s'] * 1e3:>10.2f} {row['p99_s'] * 1e3:>10.2f}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# waterfalls
# ---------------------------------------------------------------------------

def render_waterfall(trace_spans: List[Dict[str, Any]],
                     width: int = 48) -> str:
    """One request's span tree on a shared timeline (ASCII bars)."""
    if not trace_spans:
        return "(empty trace)"
    by_id = {s["span"]: s for s in trace_spans}
    t0 = min(s["ts"] for s in trace_spans if s.get("ts") is not None)
    t1 = max((s["ts"] + (s.get("dur") or 0.0)) for s in trace_spans
             if s.get("ts") is not None)
    total = max(t1 - t0, 1e-9)
    root = min(trace_spans, key=lambda s: s.get("ts") or float("inf"))
    rid = (root.get("attrs") or {}).get("request_id") or "-"
    lines = [f"trace {root['trace']}  request_id={rid}  "
             f"total={total * 1e3:.1f} ms"]
    ordered = sorted(trace_spans,
                     key=lambda s: (s.get("ts") or 0.0,
                                    _depth(s, by_id), s["span"]))
    for s in ordered:
        ts, dur = s.get("ts"), s.get("dur") or 0.0
        if ts is None:
            continue
        off = int((ts - t0) / total * width)
        bar_len = max(1, int(dur / total * width))
        bar = " " * min(off, width) + "#" * min(bar_len, width - min(off, width) + 1)
        indent = "  " * _depth(s, by_id)
        attrs = s.get("attrs") or {}
        tag = attrs.get("phase") or attrs.get("endpoint") \
            or attrs.get("verdict") or ""
        events = "".join(f" !{ev.get('name')}" for ev in s.get("events") or ()
                         if ev.get("name") in ("retry", "resume",
                                               "first_token", "stream_stall"))
        lines.append(
            f"  {indent}{s['component']}.{s['name'].split('.')[-1]:<16}"
            f"[{bar:<{width}}] {dur * 1e3:>8.1f} ms {tag}{events}")
    orphans = find_orphans(trace_spans)
    if orphans:
        lines.append(f"  WARNING: {len(orphans)} orphan span(s) — "
                     "incomplete scrape or broken propagation")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def build_report(spans: List[Dict[str, Any]], by_class: bool = False
                 ) -> Dict[str, Any]:
    traces = group_traces(spans)
    decomp = [d for d in (ttft_decomposition(t) for t in traces.values())
              if d is not None]
    ttfts = sorted(d["measured_ttft_s"] for d in decomp)
    orphan_total = sum(len(find_orphans(t)) for t in traces.values())
    report: Dict[str, Any] = {
        "spans": len(spans),
        "traces": len(traces),
        "orphan_spans": orphan_total,
        "phase_attribution": phase_attribution(spans, by_class=by_class),
    }
    if decomp:
        # Aggregate decomposition: per-phase p50/p99 of the TTFT split.
        per_phase: Dict[str, List[float]] = {}
        for d in decomp:
            for p, v in d["phases_s"].items():
                per_phase.setdefault(p, []).append(v)
            per_phase.setdefault("other", []).append(d["other_s"])
        report["ttft"] = {
            "n": len(decomp),
            "p50_s": round(percentile(ttfts, 0.5), 6),
            "p99_s": round(percentile(ttfts, 0.99), 6),
            "decomposition": {
                p: {"p50_s": round(percentile(sorted(v), 0.5), 6),
                    "p99_s": round(percentile(sorted(v), 0.99), 6)}
                for p, v in sorted(per_phase.items())},
        }
    return report


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        "trace-report", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("files", nargs="+", help="trace JSONL file(s)")
    ap.add_argument("--trace", default=None,
                    help="render ONE trace's waterfall (id prefix ok)")
    ap.add_argument("--waterfalls", type=int, default=0,
                    help="render the N slowest requests' waterfalls")
    ap.add_argument("--by-class", action="store_true",
                    help="split the attribution table by SLO class")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    args = ap.parse_args(argv)

    lines: List[str] = []
    for path in args.files:
        with open(path) as f:
            lines.extend(f.read().splitlines())
    spans = load_trace_lines(lines)     # one parse, cross-file dedupe
    traces = group_traces(spans)

    if args.trace:
        hits = [t for tid, t in traces.items()
                if tid.startswith(args.trace)]
        if not hits:
            print(f"no trace matching {args.trace!r}", file=sys.stderr)
            return 1
        for t in hits:
            print(render_waterfall(t))
        return 0

    report = build_report(spans, by_class=args.by_class)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(f"{report['spans']} spans / {report['traces']} traces "
              f"({report['orphan_spans']} orphan spans)")
        if "ttft" in report:
            t = report["ttft"]
            print(f"TTFT p50 {t['p50_s'] * 1e3:.1f} ms / "
                  f"p99 {t['p99_s'] * 1e3:.1f} ms over {t['n']} requests")
            print("decomposition (p50 ms):  " + "  ".join(
                f"{p}={row['p50_s'] * 1e3:.1f}"
                for p, row in t["decomposition"].items()))
        print()
        print(render_attribution(report["phase_attribution"]))
    if args.waterfalls > 0 and not args.json:
        ranked = sorted(
            traces.values(),
            key=lambda t: -(max((s["ts"] + (s.get("dur") or 0.0))
                                for s in t if s.get("ts") is not None)
                            - min(s["ts"] for s in t
                                  if s.get("ts") is not None))
            if any(s.get("ts") is not None for s in t) else 0.0)
        for t in ranked[:args.waterfalls]:
            print()
            print(render_waterfall(t))
    return 0


if __name__ == "__main__":
    sys.exit(main())
