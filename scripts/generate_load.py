#!/usr/bin/env python
"""Load / error generator against a gateway or model server.

The reference's monitoring playbook ships a load-and-error generator to
populate dashboards and exercise error paths
(docs/monitoring/scripts/generate-load-llmd.sh); this is that tool for the
TPU stack, plus prefix-affinity and SLO-header traffic shapes so the
scheduler's scorers and shed path light up.

Examples:
  python scripts/generate_load.py --url http://gw:8000 --qps 5 --duration 60
  python scripts/generate_load.py --url http://gw:8000 --shape prefix \
      --prefix-groups 4            # warms the prefix scorers
  python scripts/generate_load.py --url http://gw:8000 --shape slo \
      --slo-ttft-ms 200 --error-rate 0.1
  python scripts/generate_load.py --url http://gw:8000 --qps 10 \
      --faults malformed:0.1,abort:0.05,timeout:0.02   # chaos traffic
  python scripts/generate_load.py --url http://gw:8000 --deadline-ms 800 \
      --criticality-mix critical:0.2,standard:0.6,sheddable:0.2
      # lifecycle traffic: per-class p50/p99 + deadline-miss rate
  python scripts/generate_load.py --url http://gw:8000 --stream --qps 10
      # SSE streams with the continuity oracle: stream_breaks and
      # continuity_errors in the summary must be 0 under mid-stream
      # recovery chaos (see docs/resilience.md).  The oracle accepts
      # multi-token chunks (spec-decode servers emit one frame per
      # engine step) and the summary reports accepted_tokens_per_step
  python scripts/generate_load.py --url http://gw:8000 --qps 10 \
      --tenants acme:3,bulk:1 --shape prefix
      # multi-tenant traffic: each request is billed to a weighted-drawn
      # tenant (x-llmd-tenant) and, under --shape prefix, draws from that
      # TENANT'S prefix pool — cross-tenant prompts never share prefixes,
      # so prefix-cache hit rates and the per-tenant SLO scoreboards
      # (sim/cluster.py) see realistic isolation
  python scripts/generate_load.py --url http://gw:8000 --qps 10 \
      --tenants acme:3,bulk:1 --trace-out /tmp/workload.jsonl
      # record the issued workload as a replayable trace (JSONL of
      # {at_s, tenant, prompt, max_tokens, criticality, deadline_ms}) —
      # the SAME records a cluster-sim scenario's "trace" field replays
      # (docs/cluster-sim.md), so a live-gateway campaign can be re-run
      # deterministically inside the simulator
  python scripts/generate_load.py --url http://gw:8000 \
      --trace-replay /tmp/workload.jsonl --trace-speed 2.0
      # trace-driven mode: replay a recorded workload against a live
      # gateway at 2x speed (arrival times honored, not --qps)
  python scripts/generate_load.py --url http://gw:8000 --qps 10 \
      --trace-export /tmp/run.jsonl
      # post-run: scrape /debug/traces from the gateway (and any
      # --trace-urls), write the span JSONL, and append the llmd-trace
      # per-phase attribution table (p50/p99 per SLO class) to the
      # summary — TTFT decomposition instead of eyeballed math
      # (analyze further with scripts/trace_report.py)

Client-side fault kinds (--faults kind:rate[,kind:rate...], mirroring the
reference error-injection load script):
  malformed  invalid request body (error handling / 400 path)
  abort      client disconnects mid-stream (sidecar/_relay + engine abort)
  timeout    50ms client timeout (slow-upstream / hung-client path)
"""

from __future__ import annotations

import argparse
import asyncio
import json
import pathlib
import random
import sys
import time

import aiohttp

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import trace_report  # noqa: E402  (sibling script: the span analyzer)

from llm_d_tpu.server.stream_resume import (  # noqa: E402
    parse_stream_payload,
    verify_continuity,
)
from llm_d_tpu.utils.lifecycle import (  # noqa: E402
    CRITICALITY_HEADER,
    DEADLINE_EXCEEDED_HEADER,
    DEADLINE_MS_HEADER,
    KV_PLACEMENT_HEADER,
    TENANT_HEADER,
)

WORDS = ("tpu mesh shard flash ring latent expert router block cache "
         "prefill decode gateway").split()


def pick_criticality(mix: list, rng: random.Random) -> str:
    """Weighted class draw from the --criticality-mix distribution."""
    r = rng.random() * sum(w for _, w in mix)
    for cls, w in mix:
        r -= w
        if r < 0:
            return cls
    return mix[-1][0]


def make_body(args, rng: random.Random, tenant: str = "") -> tuple:
    headers = {}
    criticality = "standard"
    if args.criticality_list:
        criticality = pick_criticality(args.criticality_list, rng)
        headers[CRITICALITY_HEADER] = criticality
    if args.deadline_ms > 0:
        headers[DEADLINE_MS_HEADER] = str(args.deadline_ms)
    if tenant:
        headers[TENANT_HEADER] = tenant
    if args.shape == "prefix":
        # Prefix pools are PER TENANT: "acme pool-2 ..." never collides
        # with "bulk pool-2 ...", so multi-tenant runs exercise the real
        # cache-isolation shape instead of one global warm pool.
        group = rng.randrange(args.prefix_groups)
        pool = f"{tenant} pool-{group} " if tenant \
            else f"shared-prefix-{group} "
        prompt = (pool * args.prefix_len
                  + " ".join(rng.choices(WORDS, k=4)))
    else:
        prompt = " ".join(rng.choices(WORDS, k=args.prompt_words))
    body = {"model": args.model, "prompt": prompt,
            "max_tokens": args.max_tokens, "temperature": args.temperature}
    if args.shape == "slo":
        headers["x-prediction-based-scheduling"] = "true"
        headers["x-slo-ttft-ms"] = str(args.slo_ttft_ms)
        headers["x-slo-tpot-ms"] = str(args.slo_tpot_ms)
        if not args.criticality_list and rng.random() < 0.3:
            body["priority"] = -1              # sheddable tier
    if rng.random() < args.error_rate:
        body = {"prompt": None, "max_tokens": "boom"}   # error traffic
    return body, headers, criticality


def parse_criticality_mix(spec: str) -> list:
    """"class:weight[,class:weight...]" -> [(class, weight)]; bad entries
    dropped (the load tool must not die on a typo mid-campaign)."""
    out = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        cls, _, weight = entry.partition(":")
        cls = cls.strip()
        if cls not in ("critical", "standard", "sheddable"):
            print(f"--criticality-mix: dropping unknown class {entry!r}")
            continue
        try:
            out.append((cls, float(weight or 1.0)))
        except ValueError:
            print(f"--criticality-mix: dropping malformed entry {entry!r}")
    return out


def parse_tenant_mix(spec: str) -> list:
    """"tenant:weight[,tenant:weight...]" -> [(tenant, weight)]; bad
    entries dropped."""
    out = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        tenant, _, weight = entry.partition(":")
        tenant = tenant.strip()
        if not tenant:
            continue
        try:
            out.append((tenant, float(weight or 1.0)))
        except ValueError:
            print(f"--tenants: dropping malformed entry {entry!r}")
    return out


def pick_tenant(mix: list, rng: random.Random) -> str:
    if not mix:
        return ""
    r = rng.random() * sum(w for _, w in mix)
    for tenant, w in mix:
        r -= w
        if r < 0:
            return tenant
    return mix[-1][0]


def load_trace(path: str) -> list:
    """Read a replayable workload trace (JSONL of {at_s, tenant, prompt,
    max_tokens, criticality, deadline_ms} — the format --trace-out emits
    and a cluster-sim scenario's "trace" field consumes).  Malformed
    lines are dropped with a note."""
    records = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
                rec["at_s"] = float(rec.get("at_s", 0.0))
                records.append(rec)
            except (ValueError, TypeError, AttributeError):
                print(f"--trace-replay: dropping malformed line {i + 1}")
    records.sort(key=lambda r: r["at_s"])
    return records


def parse_faults(spec: str) -> dict:
    """"kind:rate[,kind:rate...]" -> {kind: rate}; bad entries dropped."""
    out = {}
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        kind, _, rate = entry.partition(":")
        try:
            out[kind.strip()] = float(rate)
        except ValueError:
            print(f"--faults: dropping malformed entry {entry!r}")
    return out


def pick_fault(faults: dict, rng: random.Random):
    for kind, rate in faults.items():
        if rng.random() < rate:
            return kind
    return None


def note_kv_verdict(stats: dict, tenant: str, resp) -> None:
    """Fold the gateway's x-llmd-kv-placement response marker into the
    campaign stats — globally and per tenant (the tenant's prefix pool
    is the reuse "session") — so a live-gateway run reports the same
    local_hit / peer_restore / recompute mix as the cluster-sim
    scoreboard's ``kv_verdicts`` field."""
    verdict = resp.headers.get(KV_PLACEMENT_HEADER)
    if not verdict:
        return
    kv = stats.setdefault("kv_verdicts", {})
    kv[verdict] = kv.get(verdict, 0) + 1
    if tenant:
        tkv = stats.setdefault("per_tenant", {}).setdefault(
            tenant, {"requests": 0}).setdefault("kv_verdicts", {})
        tkv[verdict] = tkv.get(verdict, 0) + 1


async def one_request(session, args, rng, stats, tenant: str = "",
                      override: dict | None = None) -> None:
    if override is not None:
        # Trace-replay record: the request IS the record, verbatim.
        tenant = str(override.get("tenant", "") or "")
        criticality = str(override.get("criticality", "standard"))
        headers = {}
        if tenant:
            headers[TENANT_HEADER] = tenant
        if criticality != "standard":
            headers[CRITICALITY_HEADER] = criticality
        if override.get("deadline_ms"):
            headers[DEADLINE_MS_HEADER] = str(override["deadline_ms"])
        body = {"model": args.model,
                "prompt": str(override.get("prompt", "replay")),
                "max_tokens": int(override.get("max_tokens",
                                               args.max_tokens)),
                "temperature": args.temperature}
    else:
        body, headers, criticality = make_body(args, rng, tenant)
    if getattr(args, "trace_out", None) is not None:
        stats.setdefault("_trace", []).append({
            "at_s": round(time.monotonic() - stats["_t0"], 4),
            "tenant": tenant, "prompt": body.get("prompt"),
            "max_tokens": body.get("max_tokens"),
            "criticality": criticality,
            "deadline_ms": args.deadline_ms or None})
    fault = pick_fault(args.fault_map, rng)
    cls = stats.setdefault("per_class", {}).setdefault(
        criticality, {"latencies": [], "deadline_miss": 0, "requests": 0})
    cls["requests"] += 1
    if tenant:
        stats.setdefault("per_tenant", {}).setdefault(
            tenant, {"requests": 0})["requests"] += 1
    t0 = time.perf_counter()
    try:
        if fault == "malformed":
            body = {"prompt": None, "max_tokens": "boom"}
        kw = {}
        if fault == "timeout":
            kw["timeout"] = aiohttp.ClientTimeout(total=0.05)
        if fault == "abort":
            body = dict(body, stream=True)
            async with session.post(f"{args.url}/v1/completions", json=body,
                                    headers=headers) as resp:
                # Read one chunk then slam the connection shut: exercises
                # the sidecar/_relay + engine abort-on-disconnect path.
                async for _chunk in resp.content.iter_any():
                    break
                resp.close()
            stats["aborted"] = stats.get("aborted", 0) + 1
        elif getattr(args, "stream", False):
            # Streaming with the continuity oracle: every token index
            # 0..n-1 must arrive exactly once ([DONE] must close it) —
            # a mid-stream failover that duplicates or drops a token is
            # a continuity error; a missing [DONE] is a stream break.
            body = dict(body, stream=True)
            async with session.post(f"{args.url}/v1/completions", json=body,
                                    headers=headers, **kw) as resp:
                try:
                    payload = await resp.read()
                    broke = False
                except aiohttp.ClientError:
                    # Abrupt mid-stream connection break (the fail-fast
                    # contract's shape): as much a stream break as a
                    # clean EOF without [DONE].
                    payload = b""
                    broke = True
                stats[resp.status] = stats.get(resp.status, 0) + 1
                note_kv_verdict(stats, tenant, resp)
                if resp.status == 504 or resp.headers.get(
                        DEADLINE_EXCEEDED_HEADER):
                    cls["deadline_miss"] += 1
                if resp.status == 200:
                    _text, metas, done = parse_stream_payload(payload)
                    problems = verify_continuity(metas)
                    if broke or not done:
                        stats["stream_breaks"] = \
                            stats.get("stream_breaks", 0) + 1
                    if problems:
                        stats["continuity_errors"] = \
                            stats.get("continuity_errors", 0) + len(problems)
                        print(f"continuity: {problems}")
                    # Accepted-tokens-per-step: a spec-decode server
                    # emits each engine step's accepted run as ONE
                    # multi-token frame, so tokens-per-token-chunk IS
                    # the accepted throughput multiplier (1.0 = no
                    # speculation).  The oracle above is chunk-size
                    # agnostic either way.
                    sizes = [len(m.get("tok") or []) for m in metas
                             if m.get("tok")]
                    stats["token_chunks"] = \
                        stats.get("token_chunks", 0) + len(sizes)
                    stats["chunk_tokens"] = \
                        stats.get("chunk_tokens", 0) + sum(sizes)
        else:
            async with session.post(f"{args.url}/v1/completions", json=body,
                                    headers=headers, **kw) as resp:
                await resp.read()
                stats[resp.status] = stats.get(resp.status, 0) + 1
                note_kv_verdict(stats, tenant, resp)
                if resp.status == 504 or resp.headers.get(
                        DEADLINE_EXCEEDED_HEADER):
                    cls["deadline_miss"] += 1
    except Exception:
        stats["error"] = stats.get("error", 0) + 1
    dt = time.perf_counter() - t0
    stats.setdefault("latencies", []).append(dt)
    cls["latencies"].append(dt)


async def run(args) -> None:
    rng = random.Random(args.seed)
    stats: dict = {"_t0": time.monotonic()}
    deadline = time.monotonic() + args.duration
    interval = 1.0 / args.qps
    async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=120)) as session:
        pending = set()
        if args.trace_replay:
            # Trace-driven: arrival times come from the recorded trace
            # (scaled by --trace-speed), not --qps/--duration.
            t0 = time.monotonic()
            for rec in load_trace(args.trace_replay):
                due = t0 + rec["at_s"] / max(args.trace_speed, 1e-9)
                delay = due - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                pending.add(asyncio.create_task(
                    one_request(session, args, rng, stats, override=rec)))
                pending = {t for t in pending if not t.done()}
        else:
            while time.monotonic() < deadline:
                pending.add(asyncio.create_task(
                    one_request(session, args, rng, stats,
                                tenant=pick_tenant(args.tenant_list, rng))))
                pending = {t for t in pending if not t.done()}
                await asyncio.sleep(interval)
        if pending:
            await asyncio.gather(*pending, return_exceptions=True)
    def pct(sorted_lats, q):
        return (sorted_lats[min(int(q * len(sorted_lats)),
                                len(sorted_lats) - 1)]
                if sorted_lats else 0.0)

    stats.pop("_t0", None)
    trace_records = stats.pop("_trace", [])
    if args.trace_out is not None:
        with open(args.trace_out, "w") as f:
            for rec in trace_records:
                f.write(json.dumps(rec) + "\n")
    per_tenant = stats.pop("per_tenant", {})
    lats = sorted(stats.pop("latencies", []))
    per_class = {}
    for cls, c in stats.pop("per_class", {}).items():
        cl = sorted(c["latencies"])
        per_class[cls] = {
            "requests": c["requests"],
            "latency_p50_s": round(pct(cl, 0.5), 4),
            "latency_p99_s": round(pct(cl, 0.99), 4),
            "deadline_miss_rate": round(
                c["deadline_miss"] / c["requests"], 4)
            if c["requests"] else 0.0,
        }
    kv_verdicts = stats.pop("kv_verdicts", {})
    breaks = stats.pop("stream_breaks", 0)
    cont_errors = stats.pop("continuity_errors", 0)
    n_chunks = stats.pop("token_chunks", 0)
    n_chunk_tokens = stats.pop("chunk_tokens", 0)
    summary = {
        "requests": sum(v for v in stats.values()),
        "status_counts": stats,
        "latency_p50_s": round(pct(lats, 0.5), 4),
        "latency_p90_s": round(pct(lats, 0.9), 4),
        "latency_p99_s": round(pct(lats, 0.99), 4),
        "per_class": per_class,
    }
    if per_tenant:
        # Per-tenant prefix-reuse rate from the placement verdicts (the
        # tenant's prefix pool is the reuse "session"): fraction of
        # requests the scheduler placed on ALREADY-warm KV — locally or
        # via a peer restore — matching the sim scoreboard's
        # kv_verdicts / prefix_hit_rate fields.
        for t in per_tenant.values():
            tkv = t.get("kv_verdicts")
            if tkv:
                total = sum(tkv.values())
                t["prefix_reuse_rate"] = round(
                    (total - tkv.get("recompute", 0)) / total, 4)
        summary["per_tenant"] = per_tenant
    if kv_verdicts:
        total = sum(kv_verdicts.values())
        summary["kv_verdicts"] = dict(sorted(kv_verdicts.items()))
        summary["prefix_reuse_rate"] = round(
            (total - kv_verdicts.get("recompute", 0)) / total, 4)
    if args.trace_out is not None:
        summary["trace_out"] = {"path": args.trace_out,
                                "records": len(trace_records)}
    if args.stream:
        summary["stream_breaks"] = breaks
        summary["continuity_errors"] = cont_errors
        # 1.0 = one token per SSE frame (no speculation); a spec-decode
        # upstream pushes this toward its accepted tokens per step.
        summary["accepted_tokens_per_step"] = round(
            n_chunk_tokens / n_chunks, 3) if n_chunks else None
    if args.trace_export:
        summary["trace"] = await export_traces(args)
    print(json.dumps(summary))


async def export_traces(args) -> dict:
    """Post-run llmd-trace scrape: fetch /debug/traces from every trace
    URL, write the merged JSONL to --trace-export, and fold the spans
    into the per-phase attribution summary (p50/p99 per SLO class) plus
    the aggregate TTFT decomposition — the load report's latency numbers
    become attributable instead of eyeballed."""
    urls = [u.strip().rstrip("/") for u in
            (args.trace_urls or args.url).split(",") if u.strip()]
    lines = []
    async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=10)) as session:
        for u in urls:
            try:
                async with session.get(f"{u}/debug/traces") as resp:
                    if resp.status != 200:
                        print(f"trace scrape {u}: HTTP {resp.status}",
                              file=sys.stderr)
                        continue
                    text = await resp.text()
            except aiohttp.ClientError as exc:
                print(f"trace scrape {u} failed: {exc}", file=sys.stderr)
                continue
            lines.extend(text.splitlines())
    # One parse over all URLs' lines: load_trace_lines dedupes by
    # (trace, span) id, covering components that share one process.
    spans = trace_report.load_trace_lines(lines)
    with open(args.trace_export, "w") as f:
        for s in spans:
            f.write(json.dumps(s) + "\n")
    report = trace_report.build_report(spans, by_class=True)
    report["exported_to"] = args.trace_export
    return report


def main() -> None:
    ap = argparse.ArgumentParser("generate-load")
    ap.add_argument("--url", required=True)
    ap.add_argument("--model", default="tiny")
    ap.add_argument("--qps", type=float, default=2.0)
    ap.add_argument("--duration", type=float, default=30.0)
    ap.add_argument("--shape", choices=["uniform", "prefix", "slo"],
                    default="uniform")
    ap.add_argument("--prompt-words", type=int, default=24)
    ap.add_argument("--prefix-groups", type=int, default=4)
    ap.add_argument("--prefix-len", type=int, default=32)
    ap.add_argument("--max-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--slo-ttft-ms", type=float, default=500.0)
    ap.add_argument("--slo-tpot-ms", type=float, default=50.0)
    ap.add_argument("--error-rate", type=float, default=0.0)
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request latency budget sent as "
                         "x-llmd-deadline-ms (0 = no deadline); the "
                         "summary reports per-class deadline-miss rate")
    ap.add_argument("--criticality-mix", default="",
                    help="SLO-class traffic mix, class:weight[,...] over "
                         "critical/standard/sheddable, e.g. "
                         "critical:0.2,standard:0.6,sheddable:0.2; sent "
                         "as x-llmd-criticality")
    ap.add_argument("--faults", default="",
                    help="client-side fault mix, kind:rate[,kind:rate...]; "
                         "kinds: malformed, abort, timeout (see module "
                         "docstring)")
    ap.add_argument("--stream", action="store_true",
                    help="SSE streaming requests with the continuity "
                         "oracle: the summary counts stream_breaks "
                         "(missing [DONE]) and continuity_errors "
                         "(duplicated/missing token indices) — both must "
                         "be 0 under mid-stream recovery chaos")
    ap.add_argument("--trace-export", default=None,
                    help="post-run: scrape /debug/traces from the trace "
                         "URLs, write the span JSONL here, and append "
                         "the per-phase (p50/p99 per SLO class) "
                         "attribution + TTFT decomposition to the "
                         "summary")
    ap.add_argument("--trace-urls", default=None,
                    help="comma list of base URLs to scrape traces from "
                         "(default: --url; add model-server/sidecar "
                         "URLs when they run in separate processes)")
    ap.add_argument("--tenants", default="",
                    help="multi-tenant traffic mix, tenant:weight[,...]; "
                         "each request is billed to a weighted-drawn "
                         "tenant (x-llmd-tenant) and --shape prefix "
                         "draws from that tenant's own prefix pool")
    ap.add_argument("--trace-out", default=None,
                    help="record the issued workload as a replayable "
                         "JSONL trace ({at_s, tenant, prompt, "
                         "max_tokens, criticality, deadline_ms}) — the "
                         "format --trace-replay and a cluster-sim "
                         "scenario's \"trace\" field consume")
    ap.add_argument("--trace-replay", default=None,
                    help="trace-driven mode: replay a recorded workload "
                         "trace (arrival times honored; --qps/--duration "
                         "ignored)")
    ap.add_argument("--trace-speed", type=float, default=1.0,
                    help="replay speed multiplier for --trace-replay "
                         "(2.0 = twice as fast)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    args.fault_map = parse_faults(args.faults)
    args.criticality_list = parse_criticality_mix(args.criticality_mix)
    args.tenant_list = parse_tenant_mix(args.tenants)
    asyncio.run(run(args))


if __name__ == "__main__":
    main()
