"""OpenAI-compatible model server (the vLLM API-server equivalent).

Endpoints and probe semantics follow the reference's contract exactly so the
gateway/EPP/monitoring stack sees an identical surface
(reference: docs/readiness-probes.md:30-67):

  GET  /health          -> 200 as soon as the process is up (liveness)
  GET  /v1/models       -> 200 only once the model is loaded (startup,
                           readiness: "model-aware readiness" doctrine)
  GET  /metrics         -> Prometheus text, ``vllm:*`` names
  POST /v1/completions  -> OpenAI completions (+SSE streaming)
  POST /v1/chat/completions -> OpenAI chat (+SSE streaming)

PD disaggregation: requests may carry ``kv_transfer_params`` and the special
``max_tokens=1`` + ``do_remote_decode`` contract; responses then include
``kv_transfer_params{remote_block_ids, remote_host, remote_port, uuid}``
(reference: README.tpu.md:182-189).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import signal
import time
import uuid as uuid_mod
from typing import Any, Dict, List, Optional

from aiohttp import web

from llm_d_tpu.engine.async_engine import AsyncEngine
from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.request import Request, RequestOutput
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.server import stream_resume
from llm_d_tpu.server.stream_resume import StreamJournal
from llm_d_tpu.utils import tracing
from llm_d_tpu.utils.config import env_float, env_int
from llm_d_tpu.utils.faultinject import FaultInjected
from llm_d_tpu.utils.lifecycle import (
    CRITICALITY_SHEDDABLE,
    DEADLINE_EXCEEDED_HEADER,
    DRAINING_HEADER,
    REQUEST_ID_HEADER,
    RESUME_OFFSET_HEADER,
    SCHED_DEPTH_HEADER,
    parse_criticality,
    parse_deadline,
    remaining_s,
)
from llm_d_tpu.utils.tokenizer import get_tokenizer

logger = logging.getLogger(__name__)


def _sampling_from_body(body: Dict[str, Any]) -> SamplingParams:
    lp = body.get("logprobs")
    if lp is True:
        # Chat schema: boolean switch + separate alternatives count
        # (0/absent = chosen-token logprob only, per the OpenAI schema).
        lp = int(body.get("top_logprobs") or 0)
    elif lp is False:
        lp = None
    return SamplingParams(
        temperature=float(body.get("temperature", 1.0)),
        top_p=float(body.get("top_p", 1.0)),
        top_k=int(body.get("top_k", 0)),
        max_tokens=int(body.get("max_tokens", body.get("max_completion_tokens", 16))),
        min_tokens=int(body.get("min_tokens", 0)),
        stop=tuple(body.get("stop") or ()),
        seed=body.get("seed"),
        ignore_eos=bool(body.get("ignore_eos", False)),
        logprobs=lp,
    )


class DPWorkerPool:
    """Leader-side cross-host dispatch for multi-host data parallelism
    (ranks mode — the reference's ``--data-parallel-address`` / RPC-port
    contract, wide-ep decode.yaml:89-93).

    The leader host serves ALL external traffic; each request either runs
    on the local ``DPEngineGroup`` or is proxied verbatim to a worker
    host's API server (the "RPC" is the same OpenAI HTTP surface — one
    wire format end to end).  Policy is least-outstanding-work over
    COMPARABLE loads (VERDICT r5 #8): both sides count scheduler depth
    (waiting + running requests).  Local depth comes straight from the
    engine; worker depth is worker-REPORTED — every inference response
    carries an ``x-llmd-sched-depth`` header sampled from the worker's
    own scheduler — plus the leader's count of dispatches whose response
    headers haven't arrived yet (requests the last report can't see).
    The previous policy compared the leader-side in-flight HTTP count,
    under which one long-lived SSE stream pinned a worker at load=1 for
    its whole life while its scheduler sat empty, over-serving the
    leader under streaming-heavy traffic.  With
    ``--data-parallel-hybrid-lb`` no pool exists: every host takes
    external traffic and balances only its local ranks (the external LB
    spreads hosts), decode.yaml:75,86.
    """

    # Shipped default; instances read the LLMD_WORKER_BACKOFF_S env knob
    # (invalid values fall back here).
    WORKER_BACKOFF_S = 15.0
    DEPTH_HEADER = SCHED_DEPTH_HEADER

    def __init__(self, workers: List[str]) -> None:
        from llm_d_tpu.utils.config import env_float
        self.worker_backoff_s = env_float("LLMD_WORKER_BACKOFF_S",
                                          self.WORKER_BACKOFF_S)
        # inflight: open proxied HTTP exchanges (metrics only, NOT load);
        # dispatching: sequence ids of dispatches no depth report has
        # covered yet (see load()); depth: the worker's last
        # self-reported scheduler depth; seq: dispatch counter.
        self.workers = [{"url": u.rstrip("/"), "inflight": 0,
                         "dispatching": set(), "seq": 0,
                         "depth": 0, "down_until": 0.0}
                        for u in workers if u.strip()]
        self._session = None

    @staticmethod
    def load(worker: dict) -> int:
        """Comparable worker load: last reported scheduler depth + the
        dispatches no report has counted yet.  A dispatch leaves the
        ``dispatching`` set when its OWN headers arrive or when a report
        from a LATER dispatch lands (that report was sampled after this
        older dispatch reached the worker, so its depth already includes
        it — keeping it would double-count every in-flight dispatch
        older than the freshest report)."""
        return worker["depth"] + len(worker["dispatching"])

    def pick(self, engine) -> Optional[dict]:
        """Returns the worker to proxy to, or None to serve locally.
        Workers that recently failed to connect are skipped until their
        backoff expires — a dead pod must not keep winning the
        least-loaded race while its requests all 500."""
        now = time.monotonic()
        live = [w for w in self.workers if w["down_until"] <= now]
        if not live:
            return None
        local = engine.scheduler.num_waiting + engine.scheduler.num_running
        best = min(live, key=self.load)
        return best if self.load(best) < local else None

    # Hop-by-hop headers: forward end-to-end headers both ways (auth,
    # tracing, accept — proxied and locally-served requests must be
    # indistinguishable to clients and gateways); these stay per-hop.
    _HOP = {"host", "content-length", "transfer-encoding", "connection",
            "keep-alive", "upgrade", "te", "trailer",
            "proxy-authorization", "proxy-authenticate"}

    def alternates(self, dead: set) -> Optional[dict]:
        """Least-loaded live worker outside ``dead`` (resume targets)."""
        now = time.monotonic()
        live = [w for w in self.workers
                if w["down_until"] <= now and w["url"] not in dead]
        return min(live, key=self.load) if live else None

    async def proxy(self, request: web.Request, body: Dict[str, Any],
                    worker: dict,
                    server=None) -> Optional[web.StreamResponse]:
        """Stream-through proxy of one inference request to a worker.

        Returns None when the worker was unreachable BEFORE any response
        bytes were committed — the caller falls back to serving locally.

        Mid-stream death of the worker is recoverable for journaled SSE
        streams (``LLMD_STREAM_RESUME``): the relay journals emitted
        token ids, and on an upstream break resumes the stream on the
        least-loaded surviving worker — or on the LOCAL engine via
        ``server`` — deduping by token offset, so the client stream
        continues without duplicate or missing tokens.  Worker-slot
        accounting is settled per attempt: the dead worker's streaming
        self-count is released when its attempt ends, and the resume
        target's exchange counts itself exactly once (the depth-report
        contract — no phantom load on either side)."""
        import aiohttp
        if self._session is None:
            self._session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=None, sock_connect=5))
        policy = stream_resume.resume_policy()
        journal = None
        if policy.enabled and bool(body.get("stream", False)):
            in_headers = {k.lower(): v for k, v in request.headers.items()}
            try:
                criticality = parse_criticality(in_headers, body)
            except ValueError:
                criticality = "standard"
            try:
                deadline_epoch = parse_deadline(in_headers, body)
            except ValueError:
                deadline_epoch = None
            if criticality != CRITICALITY_SHEDDABLE:
                journal = StreamJournal(body, criticality=criticality,
                                        deadline_epoch=deadline_epoch)
        # DP dispatch tracing: one child span per ATTEMPT (first forward
        # + every resume target), parented on the incoming hop so the
        # leader's balancing decision reads in the request tree.
        in_hdrs = {k.lower(): v for k, v in request.headers.items()}
        span = tracing.get_tracer("server").start_span(
            "server.dp_dispatch",
            parent=tracing.parse_trace_headers(in_hdrs),
            request_id=in_hdrs.get(REQUEST_ID_HEADER)
            or str(body.get("request_id") or "") or None,
            worker=worker["url"])
        try:
            return await self._proxy_attempts(
                request, body, worker, server, policy, journal, span)
        finally:
            span.end()

    async def _proxy_attempts(self, request, body, worker, server,
                              policy, journal, span):
        resp: Optional[web.StreamResponse] = None
        current: Optional[dict] = worker
        dead: set = set()
        while True:
            send_body = body
            extra_headers: Dict[str, str] = {}
            if journal is not None and journal.resume_count:
                send_body = journal.resume_body()
                extra_headers = journal.resume_headers()
            extra_headers.update(tracing.trace_headers(span.ctx()))
            span.add_event("dispatch", worker=current["url"],
                           attempt=(journal.resume_count
                                    if journal is not None else 0))
            resp, broke_exc = await self._attempt(
                request, send_body, extra_headers, current, journal,
                resp, policy, span=span)
            self._settle_recoveries(journal, server)
            if broke_exc is None:
                return resp          # relayed to completion (or None:
            #                          nothing committed, caller serves
            #                          locally)
            dead.add(current["url"])
            if journal.finish_reason and not journal.done:
                # Finish chunk already delivered; only [DONE] was lost —
                # close the stream locally (resuming would decode past
                # the delivered EOS/stop).
                journal.done = True
                try:
                    await resp.write(b"data: [DONE]\n\n")
                    await resp.write_eof()
                except (ConnectionResetError, OSError):
                    pass
                return resp
            if not journal.resumable \
                    or journal.resume_count >= policy.max_attempts \
                    or self._budget_gone(journal):
                # Degraded to today's contract: re-raise so the client
                # connection closes ABRUPTLY (a clean EOF would make the
                # truncation invisible to plain SSE clients).
                if server is not None:
                    server.engine.metrics.inc_stream_resume(
                        stream_resume.OUTCOME_FAILED)
                raise broke_exc
            journal.resume_count += 1
            journal.mark_break()
            span.add_event("resume", attempt=journal.resume_count,
                           offset=journal.offset, dead=current["url"],
                           error=f"{type(broke_exc).__name__}: "
                                 f"{broke_exc}")
            target = self.alternates(dead)
            if target is None and server is not None:
                # Every worker host is down: the leader's own engine is
                # the last resume target.
                ok = await server.resume_local(request, resp, journal,
                                               parent=span)
                self._settle_recoveries(journal, server)
                if not journal.done:
                    server.engine.metrics.inc_stream_resume(
                        stream_resume.OUTCOME_FAILED)
                    if not ok:
                        raise broke_exc
                return resp
            if target is None:
                if server is not None:
                    server.engine.metrics.inc_stream_resume(
                        stream_resume.OUTCOME_FAILED)
                raise broke_exc
            logger.warning(
                "DP worker %s died mid-stream at token %d; resuming on "
                "%s (attempt %d/%d)", current["url"], journal.offset,
                target["url"], journal.resume_count, policy.max_attempts)
            current = target

    def _budget_gone(self, journal: StreamJournal) -> bool:
        left = remaining_s(journal.deadline_epoch)
        return left is not None and left <= 0

    @staticmethod
    def _settle_recoveries(journal: Optional[StreamJournal],
                           server) -> None:
        """Drain completed (outcome, seconds) recovery pairs into the
        leader's metrics (the EPP gateway's _drain_recoveries twin)."""
        if journal is None or server is None:
            return
        for outcome, secs in journal.take_recoveries():
            server.engine.metrics.inc_stream_resume(outcome)
            server.engine.metrics.request_recovery.observe(secs)

    async def _attempt(self, request: web.Request, body: Dict[str, Any],
                       extra_headers: Dict[str, str], worker: dict,
                       journal: Optional[StreamJournal],
                       resp: Optional[web.StreamResponse],
                       policy, span=None) -> tuple:
        """One forward to one worker with per-worker load accounting.

        Returns (resp, exc): ``exc`` non-None means the stream died
        mid-relay after bytes were committed (resumable — or re-raised
        by the caller when recovery is off the table, so the client sees
        the abrupt break today's contract promises); ``resp`` None with
        ``exc`` None means nothing was committed (the caller serves
        locally)."""
        import aiohttp
        fwd_headers = {k: v for k, v in request.headers.items()
                       if k.lower() not in self._HOP
                       and k.lower() != "content-type"}  # json= sets it
        fwd_headers.update(extra_headers)
        seq = worker["seq"]
        worker["seq"] += 1
        worker["dispatching"].add(seq)
        headers_seen = False
        counted_self = False
        # Slot accounting LAST, immediately before the try whose finally
        # settles it: nothing may raise between the count and the
        # protection or a failed header build leaks the slot (PAIR001 —
        # the machine-checked form of PR 9's hand-found double-count).
        worker["inflight"] += 1
        try:
            async with self._session.post(
                    worker["url"] + request.path_qs, json=body,
                    headers=fwd_headers) as upstream:
                # Response headers arrived: this dispatch is now visible
                # in the worker's own depth report (or finished) — and so
                # is every OLDER dispatch, which reached the worker before
                # this response left it (see load()).
                depth = upstream.headers.get(self.DEPTH_HEADER)
                worker["dispatching"] = {
                    p for p in worker["dispatching"] if p > seq}
                headers_seen = True
                # Streaming reports leave at stream START and count the
                # request itself; when the exchange ends we know it left
                # the worker's scheduler, so take it back out — otherwise
                # a finished stream leaves the worker looking loaded
                # until the next report.  Non-streaming reports leave at
                # completion and already exclude themselves.  A resumed
                # stream settles each attempt's worker here, so the dead
                # endpoint's slot is released and the stream counts
                # exactly once, on the worker currently serving it.
                counted_self = upstream.headers.get(
                    "Content-Type", "").startswith("text/event-stream")
                if depth is not None:
                    try:
                        worker["depth"] = max(0, int(depth))
                    except ValueError:
                        pass
                if not counted_self:
                    # Non-SSE exchange (error body, non-streaming
                    # request): legacy verbatim relay — journaling and
                    # resume only apply to committed SSE streams.
                    journal = None
                if resp is not None and (upstream.status != 200
                                         or not counted_self):
                    # Resume refused (draining/dead-on-arrival replica):
                    # treat as a mid-stream failure of this worker.
                    logger.warning("DP resume on %s refused: HTTP %d",
                                   worker["url"], upstream.status)
                    return resp, RuntimeError(
                        f"resume target {worker['url']} refused: "
                        f"HTTP {upstream.status}")
                if resp is None:
                    resp = web.StreamResponse(
                        status=upstream.status,
                        headers={k: v for k, v in upstream.headers.items()
                                 if k.lower() not in self._HOP})
                    await resp.prepare(request)
                if journal is None:
                    async for chunk in upstream.content.iter_any():
                        await resp.write(chunk)
                else:
                    await stream_resume.relay_stream(
                        resp, upstream.content, journal,
                        fault_key=worker["url"],
                        stall_timeout_s=policy.stall_timeout_s,
                        span=span)
                try:
                    await resp.write_eof()
                except (ConnectionResetError, OSError):
                    pass        # client gone after the final frame
                return resp, None
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError,
                FaultInjected, stream_resume.StreamBroken) as exc:
            worker["down_until"] = time.monotonic() + self.worker_backoff_s
            logger.warning("DP worker %s unreachable (%s); backing off %.0fs",
                           worker["url"], exc, self.worker_backoff_s)
            if resp is None:
                return None, None    # nothing committed: serve locally
            if journal is None:
                raise                # unjournaled mid-stream: today's
            #                          fail-fast — the client sees the break
            return resp, exc         # mid-stream break (resumable)
        finally:
            worker["inflight"] -= 1
            if not headers_seen:
                worker["dispatching"].discard(seq)
            elif counted_self:
                worker["depth"] = max(0, worker["depth"] - 1)

    async def close(self) -> None:
        if self._session is not None:
            await self._session.close()


class ModelServer:
    def __init__(self, engine: EngineCore, tokenizer, model_name: str) -> None:
        self.engine = engine
        self.async_engine = AsyncEngine(engine)
        self.tokenizer = tokenizer
        self.model_name = model_name
        self.model_loaded = False
        # Multi-host DP: leader-side worker pool (set by main / tests).
        self.dp_pool: Optional[DPWorkerPool] = None
        self.started_at = time.time()
        # --- lifecycle ---
        # draining: readiness is down and new inference is refused (503)
        # while in-flight requests complete, bounded by drain_timeout_s;
        # stragglers past the bound are aborted (their computed full KV
        # blocks stay in the prefix cache / host tier, so a retry after
        # restart reuses the prefix instead of recomputing it).
        self.draining = False
        self._inflight = 0
        self._drain_task: Optional[asyncio.Task] = None
        self._exit_after_drain = False
        self.drain_timeout_s = env_float("LLMD_DRAIN_TIMEOUT_S", 30.0)
        # Default latency budget applied when the client sends none
        # (0 = no default; operators cap runaway queue time fleet-wide).
        self.deadline_default_ms = env_int("LLMD_DEADLINE_DEFAULT_MS", 0)
        if tokenizer.eos_token_id is not None:
            engine.eos_token_id = tokenizer.eos_token_id
        # Engine-side stop-string detection (finish_reason="stop" without
        # decoding to max_tokens first).
        engine.tokenizer = tokenizer

    # ---------- app ----------

    def build_app(self) -> web.Application:
        app = web.Application()
        app.router.add_get("/health", self.health)
        app.router.add_get("/v1/models", self.models)
        app.router.add_get("/metrics", self.metrics)
        app.router.add_get("/debug/traces", self.debug_traces)
        app.router.add_get("/version", self.version)
        app.router.add_post("/v1/completions", self.completions)
        app.router.add_post("/v1/chat/completions", self.chat_completions)
        app.router.add_post("/tokenize", self.tokenize)
        app.router.add_post("/admin/drain", self.admin_drain)
        app.on_startup.append(self._on_startup)
        app.on_cleanup.append(self._on_cleanup)
        return app

    async def _on_startup(self, app) -> None:
        await self.async_engine.start()
        self.model_loaded = True
        try:
            # Rolling restarts: SIGTERM flips to draining (readiness down,
            # in-flight completing) instead of dropping work on the floor;
            # after the bounded drain the process exits via the normal
            # shutdown path.  Only installable on the main thread's loop —
            # embedded/test servers skip silently.
            asyncio.get_running_loop().add_signal_handler(
                signal.SIGTERM, self._on_sigterm)
        except (NotImplementedError, RuntimeError, ValueError):
            pass

    async def _on_cleanup(self, app) -> None:
        self.async_engine.stop()
        pub = getattr(self, "kv_event_publisher", None)
        if pub is not None:
            pub.stop()
        if self.dp_pool is not None:
            await self.dp_pool.close()

    # ---------- probes / meta ----------

    async def health(self, request: web.Request) -> web.Response:
        if self.async_engine.dead is not None:
            return web.Response(status=500, text="engine dead")
        return web.Response(text="ok")

    async def models(self, request: web.Request) -> web.Response:
        if not self.model_loaded:
            return web.json_response({"error": "model loading"}, status=503)
        if self.draining:
            # Readiness flips first: the gateway's scrape + drain-filter
            # stop routing here while in-flight requests complete.
            return web.json_response(
                {"error": "draining"}, status=503,
                headers={DRAINING_HEADER: "1"})
        return web.json_response({
            "object": "list",
            "data": [{"id": self.model_name, "object": "model",
                      "created": int(self.started_at), "owned_by": "llm-d-tpu"}],
        })

    async def metrics(self, request: web.Request) -> web.Response:
        return web.Response(body=self.engine.metrics.render(),
                            content_type="text/plain")

    async def debug_traces(self, request: web.Request) -> web.Response:
        """llmd-trace span dump (JSONL; ``?drain=1`` clears the rings) —
        the ``scripts/trace_report.py`` / ``generate_load.py
        --trace-export`` scrape surface."""
        drain = request.query.get("drain") in ("1", "true")
        spans = ([s for t in tracing.all_tracers().values()
                  for s in t.drain()] if drain else tracing.snapshot_all())
        return web.Response(text=tracing.render_jsonl(spans),
                            content_type="application/jsonl")

    async def version(self, request: web.Request) -> web.Response:
        from llm_d_tpu import __version__
        return web.json_response({"version": __version__})

    async def tokenize(self, request: web.Request) -> web.Response:
        body = await request.json()
        ids = self.tokenizer.encode(body.get("prompt", ""))
        return web.json_response({"tokens": ids, "count": len(ids)})

    # ---------- drain (graceful restart protocol) ----------

    async def admin_drain(self, request: web.Request) -> web.Response:
        """Flip this replica to draining: readiness goes 503, new inference
        is refused (the gateway retries on an alternate), in-flight
        requests complete up to ``drain_timeout_s``, then stragglers are
        aborted.  Idempotent — the deploy preStop hook and the SIGTERM
        handler may both fire."""
        self._begin_drain()
        return web.json_response({
            "status": "draining",
            "inflight": self._inflight,
            "timeout_s": self.drain_timeout_s,
        })

    def _on_sigterm(self) -> None:
        logger.info("SIGTERM: draining (timeout %.1fs)", self.drain_timeout_s)
        self._begin_drain(exit_after=True)

    def _begin_drain(self, exit_after: bool = False) -> None:
        if not self.draining:
            self.draining = True
            self.engine.metrics.drain_state.set(1)
            self.engine.metrics.drain_inflight.set(self._inflight)
            self._drain_task = asyncio.get_running_loop().create_task(
                self._drain_loop())
        if exit_after and not self._exit_after_drain \
                and self._drain_task is not None:
            # SIGTERM may land AFTER the preStop hook already started the
            # drain: attach the exit to the existing drain instead of
            # no-opping (which would park the process until SIGKILL).
            self._exit_after_drain = True
            self._drain_task.add_done_callback(
                lambda _t: signal.raise_signal(signal.SIGINT))

    async def _drain_loop(self) -> None:
        bound = time.monotonic() + self.drain_timeout_s
        m = self.engine.metrics
        while time.monotonic() < bound:
            m.drain_inflight.set(self._inflight)
            if self._inflight == 0 \
                    and not getattr(self.engine, "has_work", lambda: False)():
                break
            await asyncio.sleep(0.05)
        # Bounded drain: abort stragglers so SIGKILL can't catch them
        # mid-step.  Their computed full blocks are already in the prefix
        # cache (and host/shared KV tier when configured) — the unfinished
        # prefix state is handed back through the KV plane rather than
        # burned.
        stragglers = list(self.async_engine._streams)
        for rid in stragglers:
            logger.warning("drain timeout: aborting in-flight request %s",
                           rid)
            self.async_engine.abort(rid, notify=True)
        m.drain_inflight.set(0)
        logger.info("drain complete (%d straggler(s) aborted)",
                    len(stragglers))
        # When SIGTERM initiated (or joined) this drain, the done
        # callback installed by _begin_drain re-enters aiohttp's normal
        # shutdown path via SIGINT.

    # ---------- inference ----------

    def _prompt_ids(self, body: Dict[str, Any], chat: bool) -> List[int]:
        """Prompt token ids for either endpoint schema (one derivation
        for the first serve AND a mid-stream resume — the resumed
        prefill must hash to the same prefix-cache chain)."""
        if chat:
            messages = body.get("messages", [])
            if hasattr(self.tokenizer, "_tok") and hasattr(
                    self.tokenizer._tok, "apply_chat_template"):
                return self.tokenizer._tok.apply_chat_template(
                    messages, add_generation_prompt=True)
            text = "".join(
                f"<|{m.get('role', 'user')}|>{m.get('content', '')}"
                for m in messages) + "<|assistant|>"
            return self.tokenizer.encode(text)
        prompt = body.get("prompt", "")
        if isinstance(prompt, list) and prompt and isinstance(prompt[0], int):
            return prompt
        return self.tokenizer.encode(str(prompt))

    def _make_request(self, body: Dict[str, Any], prompt_ids: List[int],
                      headers: Optional[Dict[str, str]] = None) -> Request:
        headers = headers or {}
        # Correlation contract: the body's request_id (the HTTP gateway
        # writes both) wins, then the x-request-id header (the ext_proc
        # plane mutates headers only), then a fresh mint — so engine log
        # lines, the response/stream id, and the trace all join on the
        # id the first hop chose, whichever plane routed the request.
        rid = (body.get("request_id")
               or headers.get(REQUEST_ID_HEADER)
               or f"cmpl-{uuid_mod.uuid4().hex}")
        # Deadline: absolute epoch from the gateway wins; a bare relative
        # budget (direct client) is based here.  Epoch -> engine monotonic
        # clock so queue time spent BEFORE this hop still counts.
        deadline_epoch = parse_deadline(headers, body)
        if deadline_epoch is None and self.deadline_default_ms > 0:
            deadline_epoch = time.time() + self.deadline_default_ms / 1000.0
        deadline = None
        if deadline_epoch is not None:
            deadline = time.monotonic() + (deadline_epoch - time.time())
        req = Request(
            request_id=rid,
            prompt_token_ids=prompt_ids,
            sampling=_sampling_from_body(body),
            priority=int(body.get("priority", 0)),
            criticality=parse_criticality(headers, body),
            deadline=deadline,
        )
        ktp = body.get("kv_transfer_params")
        if ktp:
            if ktp.get("do_remote_decode"):
                # Producer role: run prefill only, pin KV for remote pull.
                req.do_remote_decode = True
            elif ktp.get("remote_block_ids") or ktp.get("do_remote_prefill"):
                req.do_remote_prefill = True
                req.kv_transfer_params = ktp
        resume = body.get("resume")
        if resume:
            # Mid-stream resume admission: the relay journal's emitted
            # token ids arrive pre-generated.  The scheduler admits
            # prompt+generated as a prefill (restore-first from the
            # prefix cache / host tier, recompute on miss) and decode
            # continues from the journal offset.
            try:
                ids = [int(t) for t in (resume.get("token_ids") or [])]
            except (TypeError, ValueError) as e:
                raise ValueError("invalid resume.token_ids") from e
            off_hdr = headers.get(RESUME_OFFSET_HEADER)
            if off_hdr is not None and int(off_hdr) != len(ids):
                raise ValueError(
                    f"resume offset {off_hdr} != {len(ids)} journaled "
                    f"token ids")
            if req.do_remote_prefill or req.do_remote_decode:
                raise ValueError("resume cannot combine with PD "
                                 "kv_transfer_params roles")
            req.output_token_ids = ids
            req.resume_offset = len(ids)
        return req

    def _refuse_draining(self) -> Optional[web.Response]:
        """503 for NEW inference while draining (the gateway's retry path
        re-schedules it on an alternate replica)."""
        if not self.draining:
            return None
        return web.json_response(
            {"error": "draining: replica is shutting down"}, status=503,
            headers={DRAINING_HEADER: "1"})

    async def completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid json"}, status=400)
        refused = self._refuse_draining()
        if refused is not None:
            return refused
        if self.dp_pool is not None:
            worker = self.dp_pool.pick(self.engine)
            if worker is not None:
                proxied = await self.dp_pool.proxy(request, body, worker,
                                                   server=self)
                if proxied is not None:
                    return proxied
        return await self._run(request, body,
                               self._prompt_ids(body, chat=False),
                               chat=False)

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid json"}, status=400)
        refused = self._refuse_draining()
        if refused is not None:
            return refused
        if self.dp_pool is not None:
            worker = self.dp_pool.pick(self.engine)
            if worker is not None:
                proxied = await self.dp_pool.proxy(request, body, worker,
                                                   server=self)
                if proxied is not None:
                    return proxied
        return await self._run(request, body,
                               self._prompt_ids(body, chat=True),
                               chat=True)

    def _usage(self, req: Request, body: Dict[str, Any]) -> Dict[str, Any]:
        """Usage block incl. latency actuals (+ gateway predictions when
        present) — the reference's SSE usage contract surfaces ttft_ms /
        avg_tpot_ms / predicted_* for accuracy validation (reference:
        predicted-latency README.md:130-148)."""
        usage: Dict[str, Any] = {
            "prompt_tokens": req.num_prompt_tokens,
            "completion_tokens": len(req.output_token_ids),
            "total_tokens": req.num_tokens,
        }
        if req.first_token_time is not None:
            usage["ttft_ms"] = round(
                (req.first_token_time - req.arrival_time) * 1000.0, 3)
        n_out = len(req.output_token_ids)
        if (req.last_token_time is not None
                and req.first_token_time is not None and n_out > 1):
            usage["avg_tpot_ms"] = round(
                (req.last_token_time - req.first_token_time)
                / (n_out - 1) * 1000.0, 3)
        pred = body.get("_predicted")
        if pred:
            usage["predicted_ttft_ms"] = pred.get("ttft_ms")
            usage["avg_predicted_tpot_ms"] = pred.get("tpot_ms")
        return usage

    def _post_training_sample(self, req: Request,
                              feats: Dict[str, float]) -> None:
        """Fire-and-forget actuals to the latency-training sidecar."""
        url = getattr(self, "latency_training_url", None)
        if not url:
            return
        samples = []
        usage = self._usage(req, {})
        if "ttft_ms" in usage:
            samples.append({"target": "ttft", "features": feats,
                            "actual_ms": usage["ttft_ms"]})
        if "avg_tpot_ms" in usage:
            tf = {k: feats[k] for k in
                  ("num_waiting", "num_running", "kv_usage")}
            samples.append({"target": "tpot", "features": tf,
                            "actual_ms": usage["avg_tpot_ms"]})
        if not samples:
            return

        async def post():
            try:
                import aiohttp
                async with aiohttp.ClientSession(
                        timeout=aiohttp.ClientTimeout(total=1.0)) as s:
                    await s.post(f"{url}/samples", json=samples)
            except Exception as exc:    # best-effort telemetry, but not
                # silent: a permanently-down trainer should be visible in
                # debug logs, not discovered months later (TASK003).
                logger.debug("latency-training sample post failed: %s", exc)
        # Hold a strong reference: the loop keeps only a weak one, and a
        # GC'd task silently drops the sample.
        tasks = getattr(self, "_bg_tasks", None)
        if tasks is None:
            tasks = self._bg_tasks = set()
        task = asyncio.get_running_loop().create_task(post())
        tasks.add(task)
        task.add_done_callback(tasks.discard)

    async def _run(self, http_req: web.Request, body: Dict[str, Any],
                   prompt_ids: List[int], chat: bool) -> web.StreamResponse:
        in_headers = {k.lower(): v for k, v in http_req.headers.items()}
        try:
            req = self._make_request(body, prompt_ids, in_headers)
        except (TypeError, ValueError) as exc:
            return web.json_response(
                {"error": f"invalid request: {exc}"}, status=400)
        # Admission span: root when the request came straight from a
        # client, child of the gateway/sidecar hop otherwise; the trace
        # id seeds from x-request-id / request_id so the engine's log
        # lines (which carry the rid) join the trace with no lookup.
        span = tracing.get_tracer("server").start_span(
            "server.request",
            parent=tracing.parse_trace_headers(in_headers),
            request_id=in_headers.get(REQUEST_ID_HEADER, req.request_id),
            criticality=req.criticality,
            prompt_tokens=req.num_prompt_tokens,
            resume_offset=req.resume_offset or None)
        # Engine-side spans (queue / prefill / decode step boundaries)
        # parent on the admission span via the request object.
        req.trace_ctx = span.ctx()
        logger.debug("request %s admitted (trace=%s criticality=%s "
                     "prompt_tokens=%d)", req.request_id, span.trace_id,
                     req.criticality, req.num_prompt_tokens)
        if req.deadline_expired():
            # Budget already blown (e.g. spent queueing at the gateway):
            # refuse before burning a single engine step.
            self.engine.metrics.inc_deadline_exceeded(req.criticality)
            span.end(error="deadline exceeded at admission")
            return web.json_response(
                {"error": "deadline exceeded", "request_id": req.request_id},
                status=504, headers={DEADLINE_EXCEEDED_HEADER: "1"})
        self._inflight += 1
        try:
            if self.draining:
                self.engine.metrics.drain_inflight.set(self._inflight)
            return await self._run_inner(http_req, body, req, chat)
        finally:
            self._inflight -= 1
            if self.draining:
                self.engine.metrics.drain_inflight.set(self._inflight)
            span.end(completion_tokens=len(req.output_token_ids),
                     finish=req.state.value)

    async def _run_inner(self, http_req: web.Request, body: Dict[str, Any],
                         req: Request, chat: bool) -> web.StreamResponse:
        stream = bool(body.get("stream", False))
        created = int(time.time())
        # Load signals at admission = the predictor sidecars' features.
        arrival_feats = {
            "num_waiting": float(self.engine.scheduler.num_waiting),
            "num_running": float(self.engine.scheduler.num_running),
            "kv_usage": float(self.engine.kv_manager.usage),
            "prompt_tokens": float(req.num_prompt_tokens),
        }

        if stream:
            # Depth report for the leader's DP pool (see DPWorkerPool):
            # headers leave BEFORE this request is admitted, so count it
            # explicitly (+1) — the value a fresh scrape would see.
            resp = web.StreamResponse(headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                DPWorkerPool.DEPTH_HEADER: str(self._sched_depth() + 1)})
            await resp.prepare(http_req)
            await self._stream_tokens_into(resp, req, body, chat, created)
            await resp.write_eof()
            self._post_training_sample(req, arrival_feats)
            return resp

        final_out = None
        lp_ids: List[int] = []
        lp_vals: List[float] = []
        lp_tops: List[Dict[int, float]] = []
        async for out in self.async_engine.generate(req):
            final_out = out
            if req.sampling.logprobs is not None:
                lp_ids.extend(out.new_token_ids)
                lp_vals.extend(out.logprobs or [])
                lp_tops.extend(out.top_logprobs or [])
        text = self.tokenizer.decode(req.output_token_ids)
        text, stopped = self._apply_stop_strings(req, text, text)
        finish_reason = final_out.finish_reason if final_out else None
        if stopped:
            finish_reason = "stop"
        if finish_reason == "deadline" and not req.output_token_ids:
            # Expired while queued: nothing was produced — a 504 is the
            # honest answer.  Partial generations (evicted mid-decode)
            # return 200 below with finish_reason "deadline".
            return web.json_response(
                {"error": "deadline exceeded", "request_id": req.request_id},
                status=504, headers={DEADLINE_EXCEEDED_HEADER: "1"})
        payload = {
            "id": req.request_id,
            "object": "chat.completion" if chat else "text_completion",
            "created": created,
            "model": self.model_name,
            "choices": [{
                "index": 0,
                "finish_reason": finish_reason,
                **({"message": {"role": "assistant", "content": text}}
                   if chat else {"text": text}),
            }],
            "usage": self._usage(req, body),
        }
        if req.sampling.logprobs is not None and lp_ids:
            # Per-token chosen logprob plus top-N alternatives (weak #8:
            # round 2 only returned the chosen token's value) — chat and
            # completions use DIFFERENT OpenAI schemas.
            toks = [self.tokenizer.decode([t]) for t in lp_ids]
            if chat:
                payload["choices"][0]["logprobs"] = {"content": [
                    {"token": tok, "logprob": lp,
                     "top_logprobs": [
                         {"token": self.tokenizer.decode([tid]),
                          "logprob": v} for tid, v in top.items()]}
                    for tok, lp, top in zip(
                        toks, lp_vals,
                        lp_tops or [{}] * len(toks))]}
            else:
                offsets, pos = [], 0
                for t in toks:
                    offsets.append(pos)
                    pos += len(t)
                payload["choices"][0]["logprobs"] = {
                    "tokens": toks,
                    "token_logprobs": lp_vals,
                    "top_logprobs": [
                        {self.tokenizer.decode([tid]): lp
                         for tid, lp in top.items()}
                        for top in lp_tops] if lp_tops else None,
                    "text_offset": offsets,
                }
        if final_out is not None and final_out.kv_transfer_params:
            payload["kv_transfer_params"] = final_out.kv_transfer_params
        self._post_training_sample(req, arrival_feats)
        # Non-streaming: this request already left the scheduler — the
        # depth reported is everyone still queued/running behind it.
        headers = {DPWorkerPool.DEPTH_HEADER: str(self._sched_depth())}
        if finish_reason == "deadline":
            headers[DEADLINE_EXCEEDED_HEADER] = "1"
        return web.json_response(payload, headers=headers)

    async def _stream_tokens_into(self, resp: web.StreamResponse,
                                  req: Request, body: Dict[str, Any],
                                  chat: bool, created: int,
                                  journal: Optional[StreamJournal] = None
                                  ) -> None:
        """Generate and write the SSE token stream for one (possibly
        resumed) request into an already-prepared response.

        A resumed request starts its text delta after the restored
        prefix (the relay already delivered those tokens) and stamps the
        first chunk's ``llmd`` meta with the restore-vs-recompute
        verdict.  ``journal`` (DP-leader local resume) mirrors every
        frame through the relay journal so offset dedupe and recovery
        accounting work exactly as for a proxied resume."""
        async def write_frame(payload: Dict[str, Any]) -> None:
            frame = b"data: " + json.dumps(payload).encode() + b"\n\n"
            if journal is None or journal.admit_frame(frame):
                await resp.write(frame)

        if req.resume_offset >= req.sampling.max_tokens:
            # The break landed between the last token and [DONE]: every
            # token was already delivered — emit the finish frame (and
            # the usage/[DONE] tail below) without decoding an extra one.
            await write_frame(self._chunk(
                req, "", RequestOutput(req.request_id, [], True, "length"),
                created, chat, finished=True, finish_reason="length"))
        else:
            await self._generate_stream(req, chat, created, write_frame)
        if bool((body.get("stream_options") or {}).get("include_usage")):
            await write_frame({
                "id": req.request_id,
                "object": "chat.completion.chunk" if chat
                else "text_completion",
                "created": created, "model": self.model_name,
                "choices": [],
                "usage": self._usage(req, body),
            })
        done = b"data: [DONE]\n\n"
        if journal is not None:
            journal.admit_frame(done)
        await resp.write(done)

    async def _generate_stream(self, req: Request, chat: bool,
                               created: int, write_frame) -> None:
        """The token-generation loop of :meth:`_stream_tokens_into`."""
        # The whole answer's text a frame, as the stop strings need it; a
        # tokenizer that can decodes only the frame's new tokens.
        decode = self.tokenizer.stream_decoder()
        all_text_len = 0
        if req.resume_offset:
            all_text_len = len(decode(req.output_token_ids))
        first_meta_pending = req.resume_offset > 0
        async for out in self.async_engine.generate(req):
            text = decode(req.output_token_ids)
            delta, all_text_len = text[all_text_len:], len(text)
            delta, stopped = self._apply_stop_strings(req, delta, text)
            finished = out.finished or stopped
            reason = "stop" if stopped else out.finish_reason
            src = None
            if first_meta_pending:
                first_meta_pending = False
                src = (stream_resume.OUTCOME_RESTORED
                       if req.resume_restored_tokens > 0
                       else stream_resume.OUTCOME_RECOMPUTED)
            chunk = self._chunk(req, delta, out, created, chat,
                                finished=finished, finish_reason=reason,
                                resume_src=src)
            await write_frame(chunk)
            if stopped and not out.finished:
                # Safety net: the engine missed the stop string (e.g. it
                # spanned a longer window); terminate and settle accounts.
                self.engine.abort_request(req.request_id)
                break
            if finished:
                break

    async def resume_local(self, http_req: web.Request,
                           resp: web.StreamResponse,
                           journal: StreamJournal,
                           parent=None) -> bool:
        """Resume a journaled stream on the LOCAL engine (the DP leader's
        last resort when every worker host is down).  Writes the
        remaining tokens into the already-committed client response;
        returns True when the stream reached [DONE].  ``parent``
        (llmd-trace): the dispatch span the resume attempt spans under —
        the local continuation stays in the original request tree."""
        body = journal.resume_body()
        chat = http_req.path.endswith("/chat/completions")
        in_headers = {k.lower(): v for k, v in http_req.headers.items()}
        try:
            req = self._make_request(
                body, self._prompt_ids(body, chat), in_headers)
        except (TypeError, ValueError) as exc:
            logger.error("local resume rejected: %s", exc)
            return False
        if req.deadline_expired():
            return False
        span = tracing.get_tracer("server").start_span(
            "server.resume_local",
            parent=parent if parent is not None
            else tracing.parse_trace_headers(in_headers),
            request_id=req.request_id, offset=journal.offset)
        req.trace_ctx = span.ctx()
        logger.warning("resuming stream %s on the local engine at token "
                       "%d", req.request_id, journal.offset)
        # The resumed stream is in-flight CLIENT work: count it so a
        # drain waits for it (the drain contract lets in-flight requests
        # complete) instead of declaring the replica idle mid-resume.
        self._inflight += 1
        try:
            if self.draining:
                self.engine.metrics.drain_inflight.set(self._inflight)
            await self._stream_tokens_into(
                resp, req, body, chat, int(time.time()), journal=journal)
            await resp.write_eof()
        except (ConnectionResetError, OSError):
            # Any client-transport death (reset, EPIPE, TLS teardown):
            # free the engine slot instead of decoding to max_tokens for
            # a disconnected consumer.
            self.async_engine.abort(req.request_id)
            span.end(error="client gone")
            return False
        except asyncio.CancelledError:
            self.async_engine.abort(req.request_id)
            span.end(error="cancelled")
            raise
        finally:
            self._inflight -= 1
            if self.draining:
                self.engine.metrics.drain_inflight.set(self._inflight)
        span.end(done=journal.done)
        return journal.done

    def _sched_depth(self) -> int:
        """Scheduler depth (waiting + running) — the worker-side half of
        the DP pool's comparable-load contract."""
        s = self.engine.scheduler
        return int(s.num_waiting + s.num_running)

    def _apply_stop_strings(self, req: Request, delta: str, full: str):
        """Truncate output at the first stop string. Returns (delta', stopped)."""
        for s in req.sampling.stop:
            idx = full.find(s)
            if idx >= 0:
                delta_start = len(full) - len(delta)
                return (full[delta_start:idx] if idx > delta_start else ""), True
        return delta, False

    def _chunk(self, req, delta: str, out, created: int, chat: bool,
               finished: bool, finish_reason: Optional[str],
               resume_src: Optional[str] = None):
        choice: Dict[str, Any] = {
            "index": 0,
            "finish_reason": finish_reason if finished else None}
        if chat:
            choice["delta"] = {"content": delta}
        else:
            choice["text"] = delta
        chunk = {
            "id": req.request_id,
            "object": "chat.completion.chunk" if chat else "text_completion",
            "created": created, "model": self.model_name,
            "choices": [choice],
        }
        # Journal meta: completion-token offset + ids of this chunk's new
        # tokens (OpenAI clients ignore the extra key; the streaming
        # relays journal it for mid-stream recovery and the load
        # generator's continuity check keys on it).
        chunk[stream_resume.CHUNK_META_KEY] = stream_resume.chunk_meta(
            len(req.output_token_ids) - len(out.new_token_ids),
            out.new_token_ids, src=resume_src,
            restored_tokens=req.resume_restored_tokens)
        if out.finished and out.kv_transfer_params:
            chunk["kv_transfer_params"] = out.kv_transfer_params
        return chunk


def build_server(engine_config: EngineConfig, tokenizer_name: Optional[str] = None,
                 model_name: Optional[str] = None,
                 engine: Optional[EngineCore] = None) -> ModelServer:
    engine = engine or EngineCore(engine_config)
    tok = get_tokenizer(tokenizer_name)
    return ModelServer(engine, tok,
                       model_name or engine_config.resolve_model().name)


def derive_dp_workers(leader_address: str, n_workers: int,
                      rpc_port: int) -> List[str]:
    """Worker base URLs from the LWS naming convention: the leader pod
    ``<lws>-<g>`` has workers ``<lws>-<g>-<i>`` in the same headless
    subdomain (reference start-rank arithmetic, decode.yaml:73,93)."""
    host = leader_address
    if "//" in host:
        host = host.split("//", 1)[1]
    host = host.split(":", 1)[0]
    pod, dot, domain = host.partition(".")
    suffix = f"{dot}{domain}" if dot else ""
    return [f"http://{pod}-{i}{suffix}:{rpc_port}"
            for i in range(1, n_workers + 1)]


def engine_config_from_args(args) -> EngineConfig:
    """Parsed CLI flags -> EngineConfig (shared by ``main`` and the
    multi-chip dryrun, so deploy manifests' flags are validated through the
    SAME path the server uses).

    Parallelism mapping: ``--data-parallel-mode spmd`` (default) builds ONE
    (dp, tp) mesh — the wide-EP regime where MoE experts shard over all
    dp*tp devices (reference: wide-ep decode.yaml:76,87-93); ``ranks``
    keeps dp out of the mesh (DPEngineGroup places per-rank tp submeshes).
    """
    from llm_d_tpu.parallel.mesh import MeshConfig
    dp = args.data_parallel_size
    tp = args.tensor_parallel_size
    if dp > 1 and args.data_parallel_mode == "spmd":
        mesh = MeshConfig(dp=dp, tp=tp)
    elif tp > 1:
        mesh = MeshConfig(tp=tp)
    else:
        mesh = None
    return EngineConfig(
        model=args.model, block_size=args.block_size,
        num_blocks=args.num_blocks, max_num_seqs=args.max_num_seqs,
        max_num_batched_tokens=args.max_num_batched_tokens,
        mesh=mesh,
        allow_device_subset=args.allow_device_subset,
        num_scheduler_steps=args.num_scheduler_steps,
        async_scheduling=args.async_scheduling,
        kv_offload_blocks=args.kv_offload_blocks,
        kv_transfer=bool(args.kv_transfer_config),
        kv_shared_tier_port=args.kv_shared_tier_port,
        kv_shared_tier_peers=tuple(
            s.strip() for s in args.kv_shared_tier_peers.split(",")
            if s.strip()),
        quantization=args.quantization,
        kv_cache_hbm_bytes=(int(args.kv_cache_hbm_gb * 2**30)
                            if args.kv_cache_hbm_gb else None),
        enable_dbo=args.enable_dbo,
        dbo_decode_token_threshold=args.dbo_decode_token_threshold,
        dbo_prefill_token_threshold=args.dbo_prefill_token_threshold,
        enable_eplb=args.enable_eplb,
        eplb_config=json.loads(args.eplb_config) if args.eplb_config else None,
        spec_k=args.spec_k,
        spec_strict=(True if args.spec_strict else None),
        precompile_step_shapes=args.precompile_step_shapes)


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("llmd-serve")
    p.add_argument("--config", default=None,
                   help="YAML config file (keys = these flags); layered "
                        "with --config-overlay, CLI flags win "
                        "(reference: helmfile env -> values -> hw overlay)")
    p.add_argument("--config-overlay", action="append", default=[],
                   help="additional overlay YAML(s), later wins")
    p.add_argument("--compilation-cache-dir", default=None,
                   help="persistent XLA compile cache surviving restarts "
                        "(reference: VLLM_CACHE_ROOT mounts, "
                        "decode.yaml:152-164); default <checkout>/"
                        ".jax_cache; JAX_COMPILATION_CACHE_DIR wins over "
                        "both")
    p.add_argument("--model", default="tiny")
    p.add_argument("--tokenizer", default=None)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8200)
    p.add_argument("--block-size", type=int, default=32)
    p.add_argument("--num-blocks", type=int, default=2048)
    p.add_argument("--max-num-seqs", type=int, default=128)
    p.add_argument("--max-num-batched-tokens", type=int, default=2048)
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--data-parallel-size", type=int, default=1)
    p.add_argument(
        "--data-parallel-size-local", type=int, default=None,
        help="ranks mode, multi-host: DP ranks on THIS host (reference: "
             "--data-parallel-size-local, wide-ep decode.yaml:90); "
             "default = --data-parallel-size (single host)")
    p.add_argument(
        "--data-parallel-start-rank", type=int, default=None,
        help="ranks mode, multi-host: first global rank on this host "
             "(reference: --data-parallel-start-rank, decode.yaml:93); "
             "default LWS_WORKER_INDEX * dp_size_local")
    p.add_argument(
        "--data-parallel-address", default=None,
        help="leader host address (reference: --data-parallel-address, "
             "decode.yaml:91); used to derive worker URLs under LWS when "
             "--data-parallel-workers is not given")
    p.add_argument(
        "--data-parallel-rpc-port", type=int, default=None,
        help="worker API port the leader dispatches to (reference: "
             "--data-parallel-rpc-port, decode.yaml:92; here the RPC IS "
             "the OpenAI HTTP surface); default --port")
    p.add_argument(
        "--data-parallel-hybrid-lb", action="store_true",
        help="multi-host ranks mode: every host takes external traffic "
             "and balances only its local ranks (external LB spreads "
             "hosts); without it the leader (start rank 0) proxies to "
             "worker hosts (reference: --data-parallel-hybrid-lb, "
             "decode.yaml:75,86)")
    p.add_argument(
        "--data-parallel-workers", default="",
        help="comma list of worker base URLs (http://host:port) for "
             "leader-side dispatch; default derives from the LWS naming "
             "convention")
    p.add_argument(
        "--data-parallel-mode", choices=["spmd", "ranks"], default="spmd",
        help="spmd (default): ONE engine over a (dp, tp) device mesh — "
             "attention/KV shard per dp group, MoE experts shard over ALL "
             "dp*tp devices (expert HBM 1/EP: the wide-EP regime, "
             "reference decode.yaml:76,87-93).  ranks: N independent "
             "engine cores on disjoint tp submeshes behind a local "
             "least-loaded dispatcher (the reference's process-per-rank "
             "DP shape; experts replicated per rank)")
    p.add_argument(
        "--num-scheduler-steps", type=int, default=1,
        help="fused decode steps per device program on pure-decode rounds; "
             ">1 amortizes host<->device latency at the cost of coarser "
             "streaming granularity")
    p.add_argument(
        "--async-scheduling", action="store_true",
        help="pipeline fused decode: keep one block in flight and dispatch "
             "its successor before retiring it; requires "
             "--num-scheduler-steps > 1 (reference: --async-scheduling, "
             "decode.yaml:77,97)")
    p.add_argument(
        "--allow-device-subset", action="store_true",
        help="permit a mesh smaller than the host's device count "
             "(deliberately idle chips); default is to fail fast")
    p.add_argument(
        "--latency-training-url", default=None,
        help="latency-predictor training sidecar base URL; finished "
             "requests post (features, actual ttft/tpot) samples "
             "(reference: TRAINING_SERVER_URL)")
    p.add_argument(
        "--kv-offload-blocks", type=int, default=0,
        help="host-RAM tier capacity in KV blocks (0 = off); evicted "
             "device blocks stay restorable (reference: tiered-prefix-cache)")
    p.add_argument(
        "--kv-shared-tier-port", type=int, default=None,
        help="serve host-tier blocks to peer pods on this port (0 = "
             "ephemeral; requires --kv-offload-blocks > 0; the LMCache "
             "role)")
    p.add_argument(
        "--kv-shared-tier-peers", default="",
        help="comma list of peer shared-tier servers consulted on prefix "
             "miss before recompute: static host:port entries and/or "
             "dynamic discovery specs (dns:<svc>:<port>, "
             "k8s:[ns/]<svc>:<port>) that follow pod churn")
    p.add_argument(
        "--quantization", default=None, choices=[None, "int8"],
        help="MoE expert-weight quantization (DeepGEMM role; halves "
             "expert HBM residency)")
    p.add_argument(
        "--kv-cache-hbm-gb", type=float, default=None,
        help="auto-size --num-blocks from this HBM budget; overrides "
             "--num-blocks")
    p.add_argument(
        "--enable-dbo", action="store_true",
        help="MoE dual-batch overlap: >=2 dispatch chunks above the token "
             "threshold so all-to-all overlaps expert GEMM (reference: "
             "--enable-dbo, decode.yaml:78)")
    p.add_argument(
        "--dbo-decode-token-threshold", type=int, default=32,
        help="min tokens before DBO splits a decode batch (decode.yaml:98)")
    p.add_argument(
        "--dbo-prefill-token-threshold", type=int, default=32,
        help="min tokens before DBO splits a prefill batch (prefill.yaml:79)")
    p.add_argument(
        "--enable-eplb", action="store_true",
        help="MoE expert load balancing with redundant experts "
             "(reference: --enable-eplb, decode.yaml:79)")
    p.add_argument(
        "--eplb-config", default=None,
        help='JSON eplb config, e.g. \'{"window_size":1000,'
             '"step_interval":3000,"num_redundant_experts":32}\'')
    p.add_argument(
        "--spec-k", type=int, default=None,
        help="speculative decoding (MTP draft-and-verify): draft tokens "
             "per decode step; the engine verifies all K drafts in one "
             "fused forward and emits 1..K+1 tokens per step, "
             "byte-identical to non-spec decode for greedy and seeded "
             "sampling, with per-request adaptive backoff to K=1 on low "
             "acceptance.  Default: LLMD_SPEC_K (0 = off); "
             "LLMD_SPEC_DECODE=off is the kill switch")
    p.add_argument(
        "--spec-strict", action="store_true",
        help="fail startup instead of demoting when a requested feature "
             "(spec decode under an incompatible config) cannot be "
             "armed — no silently degraded serving configs.  Runtime "
             "per-request demotions still only count "
             "llmd_tpu:engine_feature_disabled_total.  Default: "
             "LLMD_SPEC_STRICT (0 = demote-and-count)")
    p.add_argument(
        "--precompile-step-shapes", action="store_true",
        help="compile every classic step program the bucket scheme can "
             "reach (tokens x rows x longest chunk) before the first "
             "request instead of on first use: a longer start, and no "
             "request ever waits for a compile")
    p.add_argument(
        "--kv-transfer-config", default=None,
        help="JSON KV-connector config for PD disaggregation, e.g. "
             '\'{"kv_connector":"TPUConnector","kv_role":"kv_producer",'
             '"kv_ip":"10.0.0.5","kv_port":5557}\' (reference: '
             "ms-pd/values_tpu.yaml:44,131)")
    p.add_argument(
        "--kv-events-endpoint", default=None,
        help="ZMQ endpoint of the EPP's KV-event sink (e.g. "
             "tcp://epp-host:5557); enables precise prefix routing "
             "(reference: --kv-events-config, ms-kv-events/values.yaml:40)")
    p.add_argument(
        "--pod-identity", default=None,
        help="this replica's address as the EPP sees it (host:port); "
             "defaults to <host>:<port>")
    return p


def main(argv: Optional[List[str]] = None) -> None:
    p = build_arg_parser()
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO)   # before any startup logs
    if args.config or args.config_overlay:
        from llm_d_tpu.utils.config import apply_file_config, load_layers
        layers = ([args.config] if args.config else []) + args.config_overlay
        apply_file_config(args, p, load_layers(layers), argv=argv)
    if (args.kv_shared_tier_port is not None
            or args.kv_shared_tier_peers.strip()) \
            and args.kv_offload_blocks <= 0:
        # Silently running with the cross-pod cache off while the operator
        # configured it is a fleet-wide misconfiguration, not a fallback.
        p.error("--kv-shared-tier-port/--kv-shared-tier-peers require "
                "--kv-offload-blocks > 0 (the shared tier serves the host "
                "tier's blocks)")
    from llm_d_tpu.utils.compile_cache import configure_compile_cache
    logger.info("compile cache: %s",
                configure_compile_cache(args.compilation_cache_dir))

    import os as _os

    from llm_d_tpu.parallel.mesh import maybe_init_distributed
    dp_local = args.data_parallel_size_local or args.data_parallel_size
    if dp_local > args.data_parallel_size \
            or args.data_parallel_size % dp_local:
        p.error(f"--data-parallel-size-local {dp_local} must divide "
                f"--data-parallel-size {args.data_parallel_size}")
    multi_host_ranks = (args.data_parallel_mode == "ranks"
                       and dp_local < args.data_parallel_size)
    if multi_host_ranks:
        # Reference DP semantics: hosts run INDEPENDENT engine ranks (no
        # slice-wide jax process group — each host's ranks live on its
        # local chips); the LWS env only drives rank arithmetic + worker
        # address derivation (decode.yaml:73,89-93).
        start_rank = args.data_parallel_start_rank
        if start_rank is None:
            start_rank = int(
                _os.environ.get("LWS_WORKER_INDEX", "0")) * dp_local
        logger.info("multi-host DP: local ranks %d..%d of %d (%s)",
                    start_rank, start_rank + dp_local - 1,
                    args.data_parallel_size,
                    "hybrid-lb" if args.data_parallel_hybrid_lb
                    else "leader dispatch")
    else:
        start_rank = 0
        # Multi-host TPU slice (spmd / tp): join the process group before
        # touching devices (LWS env contract; deploy/wide-ep-lws).
        if maybe_init_distributed():
            logger.info("joined LWS process group: %d hosts",
                        int(_os.environ.get("LWS_GROUP_SIZE", "1")))
    cfg = engine_config_from_args(args)
    engine = None
    if args.data_parallel_size > 1 and args.data_parallel_mode == "ranks":
        # DP = per-rank engine cores over disjoint tp-submeshes behind a
        # local least-loaded dispatcher (reference: decode.yaml:73-93).
        # (spmd mode needs no special engine: cfg.mesh carries the dp axis
        # and EngineCore itself runs the stacked SPMD program.)
        import jax as _jax

        from llm_d_tpu.engine.dp_group import DPEngineGroup
        engine = DPEngineGroup(cfg, dp_size=dp_local,
                               devices=list(_jax.local_devices()),
                               start_rank=start_rank)
    server = build_server(cfg, args.tokenizer, engine=engine)
    if multi_host_ranks and not args.data_parallel_hybrid_lb \
            and start_rank == 0:
        # Leader-side cross-host dispatch over the OpenAI HTTP surface.
        workers = [w.strip() for w in args.data_parallel_workers.split(",")
                   if w.strip()]
        if not workers:
            leader = (args.data_parallel_address
                      or _os.environ.get("LWS_LEADER_ADDRESS", ""))
            n_hosts = args.data_parallel_size // dp_local
            rpc_port = args.data_parallel_rpc_port or args.port
            if leader:
                workers = derive_dp_workers(leader, n_hosts - 1, rpc_port)
        if workers:
            server.dp_pool = DPWorkerPool(workers)
            logger.info("DP leader dispatching across %d worker hosts: %s",
                        len(workers), workers)
        else:
            logger.warning(
                "multi-host DP leader has no worker addresses (pass "
                "--data-parallel-workers or run under LWS); serving "
                "local ranks only")
    if args.latency_training_url:
        server.latency_training_url = args.latency_training_url.rstrip("/")
    if args.kv_transfer_config:
        from llm_d_tpu.transfer import KVConnectorConfig, TpuConnector
        ktc = json.loads(args.kv_transfer_config)
        conn_cfg = KVConnectorConfig(
            kv_role=ktc.get("kv_role", "kv_both"),
            host=ktc.get("kv_ip", "127.0.0.1"),
            port=int(ktc.get("kv_port", 0)),
            kv_load_failure_policy=ktc.get("kv_load_failure_policy", "fail"))
        if hasattr(server.engine, "set_kv_connectors"):
            # DP group: one transfer server per rank, ports offset by rank.
            server.engine.set_kv_connectors(conn_cfg)
            logger.info(
                "KV connectors: role=%s serving on %s ports %s",
                conn_cfg.kv_role, conn_cfg.host,
                [c.port for c in server.engine.kv_connectors])
        else:
            server.engine.kv_connector = TpuConnector(conn_cfg)
            logger.info("KV connector: role=%s serving on %s:%s",
                        conn_cfg.kv_role, conn_cfg.host,
                        server.engine.kv_connector.port)
    if args.kv_events_endpoint:
        from llm_d_tpu.events.kv_events import ZmqKvEventPublisher
        identity = args.pod_identity
        if not identity:
            # The EPP keys its prefix index by the endpoint address it
            # routes to — a wildcard bind address would never match.
            host = args.host
            if host in ("0.0.0.0", "::", ""):
                import socket as _socket
                host = _socket.gethostbyname(_socket.gethostname())
                logger.warning(
                    "kv-events: --pod-identity not set and --host is a "
                    "wildcard; guessing %s:%s (set --pod-identity to the "
                    "address the EPP routes to)", host, args.port)
            identity = f"{host}:{args.port}"
        publisher = ZmqKvEventPublisher(
            args.kv_events_endpoint, identity, model=args.model)
        # A DP group caches blocks in every rank's manager; the precise
        # prefix index must see all of them, not just rank 0's.
        for km in getattr(server.engine, "kv_managers",
                          [server.engine.kv_manager]):
            publisher.attach(km)
        publisher.start()
        server.kv_event_publisher = publisher
    web.run_app(server.build_app(), host=args.host, port=args.port)


if __name__ == "__main__":
    main()
