"""Accelerator-free inference simulator (llm-d-inference-sim equivalent).

A fake model server with the REAL API surface — OpenAI endpoints, the
three-probe readiness contract, and the ``vllm:*`` metric naming scheme — but no
engine: responses are synthesized at configurable TTFT/TPOT.  The reference
uses exactly such a component to scale-test the scheduler and autoscaler "in
wide or dense configurations on CPU-only machines" (reference:
guides/simulated-accelerators/README.md:5-7, ms-sim/values.yaml:26).

The simulator models the load signals the EPP scores on:
  - ``vllm:num_requests_running`` / ``vllm:num_requests_waiting`` via a
    bounded running-slot pool (``max_num_seqs``);
  - ``vllm:kv_cache_usage_perc`` from simulated KV blocks held by active
    requests (prompt+output tokens / block_size against ``num_blocks``);
  - a prefix cache with the engine's real chain hashing
    (``llm_d_tpu.utils.hashing``) feeding ``vllm:prefix_cache_*`` and
    optional KV events for the precise-prefix scorer.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import random
import time
import uuid as uuid_mod
from typing import Any, Dict, List, Optional

from aiohttp import web

from llm_d_tpu.server import stream_resume
from llm_d_tpu.utils import tracing
from llm_d_tpu.utils.config import env_choice, env_float, env_int
from llm_d_tpu.utils.faultinject import FaultInjected, get_injector
from llm_d_tpu.utils.hashing import hash_token_blocks
from llm_d_tpu.utils.lifecycle import (
    DEADLINE_EXCEEDED_HEADER,
    DRAINING_HEADER,
    REQUEST_ID_HEADER,
    RESUME_OFFSET_HEADER,
    parse_criticality,
    parse_deadline,
)
from llm_d_tpu.utils.metrics import EngineMetrics

logger = logging.getLogger(__name__)


class DeadlineExceeded(Exception):
    """A request's latency budget expired while it was queued for a slot
    (the sim's analogue of the scheduler's queued-deadline rejection)."""

_LOREM = ("the quick brown fox jumps over the lazy dog and runs far away "
          "into deep green woods while rain falls soft on old stone walls "
          ).split()


class SimConfig:
    def __init__(
        self,
        model: str = "sim-model",
        ttft_ms: float = 50.0,
        tpot_ms: float = 10.0,
        max_num_seqs: int = 64,
        num_blocks: int = 1024,
        block_size: int = 64,
        startup_delay_s: float = 0.0,
        seed: int = 0,
        spec_k: Optional[int] = None,
        spec_acceptance: float = 0.7,
        prefill_chunk: Optional[int] = None,
        step_prefill_token_ms: float = 0.0,
        num_scheduler_steps: int = 1,
        eplb_skew: float = 0.0,
        eplb_mode: str = "online",
        eplb_num_experts: int = 64,
        eplb_ep: int = 8,
        eplb_step_interval: int = 64,
        eplb_move_budget: Optional[int] = None,
        eplb_imbalance_threshold: Optional[float] = None,
    ) -> None:
        self.model = model
        self.ttft_ms = ttft_ms
        self.tpot_ms = tpot_ms
        self.max_num_seqs = max_num_seqs
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.startup_delay_s = startup_delay_s
        self.seed = seed
        # Speculative-decode mirror: draft depth K (None resolves the
        # engine's env knobs — LLMD_SPEC_DECODE / LLMD_SPEC_K) and the
        # seeded per-draft acceptance rate of the sim's acceptance model.
        self.spec_k = spec_k
        self.spec_acceptance = spec_acceptance
        # Mixed-round fusion mirror (round 15): the fused engine folds
        # prefill-chunk tokens into the SAME step as decode/verify rows,
        # so a decode step overlapping an in-flight prefill pays a
        # chunk-size-dependent latency tax.  prefill_chunk = None
        # resolves the engine's LLMD_PREFILL_CHUNK knob ("auto" -> 0 =
        # unchunked: the sim has no step-time model to budget with);
        # step_prefill_token_ms = 0 keeps timing byte-identical.
        self.prefill_chunk = prefill_chunk
        self.step_prefill_token_ms = step_prefill_token_ms
        # Fused-multistep mirror (round 16): the engine dispatches ONE
        # N-round program and syncs once per dispatch, so the sim charges
        # its per-step latency in N-step bursts — same total time, TPOT
        # jitter amortized, exactly the shape the real pipeline produces.
        # 1 = classic per-step timing (byte-identical to round 15).
        self.num_scheduler_steps = num_scheduler_steps
        # Live-EPLB mirror (round 17): under a Zipf(eplb_skew) routing
        # popularity, the hottest EP shard serializes the dispatch, so a
        # decode step stretches by the hot-shard overhang of the ACTIVE
        # placement.  eplb_mode="static" keeps the uniform initial
        # placement forever; "online" re-plans at eplb_step_interval with
        # the REAL delta planner (parallel.eplb) and converges after
        # ceil(moves / move_budget) background-staging steps with zero
        # stall.  eplb_skew = 0 keeps timing byte-identical (mirror off);
        # move_budget / imbalance_threshold = None resolve the engine's
        # LLMD_EPLB_MOVE_BUDGET / LLMD_EPLB_IMBALANCE_THRESHOLD knobs so
        # a chaos fleet flips modes with one environment.
        self.eplb_skew = eplb_skew
        self.eplb_mode = eplb_mode
        self.eplb_num_experts = eplb_num_experts
        self.eplb_ep = eplb_ep
        self.eplb_step_interval = eplb_step_interval
        self.eplb_move_budget = eplb_move_budget
        self.eplb_imbalance_threshold = eplb_imbalance_threshold


class InferenceSimulator:
    """State machine behind the endpoints; no accelerator anywhere."""

    def __init__(self, config: SimConfig,
                 kv_event_sink=None) -> None:
        self.config = config
        self.metrics = EngineMetrics(config.model)
        # llmd-trace: the sim emits the SAME span shapes as the real
        # engine (queue/prefill/decode phases, first_token event), so
        # the trace_report TTFT decomposition validates on CPU-only
        # machines against the full gateway -> replica tree.
        self.tracer = tracing.get_tracer("sim")
        self.started_at = time.time()
        self.model_loaded = False
        # Lifecycle mirror: draining refuses new work (503) while
        # in-flight requests complete — the chaos suite roll-restarts an
        # entire sim fleet against this flag.
        self.draining = False
        # Engine-death mirror: the ``engine.step`` fault point fires in a
        # token loop (keyed by model name, so a chaos run kills ONE
        # replica via match=) — every in-flight stream breaks abruptly
        # and new work is refused, exactly like a crashed engine core.
        self.dead = False
        # Speculative-decode mirror (round 12): with spec_k > 0 tokens
        # are emitted in variable-size CHUNKS (1..K+1 per engine step,
        # from a seeded acceptance model) on multi-token SSE frames, and
        # one TPOT is charged per STEP instead of per token — the same
        # shapes and accepted-throughput effect the real draft+verify
        # engine produces, minus the accelerator.  config.spec_k = None
        # resolves the engine's env knobs so a chaos fleet flips modes
        # with one environment.
        spec_k = config.spec_k
        if spec_k is None:
            spec_k = (env_int("LLMD_SPEC_K", 0)
                      if env_choice("LLMD_SPEC_DECODE", "auto",
                                    ("auto", "off")) != "off" else 0)
        self.spec_k = max(0, int(spec_k))
        self.spec_acceptance = config.spec_acceptance
        # Mixed-round fusion mirror (round 15): the engine's fused step
        # carries prefill-chunk rows alongside decode/verify rows, so a
        # decode TPOT stretches by the prefill tokens sharing its round.
        # The sim mirrors that as a per-step surcharge proportional to
        # the chunk size and the number of in-flight prefills (tracked
        # around the TTFT sleep).  Defaults are inert: surcharge 0 ms.
        chunk = config.prefill_chunk
        if chunk is None:
            raw = os.environ.get("LLMD_PREFILL_CHUNK", "auto")
            try:
                chunk = max(1, int(raw))
            except ValueError:
                # "auto" (or garbage): the engine would size chunks from
                # its step-time model; the sim has none, so unchunked.
                chunk = 0
        self.prefill_chunk = max(0, int(chunk))
        self.step_prefill_token_ms = max(
            0.0, float(config.step_prefill_token_ms))
        self.num_scheduler_steps = max(1, int(config.num_scheduler_steps))
        # Live-EPLB mirror (round 17; see SimConfig): placement state is
        # built lazily from the real planner, env knobs resolved here.
        self.eplb_skew = max(0.0, float(config.eplb_skew))
        self.eplb_mode = str(config.eplb_mode)
        self.eplb_num_experts = max(1, int(config.eplb_num_experts))
        self.eplb_ep = max(1, int(config.eplb_ep))
        self.eplb_step_interval = max(1, int(config.eplb_step_interval))
        budget = config.eplb_move_budget
        if budget is None:
            budget = env_int("LLMD_EPLB_MOVE_BUDGET", 64)
        self.eplb_move_budget = max(1, int(budget))
        thr = config.eplb_imbalance_threshold
        if thr is None:
            thr = env_float("LLMD_EPLB_IMBALANCE_THRESHOLD", 1.0)
        self.eplb_imbalance_threshold = float(thr)
        self._eplb_steps = 0           # decode steps charged so far
        self._eplb_state: Optional[Dict[str, Any]] = None
        self._prefill_inflight = 0
        self._running = 0
        self._waiting = 0
        self._blocks_used = 0          # simulated KV blocks held
        self._slots = asyncio.Semaphore(config.max_num_seqs)
        # Prefix "cache": block hash -> last-touch time (LRU by re-insert).
        self._cached_blocks: Dict[bytes, float] = {}
        # Optional callable(event_type, block_hashes) for KV events
        # (the ZMQ publisher hooks in here).
        self.kv_event_sink = kv_event_sink

    # ---------- token accounting ----------

    def _tokenize(self, prompt: str) -> List[int]:
        # Deterministic cheap "tokenizer": one token per 4 chars.
        data = prompt.encode()
        return [int.from_bytes(data[i:i + 2], "little") % 50000
                for i in range(0, max(len(data), 1), 4)]

    def _update_gauges(self) -> None:
        self.metrics.num_requests_running.set(self._running)
        self.metrics.num_requests_waiting.set(self._waiting)
        usable = self.config.num_blocks
        self.metrics.kv_cache_usage_perc.set(
            min(1.0, self._blocks_used / usable if usable else 0.0))
        if self.draining:
            self.metrics.drain_inflight.set(self._running + self._waiting)

    def set_draining(self) -> None:
        self.draining = True
        self.metrics.drain_state.set(1)
        self._update_gauges()

    def _prefix_hit_tokens(self, token_ids: List[int]) -> int:
        hashes = hash_token_blocks(token_ids, self.config.block_size)
        hits = 0
        for h in hashes:
            if h in self._cached_blocks:
                hits += 1
            else:
                break
        return hits * self.config.block_size

    def _store_prefix(self, token_ids: List[int]) -> None:
        hashes = hash_token_blocks(token_ids, self.config.block_size)
        # LRU capacity = num_blocks entries; evict oldest beyond it.
        now = time.monotonic()
        stored = []
        for h in hashes:
            if h not in self._cached_blocks:
                stored.append(h)
            self._cached_blocks[h] = now
        while len(self._cached_blocks) > self.config.num_blocks:
            oldest = min(self._cached_blocks, key=self._cached_blocks.get)
            del self._cached_blocks[oldest]
            if self.kv_event_sink:
                self.kv_event_sink("BlockRemoved", [oldest])
        if stored and self.kv_event_sink:
            self.kv_event_sink("BlockStored", stored)

    def restore_prefix(self, token_ids: List[int], n_blocks: int) -> int:
        """Mark the leading ``n_blocks`` prefix blocks of ``token_ids``
        resident, as if their KV had been transferred in from a peer
        replica or the shared host tier (the gateway's kv-placement
        restore hop calls this AFTER charging the modeled transfer
        time).  Restored blocks are ordinary cache entries afterwards:
        ``_prefix_hit_tokens`` counts them and they age out by LRU like
        locally-computed ones.  Returns the number of blocks restored."""
        hashes = hash_token_blocks(token_ids, self.config.block_size)
        restore = hashes[:max(0, n_blocks)]
        if not restore:
            return 0
        now = time.monotonic()
        stored = []
        for h in restore:
            if h not in self._cached_blocks:
                stored.append(h)
            self._cached_blocks[h] = now
        while len(self._cached_blocks) > self.config.num_blocks:
            oldest = min(self._cached_blocks, key=self._cached_blocks.get)
            del self._cached_blocks[oldest]
            if self.kv_event_sink:
                self.kv_event_sink("BlockRemoved", [oldest])
        if stored and self.kv_event_sink:
            self.kv_event_sink("BlockStored", stored)
        return len(restore)

    def spec_plan(self, prompt_ids: List[int], start: int,
                  max_tokens: int) -> List[int]:
        """Seeded acceptance model: per-step emitted-chunk sizes for a
        spec-decode stream, deterministic per (sim seed, prompt, resume
        offset).  Each step drafts K tokens and accepts a geometric
        prefix at ``spec_acceptance`` per draft, emitting 1 + accepted
        tokens — the real verifier's shape.  Deterministic per offset so
        a PR 9 resume's continuation chunks splice at exact journal
        offsets; empty when spec is off (one token per frame, today's
        stream byte for byte)."""
        K = self.spec_k
        if K <= 0:
            return []
        rng = random.Random(self.config.seed * 1000003
                            + len(prompt_ids) * 8191
                            + (sum(prompt_ids) & 0xFFFF) * 127 + start)
        plan: List[int] = []
        i = start
        while i < max_tokens:
            a = 0
            while a < K and rng.random() < self.spec_acceptance:
                a += 1
            c = min(1 + a, max_tokens - i)
            plan.append(c)
            i += c
        return plan

    # ---------- request lifecycle ----------

    async def admit(self, prompt_ids: List[int], max_tokens: int,
                    deadline_epoch: Optional[float] = None,
                    criticality: str = "standard",
                    start: int = 0, span=None) -> Dict[str, Any]:
        """Queue for a running slot.  Raises :class:`DeadlineExceeded`
        when the budget expires while queued (mirrors the real
        scheduler's queued-deadline rejection; the simulated KV blocks
        were never held, so they "free the same step").  Returns the
        ticket :meth:`stream_tokens` consumes.  ``span`` (llmd-trace):
        the request span the queue/prefill/decode phase spans parent on."""
        q0 = time.time()
        self._waiting += 1
        try:
            self._update_gauges()
            arrival = time.monotonic()
            left = (None if deadline_epoch is None
                    else deadline_epoch - time.time())
            try:
                if left is not None and left <= 0:
                    raise DeadlineExceeded()
                if left is None:
                    await self._slots.acquire()
                else:
                    await asyncio.wait_for(self._slots.acquire(), left)
            except (asyncio.TimeoutError, DeadlineExceeded):
                self.metrics.inc_deadline_exceeded(criticality)
                raise DeadlineExceeded() from None
        finally:
            self._waiting -= 1
            self._update_gauges()
        wait_s = time.monotonic() - arrival
        self.metrics.observe_queue_wait(criticality, wait_s)
        self.metrics.observe_phase("queue", criticality, wait_s)
        if span is not None:
            self.tracer.record_span("sim.queue", q0, time.time(),
                                    parent=span, phase="queue")
        n_blocks = (len(prompt_ids) + max_tokens) // \
            self.config.block_size + 1
        self._running += 1
        self._blocks_used += n_blocks
        self._update_gauges()
        return {"prompt_ids": prompt_ids, "max_tokens": max_tokens,
                "deadline_epoch": deadline_epoch,
                "criticality": criticality, "n_blocks": n_blocks,
                "arrival": arrival, "expired": False, "released": False,
                "start": start, "resume_src": None, "resume_restored": 0,
                "span": span}

    def release_ticket(self, ticket: Dict[str, Any]) -> None:
        """Idempotent slot/block release.  ``stream_tokens`` calls this in
        its finally; callers must ALSO call it when an admitted ticket's
        generator might never be entered (e.g. client disconnect between
        admission and the first token), or the sim's capacity leaks."""
        if ticket["released"]:
            return
        ticket["released"] = True
        self._running -= 1
        self._blocks_used -= ticket["n_blocks"]
        self._slots.release()
        self._update_gauges()

    # One prompt's worth of tokens — the prefill cost a fused round pays
    # per in-flight prefill when chunking is OFF (the engine would put
    # the whole remaining prompt in one round).  Any configured chunk is
    # smaller, which is exactly the decode-priority budgeting story.
    _UNCHUNKED_TOKENS = 512

    def _mixed_step_extra_ms(self) -> float:
        """Per-step latency surcharge a decode step pays for the
        prefill-chunk tokens fused into the same round (round 15).

        Pure function of (config, in-flight prefill count) so tests can
        assert the policy structurally without timing sleeps: 0 when the
        mirror is off or no prefill overlaps; otherwise one chunk per
        in-flight prefill, ``step_prefill_token_ms`` per token — smaller
        chunks mean a smaller tax on every overlapped decode step."""
        if self.step_prefill_token_ms <= 0.0 or self._prefill_inflight <= 0:
            return 0.0
        chunk = (self.prefill_chunk if self.prefill_chunk > 0
                 else self._UNCHUNKED_TOKENS)
        return self._prefill_inflight * chunk * self.step_prefill_token_ms

    def _eplb_model(self) -> Optional[Dict[str, Any]]:
        """Lazily build the EPLB placement cost model from the REAL
        planner (parallel.eplb is pure numpy at plan level): the Zipf
        popularity, the hot-shard overhang of the uniform initial
        placement vs. the load-proportional one, and how many
        budget-limited staging steps the online migration needs."""
        if self.eplb_skew <= 0.0:
            return None
        if self._eplb_state is None:
            import numpy as np
            from llm_d_tpu.parallel.eplb import (
                align_plan, plan_delta, plan_placement)
            E, ep = self.eplb_num_experts, self.eplb_ep
            r = (-E) % ep + ep
            load = np.arange(1, E + 1, dtype=np.float64) ** -self.eplb_skew

            def shard_imbalance(plan):
                per_replica = load / plan.num_replicas
                shard = np.zeros(ep)
                for p, e in enumerate(plan.phys_to_logical):
                    shard[p // plan.slots_per_shard] += per_replica[e]
                return float(shard.max() / shard.mean())

            initial = plan_placement(np.ones(E), r, ep)
            expert_imb = float(load.max() / load.mean())
            balanced = align_plan(plan_placement(load, r, ep), initial)
            moves = len(plan_delta(initial, balanced))
            stage_steps = -(-moves // self.eplb_move_budget)
            migrates = (self.eplb_mode == "online"
                        and expert_imb >= self.eplb_imbalance_threshold
                        and moves > 0)
            self._eplb_state = {
                "initial_imbalance": shard_imbalance(initial),
                "balanced_imbalance": shard_imbalance(balanced),
                "expert_imbalance": expert_imb,
                "moves": moves,
                "stage_steps": stage_steps,
                # Staging overlaps decode, so the old (skewed) cost
                # applies until the flip; the flip itself is free.
                "flip_step": (self.eplb_step_interval + stage_steps
                              if migrates else None),
            }
            self.metrics.eplb_imbalance.set(
                self._eplb_state["initial_imbalance"])
        return self._eplb_state

    def _eplb_step_extra_ms(self) -> float:
        """Per-step latency surcharge of serving a Zipf-skewed routing
        mix on the ACTIVE expert placement (round 17).

        Pure function of (config, decode-step counter): 0 when the
        mirror is off; otherwise ``tpot_ms`` scaled by the hot-shard
        overhang (max/mean - 1).  Static placement pays the skewed
        overhang forever; online EPLB pays it only until the migration
        flips (interval + budget-limited staging steps), then the
        balanced overhang — the steady-state step-time win the bench
        measures, with no stall spike at the flip."""
        st = self._eplb_model()
        if st is None:
            return 0.0
        flip = st["flip_step"]
        if flip is not None and self._eplb_steps >= flip:
            if not st.get("flipped"):
                st["flipped"] = True
                self.metrics.eplb_migrations.inc()
                self.metrics.eplb_migration_stall.observe(0.0)
                self.metrics.eplb_imbalance.set(st["balanced_imbalance"])
            imb = st["balanced_imbalance"]
        else:
            imb = st["initial_imbalance"]
        return self.config.tpot_ms * max(0.0, imb - 1.0)

    def eplb_report(self) -> Optional[Dict[str, Any]]:
        """Cost-model summary for bench extras / cluster projections."""
        st = self._eplb_model()
        if st is None:
            return None
        out = dict(st)
        out.update(mode=self.eplb_mode, skew=self.eplb_skew,
                   move_budget=self.eplb_move_budget,
                   step_interval=self.eplb_step_interval,
                   decode_steps=self._eplb_steps)
        return out

    async def stream_tokens(self, ticket: Dict[str, Any]):
        """Yields (token_index, token_text) at the simulated rate for an
        admitted ticket; releases the slot + blocks on exit.  A deadline
        that expires mid-generation truncates at the next token boundary
        (``ticket["expired"]`` turns True) — the real engine's
        step-boundary eviction.

        Token i's text depends only on (prompt, i), so a RESUME ticket
        (``start`` > 0 — the gateway relay's journal offset) continues
        the exact sequence an uninterrupted run would have produced: the
        chaos suite's byte-identical continuity oracle.  The resume
        handshake's restore-vs-recompute verdict lands in
        ``ticket["resume_src"]`` before the first yield (restore-first
        from the prefix cache standing in for the host/shared KV tier;
        a fired ``kv.restore`` fault degrades to recompute at full TTFT).

        The ``engine.step`` fault point (keyed by model name) mirrors
        engine death: the firing stream raises out of its handler — the
        connection breaks without [DONE] — and the whole replica turns
        ``dead`` (every other in-flight stream breaks, new work 500s)."""
        c = self.config
        prompt_ids = ticket["prompt_ids"]
        arrival = ticket["arrival"]
        deadline_epoch = ticket["deadline_epoch"]
        start = ticket.get("start", 0)
        span = ticket.get("span")
        criticality = ticket["criticality"]
        try:
            p0 = time.time()
            cached = self._prefix_hit_tokens(prompt_ids)
            self.metrics.prefix_cache_queries.inc(len(prompt_ids))
            if cached:
                self.metrics.prefix_cache_hits.inc(
                    min(cached, len(prompt_ids)))
            # Gateway-side accounting: replica counters reset on
            # kill/restore, so the fleet-level scoreboard reads the
            # per-request hit off the ticket instead of scraping.
            ticket["cached_tokens"] = min(cached, len(prompt_ids))
            ticket["prompt_tokens"] = len(prompt_ids)
            # TTFT scales down with prefix-cache hits (the signal the
            # prefix scorers exploit).
            miss_frac = 1.0 - min(cached, len(prompt_ids)) / max(
                1, len(prompt_ids))
            if start:
                restored = cached > 0
                try:
                    await get_injector().acheck("kv.restore", key=c.model)
                except FaultInjected:
                    restored = False
                if span is not None:
                    span.add_event("kv.restore",
                                   verdict="hit" if restored else "miss",
                                   offset=start)
                ticket["resume_src"] = (
                    stream_resume.OUTCOME_RESTORED if restored
                    else stream_resume.OUTCOME_RECOMPUTED)
                ticket["resume_restored"] = start if restored else 0
                # Restored resume skips the prompt+generated recompute;
                # a tier miss replays it as a full prefill.
                miss_frac = 0.0 if restored else 1.0
            # While this request prefills, overlapped decode steps pay
            # the mixed-round surcharge (see _mixed_step_extra_ms).
            self._prefill_inflight += 1
            try:
                await asyncio.sleep(c.ttft_ms / 1e3 * max(miss_frac, 0.1))
            finally:
                self._prefill_inflight -= 1
            self.metrics.prompt_tokens.inc(len(prompt_ids))
            self.metrics.time_to_first_token.observe(
                time.monotonic() - arrival)
            # Prefill phase span closes at the first-token boundary (the
            # report's decomposition splices it after the gateway's
            # queue+schedule legs).
            now = time.time()
            self.metrics.observe_phase("prefill", criticality, now - p0)
            if span is not None:
                self.tracer.record_span(
                    "sim.prefill", p0, now, parent=span, phase="prefill",
                    cached_tokens=cached or None,
                    resume_offset=start or None)
                span.add_event("first_token", offset=start)
            self._store_prefix(prompt_ids)
            reason = "length"
            emitted = 0
            d0 = time.time()
            # Spec mirror: the plan's chunk sizes are the per-step
            # accepted token counts; one TPOT per STEP (a draft+verify
            # step costs one forward whatever it emits) and the spec
            # counters advance per step.  The SSE writer consumes the
            # same plan to build multi-token frames.
            plan = self.spec_plan(prompt_ids, start, ticket["max_tokens"])
            ticket["spec_plan"] = plan
            step_starts: Dict[int, int] = {}
            pos = start
            for csize in plan:
                step_starts[pos] = csize
                pos += csize
            pending_ms = 0.0
            pending_steps = 0
            for i in range(start, ticket["max_tokens"]):
                if self.dead:
                    raise RuntimeError("engine dead")
                try:
                    await get_injector().acheck("engine.step", key=c.model)
                except FaultInjected:
                    self.dead = True
                    logger.error("sim %s: engine.step fault — replica is "
                                 "now dead", c.model)
                    if span is not None:
                        span.add_event("fault.engine.step", token=i)
                    raise
                if i in step_starts and self.spec_k > 0:
                    self.metrics.spec_draft_tokens.inc(self.spec_k)
                    self.metrics.spec_accepted_tokens.inc(
                        step_starts[i] - 1)
                if emitted > 0 and (not step_starts or i in step_starts):
                    step_ms = (c.tpot_ms + self._mixed_step_extra_ms()
                               + self._eplb_step_extra_ms())
                    self._eplb_steps += 1
                    pending_ms += step_ms
                    pending_steps += 1
                    if pending_steps >= self.num_scheduler_steps:
                        # One host dispatch per N sim steps (fused-
                        # multistep mirror): the sleep lands as an
                        # N-round burst and ITL is observed at its per-
                        # step mean — jitter amortized, total unchanged.
                        await asyncio.sleep(pending_ms / 1e3)
                        self.metrics.inter_token_latency.observe(
                            pending_ms / 1e3 / pending_steps)
                        pending_ms = 0.0
                        pending_steps = 0
                if deadline_epoch is not None \
                        and time.time() > deadline_epoch:
                    ticket["expired"] = True
                    reason = "deadline"
                    self.metrics.inc_deadline_exceeded(
                        ticket["criticality"])
                    break
                word = _LOREM[(len(prompt_ids) + i) % len(_LOREM)]
                self.metrics.generation_tokens.inc()
                emitted += 1
                yield (i, word + " ")
            self.metrics.request_success.labels(
                model_name=self.config.model,
                finished_reason=reason).inc()
            self.metrics.e2e_request_latency.observe(
                time.monotonic() - arrival)
            self.metrics.observe_phase("decode", criticality,
                                       time.time() - d0)
            if span is not None:
                self.tracer.record_span(
                    "sim.decode", d0, time.time(), parent=span,
                    phase="decode", n_tokens=emitted, finish=reason)
        finally:
            self.release_ticket(ticket)

    async def run_request(self, prompt_ids: List[int], max_tokens: int,
                          deadline_epoch: Optional[float] = None,
                          criticality: str = "standard"):
        """Admit + stream in one call (legacy surface)."""
        ticket = await self.admit(prompt_ids, max_tokens,
                                  deadline_epoch, criticality)
        async for item in self.stream_tokens(ticket):
            yield item


class SimServer:
    """HTTP surface identical to the real model server's contract."""

    def __init__(self, sim: InferenceSimulator) -> None:
        self.sim = sim

    def build_app(self) -> web.Application:
        app = web.Application()
        app.router.add_get("/health", self.health)
        app.router.add_get("/v1/models", self.models)
        app.router.add_get("/metrics", self.metrics)
        app.router.add_get("/debug/traces", self.debug_traces)
        app.router.add_post("/v1/completions", self.completions)
        app.router.add_post("/v1/chat/completions", self.chat_completions)
        app.router.add_post("/admin/drain", self.admin_drain)
        app.on_startup.append(self._on_startup)
        return app

    async def admin_drain(self, request: web.Request) -> web.Response:
        """Same drain protocol as the real model server: readiness flips,
        new inference 503s, in-flight completes (the caller owns the
        bounded wait)."""
        self.sim.set_draining()
        return web.json_response({
            "status": "draining",
            "inflight": self.sim._running + self.sim._waiting,
        })

    async def _on_startup(self, app) -> None:
        async def load():
            await asyncio.sleep(self.sim.config.startup_delay_s)
            self.sim.model_loaded = True
        # Hold a strong reference: the loop keeps only a weak one, and a
        # GC'd task would leave the replica never-ready (TASK001).
        self._load_task = asyncio.get_running_loop().create_task(load())

    async def health(self, request: web.Request) -> web.Response:
        if self.sim.dead:
            return web.Response(status=500, text="engine dead")
        return web.Response(text="ok")

    async def models(self, request: web.Request) -> web.Response:
        if self.sim.dead:
            return web.json_response({"error": "engine dead"}, status=503)
        if not self.sim.model_loaded:
            return web.json_response({"error": "model loading"}, status=503)
        if self.sim.draining:
            return web.json_response({"error": "draining"}, status=503,
                                     headers={DRAINING_HEADER: "1"})
        return web.json_response({
            "object": "list",
            "data": [{"id": self.sim.config.model, "object": "model",
                      "created": int(self.sim.started_at),
                      "owned_by": "llm-d-tpu-sim"}],
        })

    async def metrics(self, request: web.Request) -> web.Response:
        return web.Response(body=self.sim.metrics.render(),
                            content_type="text/plain")

    async def debug_traces(self, request: web.Request) -> web.Response:
        """llmd-trace span dump (JSONL; ``?drain=1`` clears the rings)."""
        drain = request.query.get("drain") in ("1", "true")
        spans = ([s for t in tracing.all_tracers().values()
                  for s in t.drain()] if drain else tracing.snapshot_all())
        return web.Response(text=tracing.render_jsonl(spans),
                            content_type="application/jsonl")

    async def completions(self, request: web.Request) -> web.StreamResponse:
        return await self._run(request, chat=False)

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        return await self._run(request, chat=True)

    async def _run(self, http_req: web.Request, chat: bool) -> web.StreamResponse:
        try:
            body = await http_req.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid json"}, status=400)
        rid = (body.get("request_id")
               or http_req.headers.get(REQUEST_ID_HEADER)
               or f"cmpl-{uuid_mod.uuid4().hex}")
        if self.sim.dead:
            # Dead-engine mirror: fail fast like the real server's
            # /health-500 engine (gateway retries/resumes elsewhere).
            return web.json_response(
                {"error": "engine dead", "request_id": rid}, status=500)
        if self.sim.draining:
            # Same contract as the real server: new inference 503s while
            # draining; the gateway's retry path re-schedules elsewhere.
            return web.json_response(
                {"error": "draining: replica is shutting down",
                 "request_id": rid},
                status=503, headers={DRAINING_HEADER: "1"})
        in_headers = {k.lower(): v for k, v in http_req.headers.items()}
        try:
            deadline_epoch = parse_deadline(in_headers, body)
            criticality = parse_criticality(in_headers, body)
        except ValueError as exc:
            return web.json_response(
                {"error": f"invalid request: {exc}", "request_id": rid},
                status=400)
        if chat:
            prompt = "".join(m.get("content", "")
                             for m in body.get("messages", []))
        else:
            prompt = body.get("prompt", "")
            if isinstance(prompt, list):
                prompt = " ".join(map(str, prompt))
        prompt_ids = self.sim._tokenize(str(prompt))
        max_tokens = int(body.get("max_tokens",
                                  body.get("max_completion_tokens", 16)))
        created = int(time.time())
        # Mid-stream resume handshake (mirrors the real model server):
        # the relay's journal offset arrives as x-llmd-resume-offset /
        # body["resume"]; token i depends only on (prompt, i), so the
        # continuation is byte-identical to an uninterrupted run.
        resume = body.get("resume") or {}
        try:
            start = int(in_headers.get(RESUME_OFFSET_HEADER,
                                       resume.get("offset") or 0))
        except (TypeError, ValueError):
            return web.json_response(
                {"error": "invalid resume offset", "request_id": rid},
                status=400)
        if not 0 <= start <= max_tokens:
            return web.json_response(
                {"error": f"resume offset {start} out of range",
                 "request_id": rid}, status=400)

        # Request span: child of the forwarding hop (gateway / sidecar /
        # DP leader) when trace headers arrived, root otherwise — the
        # trace id seeds from the request id either way, so a resumed
        # stream's spans land under the ORIGINAL trace.
        span = self.sim.tracer.start_span(
            "sim.request",
            parent=tracing.parse_trace_headers(in_headers),
            request_id=rid, criticality=criticality,
            resume_offset=start or None)
        try:
            return await self._run_traced(
                http_req, body, chat, rid, prompt_ids, max_tokens,
                deadline_epoch, criticality, start, created, span)
        finally:
            span.end()

    async def _run_traced(self, http_req, body, chat, rid, prompt_ids,
                          max_tokens, deadline_epoch, criticality, start,
                          created, span) -> web.StreamResponse:
        stream = bool(body.get("stream", False))
        model = self.sim.config.model
        try:
            # Admission BEFORE the stream is prepared so a queued-deadline
            # expiry can still answer an honest 504.
            ticket = await self.sim.admit(prompt_ids, max_tokens,
                                          deadline_epoch, criticality,
                                          start=start, span=span)
        except DeadlineExceeded:
            span.add_event("deadline_expired", where="queued")
            return web.json_response(
                {"error": "deadline exceeded", "request_id": rid},
                status=504, headers={DEADLINE_EXCEEDED_HEADER: "1"})

        if stream:
            resp = web.StreamResponse(headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache"})
            try:
                await resp.prepare(http_req)
            except BaseException:
                # Client gone before the generator ever ran: its finally
                # can't fire, so release here or the slot leaks.
                self.sim.release_ticket(ticket)
                raise
            # Frame assembly: with spec decode on, tokens group into the
            # plan's per-step chunks — ONE SSE frame per engine step
            # carrying the whole accepted run in its llmd meta (the
            # multi-token journal/offset shape the relays and PR 9
            # resumes must handle); spec off = one token per frame,
            # today's stream byte for byte.
            first = True
            buf_start: Optional[int] = None
            buf_words: List[str] = []
            pi = 0

            async def flush(finished: bool) -> None:
                nonlocal first, buf_start, buf_words
                if buf_start is None:
                    return
                choice: Dict[str, Any] = {
                    "index": 0,
                    "finish_reason": "length" if finished else None}
                text = "".join(buf_words)
                if chat:
                    choice["delta"] = {"content": text}
                else:
                    choice["text"] = text
                src = ticket["resume_src"] if first and start else None
                first = False
                toks = [(len(prompt_ids) + j) % len(_LOREM)
                        for j in range(buf_start,
                                       buf_start + len(buf_words))]
                chunk = {"id": rid, "created": created, "model": model,
                         "object": ("chat.completion.chunk" if chat
                                    else "text_completion"),
                         "choices": [choice],
                         stream_resume.CHUNK_META_KEY:
                         stream_resume.chunk_meta(
                             buf_start, toks, src=src,
                             restored_tokens=ticket["resume_restored"])}
                buf_start, buf_words = None, []
                await resp.write(b"data: " + json.dumps(chunk).encode()
                                 + b"\n\n")

            async for i, text in self.sim.stream_tokens(ticket):
                if buf_start is None:
                    buf_start = i
                buf_words.append(text)
                # The plan lands on the ticket at generator start (the
                # async-for above primes it), so read it lazily here.
                plan = ticket.get("spec_plan") or []
                target = plan[pi] if pi < len(plan) else 1
                finished = i == max_tokens - 1
                if len(buf_words) >= target or finished:
                    await flush(finished)
                    pi += 1
            await flush(False)      # deadline-truncated tail, if any
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
            return resp

        parts: List[str] = []
        async for _i, text in self.sim.stream_tokens(ticket):
            parts.append(text)
        full = "".join(parts)
        if ticket["expired"] and not parts:
            # Parity with the real server: nothing generated before the
            # budget blew -> an honest 504, not a 200 with empty text.
            return web.json_response(
                {"error": "deadline exceeded", "request_id": rid},
                status=504, headers={DEADLINE_EXCEEDED_HEADER: "1"})
        ktp = body.get("kv_transfer_params") or {}
        payload = {
            "id": rid,
            "object": "chat.completion" if chat else "text_completion",
            "created": created,
            "model": model,
            "choices": [{
                "index": 0,
                "finish_reason": "deadline" if ticket["expired"]
                else "length",
                **({"message": {"role": "assistant", "content": full}}
                   if chat else {"text": full}),
            }],
            "usage": {
                "prompt_tokens": len(prompt_ids),
                "completion_tokens": max_tokens,
                "total_tokens": len(prompt_ids) + max_tokens,
            },
        }
        if ktp.get("do_remote_decode"):
            # PD producer contract (README.tpu.md:182-189): a
            # do_remote_decode prefill answers with the transfer params the
            # sidecar attaches for the decode pull.  The sim has no KV to
            # move, so the params are synthetic — enough for the sidecar /
            # chaos suite to exercise the full two-step orchestration on
            # CPU-only machines.
            payload["kv_transfer_params"] = {
                "remote_block_ids": list(range(
                    len(prompt_ids) // self.sim.config.block_size + 1)),
                "remote_host": "sim", "remote_port": 0, "uuid": rid,
                "sim": True,
            }
        return web.json_response(
            payload,
            headers=({DEADLINE_EXCEEDED_HEADER: "1"}
                     if ticket["expired"] else {}))


def build_sim_server(config: Optional[SimConfig] = None,
                     kv_event_sink=None) -> SimServer:
    return SimServer(InferenceSimulator(config or SimConfig(),
                                        kv_event_sink=kv_event_sink))


def main(argv: Optional[List[str]] = None) -> None:
    p = argparse.ArgumentParser("llmd-sim")
    p.add_argument("--model", default="sim-model")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8200)
    p.add_argument("--time-to-first-token", type=float, default=50.0,
                   help="simulated TTFT in ms")
    p.add_argument("--inter-token-latency", type=float, default=10.0,
                   help="simulated TPOT in ms")
    p.add_argument("--max-num-seqs", type=int, default=64)
    p.add_argument("--num-blocks", type=int, default=1024)
    p.add_argument("--block-size", type=int, default=64)
    p.add_argument("--startup-delay", type=float, default=0.0,
                   help="seconds before /v1/models turns ready")
    p.add_argument("--spec-k", type=int, default=None,
                   help="speculative-decode mirror: draft depth K "
                        "(tokens stream in 1..K+1 chunks per step from "
                        "a seeded acceptance model, one TPOT per step); "
                        "default resolves LLMD_SPEC_DECODE/LLMD_SPEC_K")
    p.add_argument("--spec-acceptance", type=float, default=0.7,
                   help="seeded per-draft acceptance rate of the spec "
                        "mirror's acceptance model")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="mixed-round fusion mirror: prefill chunk size "
                        "fused into each decode step (0 = unchunked); "
                        "default resolves LLMD_PREFILL_CHUNK")
    p.add_argument("--step-prefill-token-ms", type=float, default=0.0,
                   help="per-token latency surcharge a decode step pays "
                        "for prefill tokens sharing its fused round "
                        "(0 = off, timing unchanged)")
    p.add_argument("--num-scheduler-steps", type=int, default=1,
                   help="fused-multistep mirror: sim steps per host "
                        "dispatch (latency charged in N-step bursts, "
                        "TPOT jitter amortized; 1 = per-step timing)")
    p.add_argument("--eplb-skew", type=float, default=0.0,
                   help="live-EPLB mirror: Zipf exponent of the routing "
                        "popularity; decode steps stretch by the "
                        "hot-shard overhang of the active placement "
                        "(0 = off, timing unchanged)")
    p.add_argument("--eplb-mode", choices=("online", "static"),
                   default="online",
                   help="online = migrate to the balanced placement at "
                        "the step interval (budgeted staging, zero "
                        "stall); static = keep the uniform placement")
    args = p.parse_args(argv)

    cfg = SimConfig(
        model=args.model, ttft_ms=args.time_to_first_token,
        tpot_ms=args.inter_token_latency, max_num_seqs=args.max_num_seqs,
        num_blocks=args.num_blocks, block_size=args.block_size,
        startup_delay_s=args.startup_delay, spec_k=args.spec_k,
        spec_acceptance=args.spec_acceptance,
        prefill_chunk=args.prefill_chunk,
        step_prefill_token_ms=args.step_prefill_token_ms,
        num_scheduler_steps=args.num_scheduler_steps,
        eplb_skew=args.eplb_skew, eplb_mode=args.eplb_mode)
    logging.basicConfig(level=logging.INFO)
    web.run_app(build_sim_server(cfg).build_app(),
                host=args.host, port=args.port)


if __name__ == "__main__":
    main()
