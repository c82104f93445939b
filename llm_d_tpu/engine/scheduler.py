"""Continuous-batching scheduler with chunked prefill and preemption.

One scheduler invocation composes a mixed prefill+decode step under a token
budget — the engine-side half of what the reference gets from vLLM's
scheduler (continuous batching, chunked prefill, recompute-preemption).
Unified steps (prefills and decodes in one batch) keep the TPU busy with
large matmuls while decode latency stays bounded by the token budget.

Scheduling policy: decode entries first (decode steps starve last).  Then
prefill, whose candidates are every request with prompt left to compute:
running chunks in the order of their admission, then waiting requests by
(criticality tier, priority, arrival).  A pass funds in two rounds.  First,
in that order, every candidate whose WHOLE uncomputed remainder (after the
prefix-cache hit, for a request not admitted yet) fits in the budget the
pass still has: it ends in this step and emits its first token, so a short
ask no longer waits behind the chunks of older long contexts.  Then what is
left, as ever: running chunks in admission order, waiting requests first
come first served.  The threshold is the pass's own remaining budget, so
the traffic decides: where every prompt fits a step, or none does, no
candidate passes another and the pass composes first come first served.

What a long context pays.  The oldest unfinished prefill receives, every
pass, all of the prefill budget that the pass's short asks did not take: it
is delayed by the tokens funded ahead of it and by nothing else
(``prefill_ahead_tokens``).  Where short asks take at most a share s of a
step's budget B, a context with R tokens left ends within
ceil(R / ((1 - s) * B)) passes of reaching the head, against ceil(R / B)
first come first served.  The long contexts keep their order among
themselves (the remainders are not sorted).  A candidate passes only those
of its own (tier, priority) class or a less important one.  Pages and
sequence slots follow the same rule: a short ask that passes others takes
pages only while the pool also holds what the rest of the step's budget
could ask for, never preempts for them, and leaves a sequence slot for
every waiting request it passed; one that finds no pages is skipped for
the pass and stops nobody.

The prefix-cache hit of a waiting request is looked up when a pass first
considers it and kept on the request (``Request.prefix_hit``): later passes
size its remainder from that, with no walk over its pages.  The pages may
be evicted before the request is admitted; ``KVCacheManager.allocate`` then
refuses the stale hit and the request takes the first-come-first-served
round, which looks the hit up afresh, as it does for any request that
waited (pages cached meanwhile are found there).

On block exhaustion the most recently added running request in the lowest
SLO class is preempted and recomputed later (sheddable before standard
before critical; metric: ``vllm:num_preemptions_total``).

Lifecycle: requests carry an optional absolute deadline.  Every
``schedule()`` pass first expires deadlines — queued requests whose
budget passed are refused, running ones are evicted at the step boundary
— and frees their KV blocks the same step (the server renders the 504).

Generation by diffusion over blocks (``block_length`` B > 0).  A request no
longer asks for one token a step.  Its keys are final ("computed") in whole
blocks only, and what it asks for follows from its token lists:

  - whole blocks of known tokens that are not computed yet (the prompt's,
    a preempted request's answer so far, or the block the last denoising
    pass completed) are a CHUNK whose keys are kept: a multiple of B tokens,
    so every chunk ends on a block boundary, and the prompt's chunks stop at
    its last whole block.  The chunk of one just-completed block is the
    block's COMMIT pass;
  - otherwise the block that holds ``num_computed_tokens`` is open, and the
    request asks for a DENOISING pass over its B slots: KV is allocated to
    the block's end, nothing of the pass is kept, and
    ``num_computed_tokens`` stays where it is until the block commits.

Denoising and commit passes are the decode entries (B tokens each, funded
first, counted as ``decode_tokens``); every other chunk is prefill.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from llm_d_tpu.engine.kv_cache import KVCacheManager
from llm_d_tpu.engine.request import Request, RequestState


@dataclasses.dataclass
class ScheduledRequest:
    request: Request
    num_new_tokens: int           # tokens computed this step
    is_first_schedule: bool = False
    # Speculative decode: draft tokens scheduled ON TOP of num_new_tokens
    # for this decode entry (KV blocks already allocated to cover them;
    # the engine's draft+verify program appends up to this many extra
    # tokens and rolls the rejected tail's blocks back the same step).
    num_draft_tokens: int = 0
    # Block diffusion: a denoising pass over the request's open block
    # (nothing of it is kept), or the commit pass of the block just
    # completed.  Neither: a chunk of prefill.
    denoise: bool = False
    commit: bool = False


@dataclasses.dataclass
class SchedulerOutput:
    scheduled: List[ScheduledRequest]
    preempted: List[Request]
    total_tokens: int
    # Step composition under decode-priority budgeting: decode entries'
    # mandatory tokens, their speculative draft tokens (on top), and
    # prefill-chunk tokens.  total_tokens == decode + prefill; the engine
    # feeds these to the step span, the step-composition counters and the
    # step-latency model without recomputing them from the rows.
    decode_tokens: int = 0
    spec_tokens: int = 0
    prefill_tokens: int = 0
    # Of ``prefill_tokens``: those funded ahead of an older request's
    # unfinished prefill (module docstring; 0 where a pass composed first
    # come first served).
    prefill_ahead_tokens: int = 0

    @property
    def empty(self) -> bool:
        return not self.scheduled


class Scheduler:
    def __init__(
        self,
        kv: KVCacheManager,
        max_num_seqs: int = 64,
        max_num_batched_tokens: int = 1024,
        max_model_len: int = 32000,
        block_length: int = 0,
    ) -> None:
        self.kv = kv
        # Block diffusion (module docstring); 0 = autoregressive.
        self.block_length = block_length
        self.max_num_seqs = max_num_seqs
        self.max_num_batched_tokens = max_num_batched_tokens
        self.max_model_len = max_model_len
        self.waiting: collections.deque[Request] = collections.deque()
        self.running: List[Request] = []
        self.num_preemptions = 0
        self.num_deadline_evictions = 0
        # Blocks held outside the scheduler (e.g. PD producer pins awaiting a
        # remote pull). While any exist, a stalled sole-running request waits
        # for their asynchronous release instead of being aborted.
        self.external_pinned_blocks = lambda: 0
        # Speculative decode (set by the engine when spec decode is on):
        # callable(Request) -> draft tokens to schedule for this decode
        # entry.  Draft tokens are budgeted like real tokens and their KV
        # blocks allocated up front, but they are strictly opportunistic —
        # the allocation shrinks to the free pool (never preempts: evicting
        # real work for speculative capacity would be a net loss) and the
        # engine rolls the rejected tail back after verification.
        self.spec_lookahead: Optional[Callable[[Request], int]] = None
        # Decode-priority chunk budgeting (set by the engine): callable
        # (decode_tokens_funded) -> per-chunk prefill token cap for this
        # pass, or None for "budget-bound only" (the historical behavior).
        # Called AFTER decode entries are funded, so an adaptive policy can
        # size prefill chunks to the decode load actually in the step.
        self.prefill_chunk_cap: Optional[
            Callable[[int], Optional[int]]] = None
        # Composition of the most recent schedule() pass (tests and the
        # engine's observability read this without re-deriving it).
        self.last_schedule_stats: Dict[str, int] = {}

    # ---------- queue ops ----------

    def add_request(self, request: Request) -> None:
        request.state = RequestState.WAITING
        self.waiting.append(request)

    def abort_request(self, request_id: str) -> Optional[Request]:
        for q in (self.waiting, self.running):
            for r in list(q):
                if r.request_id == request_id:
                    q.remove(r)
                    r.state = RequestState.FINISHED_ABORTED
                    self.kv.free(r)
                    return r
        return None

    @property
    def num_waiting(self) -> int:
        return len(self.waiting)

    @property
    def num_running(self) -> int:
        return len(self.running)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # ---------- core ----------

    def _preempt_for(self, needy: Request, preempted_now: set,
                     scheduled_ids: set) -> bool:
        """Preempt the most recent running request in the LOWEST SLO class
        other than ``needy`` (sheddable victims before standard before
        critical; most-recent-first within a class, so the class tiers
        only reorder — the historical recency policy is the tie-break).

        Requests already scheduled in this pass are not eligible victims:
        freeing their blocks after they were appended to ``scheduled`` would
        corrupt the batch the engine is about to build.  With KV regions
        (SPMD dp) only same-region victims help — freeing a foreign shard's
        blocks cannot satisfy ``needy``'s allocation.
        """
        region = self.kv.region_of_request(needy)
        # Stable sort over reversed(running): most-recent-first within each
        # tier, tiers from sheddable down to critical.
        victims = sorted(reversed(self.running), key=lambda r: -r.slo_tier)
        for victim in victims:
            if victim is needy or victim.request_id in scheduled_ids:
                continue
            if self.kv.num_regions > 1 \
                    and self.kv.region_of_request(victim) != region:
                continue
            self.running.remove(victim)
            self.kv.free(victim)
            victim.num_computed_tokens = 0
            victim.reset_block()
            victim.num_preemptions += 1
            victim.state = RequestState.PREEMPTED
            self.waiting.appendleft(victim)
            preempted_now.add(victim.request_id)
            self.num_preemptions += 1
            return True
        return False

    def _expire_deadlines(self, expired_out: List[Request]) -> None:
        """Refuse queued requests and evict running ones whose deadline
        passed; their KV blocks return to the pool THIS step (a request
        that already blew its budget must not keep burning TPU steps and
        cache).  Evicted requests finish with state FINISHED_DEADLINE —
        the engine surfaces them as outputs and the server maps them to
        504 + x-llmd-deadline-exceeded."""
        now = time.monotonic()
        for q in (self.waiting, self.running):
            for req in [r for r in list(q) if r.deadline_expired(now)]:
                q.remove(req)
                self.kv.free(req)
                req.state = RequestState.FINISHED_DEADLINE
                self.num_deadline_evictions += 1
                expired_out.append(req)

    def _schedule_running(self, req: Request, budget: int,
                          cap: Optional[int],
                          scheduled: List[ScheduledRequest],
                          preempted: List[Request],
                          preempted_now: set,
                          scheduled_ids: set) -> Tuple[int, int]:
        """Fund one running request (decode entry or in-flight prefill
        chunk) out of ``budget``; returns ``(n, spec_n)`` actually
        scheduled (``(0, 0)`` when nothing fit).  Only what is returned
        may be charged to the budget — a request that bails leaves its
        slack for later chunks (budget conservation)."""
        unit = self.block_length or 1
        remaining, denoise = self._wanted_tokens(req)
        n = min(remaining, budget // unit * unit)
        if cap is not None:
            n = min(n, max(int(cap) // unit * unit, unit))
        # Terminal path: a request whose block demand exceeds the whole
        # pool can never run — fail it instead of livelocking with n=0
        # forever (has_work() true, no progress, no client error).
        needed = -(-(req.num_computed_tokens + n) // self.kv.block_size)
        if needed > self.kv.max_request_blocks:
            self.running.remove(req)
            self.kv.free(req)
            req.state = RequestState.FINISHED_ABORTED
            preempted.append(req)
            return 0, 0
        while True:
            ok = self.kv.allocate(req, req.num_computed_tokens + n)
            if ok is not None:
                break
            if self._preempt_for(req, preempted_now, scheduled_ids):
                continue
            # Nothing to preempt: shrink the chunk to the blocks that are
            # actually free so mid-prefill requests keep making progress
            # (partial pools must not stall the pass).
            fit = ((len(req.block_ids) + self.kv.free_blocks_for(req))
                   * self.kv.block_size) - req.num_computed_tokens
            if fit >= n:
                # Bookkeeping race (free-list vs region accounting, e.g.
                # blocks parked in the evictor): the pool claims ``n``
                # fits but allocate refused.  Shrink by one block and
                # retry instead of dropping the whole chunk — strictly
                # decreasing, so the loop terminates, and the tokens this
                # request ends up not using were never charged, so they
                # remain in the budget for later prefill chunks.
                fit = n - self.kv.block_size
            n = max(fit, 0)
            if n <= 0:
                break
        if n <= 0:
            # Nothing schedulable and nothing preemptable: if no other
            # request holds reclaimable blocks this will never resolve —
            # unless blocks are pinned outside the scheduler (PD transfer
            # in flight), whose async release will unblock us.
            if not scheduled and len(self.running) == 1 \
                    and not self.kv.can_allocate(
                        1, self.kv.region_of_request(req)) \
                    and self.external_pinned_blocks() == 0:
                self.running.remove(req)
                self.kv.free(req)
                req.state = RequestState.FINISHED_ABORTED
                preempted.append(req)
            return 0, 0
        spec_n = 0
        if (self.spec_lookahead is not None and n == 1
                and req.num_computed_tokens == req.num_tokens - 1):
            # Decode entry under spec decode: schedule up to K draft
            # tokens on top of the mandatory one.  Drafts pay token
            # budget like real compute and shrink to the free block
            # pool — speculation never preempts or blocks real work.
            spec_n = min(max(0, int(self.spec_lookahead(req))),
                         budget - n)
            while spec_n > 0 and self.kv.allocate(
                    req, req.num_computed_tokens + n + spec_n) is None:
                spec_n -= 1
        scheduled.append(ScheduledRequest(
            req, n, num_draft_tokens=spec_n, denoise=denoise,
            commit=bool(self.block_length) and not denoise
            and self._is_decode(req)))
        scheduled_ids.add(req.request_id)
        return n, spec_n

    def _wanted_tokens(self, req: Request,
                       computed: Optional[int] = None) -> Tuple[int, bool]:
        """(tokens the request asks of a step before budget and cap, whether
        that is a denoising pass), with ``computed`` of its tokens' keys
        final (its own count unless given: a waiting request's prefix hit).
        Autoregressive: what is left of its known tokens, or the next
        token's KV.  Block diffusion: its whole blocks of known tokens not
        computed yet, else a pass over its open block."""
        if computed is None:
            computed = req.num_computed_tokens
        B = self.block_length
        if not B:
            return max(req.num_tokens - computed, 1), False
        whole = req.num_tokens // B * B - computed
        return (whole, False) if whole > 0 else (B, True)

    def _wanted_waiting(self, req: Request, n_cached: int) -> Tuple[int, bool]:
        """``_wanted_tokens`` of a waiting request with ``n_cached`` tokens
        computed: its prompt's remainder, never less than it has."""
        if self.block_length:
            return self._wanted_tokens(req, n_cached)
        return req.num_tokens - n_cached, False

    def _chunk_tokens(self, remaining: int, budget: int,
                      cap: Optional[int]) -> int:
        """What a pass with ``budget`` left grants a request that asks for
        ``remaining``: whole units, under the engine's per-chunk cap."""
        unit = self.block_length or 1
        n = min(remaining, budget // unit * unit)
        if cap is not None:
            n = min(n, max(int(cap) // unit * unit, unit))
        return n

    def _is_decode(self, r: Request) -> bool:
        """A decode entry, funded before any prefill chunk.  Autoregressive:
        the request has emitted output and only its last token's KV is left
        to compute (the engine's per-row predicate).  Block diffusion: its
        prompt's whole blocks are computed and it asks for one block, a
        denoising pass or a commit."""
        B = self.block_length
        if B:
            return (r.num_computed_tokens >= r.num_prompt_tokens // B * B
                    and self._wanted_tokens(r)[0] <= B)
        return (r.num_tokens > r.num_prompt_tokens
                and r.num_tokens - r.num_computed_tokens <= 1
                and not r.do_remote_decode)

    def _prefix_hit(self, req: Request, fresh: bool,
                    chunks: Sequence[Request] = ()) -> Tuple[List[int], int]:
        """(pages to adopt, tokens they hold) for a request that holds no
        page yet.  Looked up when a pass first considers the request and
        kept on it, with ONE block to ask the manager about in later
        passes; the pages are walked again only when that block has been
        cached since.  It is the block whose caching would make the
        remainder fit a step (an ask that arrived before its own session's
        context was computed), or, where one of the running prefills
        ``chunks`` is computing the request's very next block, that block:
        the hit is then not taken at all, because the request would compute
        again what the step in flight is computing for it.  ``fresh`` (the
        first-come-first-served round, which is about to admit it) always
        looks up, and keeps the region the look-up pinned, as ever; a kept
        answer pins nothing: ``allocate`` assigns the region when the pages
        are taken."""
        kept = req.prefix_hit
        if kept is not None and not fresh and (
                kept[2] is None or not self.kv.caches_block(req, kept[2])):
            return kept[0], kept[1]
        reuse, n_cached = self.kv.find_cached_prefix(req)
        if req.do_remote_prefill:
            # PD consumer: KV arrives via the connector; only the
            # last prompt token is computed locally.
            reuse, n_cached = [], 0
        ask = None
        if not fresh:
            self.kv.unpin(req)
            nxt = len(reuse)
            decides = -(-(req.num_tokens - self.max_num_batched_tokens)
                        // self.kv.block_size) - 1
            if any(self.kv.same_block(req, c, nxt) for c in chunks):
                reuse, n_cached, ask = [], 0, nxt
            elif kept is None and decides >= nxt:
                ask = decides
        req.prefix_hit = (reuse, n_cached, ask)
        return reuse, n_cached

    def _pages_to_spare(self, req: Request, tokens_after: int, budget: int,
                        passed: int) -> bool:
        """May ``req``, which would pass ``passed`` older candidates, take
        the pages that bring it to ``tokens_after``?  Only while the pool
        holds, beside them, a page for every token of the ``budget`` the
        pass has left: those it passes find what they would have found."""
        bs = self.kv.block_size
        need = (-(-tokens_after // bs) - len(req.block_ids)
                + -(-budget // bs) + passed)
        region = (self.kv.region_of_request(req) if req.block_ids else None)
        return self.kv.can_allocate(need, region)

    def _admit(self, req: Request, reuse: List[int], n_cached: int, n: int,
               denoise: bool, first: bool,
               scheduled: List[ScheduledRequest],
               scheduled_ids: set) -> bool:
        """Move a waiting request to running with its first ``n`` tokens
        after ``n_cached`` cached ones, if the pool grants the pages."""
        if self.kv.allocate(req, n_cached + n, reuse) is None:
            return False
        req.num_computed_tokens = n_cached
        req.prefix_hit = None
        if first:
            # Metrics see prompt-region hits only; a resume admission
            # may restore past the prompt into the generated region —
            # that surplus is the restored-vs-recomputed signal.
            req.num_cached_prompt_tokens = min(
                n_cached, req.num_prompt_tokens)
            if req.resume_offset:
                req.resume_restored_tokens = max(
                    0, n_cached - req.num_prompt_tokens)
        self.waiting.remove(req)
        self.running.append(req)
        req.state = RequestState.RUNNING
        scheduled.append(ScheduledRequest(
            req, n, is_first_schedule=first, denoise=denoise))
        scheduled_ids.add(req.request_id)
        return True

    def _refuse(self, req: Request, state: RequestState,
                finished: List[Request]) -> None:
        """A waiting request that can never be admitted leaves the queue."""
        self.waiting.remove(req)
        req.state = state
        finished.append(req)

    def schedule(self) -> SchedulerOutput:
        scheduled: List[ScheduledRequest] = []
        preempted: List[Request] = []
        self._expire_deadlines(preempted)
        budget = self.max_num_batched_tokens
        # Requests preempted during this pass are not re-admitted in the same
        # step: re-admission would recreate the memory pressure that forced
        # the preemption (thrash).
        preempted_now: set = set()
        scheduled_ids: set = set()
        decode_tokens = spec_tokens = prefill_tokens = ahead_tokens = 0

        # 1. Decode entries first (decode-priority budgeting): every
        # in-flight stream's next token — plus its speculative lookahead —
        # is funded before ANY prefill chunk sees the budget, so a large
        # chunk can never push a decode out of the step and stall TPOT.
        # A decode entry has emitted output and only its last token's KV
        # left to compute (the engine's per-row is_decode predicate);
        # everything else running is an in-flight prefill chunk.
        running = list(self.running)
        is_decode = self._is_decode
        # What the smallest entry costs: a token, or a block.
        unit = self.block_length or 1
        bs = self.kv.block_size

        decodes = [r for r in running if is_decode(r)]
        chunks = [r for r in running if not is_decode(r)]
        for req in decodes:
            if budget < unit:
                break
            if req.request_id in preempted_now:
                continue        # evicted by an earlier request in this pass
            n, spec_n = self._schedule_running(
                req, budget, None, scheduled, preempted,
                preempted_now, scheduled_ids)
            budget -= n + spec_n
            decode_tokens += n
            spec_tokens += spec_n

        # Prefill spends what the decodes left, per-chunk-capped by the
        # engine's policy (fixed LLMD_PREFILL_CHUNK or the step-latency
        # model sized against the funded decode load).
        cap: Optional[int] = None
        if self.prefill_chunk_cap is not None:
            cap = self.prefill_chunk_cap(decode_tokens + spec_tokens)

        # 2. The candidates that end in this step, in today's order (module
        # docstring).  One that no step could finish (its remainder is more
        # than ``step_max``, a whole step's budget under the cap) is LONG
        # and may be passed; it waits in ``later_*`` for rounds 3 and 4.  The
        # walk stops at the first candidate that is neither: it and all
        # behind it are funded first come first served.  ``rank`` is the
        # most important class among those passed, which none of a lesser
        # class passes.
        step_max = self._chunk_tokens(
            self.max_num_batched_tokens, self.max_num_batched_tokens, cap)
        later_chunks: List[Request] = []
        later_waiting: List[Request] = []
        skipped = 0             # short asks that found no pages
        rank = (float("inf"), 0)
        walking = True

        def passed_up(later: List[Request], req: Request) -> None:
            nonlocal rank
            later.append(req)
            rank = min(rank, (req.slo_tier, req.priority))

        for i, req in enumerate(chunks):
            if budget < unit:
                break
            if req.request_id in preempted_now:
                continue
            remaining, _ = self._wanted_tokens(req)
            n = self._chunk_tokens(remaining, budget, cap)
            if n < remaining and remaining <= step_max:
                later_chunks.extend(chunks[i:])
                walking = False
                break
            after = req.num_computed_tokens + n
            if n < remaining or (later_chunks and (
                    (req.slo_tier, req.priority) > rank
                    or not self._pages_to_spare(req, after, budget,
                                                len(later_chunks))
                    or self.kv.allocate(req, after) is None)):
                passed_up(later_chunks, req)
                continue
            n, _ = self._schedule_running(
                req, budget, cap, scheduled, preempted,
                preempted_now, scheduled_ids)
            budget -= n
            prefill_tokens += n
            if later_chunks:
                ahead_tokens += n
        # Waiting requests by (criticality tier, priority, arrival) (lower
        # value = more important, matching InferenceObjective; the SLO class
        # is the outer tier, per-request priority the inner).
        pending = sorted(self.waiting,
                         key=lambda r: (r.slo_tier, r.priority,
                                        r.arrival_time))
        for i, req in enumerate(pending):
            if budget < unit:
                break
            passed = len(later_chunks) + len(later_waiting) + skipped
            if not walking or len(self.running) + len(later_waiting) \
                    + skipped >= self.max_num_seqs:
                # (A sequence slot is left for every waiting request passed.)
                later_waiting.extend(pending[i:])
                break
            if req.request_id in preempted_now:
                continue
            if req.num_tokens >= self.max_model_len:
                # Oversized prompt: refuse by finishing with length.
                self._refuse(req, RequestState.FINISHED_LENGTH, preempted)
                continue
            first = req.num_computed_tokens == 0 and not req.block_ids
            reuse, n_cached = (self._prefix_hit(req, False, chunks) if first
                               else ([], req.num_computed_tokens))
            remaining, denoise = self._wanted_waiting(req, n_cached)
            n = self._chunk_tokens(remaining, budget,
                                   None if denoise else cap)
            if n < remaining and remaining <= step_max:
                walking = False
                later_waiting.extend(pending[i:])
                break
            # A request that goes straight to a denoising pass is no short
            # prefill: it keeps its place where nobody was passed, and
            # passes nobody.
            if n < remaining or n <= 0 or (passed and (
                    denoise or (req.slo_tier, req.priority) > rank
                    or not self._pages_to_spare(req, n_cached + n, budget,
                                                passed))):
                passed_up(later_waiting, req)
                continue
            if not self._admit(req, reuse, n_cached, n, denoise, first,
                               scheduled, scheduled_ids):
                if reuse and not self.kv.holds_prefix(req, reuse):
                    # The hit was evicted since the look-up: round 4 looks
                    # it up afresh.
                    req.prefix_hit = None
                    passed_up(later_waiting, req)
                elif -(-n // bs) > self.kv.max_request_blocks:
                    # First chunk alone exceeding the whole pool can never
                    # be admitted.
                    self._refuse(req, RequestState.FINISHED_ABORTED,
                                 preempted)
                else:
                    # No pages: skipped for this pass, and nobody waits
                    # behind it.  Drop the region pin (SPMD dp): the next
                    # pass re-assigns by capacity.
                    self.kv.unpin(req)
                    skipped += 1
                continue
            budget -= n
            if denoise:
                decode_tokens += n
            else:
                prefill_tokens += n
                if passed:
                    ahead_tokens += n

        # 3. In-flight chunked prefills that do not end in this step, in
        # admission order.
        for req in later_chunks:
            if budget < unit:
                break
            if req.request_id in preempted_now:
                continue
            n, _ = self._schedule_running(
                req, budget, cap, scheduled, preempted,
                preempted_now, scheduled_ids)
            budget -= n
            prefill_tokens += n

        # 4. The waiting requests that are left, first come first served
        # within (criticality tier, priority).
        for req in later_waiting:
            if budget < unit or len(self.running) >= self.max_num_seqs:
                break
            if req.request_id in preempted_now:
                continue
            if req.num_tokens >= self.max_model_len:
                self._refuse(req, RequestState.FINISHED_LENGTH, preempted)
                continue
            first = req.num_computed_tokens == 0 and not req.block_ids
            reuse, n_cached = (self._prefix_hit(req, True) if first
                               else ([], req.num_computed_tokens))
            # Nothing to prefill (block diffusion: a prompt shorter than a
            # block, or one whose whole blocks the cache holds): straight
            # to the first denoising pass, a decode entry.
            remaining, denoise = self._wanted_waiting(req, n_cached)
            # First chunks obey the same per-chunk cap as running ones.
            n = self._chunk_tokens(remaining, budget,
                                   None if denoise else cap)
            if n <= 0:
                continue
            if not self._admit(req, reuse, n_cached, n, denoise, first,
                               scheduled, scheduled_ids):
                # First chunk alone exceeding the whole pool can never be
                # admitted — fail it rather than blocking the queue forever.
                if -(-n // bs) > self.kv.max_request_blocks:
                    self._refuse(req, RequestState.FINISHED_ABORTED,
                                 preempted)
                    continue
                # Drop the region pin (SPMD dp): prefix affinity must not
                # pin the queue head to one full region while others idle —
                # the next pass re-assigns by capacity.
                self.kv.unpin(req)
                break               # head-of-line: don't skip ahead of FIFO
            budget -= n
            if denoise:
                decode_tokens += n
            else:
                prefill_tokens += n

        self.last_schedule_stats = {
            "decode_tokens": decode_tokens,
            "spec_tokens": spec_tokens,
            "prefill_tokens": prefill_tokens,
            "prefill_ahead_tokens": ahead_tokens,
            "chunk_cap": -1 if cap is None else int(cap),
            "budget_left": budget,
        }
        return SchedulerOutput(
            scheduled=scheduled, preempted=preempted,
            total_tokens=sum(s.num_new_tokens for s in scheduled),
            decode_tokens=decode_tokens, spec_tokens=spec_tokens,
            prefill_tokens=prefill_tokens,
            prefill_ahead_tokens=ahead_tokens)

    def finish(self, request: Request, state: RequestState) -> None:
        request.state = state
        if request in self.running:
            self.running.remove(request)
        self.kv.free(request)
