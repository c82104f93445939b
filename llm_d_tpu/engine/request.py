"""Request lifecycle objects shared by engine, server, and connectors."""

from __future__ import annotations

import dataclasses
import enum
import time
from typing import Any, Dict, List, Optional, Tuple

from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.utils.lifecycle import CRITICALITY_TIERS


class RequestState(enum.Enum):
    WAITING = "waiting"
    RUNNING = "running"
    PREEMPTED = "preempted"
    FINISHED_STOPPED = "stop"          # hit stop token / stop string
    FINISHED_LENGTH = "length"         # hit max_tokens / max_model_len
    FINISHED_ABORTED = "abort"
    # Deadline passed while queued or running: the scheduler refuses /
    # evicts and frees KV blocks the same step (the server renders 504
    # with x-llmd-deadline-exceeded).
    FINISHED_DEADLINE = "deadline"
    # PD: prefill done on a producer engine, KV ready for remote pull
    # (reference contract: README.tpu.md:182-189 kv_transfer_params).
    FINISHED_REMOTE_PREFILL = "remote_prefill"

    @property
    def finished(self) -> bool:
        return self in (RequestState.FINISHED_STOPPED,
                        RequestState.FINISHED_LENGTH,
                        RequestState.FINISHED_ABORTED,
                        RequestState.FINISHED_DEADLINE,
                        RequestState.FINISHED_REMOTE_PREFILL)


@dataclasses.dataclass
class Request:
    request_id: str
    prompt_token_ids: List[int]
    sampling: SamplingParams
    arrival_time: float = dataclasses.field(default_factory=time.monotonic)
    priority: int = 0
    # SLO class (critical | standard | sheddable): a priority TIER above
    # the per-request ``priority`` int — it drives queue order, preemption
    # victim selection (sheddable shed first), and metric labels.
    criticality: str = "standard"
    # Absolute deadline on the ENGINE clock (time.monotonic()); None = no
    # budget.  The scheduler refuses expired queued requests and evicts
    # expired running ones at step boundaries.
    deadline: Optional[float] = None

    state: RequestState = RequestState.WAITING
    output_token_ids: List[int] = dataclasses.field(default_factory=list)
    # How many tokens of (prompt + output) have KV computed in the cache.
    num_computed_tokens: int = 0
    block_ids: List[int] = dataclasses.field(default_factory=list)
    num_cached_prompt_tokens: int = 0      # prefix-cache hits (metrics/scoring)
    # While it waits: (pages to adopt, tokens they hold, the block whose
    # caching would let it end in one step or None) as the scheduler's look
    # at it found them; None before that and once admitted.
    prefix_hit: Optional[Tuple[List[int], int, Optional[int]]] = None
    num_preemptions: int = 0
    # Queue-wait metric latch: preemption resets the computed-token state,
    # so ``is_first_schedule`` fires again on re-admission — without this
    # the histogram would record run time as queue wait.
    queue_wait_observed: bool = False
    first_token_time: Optional[float] = None
    last_token_time: Optional[float] = None
    # llmd-trace: the admitting hop's span context (utils.tracing
    # TraceContext) — engine phase spans (queue / prefill / decode,
    # recorded retroactively at step boundaries) parent on it so the
    # engine's timeline joins the request's end-to-end trace.  None =
    # untraced admission (direct API use, tests).
    trace_ctx: Optional[Any] = None
    # Engine-clock (time.monotonic) stamp of the FIRST schedule — the
    # queue/prefill phase boundary the trace spans are cut at.
    first_schedule_time: Optional[float] = None

    # --- PD disaggregation ---
    # kv_role=producer engines stop after prefill and publish these;
    # kv_role=consumer engines receive them and pull KV before decode.
    kv_transfer_params: Optional[Dict[str, Any]] = None
    do_remote_prefill: bool = False    # consumer side: pull KV before decode
    do_remote_decode: bool = False     # producer side: stop after prefill

    # --- mid-stream resume (journaled decode failover) ---
    # A resumed request arrives with output_token_ids PRE-POPULATED from
    # the relay's journal: the first resume_offset completion tokens were
    # already delivered by a dead replica.  The scheduler admits
    # prompt+generated as a prefill (restore-first from the prefix cache
    # / host tier, recompute on miss) and the server emits tokens from
    # resume_offset on.  resume_restored_tokens records how many
    # GENERATED-region tokens the cache tiers satisfied at admission
    # (the restored-vs-recomputed outcome signal).
    resume_offset: int = 0
    resume_restored_tokens: int = 0

    # --- speculative decode (MTP draft-and-verify) ---
    # Drafts the drafter head proposed for THIS request's next decode
    # step, produced on device by the previous spec step and fetched in
    # its one batched sync.  ``spec_drafts_at`` tags the ``num_tokens``
    # they were drafted from: any token appended outside the spec path
    # (prefill completion, fallback rounds, resume) makes them stale and
    # they are silently dropped.  The adaptive per-request draft depth
    # lives in the predictor's acceptance tracker, read fresh each
    # schedule pass; ``spec_drafted``/``spec_accepted`` accumulate
    # lifetime draft/accept counts for metrics and the usage surface.
    spec_drafts: List[int] = dataclasses.field(default_factory=list)
    spec_drafts_at: int = -1
    spec_drafted: int = 0
    spec_accepted: int = 0

    # --- generation by diffusion over blocks ---
    # The block being denoised is the one that holds position
    # ``num_computed_tokens`` (its keys are not final yet).  Its slots below
    # ``num_tokens`` are revealed and live in the token lists like any
    # other token; ``revealed_ahead`` holds the slots a pass revealed BEYOND
    # a slot that is still masked (position -> (token, logprob, top-N or
    # None)), which cannot be streamed yet and join ``output_token_ids``
    # when the gap closes.  Every other slot of the block is masked.  The
    # engine tracks this itself and never compares ids with the mask token:
    # a prompt may contain that id.  ``denoise_step`` counts the block's
    # passes so far (it sets a pass's quota).  Preemption drops both: the
    # request resumes from its token lists alone.
    revealed_ahead: Dict[int, Any] = dataclasses.field(default_factory=dict)
    denoise_step: int = 0

    # --- the classic step path one step ahead (engine.py) ---
    # Tokens a launched step is sampling that the host does not hold yet:
    # one placeholder ``-(row + 1)`` each, naming the row of the step in
    # flight whose sampled id it stands for (at most two: the step in
    # flight and the one composed behind it).  They count as the request's
    # tokens wherever the next step is composed (``num_tokens``,
    # ``all_token_ids``: the scheduler and the batch see a row that has
    # its next input), and never enter ``output_token_ids``, which holds
    # confirmed tokens only: the server's thread reads it, and only
    # confirmed tokens are hashed into the prefix cache.
    inflight_token_ids: List[int] = dataclasses.field(default_factory=list)
    # A stack with recurrent layers: the request's slot of the engine's
    # state pool, held while it holds pages (``KVCacheManager``); 0 = none.
    state_slot: int = 0
    # A cache in groups by layer kind (``KVCacheManager``): the request's
    # pages of the window group, entry for entry beside ``block_ids`` (which
    # are the full group's); an entry the window has passed, or a prefix
    # hit never took, is 0, the trash page.  ``window_first_block``: the
    # first entry that may still hold a page.
    window_block_ids: List[int] = dataclasses.field(default_factory=list)
    window_first_block: int = 0

    def reset_block(self) -> None:
        self.revealed_ahead = {}
        self.denoise_step = 0

    @property
    def slo_tier(self) -> int:
        """Criticality as a priority tier (critical=-1 < standard=0 <
        sheddable=1); unknown classes behave as standard."""
        return CRITICALITY_TIERS.get(self.criticality, 0)

    def deadline_expired(self, now: Optional[float] = None) -> bool:
        if self.deadline is None:
            return False
        return (now if now is not None else time.monotonic()) > self.deadline

    @property
    def num_prompt_tokens(self) -> int:
        return len(self.prompt_token_ids)

    @property
    def num_tokens(self) -> int:
        return (self.num_prompt_tokens + len(self.output_token_ids)
                + len(self.inflight_token_ids))

    def token_at(self, i: int) -> int:
        """``all_token_ids[i]`` without building the list."""
        i -= len(self.prompt_token_ids)
        if i < 0:
            return self.prompt_token_ids[i]
        out = self.output_token_ids
        return out[i] if i < len(out) else self.inflight_token_ids[i - len(out)]

    @property
    def all_token_ids(self) -> List[int]:
        return (self.prompt_token_ids + self.output_token_ids
                + self.inflight_token_ids)


@dataclasses.dataclass
class RequestOutput:
    request_id: str
    new_token_ids: List[int]
    finished: bool
    finish_reason: Optional[str] = None
    kv_transfer_params: Optional[Dict[str, Any]] = None
    logprobs: Optional[List[float]] = None
    # Per new token: {token_id: logprob} of the top-N alternatives
    # (the OpenAI ``logprobs`` field's data; weak #8 in round-2 review).
    top_logprobs: Optional[List[Dict[int, float]]] = None
