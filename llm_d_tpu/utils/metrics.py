"""Prometheus metrics with the llm-d metric naming scheme.

The reference stack's observability contract is metrics-first: every model
server exposes ``vllm:*`` metrics that the scheduler scrapes for load
balancing, and the EPP exposes ``inference_extension_*`` /
``llm_d_inference_scheduler_*`` metrics (reference:
docs/monitoring/example-promQL-queries.md:8-80, SURVEY.md §5).  We reproduce
the same names so existing dashboards/PromQL and the scoring contract carry
over unchanged.

Uses ``prometheus_client`` under a private registry per component so several
components can live in one test process.
"""

from __future__ import annotations

import time
from typing import Dict, Iterable, Optional

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

# Canonical ``llmd_tpu:*`` names consumed OUTSIDE this module (the EPP's
# scrape loop keys on the exact string).  llmd-check pass MET forbids
# respelling any ``llmd_tpu:*`` name outside this file — consumers import
# these constants.
DRAIN_STATE_METRIC = "llmd_tpu:drain_state"
COLLECTIVE_BYTES_METRIC = "llmd_tpu:collective_bytes_total"
# Mid-stream recovery (journaled decode failover): resumes by outcome
# (restored = generated-region KV came back from the prefix cache /
# host/shared tier; recomputed = tier miss, replayed as prefill;
# failed = budget/attempts gone, the break reached the client) and the
# detection->first-resumed-token latency.  Declared on BOTH the gateway
# (EppMetrics) and the model server's DP relay (EngineMetrics) — the two
# relays that journal streams; registries are per-component.
STREAM_RESUME_METRIC = "llmd_tpu:stream_resume_total"
REQUEST_RECOVERY_METRIC = "llmd_tpu:request_recovery_seconds"
# llmd-trace's span->Prometheus bridge: per-request phase durations
# (queue | schedule | prefill | transfer | first_decode | decode |
# resume — utils/tracing.py PHASES) by criticality class.  This is the
# TTFT decomposition ROADMAP item 2's gated PD bench metric consumes,
# folded into the existing Grafana world; declared on BOTH the gateway
# (EppMetrics: queue/schedule phases) and the model server/sim
# (EngineMetrics: prefill/transfer/decode phases) — registries are
# per-component.
REQUEST_PHASE_METRIC = "llmd_tpu:request_phase_seconds"
# Speculative decode (MTP draft-and-verify): drafts proposed vs drafts
# accepted by target-model verification.  accepted/drafted is the live
# acceptance rate the adaptive-K policy acts on; accepted counts DRAFT
# tokens only (the per-step correction/bonus token is ordinary decode
# output and lands in vllm:generation_tokens_total like any other).
SPEC_DRAFT_METRIC = "llmd_tpu:spec_draft_tokens_total"
SPEC_ACCEPTED_METRIC = "llmd_tpu:spec_accepted_tokens_total"
# Fused mixed-round step composition (chunked-prefill/decode fusion):
# prefill-chunk tokens vs decode(+verify) tokens computed per engine
# step.  rate(prefill)/(rate(prefill)+rate(decode)) is the prefill
# share — the dashboard signal that decode-priority chunk budgeting is
# holding TPOT while prefill chunks ride the decode rounds' weight
# stream.
STEP_PREFILL_TOKENS_METRIC = "llmd_tpu:step_prefill_tokens_total"
STEP_DECODE_TOKENS_METRIC = "llmd_tpu:step_decode_tokens_total"
# Of the prefill-chunk tokens: those a scheduler pass funded AHEAD of an older
# request's unfinished prefill, because their request ends in that step
# (engine/scheduler.py).  Over step_prefill_tokens it is the share of prefill
# that went out of arrival order: 0 where every prompt fits a step or none does.
PREFILL_AHEAD_TOKENS_METRIC = "llmd_tpu:prefill_ahead_tokens_total"
# Generation by diffusion over blocks: forward passes over a block by kind
# (``denoise``: nothing of the pass is kept; ``commit``: the block's final
# keys and values are written) and the tokens those passes revealed.
# revealed / passes is what a pass yields: 4 / 5 of a token at a block of 4
# in 4 steps, more where a confident model reveals several slots a pass.
DIFFUSION_PASSES_METRIC = "llmd_tpu:diffusion_block_passes_total"
DIFFUSION_REVEALED_METRIC = "llmd_tpu:diffusion_revealed_tokens_total"
# Composition demotions (round 16, everything-on): every surviving
# demotion — a per-request fall-off (a do_remote_decode row leaving the
# fused spec path, a fused-multistep plan bailing to single-round) or a
# startup feature disable — increments this by (feature, blocker).
# After round 16 the startup set is empty by design, so a nonzero
# startup-labeled rate is a regression; LLMD_SPEC_STRICT=1 turns a
# startup disable into a refused boot instead of a counter bump.
FEATURE_DISABLED_METRIC = "llmd_tpu:engine_feature_disabled_total"
# Device dispatches: one compiled-program launch plus one host fetch.
# rate(steps)/rate(dispatches) is the N-round amortization ratio — ~N
# under fused multistep, ~1 on the classic per-step path — the
# dashboard proof that host round-trips per decoded token dropped.
ENGINE_DISPATCH_METRIC = "llmd_tpu:engine_dispatch_total"
# A paged cache in groups by layer kind (engine/kv_cache.py): pages a group
# holds (referenced or kept for a later hit), window pages given back as the
# window passed them, cached blocks a group's LRU gave up, and the tokens of
# prefix hits one group could grant and the other had evicted, by the group
# that lost them.
KV_GROUP_PAGES_METRIC = "llmd_tpu:kv_group_pages_in_use"
KV_WINDOW_RELEASED_METRIC = "llmd_tpu:kv_window_pages_released_total"
KV_GROUP_EVICTIONS_METRIC = "llmd_tpu:kv_group_evictions_total"
PREFIX_HIT_LOST_METRIC = "llmd_tpu:prefix_cache_hit_tokens_lost_total"
ENGINE_STEP_METRIC = "llmd_tpu:engine_steps_total"
# The classic step path one step ahead (engine.py module docstring): steps
# composed and launched while their predecessor was still on the device,
# and the rows of such steps whose result was dropped because the
# predecessor's token stopped the request (EOS, a stop string, an abort,
# a deadline).  rate(run_ahead)/rate(steps) is the share of steps whose
# host part the device never waited for; wasted rows cost device work
# only.
RUN_AHEAD_STEPS_METRIC = "llmd_tpu:run_ahead_steps_total"
RUN_AHEAD_WASTED_ROWS_METRIC = "llmd_tpu:run_ahead_wasted_rows_total"
# The state pool of a stack with recurrent layers (a state-space mixer
# beside attention): slots held by running sequences, and rows whose state
# the step program zeroed (a chunk from position 0: new or recomputed).
SSM_STATE_SLOTS_METRIC = "llmd_tpu:ssm_state_slots_in_use"
SSM_STATE_RESETS_METRIC = "llmd_tpu:ssm_state_resets_total"
# Live EPLB (round 17, online expert migration): the window imbalance
# (max/mean per-expert load; 1.0 = even), completed migrations (atomic
# table+weight flips), slot-weight bytes staged in the background, and
# the host-blocked time at each flip.  Stall ≈ 0 is the tentpole claim —
# staging is async device-to-device copy overlapped with decode, the
# flip is a params-dict reference swap gated on slab readiness.
EPLB_IMBALANCE_METRIC = "llmd_tpu:eplb_imbalance"
EPLB_MIGRATIONS_METRIC = "llmd_tpu:eplb_migrations_total"
EPLB_MIGRATED_BYTES_METRIC = "llmd_tpu:eplb_migrated_bytes_total"
EPLB_MIGRATION_STALL_METRIC = "llmd_tpu:eplb_migration_stall_seconds"
# Cluster-sim SLO scoreboard (round 18, chaos testbed): the fraction of
# a tenant bucket's finished requests that met BOTH their class SLO
# targets (TTFT and TPOT) over the scenario, and the live replica count
# the simulated fleet is serving with.  tenant_bucket is a stable hash
# of the tenant id into LLMD_SIM_TENANT_BUCKETS buckets — thousands of
# tenants must not become thousands of label values.
SLO_ATTAINMENT_METRIC = "llmd_tpu:slo_attainment_ratio"
CLUSTER_SIM_REPLICAS_METRIC = "llmd_tpu:cluster_sim_replicas"
# Global prefix-cache fabric (round 20): KV block events ingested by the
# EPP's precise prefix index (ZMQ or inproc, by event type), and the
# kv-placement-scorer's per-pick verdict — local_hit (winner already held
# the prefix), peer_restore (cheaper to pull the missing blocks from a
# peer/host tier than recompute), recompute (no restorable coverage
# worth the wire bytes).  A recompute-dominated mix on prefix-heavy
# traffic means the index is cold or the transfer model prices links as
# slower than prefill.
KV_EVENTS_METRIC = "llmd_tpu:kv_events_total"
KV_PLACEMENT_DECISION_METRIC = "llmd_tpu:kv_placement_decision_total"

# Buckets mirroring vLLM's TTFT / TPOT histograms (seconds).
_TIME_BUCKETS = (
    0.001, 0.005, 0.01, 0.02, 0.04, 0.06, 0.08, 0.1, 0.25, 0.5,
    0.75, 1.0, 2.5, 5.0, 7.5, 10.0, 20.0, 40.0, 80.0,
)


class EngineMetrics:
    """The ``vllm:*`` metric family exposed by every model-server replica.

    The EPP's load-aware scorers consume exactly these
    (kv-cache-utilization-scorer and queue-scorer read
    ``vllm:kv_cache_usage_perc`` / ``vllm:num_requests_waiting``; reference:
    gaie-inference-scheduling/values.yaml:4-6, gaie-kv-events/values.yaml:58-59).
    """

    def __init__(self, model_name: str, registry: Optional[CollectorRegistry] = None):
        self.registry = registry or CollectorRegistry()
        self.model_name = model_name
        labels = {"model_name": model_name}

        def gauge(name: str, doc: str) -> Gauge:
            g = Gauge(name, doc, list(labels), registry=self.registry)
            return g.labels(**labels)

        def counter(name: str, doc: str) -> Counter:
            c = Counter(name, doc, list(labels), registry=self.registry)
            return c.labels(**labels)

        def histo(name: str, doc: str, buckets=_TIME_BUCKETS) -> Histogram:
            h = Histogram(name, doc, list(labels), buckets=buckets, registry=self.registry)
            return h.labels(**labels)

        # Scheduler-consumed load signals.
        self.kv_cache_usage_perc = gauge(
            "vllm:kv_cache_usage_perc", "Fraction of KV-cache blocks in use (0..1).")
        self.num_requests_waiting = gauge(
            "vllm:num_requests_waiting", "Requests queued, not yet scheduled.")
        self.num_requests_running = gauge(
            "vllm:num_requests_running", "Requests currently in the running batch.")
        # Latency distributions.
        self.time_to_first_token = histo(
            "vllm:time_to_first_token_seconds", "Time from arrival to first output token.")
        self.inter_token_latency = histo(
            "vllm:inter_token_latency_seconds", "Latency between consecutive output tokens.")
        self.e2e_request_latency = histo(
            "vllm:e2e_request_latency_seconds", "End-to-end request latency.")
        # Prefix-cache effectiveness (approximate-scorer calibration input).
        self.prefix_cache_queries = counter(
            "vllm:prefix_cache_queries_total", "Tokens queried against the prefix cache.")
        self.prefix_cache_hits = counter(
            "vllm:prefix_cache_hits_total", "Tokens served from the prefix cache.")
        # Work counters.
        self.prompt_tokens = counter(
            "vllm:prompt_tokens_total", "Prefill tokens processed.")
        self.generation_tokens = counter(
            "vllm:generation_tokens_total", "Output tokens generated.")
        self.request_success = Counter(
            "vllm:request_success", "Finished requests.",
            ["model_name", "finished_reason"], registry=self.registry)
        self.preemptions = counter(
            "vllm:num_preemptions_total", "Requests preempted to reclaim KV blocks.")
        # Gaps the reference documents as missing (example-promQL-queries.md:104-121)
        # -- we close them.
        self.kv_transfer_time = histo(
            "llmd_tpu:kv_transfer_seconds", "P->D KV-cache transfer time per request.")
        self.kv_cache_evictions = counter(
            "llmd_tpu:kv_cache_evictions_total", "Cached KV blocks evicted (LRU).")
        self.kv_offload_saves = counter(
            "llmd_tpu:kv_offload_saved_blocks_total", "KV blocks offloaded to host tier.")
        self.kv_offload_loads = counter(
            "llmd_tpu:kv_offload_loaded_blocks_total", "KV blocks restored from host tier.")
        self.kv_shared_tier_hits = counter(
            "llmd_tpu:kv_shared_tier_hits_total",
            "KV blocks fetched from a peer pod's shared tier.")
        self.kv_shared_tier_misses = counter(
            "llmd_tpu:kv_shared_tier_misses_total",
            "Shared-tier lookups that missed on every peer.")
        # --- lifecycle (deadlines / SLO classes / drain) ---
        # Criticality-labeled: per-class queueing and deadline losses are
        # the SLO dashboard's primary signals (a sheddable-only miss rate
        # under overload is healthy; a critical one is an incident).
        self._queue_wait = Histogram(
            "llmd_tpu:request_queue_wait_seconds",
            "Arrival-to-first-schedule wait, by criticality class.",
            ["model_name", "criticality"], buckets=_TIME_BUCKETS,
            registry=self.registry)
        self._deadline_exceeded = Counter(
            "llmd_tpu:deadline_exceeded_total",
            "Requests refused or evicted after their deadline passed, "
            "by criticality class.",
            ["model_name", "criticality"], registry=self.registry)
        self.drain_inflight = gauge(
            "llmd_tpu:drain_inflight",
            "In-flight requests still completing while this replica "
            "drains (0 when not draining or drained).")
        self.drain_state = gauge(
            DRAIN_STATE_METRIC,
            "1 while this replica is draining (readiness down, in-flight "
            "completing); the EPP's drain-filter keys on this.")
        # EP interconnect accounting (round 10, quantized collectives):
        # wire bytes the MoE dispatch/combine exchanges ship, estimated
        # from the routed token count at the resolved wire dtype
        # (parallel/quant_collectives.py is the byte model) — the
        # dashboard signal that LLMD_COLLECTIVE_DTYPE=int8 actually cut
        # interconnect traffic, and by how much per phase.
        self._collective_bytes = Counter(
            COLLECTIVE_BYTES_METRIC,
            "EP collective wire bytes shipped (dispatch/combine, "
            "estimated from routed tokens), by collective and wire "
            "dtype.",
            ["model_name", "collective", "dtype"], registry=self.registry)
        # Mid-stream recovery at the DP-leader relay (the gateway-side
        # twin lives on EppMetrics; see the module-level constants).
        self._stream_resume = Counter(
            STREAM_RESUME_METRIC,
            "Mid-stream resumes at this relay, by outcome "
            "(restored | recomputed | failed).",
            ["model_name", "outcome"], registry=self.registry)
        self.request_recovery = histo(
            REQUEST_RECOVERY_METRIC,
            "Mid-stream break detection to first resumed token.")
        # llmd-trace phase bridge (see REQUEST_PHASE_METRIC).
        self._request_phase = Histogram(
            REQUEST_PHASE_METRIC,
            "Per-request phase duration (TTFT/TPOT attribution), by "
            "phase and criticality class.",
            ["model_name", "phase", "criticality"], buckets=_TIME_BUCKETS,
            registry=self.registry)
        # Speculative decode (see the SPEC_* constants above).
        self.spec_draft_tokens = counter(
            SPEC_DRAFT_METRIC,
            "Draft tokens proposed by the MTP drafter and verified by "
            "the target model.")
        self.spec_accepted_tokens = counter(
            SPEC_ACCEPTED_METRIC,
            "Draft tokens the target model accepted (emitted verbatim).")
        # Step composition (see the STEP_* constants above): incremented
        # host-side from scheduler metadata on every engine step, classic
        # and fused alike — never a device sync.
        self.step_prefill_tokens = counter(
            STEP_PREFILL_TOKENS_METRIC,
            "Prefill-chunk tokens computed per engine step.")
        self.step_decode_tokens = counter(
            STEP_DECODE_TOKENS_METRIC,
            "Decode + speculative-verify tokens computed per engine "
            "step.")
        self.prefill_ahead_tokens = counter(
            PREFILL_AHEAD_TOKENS_METRIC,
            "Prefill tokens funded ahead of an older request's unfinished "
            "prefill (their request ends in that step).")
        self._diffusion_passes = Counter(
            DIFFUSION_PASSES_METRIC,
            "Block-diffusion forward passes over one block, by kind "
            "(denoise, commit).",
            ["model_name", "kind"], registry=self.registry)
        self.diffusion_revealed_tokens = counter(
            DIFFUSION_REVEALED_METRIC,
            "Tokens revealed by block-diffusion denoising passes.")
        # Composition demotions + dispatch amortization (see the
        # FEATURE_DISABLED / ENGINE_DISPATCH constants above).
        self._feature_disabled = Counter(
            FEATURE_DISABLED_METRIC,
            "Requested features demoted, at startup or per request, by "
            "feature and blocker.",
            ["model_name", "feature", "blocker"], registry=self.registry)
        self._kv_group_pages = Gauge(
            KV_GROUP_PAGES_METRIC,
            "Pages a cache group holds: referenced by a running sequence or "
            "kept for a later prefix hit.",
            ["model_name", "group"], registry=self.registry)
        self.kv_window_pages_released = counter(
            KV_WINDOW_RELEASED_METRIC,
            "Window-group pages given back because no later query of "
            "their sequence can see them.")
        self._kv_group_evictions = Counter(
            KV_GROUP_EVICTIONS_METRIC,
            "Cached KV blocks evicted (LRU), by cache group.",
            ["model_name", "group"], registry=self.registry)
        self._prefix_hit_lost = Counter(
            PREFIX_HIT_LOST_METRIC,
            "Tokens of prefix-cache hits that one cache group could grant "
            "and the other had evicted, by the group that lost them.",
            ["model_name", "group"], registry=self.registry)
        self.engine_dispatches = counter(
            ENGINE_DISPATCH_METRIC,
            "Compiled-program dispatches (one host fetch each); "
            "steps/dispatches is the multistep amortization ratio.")
        self.engine_steps = counter(
            ENGINE_STEP_METRIC,
            "Engine rounds retired (a fused-multistep dispatch retires "
            "N at once).")
        self.run_ahead_steps = counter(
            RUN_AHEAD_STEPS_METRIC,
            "Classic steps launched before their predecessor's tokens "
            "were fetched (no sequence slot was free).")
        self.run_ahead_wasted_rows = counter(
            RUN_AHEAD_WASTED_ROWS_METRIC,
            "Rows of a step whose request had stopped by the time the "
            "step was retired: their result is dropped.")
        self.ssm_state_slots_in_use = gauge(
            SSM_STATE_SLOTS_METRIC,
            "Slots of the recurrent-state pool held by running sequences.")
        self.ssm_state_resets = counter(
            SSM_STATE_RESETS_METRIC,
            "Rows whose recurrent state a step program zeroed: their "
            "chunk started at position 0.")
        # Live EPLB (see the EPLB_* constants above).
        self.eplb_imbalance = gauge(
            EPLB_IMBALANCE_METRIC,
            "Windowed per-expert load imbalance (max/mean; 1.0 = even) "
            "driving the migration hysteresis gate.")
        self.eplb_migrations = counter(
            EPLB_MIGRATIONS_METRIC,
            "Completed live expert migrations (atomic table+weight "
            "flips).")
        self.eplb_migrated_bytes = counter(
            EPLB_MIGRATED_BYTES_METRIC,
            "Expert-slot weight bytes staged by background migration "
            "copies (incl. int8 sibling planes).")
        self.eplb_migration_stall = histo(
            EPLB_MIGRATION_STALL_METRIC,
            "Host-blocked seconds at a migration flip (≈0: staging is "
            "async; the flip is a reference swap).")

    def observe_phase(self, phase: str, criticality: str,
                      seconds: float) -> None:
        self._request_phase.labels(
            model_name=self.model_name, phase=phase,
            criticality=criticality).observe(max(0.0, seconds))

    def inc_stream_resume(self, outcome: str) -> None:
        self._stream_resume.labels(
            model_name=self.model_name, outcome=outcome).inc()

    def observe_queue_wait(self, criticality: str, seconds: float) -> None:
        self._queue_wait.labels(
            model_name=self.model_name, criticality=criticality).observe(
            seconds)

    def inc_deadline_exceeded(self, criticality: str) -> None:
        self._deadline_exceeded.labels(
            model_name=self.model_name, criticality=criticality).inc()

    def add_diffusion_passes(self, kind: str, n: int) -> None:
        if n:
            self._diffusion_passes.labels(
                model_name=self.model_name, kind=kind).inc(n)

    def set_group_pages(self, group: str, pages: int) -> None:
        self._kv_group_pages.labels(
            model_name=self.model_name, group=group).set(pages)

    def add_group_evictions(self, group: str, n: int) -> None:
        self._kv_group_evictions.labels(
            model_name=self.model_name, group=group).inc(n)

    def add_prefix_hit_lost(self, group: str, tokens: int) -> None:
        self._prefix_hit_lost.labels(
            model_name=self.model_name, group=group).inc(tokens)

    def inc_feature_disabled(self, feature: str, blocker: str) -> None:
        self._feature_disabled.labels(
            model_name=self.model_name, feature=feature,
            blocker=blocker).inc()

    def add_collective_bytes(self, collective: str, dtype: str,
                             n: int) -> None:
        self._collective_bytes.labels(
            model_name=self.model_name, collective=collective,
            dtype=dtype).inc(n)

    def render(self) -> bytes:
        return generate_latest(self.registry)


class EppMetrics:
    """Scheduler-side metrics (``inference_extension_*`` family and the PD
    decision counter; reference: example-promQL-queries.md:40-80)."""

    def __init__(self, registry: Optional[CollectorRegistry] = None):
        self.registry = registry or CollectorRegistry()
        self.scheduling_duration = Histogram(
            "inference_extension_scheduler_e2e_duration_seconds",
            "End-to-end scheduling latency per request.",
            registry=self.registry,
            buckets=(0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5))
        self.plugin_duration = Histogram(
            "inference_extension_scheduler_plugin_duration_seconds",
            "Per-plugin processing latency.", ["plugin"],
            registry=self.registry,
            buckets=(0.00001, 0.0001, 0.001, 0.01, 0.1))
        self.pd_decisions = Counter(
            "llm_d_inference_scheduler_pd_decision_total",
            "Prefill/decode disaggregation decisions.", ["decision_type"],
            registry=self.registry)
        self.prefix_indexer_size = Gauge(
            "inference_extension_prefix_indexer_size",
            "Blocks tracked by the prefix indexer.", registry=self.registry)
        self.prefix_indexer_hit_ratio = Gauge(
            "inference_extension_prefix_indexer_hit_ratio",
            "Prefix indexer hit ratio over recent requests.", registry=self.registry)
        # Global prefix-cache fabric (round 20): indexer ingest volume and
        # the kv-placement-scorer's per-pick restore-vs-recompute verdict.
        self.kv_events = Counter(
            KV_EVENTS_METRIC,
            "KV block events ingested by the prefix index, by type "
            "(BlockStored | BlockRemoved | AllBlocksCleared).",
            ["type"], registry=self.registry)
        self.kv_placement_decisions = Counter(
            KV_PLACEMENT_DECISION_METRIC,
            "kv-placement-scorer verdicts on picked endpoints "
            "(local_hit | peer_restore | recompute).",
            ["verdict"], registry=self.registry)
        self.flow_control_queue = Gauge(
            "inference_extension_flow_control_queue_size",
            "Requests held by gateway flow control.", registry=self.registry)
        self.flow_control_rejects = Counter(
            "inference_extension_flow_control_rejects_total",
            "Requests rejected by gateway flow control.", ["reason"],
            registry=self.registry)
        self.requests_total = Counter(
            "inference_objective_request_total",
            "Requests scheduled.", ["target"], registry=self.registry)
        self.shed_total = Counter(
            "inference_objective_request_shed_total",
            "Requests shed due to SLO headroom exhaustion.", registry=self.registry)
        # Request-level resilience (breaker + retry-on-alternate-endpoint).
        self.breaker_state = Gauge(
            "llmd_tpu:endpoint_breaker_state",
            "Per-endpoint circuit breaker state (0=closed, 1=open, "
            "2=half-open).", ["endpoint"], registry=self.registry)
        self.breaker_transitions = Counter(
            "llmd_tpu:endpoint_breaker_transitions_total",
            "Breaker state transitions.", ["endpoint", "to"],
            registry=self.registry)
        self.gateway_retries = Counter(
            "llmd_tpu:gateway_retries_total",
            "Forwards retried on an alternate endpoint.", ["reason"],
            registry=self.registry)
        self.gateway_retry_exhausted = Counter(
            "llmd_tpu:gateway_retry_exhausted_total",
            "Requests that failed after the full retry budget.",
            registry=self.registry)
        # Lifecycle: deadline refusals at the gateway (expired before or
        # while queued in flow control) by criticality class.
        self.gateway_deadline_exceeded = Counter(
            "llmd_tpu:gateway_deadline_exceeded_total",
            "Requests 504'd at the gateway because their deadline passed.",
            ["criticality"], registry=self.registry)
        # Mid-stream recovery (journaled decode failover at the relay).
        self.stream_resume = Counter(
            STREAM_RESUME_METRIC,
            "Mid-stream resumes at the gateway relay, by outcome "
            "(restored | recomputed | failed).",
            ["outcome"], registry=self.registry)
        self.request_recovery = Histogram(
            REQUEST_RECOVERY_METRIC,
            "Mid-stream break detection to first resumed token.",
            buckets=_TIME_BUCKETS, registry=self.registry)
        # llmd-trace phase bridge, gateway side (queue = flow-control
        # wait, schedule = plugin-pipeline decision); the engine-side
        # twin lives on EngineMetrics (see REQUEST_PHASE_METRIC).
        self._request_phase = Histogram(
            REQUEST_PHASE_METRIC,
            "Per-request phase duration at the gateway (TTFT "
            "attribution), by phase and criticality class.",
            ["phase", "criticality"], buckets=_TIME_BUCKETS,
            registry=self.registry)

    def observe_phase(self, phase: str, criticality: str,
                      seconds: float) -> None:
        self._request_phase.labels(
            phase=phase, criticality=criticality).observe(
            max(0.0, seconds))

    def render(self) -> bytes:
        return generate_latest(self.registry)


class ClusterMetrics:
    """Cluster-simulator fleet metrics (the chaos testbed's judge feed).

    One instance per :class:`~llm_d_tpu.sim.cluster.ClusterSim` run; the
    scoreboard publishes its per-(class, tenant-bucket) attainment here
    so the same PromQL that would watch production watches a scenario.
    """

    def __init__(self, registry: Optional[CollectorRegistry] = None):
        self.registry = registry or CollectorRegistry()
        self.slo_attainment = Gauge(
            SLO_ATTAINMENT_METRIC,
            "Fraction of finished requests meeting BOTH class SLO "
            "targets (TTFT and TPOT), by class and tenant bucket.",
            ["criticality", "tenant_bucket"], registry=self.registry)
        self.replicas = Gauge(
            CLUSTER_SIM_REPLICAS_METRIC,
            "Live (booted, not dead, not removed) replicas in the "
            "simulated fleet.", registry=self.registry)

    def render(self) -> bytes:
        return generate_latest(self.registry)


def parse_prometheus_text(text: str) -> Dict[str, float]:
    """Tiny parser for the exposition format: returns ``{metric{labels}: value}``
    plus bare ``{metric: value}`` for the first sample of each name.

    This is what the EPP metrics scraper uses against model-server ``/metrics``
    (the reference EPP scrapes vLLM the same way)."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        try:
            key, value = line.rsplit(" ", 1)
            # Drop optional timestamp.
            parts = value.split()
            val = float(parts[0])
        except ValueError:
            continue
        out[key] = val
        bare = key.split("{", 1)[0]
        out.setdefault(bare, val)
    return out


class StopWatch:
    """Context manager feeding a Histogram."""

    def __init__(self, histogram):
        self.histogram = histogram

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.histogram.observe(time.perf_counter() - self._t0)
        return False
