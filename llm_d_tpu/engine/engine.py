"""EngineCore: the JAX serving engine (the reference's vLLM equivalent).

Owns the device state (params + paged KV cache), turns scheduler output into
static-shape batches (bucketed so XLA compiles a bounded set of programs),
runs one fused forward+sample program per step, and advances request state.

TPU-first choices:
  - one jitted step handles mixed prefill+decode (ragged batch) — big
    matmuls for the MXU even when decodes dominate;
  - token/sequence dims bucket to powers of two: no data-dependent shapes;
  - KV cache buffers are donated each step (in-place paged updates);
  - sampling happens on device, only sampled ids travel host-ward.

The loop's order (classic step path, ``EngineCore._step``).  An iteration
launches a step (schedule, build, one copy, one launch: ``_launch``), then
waits for its tokens and files them (``_retire``: placeholders replaced,
full blocks hashed, stops checked, outputs emitted).  Where no arrival could
join the step after it anyway, the iteration first composes and launches
THAT step behind the one in flight and only then fetches: the next step's
decode rows take their input token from the previous step's ids on the
device (``packed_batch.feed_tokens``), so the device never waits for the
host's part of an iteration, and each iteration still retires exactly one
step.  The gate (``_may_run_ahead``) is read off the engine's own state,
not an option: an autoregressive engine on this path, every sequence slot
taken once the rows the step in flight finishes by length have left, no KV
pull pending, and blocks in the pool for every running row's next ask.
Depth one, no deeper: a stop the host cannot foresee (EOS, a stop string,
an abort, a deadline) is seen one step late and wastes the one row already
launched; a second step ahead would waste two and delay a replacement
request by two steps for no gain, since one step already hides all of the
host behind the device.  ``async_scheduling`` / ``num_scheduler_steps``
name the multi-step paths' own double buffering (``_inflight``), which this
does not touch.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from llm_d_tpu.engine.kv_cache import KVCacheManager
from llm_d_tpu.engine.packed_batch import BatchLayout, feed_tokens
from llm_d_tpu.engine.request import Request, RequestOutput, RequestState
from llm_d_tpu.engine.scheduler import Scheduler, SchedulerOutput
from llm_d_tpu.engine.step_clock import StepClock
from llm_d_tpu.models import get_model
from llm_d_tpu.models.config import (
    CROSS, FULL, GMU, LINEAR, MAMBA, NO_WINDOW, SLIDING, ModelConfig,
    get_config)
from llm_d_tpu.ops import moe as moe_ops
from llm_d_tpu.ops import sampling as sampling_ops
from llm_d_tpu.ops.parts import part
from llm_d_tpu.parallel.mesh import MeshConfig, make_mesh
from llm_d_tpu.parallel.sharding import logical_to_sharding, shard_pytree
from llm_d_tpu.utils import tracing
from llm_d_tpu.utils.config import env_choice, env_float, env_int
from llm_d_tpu.utils.faultinject import get_injector
from llm_d_tpu.utils.metrics import EngineMetrics

logger = logging.getLogger(__name__)

# Speculative-decode master modes (LLMD_SPEC_DECODE): "auto" = run the
# draft+verify program whenever spec_k > 0, "off" = kill switch.
SPEC_DECODE_MODES = ("auto", "off")


def _next_bucket(n: int, lo: int, hi: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return min(b, hi)


def _keys_seen_upto(x, w):
    """Sum of min(p + 1, w) over the positions p < x: the keys the queries
    before position x see under a window (or a top-k) of w."""
    y = np.minimum(x, w)
    return y * (y + 1) // 2 + (x - y) * w


def _tile_spans(ends, news, qt: int):
    """(first, last) query position of every query tile of a dispatch whose
    row r computes ``news[r]`` tokens ending at context ``ends[r]``, ``qt``
    slots a tile, a row's tiles consecutive
    (``ops.attention.query_tiles``)."""
    ends = np.asarray(ends, np.int64)
    news = np.minimum(np.asarray(news, np.int64), ends)
    tiles = -(-news // qt)                          # of each row
    row = np.repeat(np.arange(len(news)), tiles)
    nth = np.arange(len(row)) - np.repeat(np.cumsum(tiles) - tiles, tiles)
    q_first = (ends - news)[row] + nth * qt
    return q_first, np.minimum(q_first + qt, ends[row]) - 1


def kv_bytes_per_token(layout: Dict[str, int]) -> int:
    """Bytes one token's KV costs per layer: the bf16 rows of every cache
    buffer.  The single source of the byte accounting shared by pool sizing
    and the bench's roofline/kv_bytes_per_step terms."""
    return sum(layout.values()) * 2


def derive_num_blocks(hbm_budget_bytes: int, layout: Dict[str, int],
                      num_layers: int, block_size: int,
                      layers: Optional[Dict[str, int]] = None) -> int:
    """Block-pool sizing: how many paged-KV blocks (one block's rows across
    all layers and cache buffers) fit a fixed HBM budget.  ``layers``: the
    layers each buffer holds rows of, where not all hold ``num_layers``
    (buffers by layer kind: the model's ``kv_cache_layers``)."""
    if layers is None:
        layers = dict.fromkeys(layout, num_layers)
    block_bytes = block_size * 2 * sum(
        layers[name] * width for name, width in layout.items())
    return max(hbm_budget_bytes // block_bytes, 2)


def window_group_blocks(sliding_window: int, block_size: int,
                        max_num_seqs: int, max_num_batched_tokens: int) -> int:
    """Pages of the window group of a cache in groups by layer kind
    (kv_cache.py), from the engine's limits.  A running sequence holds at
    most the window's keys before its chunk, the chunk itself and a page
    of slack at either end (the window and the chunk begin anywhere in a
    page); every sequence slot gets that, and the group half as much again
    for the pages under the last window of contexts that finished, which a
    later prefix hit needs; one more is the trash page."""
    per_slot = -(-(sliding_window - 1 + max_num_batched_tokens)
                 // block_size) + 2
    return max_num_seqs * per_slot * 3 // 2 + 1


def derive_group_blocks(model_config: ModelConfig, block_size: int,
                        max_num_seqs: int, max_num_batched_tokens: int,
                        num_blocks: int) -> Tuple[int, int]:
    """(pages of the full group, pages of the window group) of a stack whose
    pages may go by layer kind (``ModelConfig.kv_cache_groups``), out of
    what ``num_blocks`` pages of every layer cost as one pool.  The window
    group takes what ``window_group_blocks`` says and the full group the
    rest.  Where the rule's window group would be no smaller than that one
    pool, grouping frees nothing: ``(num_blocks, 0)``, one group, the
    stack served as every stack of one kind is."""
    c = model_config
    window = window_group_blocks(
        c.sliding_window, block_size, max_num_seqs, max_num_batched_tokens)
    if window >= num_blocks:
        return num_blocks, 0
    n_window = c.layer_types.count(SLIDING)
    n_full = c.num_layers - n_window
    # (in pages of one layer: the pool's, less the window layers')
    return (num_blocks * c.num_layers - window * n_window) // n_full, window


@dataclasses.dataclass
class EngineConfig:
    model: str = "tiny"                      # preset name
    model_config: Optional[ModelConfig] = None
    block_size: int = 32
    num_blocks: int = 256                    # KV blocks incl. null block 0
    max_num_seqs: int = 64
    max_num_batched_tokens: int = 1024
    enable_prefix_caching: bool = True
    attn_backend: str = "auto"
    mesh: Optional[MeshConfig] = None        # None = single device
    # Permit a mesh smaller than the host's device count (tests / dryruns on
    # virtual device pools). Production default: fail fast on idle chips.
    allow_device_subset: bool = False
    seed: int = 0
    min_token_bucket: int = 16
    min_seq_bucket: int = 8
    # Fused multi-step decode: when a step is pure decode, run this many
    # engine steps in one device program with on-device token feedback —
    # amortizes host<->device transfer latency.
    num_scheduler_steps: int = 1
    # Async scheduling (the reference's --async-scheduling,
    # decode.yaml:77,97): keep ONE fused decode block in flight and dispatch
    # its successor — last token ids taken straight from the in-flight
    # block's device array — before retiring it, so host-side token
    # processing, stop checks and block allocation overlap device compute.
    # Stops discovered at retire discard the successor's tokens for that
    # request (same discard rule fused decode already has); new arrivals
    # drain the pipeline and re-enter continuous batching.
    async_scheduling: bool = False
    # DBO (MoE models): dual-batch overlap — force >= 2 MoE dispatch chunks
    # above the token threshold so the all-to-all of one chunk overlaps the
    # expert GEMM of the other (reference: --enable-dbo
    # --dbo-{decode,prefill}-token-threshold, decode.yaml:78,98-99).
    enable_dbo: bool = False
    dbo_decode_token_threshold: int = 32
    dbo_prefill_token_threshold: int = 32
    # EPLB (MoE models): redundant-expert load balancing
    # (reference: --enable-eplb --eplb-config, decode.yaml:79,100-104).
    enable_eplb: bool = False
    eplb_config: Optional[Dict[str, Any]] = None
    # Tiered prefix cache: host-RAM blocks surviving device eviction
    # (reference: tiered-prefix-cache/cpu, OffloadingConnector role).
    kv_offload_blocks: int = 0            # 0 = off
    # Cross-pod shared tier (the LMCache role): serve host-tier blocks to
    # peers over the C++ transfer server / consult peers on local miss.
    kv_shared_tier_port: Optional[int] = None   # None = don't serve; 0 = ephemeral
    kv_shared_tier_peers: Tuple[str, ...] = ()  # "host:port" peer servers
    # MoE expert-weight quantization (DeepGEMM role; "int8" or None).
    quantization: Optional[str] = None
    # Auto-size the block pool from an HBM budget instead of num_blocks,
    # see derive_num_blocks.
    kv_cache_hbm_bytes: Optional[int] = None
    # A KV connector will be attached once the engine stands
    # (--kv-transfer-config): what the engine sizes at construction for it.
    kv_transfer: bool = False
    # Speculative decoding (MTP draft-and-verify): "auto" runs the fused
    # draft+verify program on pure-decode rounds whenever spec_k > 0;
    # "off" is a kill switch that restores today's engine byte for byte.
    # None resolves LLMD_SPEC_DECODE.
    spec_decode: Optional[str] = None
    # Draft tokens per step (K).  0 = spec decode off (the shipped
    # default: nothing changes until an operator opts in).  None resolves
    # LLMD_SPEC_K; the --spec-k server flag sets it explicitly.  The
    # engine schedules up to K+1 tokens per sequence per decode step and
    # rolls rejected KV back the same step; output stays byte-identical
    # to non-spec decode for greedy and seeded sampling.
    spec_k: Optional[int] = None
    # Bench/diagnostics only: replace draft
    # verification with a SEEDED per-draft acceptance coin at this rate,
    # so accepted-tok/s is measurable at a controlled acceptance whatever
    # the drafter's real hit rate on random-init weights.  Changes model
    # output — never set on a serving path.
    spec_fixed_accept: Optional[float] = None
    # Strict composition mode (--spec-strict / LLMD_SPEC_STRICT): a
    # requested feature the engine would demote at STARTUP refuses to
    # boot instead of shipping a silently degraded config behind a log
    # line.  After round 16 the startup-blocker set is empty by design
    # (spec composes with multistep/async, stacked dp and EPLB), so this
    # is a regression tripwire; per-request runtime demotions
    # (do_remote_decode rows) stay counter-only either way.  None
    # resolves LLMD_SPEC_STRICT (default 0).
    spec_strict: Optional[bool] = None
    # Compile (and run once, on an all-padding batch) every classic step
    # program the bucket scheme can reach before serving the first
    # request, instead of on first use: no request then waits for a
    # compile, whatever prompt lengths arrive.
    precompile_step_shapes: bool = False

    def resolve_model(self) -> ModelConfig:
        return self.model_config or get_config(self.model)


@dataclasses.dataclass
class _LaunchedStep:
    """A classic step between its launch and its retire: what the retire
    needs of the moment of the launch, since the requests' own state may
    have moved one step on by then."""
    sched: SchedulerOutput
    scheduled: List                 # the rows, in the batch's order
    rows: np.ndarray                # flat sample row of each
    # Per row (autoregressive): (the step samples its next token, that is
    # the first one after its prompt, the step completes a KV block).
    samples: List[Tuple[bool, bool, bool]]
    # ids [, logprobs] [, top (ids, lps)] [, touched]: the ONE fetch
    fetch: Dict[str, Any]
    routed: Any                     # EPLB: routed expert ids, or None
    routed_valid: Optional[np.ndarray]
    kv: Dict[str, int]              # the step's ``_kv_counts`` and the like
    bucket: int                     # token rows of the step's program
    t0: float                       # the clock read before the launch
    ahead: bool                     # launched with its predecessor in flight


class EngineCore:
    def __init__(
        self,
        config: EngineConfig,
        params: Optional[Any] = None,
        metrics: Optional[EngineMetrics] = None,
        devices: Optional[List[jax.Device]] = None,
    ) -> None:
        """``devices`` pins this core to a device subset — the DP group gives
        each rank a disjoint tp-submesh (reference: per-rank engine cores,
        decode.yaml:73-93)."""
        self.config = config
        self.model_config = config.resolve_model()
        c = self.model_config
        if config.kv_cache_hbm_bytes:
            # The budget is PER DEVICE: stacked (SPMD dp) engines split the
            # pool 1/dp per shard, so the global count scales by dp to keep
            # each chip's residency at the budget.
            dp = config.mesh.dp if config.mesh else 1
            derived = dp * derive_num_blocks(
                config.kv_cache_hbm_bytes,
                get_model(c).kv_cache_layout(c), c.num_layers,
                config.block_size, get_model(c).kv_cache_layers(c))
            logger.info(
                "kv pool auto-sized: %d blocks (%.2f GiB/device budget"
                ", dp=%d)", derived, config.kv_cache_hbm_bytes / 2**30, dp)
            config = dataclasses.replace(config, num_blocks=derived)
            self.config = config
        # Pages in groups by layer kind (kv_cache.py): ``_window_blocks``
        # pages of the window group, 0 = one group, every stack's form
        # until the lines below say otherwise.
        self._window_blocks = 0
        self._groups_blocker = self._cache_groups_blocker()
        if c.kv_cache_groups and not self._groups_blocker:
            full, self._window_blocks = derive_group_blocks(
                c, config.block_size, config.max_num_seqs,
                config.max_num_batched_tokens, config.num_blocks)
        if self._window_blocks:
            logger.info(
                "kv cache in groups by layer kind: %d pages for the %d full "
                "layers, %d for the %d window layers (the bytes of %d pages "
                "of every layer)", full, len(c.layers_of(FULL)),
                self._window_blocks, len(c.layers_of(SLIDING)),
                config.num_blocks)
            config = dataclasses.replace(config, num_blocks=full)
            self.config = config
        if config.async_scheduling and config.num_scheduler_steps <= 1:
            # The pipeline operates on fused decode blocks; without them the
            # flag would be a silent no-op.
            raise ValueError(
                "async_scheduling requires num_scheduler_steps > 1 "
                "(it pipelines fused decode blocks)")

        if config.mesh:
            self.mesh = make_mesh(config.mesh, devices,
                                  allow_subset=config.allow_device_subset)
        else:
            pool = devices or jax.devices()
            self.mesh = make_mesh(MeshConfig(), [pool[0]])
        d0 = self.mesh.devices.flat[0]
        logger.info(
            "engine on %d of %d %s device(s) (%s), first: %s",
            self.mesh.devices.size, len(devices or jax.devices()),
            d0.platform, d0.device_kind, d0)
        # SPMD data parallelism: dp > 1 turns on "stacked" mode — batch and
        # KV arrays carry a leading [dp] dim sharded P("dp"), requests pin
        # to one dp shard (KV regions), attention runs per shard under
        # partial-manual shard_map while MoE EP spans ALL devices (the
        # wide-EP regime; see parallel.dp_attention).  dp == 1 is exactly
        # the historical single-mesh path.
        self.dp = config.mesh.dp if config.mesh else 1
        if self.dp > 1 and (config.mesh.sp or 1) > 1:
            raise ValueError(
                "SPMD dp and sp are mutually exclusive in-engine (ring "
                "attention shards sequences, dp shards requests)")
        # A stack with recurrent layers (``ModelConfig.has_recurrent_state``):
        # every running sequence owns a slot of the state pool beside its
        # pages, taken and dropped with them (kv_cache.py), and no prefix
        # hit is granted: cached pages without the state at that boundary
        # would be wrong (state snapshots: ROADMAP queue B).
        self.kv_manager = KVCacheManager(
            config.num_blocks, config.block_size,
            enable_prefix_caching=(config.enable_prefix_caching
                                   and not self._has_state),
            num_regions=self.dp,
            state_slots=config.max_num_seqs if self._has_state else 0,
            window_blocks=self._window_blocks,
            sliding_window=c.sliding_window if self._window_blocks else 0)
        self.scheduler = Scheduler(
            self.kv_manager,
            max_num_seqs=config.max_num_seqs,
            max_num_batched_tokens=config.max_num_batched_tokens,
            max_model_len=c.max_model_len,
            block_length=self.block_length)
        # Decode-priority chunk budgeting (round 15): the scheduler funds
        # decode entries (plus spec lookahead) first and asks this engine
        # for a per-chunk prefill token cap.  LLMD_PREFILL_CHUNK pins a
        # fixed cap; "auto" (the default) sizes chunks from the online
        # step-latency model against LLMD_STEP_TIME_TARGET_MS — with no
        # target set the cap stays off and chunks are budget-bound only
        # (the historical behavior, byte for byte).
        from llm_d_tpu.predictor.model import StepTimeModel
        raw_chunk = os.environ.get("LLMD_PREFILL_CHUNK", "auto")
        self._prefill_chunk_fixed: Optional[int] = None
        if raw_chunk != "auto":
            try:
                self._prefill_chunk_fixed = max(1, int(raw_chunk))
            except ValueError:
                logger.warning(
                    "LLMD_PREFILL_CHUNK=%r is neither 'auto' nor an "
                    "integer; using 'auto'", raw_chunk)
        self._step_time_target_ms = env_float("LLMD_STEP_TIME_TARGET_MS", 0.0)
        self.step_time_model = StepTimeModel()
        self.scheduler.prefill_chunk_cap = self._prefill_chunk_cap
        self.metrics = metrics or EngineMetrics(c.name)
        # (feature, blocker) pairs already warned about — runtime
        # demotions (e.g. a do_remote_decode row every schedule pass)
        # count on every occurrence but log once.
        self._disabled_seen: set = set()
        self._check_block_diffusion()
        self._check_recurrent_state()
        self._check_layer_kinds()
        self._check_cache_groups()
        # llmd-trace: engine phase spans (queue/prefill/decode + step
        # boundaries).  Everything recorded here is host-side clock
        # arithmetic materialized AFTER the jitted dispatch — tracing can
        # never add a device sync to the hot loop (the JIT llmd-check
        # pass and the tests/test_tracing.py guard pin this).
        self.tracer = tracing.get_tracer("engine")
        # Phase clock of the step loop (engine/step_clock.py), and what
        # _note_step said of the iteration under way, for step() to write.
        self._clock = StepClock()
        self._step_note: Optional[Tuple[float, float, Any, Dict]] = None
        # EP interconnect accounting (round 10): on a multi-device mesh
        # every computed token's k routed copies cross the dispatch and
        # combine exchanges once per MoE layer — estimate the wire bytes
        # at the resolved collective dtype and export them as
        # llmd_tpu:collective_bytes_total (the byte model is
        # parallel/quant_collectives.py; single-device engines ship no
        # collective bytes).
        self._collective_wire = None
        if c.is_moe and self.mesh.devices.size > 1:
            from llm_d_tpu.parallel.quant_collectives import (
                a2a_row_bytes, psum_bytes_per_token,
                resolve_collective_dtype)
            self._collective_wire = resolve_collective_dtype()
            logger.info("MoE collectives on the %s wire (%s backend)",
                        self._collective_wire, jax.default_backend())
            Lm = c.num_layers - c.first_dense_layers
            ep = self.mesh.devices.size
            if c.num_experts % ep == 0 and ep & (ep - 1) == 0:
                # a2a-eligible mesh: engine token buckets are powers of
                # two (>= min_token_bucket), so a power-of-two ep makes
                # dispatch='auto' pick a2a on every step — charge the
                # dispatch/combine model.  (E % ep always holds when the
                # engine builds: the expert weights shard over the EP
                # axes.)
                row = a2a_row_bytes(c.hidden_size, self._collective_wire)
                self._a2a_token_bytes = {
                    phase: b * c.num_experts_per_tok * Lm
                    for phase, b in row.items()}
            else:
                # A non-power-of-two ep never divides the token buckets,
                # so EVERY step runs the psum fallback: charge the
                # allreduce model (k-independent, full activation) so
                # the dashboard reads what the slice actually ships.
                self._a2a_token_bytes = {
                    "allreduce": psum_bytes_per_token(
                        c.hidden_size, self._collective_wire) * Lm}

        # --- device state ---
        self.model = get_model(c)       # models.llama (dense) or models.moe
        layout = self.model.kv_cache_layout(c)
        self._announce_attention_path(layout)   # may refuse: before init
        rules = self.model.sharding_rules(c)
        owns_params = params is None
        if params is None:
            def init(key):
                return self.model.init_params(c, key)
            key = jax.random.PRNGKey(config.seed)
            if self.mesh.devices.size > 1 and config.quantization is None:
                # Initialize straight into the mesh sharding: a model that
                # needs the host (llama3-8b: 16 GB of bf16) never fits the
                # first device whole.
                params = jax.jit(init, out_shardings=logical_to_sharding(
                    rules, jax.eval_shape(init, key), self.mesh))(key)
            else:
                params = init(key)
        if config.enable_dbo and not c.is_moe:
            raise ValueError(
                "enable_dbo overlaps MoE dispatch with expert compute; "
                f"model {c.name!r} is dense")
        if config.quantization == "int8":
            if not c.is_moe:
                # Silently serving bf16 while the operator believes HBM
                # was halved is a misconfiguration, not a fallback.
                raise ValueError(
                    "quantization='int8' quantizes MoE expert weights; "
                    f"model {c.name!r} is dense")
            if "w_gate_q" not in params.get("moe_layers", {}):
                from llm_d_tpu.ops.quant import quantize_moe_experts
                # Donation (halved peak HBM) only for self-initialized
                # params: donating caller-provided arrays would invalidate
                # buffers the caller may still use.
                params = quantize_moe_experts(params, donate=owns_params)
        elif config.quantization is not None:
            raise ValueError(f"unknown quantization {config.quantization!r}")
        # ``ops.moe.expert_ffn``'s own gate: the int8 kernels serve this
        # engine's steps (``_moe_counts`` says which one by the bucket).
        self._int8_expert_kernels = (
            config.quantization == "int8" and self.mesh.devices.size == 1
            and moe_ops.int8_kernels_serve())
        shardings = logical_to_sharding(rules, params, self.mesh)
        self.params = shard_pytree(params, shardings)
        self.eplb = None
        if config.enable_eplb and c.is_moe:
            from llm_d_tpu.parallel.eplb import EplbConfig, EplbController
            self.eplb = EplbController(
                c.num_experts, self.mesh.devices.size,
                EplbConfig.from_dict(config.eplb_config))
            # Physical expert table replaces the logical weights on device.
            self.params = self.eplb.install(self.params, self.mesh, rules)
            self.eplb.metrics = self.metrics
            self.eplb.tracer = self.tracer

        num_slots = config.num_blocks * config.block_size
        # Folded layout [L, slots, row_width]: 128-lane-aligned page DMAs
        # and contiguous scatter rows (see ops/attention.py docstring).
        # Buffer names/widths come from the model: dense models carry
        # {k, v} of KVH*D each; MLA models ONE latent buffer (models/mla).
        # Stacked mode prepends a [dp] dim sharded over the dp axis: each
        # shard owns slots_local = num_slots/dp rows — per-device KV
        # capacity scales 1/dp, the wide-EP memory profile.
        specs = self.model.kv_cache_spec(c)
        planes = self.model.kv_cache_layers(c)
        if self.dp > 1:
            slots_local = num_slots // self.dp
            # Allocated sharded (device=): the whole pool never lands on
            # the first device on its way to the mesh.
            self.kv_cache = {
                name: jnp.zeros(
                    (self.dp, planes[name], slots_local, width), jnp.bfloat16,
                    device=NamedSharding(self.mesh, P("dp", *specs[name])))
                for name, width in layout.items()}
        else:
            if self._window_blocks:
                # Pages by layer kind: ONE plane, each layer's region after
                # the last (its kind's group's pages, ``with_layer_tables``),
                # so one traced layer body serves both kinds.
                planes = dict.fromkeys(planes, 1)
                num_slots = config.block_size * sum(
                    self._window_blocks if t == SLIDING else config.num_blocks
                    for t in c.layer_types)
            self.kv_cache = {
                name: jnp.zeros(
                    (planes[name], num_slots, width), jnp.bfloat16,
                    device=NamedSharding(self.mesh, specs[name]))
                for name, width in layout.items()}
        self._replicated = NamedSharding(self.mesh, P())
        self._dp_sharded = NamedSharding(self.mesh, P("dp"))
        if self._has_state:
            # The state pool rides the step programs with the caches, as
            # more entries of the one donated dict: a slot a sequence
            # (1..max_num_seqs; slot 0 takes the padded rows' writes).
            self.kv_cache.update({
                name: jnp.zeros(sd.shape, sd.dtype, device=self._replicated)
                for name, sd in self.model.state_pool_shapes(
                    c, config.max_num_seqs + 1).items()})

        self.max_blocks_per_seq = -(-c.max_model_len // config.block_size)
        # Lives on the device: the classic step program splits it itself
        # and hands the successor back (never fetched).  Committed here so
        # the first step's program is the one every later step reuses.
        self._rng = jax.device_put(jax.random.PRNGKey(config.seed),
                                   self._replicated)
        self._step_count = 0
        # Device dispatches (one program launch + one host fetch each):
        # step_count / dispatch_count is the N-round amortization ratio
        # the everything-on acceptance test asserts (~N under fused
        # multistep, ~1 classic).
        self._dispatch_count = 0
        # PD producer: finished prefills whose blocks stay pinned until the
        # decode engine pulls them (reference contract: README.tpu.md:182-189).
        self.pinned_transfers: Dict[str, Request] = {}
        # Stalled-request abort must wait for pinned PD blocks (released
        # asynchronously when the decode engine finishes its pull).
        self.scheduler.external_pinned_blocks = lambda: sum(
            len(r.block_ids) for r in self.pinned_transfers.values())
        # Optional KV connector (set by the server / PD wiring).
        self._kv_connector = None
        # Requests rejected before scheduling (e.g. kv_transfer_params with
        # no connector); surfaced as outputs on the next step.
        self._rejected: List[RequestOutput] = []
        self.eos_token_id: Optional[int] = None
        # Optional tokenizer enables engine-side stop-string detection (the
        # server sets it; without one, stop strings fall back to server-side
        # truncation only).
        self.tokenizer = None
        self._last_evictions = 0
        self._last_preemptions = 0
        # (a cache in groups: what /metrics has been told, by group; the
        # series exist from the start, at 0)
        self._group_evictions: Dict[str, int] = {}
        self._group_hit_lost: Dict[str, int] = {}
        if self._window_blocks:
            for g in self.kv_manager.groups:
                self.metrics.add_group_evictions(g.name, 0)
                self.metrics.add_prefix_hit_lost(g.name, 0)

        self.host_tier = None
        if config.kv_offload_blocks > 0:
            from llm_d_tpu.engine.offload import HostKVTier
            self.host_tier = HostKVTier(
                self, config.kv_offload_blocks,
                serve_port=config.kv_shared_tier_port,
                peers=list(config.kv_shared_tier_peers))

        # Async scheduling: the one in-flight fused decode block.
        self._inflight: Optional[Dict[str, Any]] = None
        # Stacked mode: EPLB valid-token mask for the last built batch.
        self._routed_valid: Optional[np.ndarray] = None

        # --- speculative decoding (MTP draft-and-verify) ---
        # Resolution: the master mode must be "auto" AND a positive K
        # configured (config/LLMD_SPEC_K/--spec-k) — the shipped default
        # K of 0 keeps the engine byte-identical to the pre-spec one.
        spec_mode = config.spec_decode or env_choice(
            "LLMD_SPEC_DECODE", "auto", SPEC_DECODE_MODES)
        if spec_mode not in SPEC_DECODE_MODES:
            raise ValueError(f"unknown spec_decode {spec_mode!r} "
                             f"(choices: {SPEC_DECODE_MODES})")
        spec_k = (config.spec_k if config.spec_k is not None
                  else env_int("LLMD_SPEC_K", 0))
        self.spec_k = 0
        self.draft_params = None
        self.spec_tracker = None
        self._spec_fn = None
        self._fused_fns: Dict[Tuple[bool, bool], Any] = {}
        # N-round fused-multistep programs, keyed like _fused_fns.
        self._fms_fns: Dict[Tuple[bool, bool], Any] = {}
        self.spec_strict = (bool(config.spec_strict)
                            if config.spec_strict is not None
                            else env_int("LLMD_SPEC_STRICT", 0) != 0)
        if spec_mode != "off" and spec_k > 0:
            # Round 16: the composition gates are gone.  Spec decode is
            # the body of the fused pipeline — num_scheduler_steps > 1
            # loops the mixed round on device (_build_fused_multistep_fn),
            # stacked dp builds per-shard verify strides, and EPLB's
            # routed-id collection rides the fused program — so the
            # blocker set is empty by design and everything arms
            # together.  Any blocker that resurfaces is a regression:
            # _disable_feature makes it a refused boot under
            # LLMD_SPEC_STRICT=1 and a scrapeable counter otherwise.
            blockers = self._spec_blockers()
            for blocker in blockers:
                self._disable_feature("spec_decode", blocker,
                                      startup=True)
            if not blockers:
                from llm_d_tpu.predictor.model import SpecAcceptanceTracker
                self.spec_k = int(spec_k)
                self.draft_params = jax.device_put(
                    self.model.init_draft_params(
                        c, jax.random.PRNGKey(config.seed + 1)),
                    NamedSharding(self.mesh, P()))
                self.spec_tracker = SpecAcceptanceTracker(self.spec_k)
                # The base fused mixed-round program; logprobs variants
                # compile on first use (keyed by (want_logprobs,
                # want_top) like the classic _step_fn/_step_fn_top pair).
                self._spec_fn = self._build_fused_fn(self.spec_k)
                self._fused_fns = {(False, False): self._spec_fn}
                self.scheduler.spec_lookahead = self._spec_lookahead
                logger.info("spec decode on: K=%d%s", self.spec_k,
                            f" (fixed acceptance "
                            f"{config.spec_fixed_accept})"
                            if config.spec_fixed_accept is not None else "")

        # The classic path one step ahead (module docstring).  ``_fed``:
        # the ids the last classic step sampled, still on the device, as
        # the next step program's operand (zeros before the first step;
        # empty where the program takes none: block diffusion, stacked dp).
        self._fed: Tuple[jax.Array, ...] = (jax.device_put(
            np.zeros(config.max_num_seqs, np.int32), self._replicated),
        ) if self.dp == 1 and not self.block_length else ()
        # The classic step launched and not yet retired, and the instant
        # the last one's tokens were fetched (where the next span starts).
        self._ahead: Optional[_LaunchedStep] = None
        self._fetched_at = 0.0
        self._step_fn = self._build_step_fn(packed=True)
        # Variant computing top-N logprobs, compiled on first use (steps
        # with no logprobs request never pay the extra top_k).
        self._step_fn_top = None
        self._multistep_fn = (
            self._build_multistep_fn(config.num_scheduler_steps)
            if config.num_scheduler_steps > 1 else None)
        # What of the gate never changes: the autoregressive classic path
        # alone, one shard, no EPLB (it reads a step's routing before the
        # next is placed) and no host tier (its flush would wait for the
        # step in flight).
        self._runs_ahead = (
            bool(self._fed) and self._spec_fn is None
            and self._multistep_fn is None and self.eplb is None
            and self.host_tier is None)
        if config.precompile_step_shapes:
            self.precompile_step_shapes()

    @property
    def block_length(self) -> int:
        """The model's diffusion block length B (0: autoregressive): a row
        that generates brings a whole block of queries to every step and
        gets candidates for every slot of it back (models/config.py)."""
        return self.model_config.diffusion_block_length

    def _spec_requested(self) -> bool:
        """Speculative decoding asked for (configuration or environment),
        whether or not this engine can serve it."""
        cfg = self.config
        return ((cfg.spec_decode or env_choice(
            "LLMD_SPEC_DECODE", "auto", SPEC_DECODE_MODES)) != "off"
            and (cfg.spec_k if cfg.spec_k is not None
                 else env_int("LLMD_SPEC_K", 0)) > 0)

    def _check_block_diffusion(self) -> None:
        """What a block-diffusion model cannot be served with is refused
        here, at start-up, not silently dropped: only the classic step path
        knows a step that yields 0..B tokens a row."""
        B, cfg = self.block_length, self.config
        if not B:
            return
        if cfg.block_size % B:
            # A prefix-cache hit is a multiple of the page and must be one
            # of the block: cached keys were computed under the block mask.
            raise ValueError(
                f"block_size {cfg.block_size} is no multiple of the model's "
                f"diffusion block length {B}")
        if cfg.max_num_batched_tokens < B:
            raise ValueError(
                f"max_num_batched_tokens {cfg.max_num_batched_tokens} holds "
                f"no block of {B}")
        spec_on = self._spec_requested()
        blocker = self._spec_blockers()[0]
        for feature, asked in (
                ("multistep", cfg.num_scheduler_steps > 1),
                ("spec_decode", spec_on),
                ("stacked_dp", self.dp > 1)):
            if asked:
                self.metrics.inc_feature_disabled(feature, blocker)
                raise ValueError(
                    f"{feature} requested but unavailable ({blocker}): "
                    f"refusing to start")

    def _check_recurrent_state(self) -> None:
        """What a stack with recurrent state beside its paged KV cannot be
        served with is refused here, at start-up: only the classic step
        path (run ahead included) carries the state pool, on one shard;
        the prefix cache is switched off and counted, not refused."""
        cfg = self.config
        if not self._has_state:
            return
        blocker = ("recurrent_state: a sequence's state is overwritten "
                   "every token, beside its pages")
        if cfg.enable_prefix_caching:
            self._disable_feature("prefix_caching", blocker)
        spec_on = self._spec_requested()
        mesh = cfg.mesh
        for feature, asked in (
                ("multistep", cfg.num_scheduler_steps > 1),
                ("spec_decode", spec_on),
                ("stacked_dp", self.dp > 1),
                ("tensor_parallel", bool(mesh) and (mesh.tp or 1) > 1),
                ("sequence_parallel", bool(mesh) and (mesh.sp or 1) > 1),
                ("kv_offload", cfg.kv_offload_blocks > 0)):
            if asked:
                self.metrics.inc_feature_disabled(feature, blocker)
                raise ValueError(
                    f"{feature} requested but unavailable ({blocker}): "
                    f"refusing to start")

    def _check_layer_kinds(self) -> None:
        """What an MLA stack of two layer kinds, a learned key selection or
        a share of the routed experts cannot be served with is refused
        here, at start-up: only the classic step path (run ahead included)
        on one device knows cache buffers by layer kind, and the int8
        expert kernels assume that every expert is held."""
        c, cfg = self.model_config, self.config
        mesh = cfg.mesh
        sharded = bool(mesh) and max(
            mesh.tp or 1, mesh.sp or 1, mesh.dp or 1) > 1
        for mechanism, on, unserved in (
                ("layer_kinds: cache buffers and head counts by layer kind",
                 c.mla_by_kind, (
                     ("multistep", cfg.num_scheduler_steps > 1),
                     ("spec_decode", self._spec_requested()),
                     ("sharded_mesh", sharded),
                     ("kv_offload", cfg.kv_offload_blocks > 0),
                     ("int8_experts", cfg.quantization == "int8"))),
                ("expert_share: a slice of the experts under a full-width "
                 "router",
                 bool(c.num_local_experts), (
                     ("sharded_mesh", sharded),
                     ("eplb", cfg.enable_eplb),
                     ("int8_experts", cfg.quantization == "int8")))):
            for feature, asked in unserved if on else ():
                if asked:
                    self.metrics.inc_feature_disabled(feature, mechanism)
                    raise ValueError(
                        f"{feature} requested but unavailable "
                        f"({mechanism}): refusing to start")

    def _cache_groups_blocker(self) -> str:
        """Why a stack whose pages could go by layer kind is served as ONE
        group by this engine ('' where nothing asked for stands in the way):
        only the classic step path (run ahead included) on one shard gives
        a window's pages back as it passes them, and the host tier and the
        wire move one group's pages.  Such a stack keeps every feature a
        stack of one kind has, at one pool's capacity."""
        cfg = self.config
        if not self.model_config.kv_cache_groups:
            return ""
        asked = [feature for feature, on in (
            ("multistep", cfg.num_scheduler_steps > 1),
            ("spec_decode", self._spec_requested()),
            ("stacked_dp", bool(cfg.mesh) and (cfg.mesh.dp or 1) > 1),
            ("kv_offload", cfg.kv_offload_blocks > 0),
            ("kv_transfer", cfg.kv_transfer)) if on]
        if not asked:
            return ""
        return "%s asked for: one group of pages" % ", ".join(asked)

    def _check_cache_groups(self) -> None:
        """A stack served as one group because of what was asked for says
        so once, counted (``engine_feature_disabled_total``): its window
        layers then hold every token, as before PR 46."""
        if self._groups_blocker:
            self._disable_feature("cache_groups", self._groups_blocker)

    @property
    def _has_state(self) -> bool:
        return self.model_config.has_recurrent_state

    @property
    def kv_connector(self):
        """The KV connector (set by the server / PD wiring), or None."""
        return self._kv_connector

    @kv_connector.setter
    def kv_connector(self, connector) -> None:
        c = self.model_config
        if connector is not None and self._window_blocks:
            # A pull or a PD hand-over moves the full group's pages; the
            # window group's would stay behind.  ``EngineConfig.kv_transfer``
            # (the server sets it from --kv-transfer-config) builds the
            # engine with one group, which takes a connector.
            self.metrics.inc_feature_disabled(
                "kv_transfer", "cache_groups: the wire carries one group's "
                "pages")
            raise ValueError(
                "a KV connector moves one group's pages and this engine was "
                "built with its cache in groups by layer kind: build it "
                "with EngineConfig.kv_transfer set")
        if connector is not None and c.mla_by_kind:
            self.metrics.inc_feature_disabled(
                "kv_transfer", "layer_kinds: the wire carries one row "
                "width for every layer")
            raise ValueError(
                "a KV connector moves rows of one width for every layer; "
                "this model's cache buffers go by layer kind: refusing to "
                "attach it")
        if connector is not None and self._has_state:
            # A pull or a PD hand-over moves pages; the recurrent state
            # would stay behind.
            self.metrics.inc_feature_disabled(
                "kv_transfer", "recurrent_state: the state pool is not "
                "transferred")
            raise ValueError(
                "a KV connector moves pages only; this model's sequences "
                "also own recurrent state, which is not transferred: "
                "refusing to attach it")
        self._kv_connector = connector

    def step_shapes(self) -> List[Tuple[int, int, int]]:
        """Every (T, S, Q) bucket triple a classic step can have: T tokens
        in all, S rows, Q = the longest row's tokens, each rounded up as
        ``_build_batch`` rounds it.  A triple is reachable when some n rows
        in S's range, the longest of q tokens in Q's range, hold a total
        in T's range: q + (n - 1) <= total <= n q.  A block-diffusion
        engine's rows hold whole blocks of B tokens each (prompt chunks,
        denoising and commit passes alike), so no row has one token and
        q + (n - 1) B <= total."""
        cfg = self.config
        unit = self.block_length or 1

        def ranges(lo: int, hi: int) -> List[Tuple[int, int]]:
            """(smallest count, bucket) of each bucket from ``lo`` up."""
            out, b, prev = [], lo, 0
            while b < hi:
                out.append((prev + 1, b))
                prev, b = b, b * 2
            return out + [(prev + 1, hi)]

        tokens = ranges(cfg.min_token_bucket, cfg.max_num_batched_tokens)
        shapes = []
        for n_lo, S in ranges(min(cfg.min_seq_bucket, cfg.max_num_seqs),
                              cfg.max_num_seqs):
            for t_lo, T in tokens:
                if unit == 1 and max(t_lo, n_lo) <= min(T, S):
                    shapes.append((T, S, 1))         # decode: total = rows
                for q_lo, Q in tokens:
                    if Q <= T and max(t_lo, max(q_lo, 2, unit)
                                      + (n_lo - 1) * unit) <= min(T, S * Q):
                        shapes.append((T, S, Q))
        return shapes

    def precompile_step_shapes(self) -> int:
        """Run the classic step program of every reachable bucket triple on
        an all-padding batch (every write lands in the trash block, the
        key is not advanced).  The other step paths (speculative, fused
        multi-step) still compile on first use."""
        shapes = self.step_shapes()
        t0 = time.monotonic()
        for T, S, Q in shapes:
            layout = self._layout(T, S, Q, dp=self.dp)
            packed = jax.device_put(
                layout.new_buffer(),
                self._replicated if self.dp == 1 else self._dp_sharded)
            self.kv_cache = self._step_fn(
                self.params, self.kv_cache, packed, self._rng, *self._fed,
                layout)[2]
        jax.block_until_ready(self.kv_cache)
        logger.info("precompiled %d step programs in %.1fs", len(shapes),
                    time.monotonic() - t0)
        return len(shapes)

    # ---------- feature-composition accounting ----------

    def _announce_attention_path(self, layout: Dict[str, int]) -> None:
        """Say ONCE, at construction, which attention implementation the
        step programs will contain — the per-call gates in ops/attention.py
        and models/mla.py are static per engine, so a drop to the chunked
        XLA path is a property of the config, not something to discover
        from a profile."""
        from llm_d_tpu.ops.attention import (
            pallas_ineligible_reason, resolve_backend)
        backend = resolve_backend(self.config.attn_backend)
        logger.info("attention backend: %s (%s requested, %s platform)",
                    backend, self.config.attn_backend,
                    jax.default_backend())
        # (heads, cache row width) one shard's Pallas prefill kernels see:
        # what sets their query tile.  None: another path serves prefill.
        self._prefill_tile_dims: Optional[Tuple[int, int]] = None
        # ``mla_masked_attention`` serves the MLA layers that see a window,
        # it and ``dsa_index.index_bias`` those that select their keys.
        self._window_kernel = self._select_kernel = False
        if backend != "pallas":
            return
        # A tp shard sees its slice of the heads and of the folded dense
        # rows (the MLA latent row is replicated over tp).
        heads_tp = self.config.mesh.tp if self.config.mesh else 1
        tp = heads_tp if not self.model_config.use_mla else 1
        reason = next(filter(None, (
            pallas_ineligible_reason(self.config.block_size, w // tp)
            for w in layout.values())), None)
        if reason is not None:
            self._disable_feature("pallas_attention", reason)
            return
        c = self.model_config
        if c.linear_by_layer:
            # (its FULL layers are plain latent attention: the two MLA
            # kernels and their tile accounting serve them, below)
            self._announce_linear_layers()
        elif c.mla_by_kind:
            # Layers that select keys and layers that see a window are
            # served by a kernel of their own, dense under the selection
            # or the window as a bias (ops/sparse_mla.py,
            # ops/pallas/mla_masked.py): the tile and key block accounting
            # of the two MLA kernels describes neither.
            from llm_d_tpu.ops import sparse_mla
            for kind in c.mla_layer_kinds or (FULL,):
                g = c.mla_geometry(kind)
                reason = sparse_mla.kernel_refusal(
                    g, self.config.block_size,
                    -(-c.max_model_len // self.config.block_size)
                    * self.config.block_size
                ) if g.index_topk or g.window else None
                if reason is not None:
                    self._disable_feature("pallas_attention",
                                          f"{kind}: {reason}")
                elif g.window:
                    self._window_kernel = True
                elif g.index_topk:
                    self._select_kernel = True
            return
        self._prefill_tile_dims = (
            c.num_heads // heads_tp, next(iter(layout.values())) // tp)
        if c.mixer_by_layer:
            self._announce_layer_kinds()

    def _announce_linear_layers(self) -> None:
        """The path a stack's LINEAR layers took (Pallas backend): static
        per engine, as the attention path above."""
        from llm_d_tpu.ops.linear_attention import pallas_ineligible_reason
        c = self.model_config
        reason = pallas_ineligible_reason(
            c.lin_num_heads, c.lin_key_dim, c.lin_value_dim)
        if reason:
            self._disable_feature("pallas_linear_attention", reason)
        logger.info(
            "layer kind %s x %d: %s", LINEAR, c.layer_types.count(LINEAR),
            "XLA delta-rule update and chunked form" if reason else
            "delta_decode_update / delta_chunk_scan (Pallas) over XLA's "
            "piece terms")

    def _announce_layer_kinds(self) -> None:
        """The path each layer KIND of a decoder-hybrid-decoder stack took
        (Pallas backend, cache geometry eligible): static per engine, as
        the attention path above."""
        from llm_d_tpu.ops.ssm import ssm1_pallas_ineligible_reason
        c = self.model_config
        reason = ssm1_pallas_ineligible_reason(
            c.ssm_inner_size, c.ssm_state_size, c.ssm_chunk_size)
        if reason:
            self._disable_feature("pallas_ssm", reason)
        paths = {
            MAMBA: ("XLA selective scan and one-token update" if reason else
                    "ssm1_chunk_scan / ssm1_decode_update (Pallas)"),
            SLIDING: "flash_prefill_paged / paged_attention_decode_update, "
                     "heads paired, window %d" % c.sliding_window,
            FULL: "paged_attention_read on the sampled rows (layer %d: the "
                  "step's keys and values scattered first); "
                  "flash_prefill_paged elsewhere" % c.cross_kv_layer,
            CROSS: "paged_attention_read over layer %d's plane, nothing "
                   "written" % c.cross_kv_layer,
            GMU: "XLA dots on the sampled rows"}
        for kind in dict.fromkeys(c.layer_types):
            logger.info("layer kind %s x %d: %s", kind,
                        c.layer_types.count(kind), paths[kind])

    def _spec_blockers(self) -> List[str]:
        """Startup conditions that would force spec decode off.  Empty
        since round 16 — the fused pipeline owns multistep/async rounds
        with spec verify in the loop body, stacked dp carries per-shard
        verify strides, and EPLB collects routed ids from the fused
        program — kept as the single place a future incompatibility
        must be declared so _disable_feature (strict mode + the
        feature-disabled counter) governs it rather than an ad-hoc log
        line.  One is declared: a block-diffusion model, whose step yields
        0..B tokens a row and whose block needs a commit pass; the
        speculative, multi-step and fused multi-step programs all retire
        one token (plus drafts) a row (``_check_block_diffusion`` refuses
        them at start-up, strict mode or not)."""
        if self.block_length:
            return ["block_diffusion: the step yields 0..B tokens a row"]
        return []

    def _disable_feature(self, feature: str, blocker: str,
                         startup: bool = False) -> None:
        """Account for a feature demotion: count it
        (engine_feature_disabled_total{feature,blocker}), log it, and —
        for STARTUP demotions under strict mode — refuse to boot rather
        than serve a silently degraded config."""
        self.metrics.inc_feature_disabled(feature, blocker)
        if startup and self.spec_strict:
            raise ValueError(
                f"{feature} requested but unavailable ({blocker}) and "
                f"LLMD_SPEC_STRICT/--spec-strict is set: refusing to "
                f"start with a silently degraded config")
        if (feature, blocker) not in self._disabled_seen:
            self._disabled_seen.add((feature, blocker))
            logger.warning("%s demoted: %s", feature, blocker)

    # ---------- jitted step ----------

    def _prefill_chunk_cap(self, decode_tokens: int) -> Optional[int]:
        """Per-chunk prefill token cap for one schedule pass (the
        scheduler's decode-priority callback; ``decode_tokens`` is the
        decode + spec-lookahead load already funded).  Fixed
        LLMD_PREFILL_CHUNK wins; otherwise the step-latency model picks
        the largest chunk predicted to keep the step under the target
        step time; no target -> None (budget-bound only)."""
        if self._prefill_chunk_fixed is not None:
            return self._prefill_chunk_fixed
        if self._step_time_target_ms <= 0.0 \
                or not self.step_time_model.trained:
            return None
        # Under fused multistep the funded chunk is re-run every round of
        # the N-round dispatch, so size it against the per-round budget.
        rounds = (max(1, self.config.num_scheduler_steps)
                  if self._spec_fn is not None else 1)
        return self.step_time_model.chunk_for(
            decode_tokens, self._step_time_target_ms,
            lo=self.config.min_token_bucket,
            hi=self.config.max_num_batched_tokens, rounds=rounds)

    def _moe_opts(self) -> Optional[Dict[str, Any]]:
        """MoE dispatch knobs, captured by every step program.  The model
        picks the phase-specific DBO threshold from the program's static
        query width (Q == 1 <=> pure decode — true for single-step and fused
        decode alike; reference decode.yaml:98-99).  -1 = DBO explicitly
        off: an engine-built program must not fall back to the standalone-op
        env vars."""
        if not self.model_config.is_moe:
            return None
        if not self.config.enable_dbo:
            return dict(dbo_decode_min_tokens=-1, dbo_prefill_min_tokens=-1)
        return dict(
            dbo_decode_min_tokens=self.config.dbo_decode_token_threshold,
            dbo_prefill_min_tokens=self.config.dbo_prefill_token_threshold)

    def _build_step_fn(self, want_top_logprobs: bool = False,
                       packed: bool = False):
        """The classic step program.  ``packed`` (what the engine serves):
        ``step_fn(params, kv_cache, buffer, rng, layout)`` takes the batch
        as the one int32 buffer of the static ``layout`` (packed_batch.py),
        splits ``rng`` itself and returns the successor key as one more
        output: one copy and one launch a step.  Where the engine may run a
        step ahead (``_fed``: autoregressive, one shard) the program is
        ``step_fn(params, kv_cache, buffer, rng, prev_ids, layout)``: it
        takes the ids the previous step sampled (``[max_num_seqs]``, never
        donated: the host fetches them later), feeds them to the rows that
        name one (``feed_tokens``) and returns its own in that shape, so a
        step that runs ahead and one that does not are one program a
        bucket.  Otherwise the same body over a dict batch and a ready
        step key, for tools that lower it."""
        c = self.model_config
        block_size = self.config.block_size
        backend = self.config.attn_backend
        model, mesh = self.model, self.mesh
        moe_opts = self._moe_opts()

        # What the forward returns beside the hidden rows and the cache:
        # EPLB's routed ids, and on one device the experts the step touches
        # (an EP mesh leaves the count out).
        collect = {}
        if self.eplb is not None:
            collect["collect_routed"] = True
        if c.is_moe and mesh.devices.size == 1:
            collect["count_touched"] = True

        def step_body(params, kv_cache, batch, rng):
            hidden, kv_cache, *extra = model.forward(
                params, kv_cache, batch, c, block_size, backend,
                mesh=mesh, moe_opts=moe_opts, **collect)
            extra = dict(zip(collect, extra))
            routed = extra.get("collect_routed")
            touched = extra.get("count_touched")
            logits = model.compute_logits(params, hidden, c)
            with part("sample"):
                if c.diffusion_block_length:
                    ids, logprobs, top = reveal_body(logits, batch, rng)
                    return ids, logprobs, kv_cache, routed, touched, top
                if logits.ndim == 3:
                    # Stacked (SPMD dp): flatten [dp, S_l, V] -> [dp*S_l, V]
                    # so sampling is row-wise; the merged dim stays
                    # dp-sharded and the host indexes outputs by flat row
                    # (shard * S_l + s).
                    logits = logits.reshape(-1, logits.shape[-1])
                    batch = dict(batch, **{
                        k: batch[k].reshape(-1)
                        for k in ("temperature", "top_k", "top_p",
                                  "seeds", "gen_idx")})
                ids = sampling_ops.sample(
                    logits, batch["temperature"], batch["top_k"],
                    batch["top_p"], rng, seeds=batch["seeds"],
                    gen_idx=batch["gen_idx"])
                if want_top_logprobs:
                    logprobs, top_ids, top_lps = \
                        sampling_ops.compute_top_logprobs(logits, ids)
                    top = (top_ids, top_lps)
                else:
                    logprobs = sampling_ops.compute_logprobs(logits, ids)
                    top = None
            return ids, logprobs, kv_cache, routed, touched, top

        def reveal_body(logits, batch, rng):
            """A block-diffusion step's epilogue: ``logits`` [S * B, V] of
            every slot of every row's block.  Each slot's candidate (argmax,
            or a sample under a temperature) and, by the model's reveal
            rule on the device, which masked slots take theirs now; returns
            (ids [S, B], -1 where the pass revealed nothing; logprobs
            [S, B]; top-N or None)."""
            B = c.diffusion_block_length

            def slots(x):
                return jnp.repeat(x, B)

            gen_idx = (batch["gen_idx"][:, None]
                       + jnp.arange(B, dtype=jnp.int32)[None, :]).reshape(-1)
            x0 = sampling_ops.sample(
                logits, slots(batch["temperature"]), slots(batch["top_k"]),
                slots(batch["top_p"]), rng, seeds=slots(batch["seeds"]),
                gen_idx=gen_idx)
            ids, logprobs = sampling_ops.reveal(
                logits, x0, batch["slot_masked"] > 0, batch["reveal_quota"],
                c.diffusion_remasking, c.diffusion_confidence_threshold)
            top = None
            if want_top_logprobs:
                _, top_ids, top_lps = sampling_ops.compute_top_logprobs(
                    logits, x0)
                top = (top_ids, top_lps)
            return ids, logprobs, top

        if not packed:
            return jax.jit(step_body, donate_argnums=(1,))

        # The key's split, lowered ONCE and called from every step program
        # as a ready StableHLO module: traced into each of the 110-130
        # bucket programs, ``jax.random.split`` cost a tenth of a second of
        # Python lowering apiece on the chip's host (a sixth of set-up).
        split = jax.export.export(
            jax.jit(jax.random.split),
            platforms=(mesh.devices.flat[0].platform,))(
                jax.ShapeDtypeStruct((2,), jnp.uint32))

        if not self._fed:
            @functools.partial(jax.jit, static_argnums=(4,),
                               donate_argnums=(1,))
            def step_fn(params, kv_cache, buffer, rng, layout):
                # Bit-identical to the host-side ``rng, key = split(rng)``.
                with part("sample"):
                    rng, step_key = split.call(rng)
                with part("tiles"):
                    batch = layout.unpack(buffer)
                return (*step_body(params, kv_cache, batch, step_key), rng)

            return step_fn

        replicated = self._replicated

        @functools.partial(jax.jit, static_argnums=(5,), donate_argnums=(1,))
        def step_fn(params, kv_cache, buffer, rng, prev_ids, layout):
            with part("sample"):
                rng, step_key = split.call(rng)
            with part("tiles"):
                batch = layout.unpack(buffer)
            with part("embed"):
                batch = feed_tokens(batch, prev_ids)
            ids, *rest = step_body(params, kv_cache, batch, step_key)
            # Every bucket returns its ids in the operand's shape and
            # placement, so any step's ids feed any bucket's program.
            with part("sample"):
                ids = jax.lax.with_sharding_constraint(
                    jnp.pad(ids, (0, prev_ids.shape[0] - ids.shape[0])),
                    replicated)
            return (ids, *rest, rng)

        return step_fn

    def _build_multistep_fn(self, K: int):
        """K fused decode iterations: sampled ids feed the next iteration on
        device; only the final [K, S] id matrix is fetched by the host."""
        c = self.model_config
        block_size = self.config.block_size
        backend = self.config.attn_backend
        model, mesh = self.model, self.mesh
        moe_opts = self._moe_opts()

        collect_routed = self.eplb is not None

        @functools.partial(jax.jit, static_argnums=(), donate_argnums=(1,))
        def multistep_fn(params, kv_cache, mbatch, rng):
            # Row layout: [S] classic, [dp, S_l] stacked (SPMD dp) — all the
            # index arithmetic below is shape-polymorphic over the leading
            # dim; sampling flattens rows either way.
            shape = mbatch["last_ids"].shape
            bt = mbatch["block_tables"]
            seq_ids = jnp.broadcast_to(
                jnp.arange(shape[-1], dtype=jnp.int32), shape)

            def one_iter(carry, xs):
                key, it = xs
                kv_cache, last_ids, pos0 = carry
                # Decode batch: T == S, one token per sequence.
                slot = (jnp.take_along_axis(
                    bt, (pos0 // block_size)[..., None], axis=-1)[..., 0]
                    * block_size + pos0 % block_size)
                batch = dict(
                    token_ids=last_ids,
                    positions=pos0,
                    token_seq_ids=seq_ids,
                    token_qpos=jnp.zeros(shape, jnp.int32),
                    slot_mapping=jnp.where(
                        mbatch["active"], slot, pos0 % block_size),
                    block_tables=bt,
                    seq_lens=jnp.where(mbatch["active"], pos0 + 1, 0),
                    sample_idx=seq_ids,
                    qtok_idx=seq_ids[..., None],
                )
                if collect_routed:
                    hidden, kv_cache, routed = model.forward(
                        params, kv_cache, batch, c, block_size, backend,
                        mesh=mesh, collect_routed=True, moe_opts=moe_opts)
                else:
                    hidden, kv_cache = model.forward(
                        params, kv_cache, batch, c, block_size, backend,
                        mesh=mesh, moe_opts=moe_opts)
                    routed = jnp.zeros((), jnp.int32)
                logits = model.compute_logits(params, hidden, c)
                ids = sampling_ops.sample(
                    logits.reshape(-1, logits.shape[-1]),
                    mbatch["temperature"].reshape(-1),
                    mbatch["top_k"].reshape(-1),
                    mbatch["top_p"].reshape(-1), key,
                    seeds=mbatch["seeds"].reshape(-1),
                    gen_idx=(mbatch["gen0"] + it).reshape(-1)
                ).reshape(shape)
                ids = jnp.where(mbatch["active"], ids, 0)
                return (kv_cache, ids, pos0 + 1), (ids, routed)

            keys = jax.random.split(rng, K)
            (kv_cache, _, _), (ids_ks, routed_ks) = jax.lax.scan(
                one_iter, (kv_cache, mbatch["last_ids"],
                           mbatch["pos0"]),
                (keys, jnp.arange(K, dtype=jnp.int32)))
            return ids_ks, kv_cache, routed_ks   # [K, *S], ..., [K, Lm, T, k]

        return multistep_fn

    def _try_multistep(self, sched: SchedulerOutput) -> Optional[int]:
        """If this is a pure-decode round eligible for fusion, pre-allocate
        K tokens per request and return K; else None."""
        K = self.config.num_scheduler_steps
        if self._multistep_fn is None or not sched.scheduled:
            return None
        for sr in sched.scheduled:
            req = sr.request
            if (sr.num_new_tokens != 1
                    or req.num_computed_tokens != req.num_tokens - 1
                    or req.do_remote_decode
                    or req.sampling.logprobs is not None):
                return None
            if req.num_tokens + K >= self.model_config.max_model_len:
                return None
        # Pre-allocate blocks to cover K new tokens for every request.
        allocated: List[Tuple[Request, List[int]]] = []
        for sr in sched.scheduled:
            req = sr.request
            ok = self.kv_manager.allocate(req, req.num_computed_tokens + K)
            if ok is None:
                # Roll back earlier requests' speculative tail blocks —
                # holding them until finish is a fragmentation source under
                # exactly the memory pressure that made allocation fail.
                for r, blocks in reversed(allocated):
                    self.kv_manager.release_tail(r, blocks)
                return None   # fall back to single-step
            allocated.append((req, ok))
        return K

    def _block_offset(self, req: Request) -> int:
        """Global -> shard-local block id rebase for this request (0 when
        dp == 1: region 0 spans the whole pool)."""
        return self.kv_manager.region_of_request(req) \
            * self.kv_manager.blocks_per_region if self.dp > 1 else 0

    def _ms_meta(self, scheduled) -> Tuple[Dict[str, np.ndarray], List,
                                           np.ndarray]:
        """Host-side batch arrays for a fused decode block.

        Returns (meta arrays flat over [dp * S_l] rows, scheduled list in
        row order, row index per scheduled entry).  Block-table ids are
        shard-local (stacked mode scatters into per-shard cache planes)."""
        cfg = self.config
        per = (self._split_by_shard(scheduled) if self.dp > 1
               else [list(scheduled)])
        S_l = _next_bucket(max(len(p) for p in per),
                           min(cfg.min_seq_bucket, cfg.max_num_seqs),
                           cfg.max_num_seqs)
        S = S_l * self.dp
        B = self.max_blocks_per_seq

        last_ids = np.zeros(S, np.int32)
        pos0 = np.zeros(S, np.int32)
        block_tables = np.zeros((S, B), np.int32)
        active = np.zeros(S, bool)
        temperature = np.zeros(S, np.float32)
        top_k = np.zeros(S, np.int32)
        top_p = np.ones(S, np.float32)
        seeds = np.full(S, -1, np.int32)
        gen0 = np.zeros(S, np.int32)
        ordered: List = []
        rows: List[int] = []
        for r, shard in enumerate(per):
            for i, sr in enumerate(shard):
                s = r * S_l + i
                req = sr.request
                ordered.append(sr)
                rows.append(s)
                last_ids[s] = req.all_token_ids[req.num_computed_tokens]
                pos0[s] = req.num_computed_tokens
                block_tables[s, :len(req.block_ids)] = \
                    np.asarray(req.block_ids, np.int32) \
                    - self._block_offset(req)
                active[s] = True
                temperature[s] = req.sampling.temperature
                top_k[s] = req.sampling.top_k
                top_p[s] = req.sampling.top_p
                if req.sampling.seed is not None:
                    # Mask into int32: a 64-bit seed must not OverflowError
                    # the batch array (and kill the whole server's loop).
                    seeds[s] = int(req.sampling.seed) & 0x7FFFFFFF
                gen0[s] = len(req.output_token_ids)
        meta = dict(last_ids=last_ids, pos0=pos0, block_tables=block_tables,
                    active=active, temperature=temperature, top_k=top_k,
                    top_p=top_p, seeds=seeds, gen0=gen0)
        return meta, ordered, np.asarray(rows, np.int32)

    def _ms_dispatch(self, meta: Dict[str, Any], scheduled, K: int,
                     rows: np.ndarray) -> Dict[str, Any]:
        """Launch one fused decode block; returns the in-flight record
        WITHOUT synchronizing (ids stay on device until retire).

        Stacked mode reshapes the flat host meta to [dp, S_l, ...] sharded
        P("dp"); device arrays riding over from a predecessor block
        (``last_ids``) already carry the stacked shape."""
        if self.dp > 1:
            S_l = meta["pos0"].shape[0] // self.dp

            def to_dev(v):
                if isinstance(v, jax.Array):
                    return v
                return jnp.asarray(v.reshape(self.dp, S_l, *v.shape[1:]))
            mbatch = jax.device_put(
                {k: to_dev(v) for k, v in meta.items()}, self._dp_sharded)
        else:
            mbatch = jax.device_put(
                {k: (v if isinstance(v, jax.Array) else jnp.asarray(v))
                 for k, v in meta.items()},
                self._replicated)
        t0 = self._clock.mark("dispatch")
        self._rng, step_key = jax.random.split(self._rng)
        ids_ks, self.kv_cache, routed_ks = self._multistep_fn(
            self.params, self.kv_cache, mbatch, step_key)
        self._dispatch_count += 1
        self.metrics.engine_dispatches.inc()
        return dict(scheduled=list(scheduled), K=K, meta=meta, rows=rows,
                    ids_dev=ids_ks, routed_dev=routed_ks, t0=t0)

    def _ms_retire(self, inflight: Dict[str, Any]) -> List[RequestOutput]:
        """Synchronize one in-flight block and advance request state."""
        scheduled, K = inflight["scheduled"], inflight["K"]
        rows = inflight["rows"]
        # [K, S] / [K, dp, S_l] -> [K, S_total] flat rows.  Deliberate
        # sync point: retire() exists to materialize this block's tokens,
        # and the successor block is already dispatched so the device
        # stays busy while the host syncs.
        self._clock.mark("fetch")
        # llmd: ignore[JIT] the one intended multistep-retire host sync
        ids_ks = np.asarray(jax.device_get(inflight["ids_dev"]))
        now = self._clock.mark("post")
        ids_ks = ids_ks.reshape(K, -1)
        self._step_count += K
        self.metrics.engine_steps.inc(K)
        # K engine steps in one device program, one span.
        self._note_step(inflight["t0"], now,
                        [sr.request for sr in scheduled], 0,
                        K * len(scheduled), fused=True, rounds=K,
                        kv=self._kv_counts(
                            [sr.request.num_computed_tokens + K
                             for sr in scheduled], K))
        if self.eplb is not None:
            # Fused decode is EXACTLY the traffic EPLB exists to balance;
            # only real sequences' rows count.  (A successor block already
            # dispatched keeps using the pre-rebalance physical
            # table+weights pair — consistent, balanced one block later.)
            # Normalize [K, Lm, S, k] to the layer-leading [Lm, K*S, k]
            # the per-layer load tracker expects.
            routed_ms = jnp.moveaxis(
                inflight["routed_dev"][:, :, rows, :], 1, 0)
            routed_ms = routed_ms.reshape(
                routed_ms.shape[0], -1, routed_ms.shape[-1])
            self.params = self.eplb.on_step(
                routed_ms, self._step_count, self.params, self.mesh)

        outputs: List[RequestOutput] = []
        for s, sr in zip(rows, scheduled):
            req = sr.request
            if req.state is not RequestState.RUNNING:
                # Finished (stop in an earlier retire) or aborted while this
                # block was in flight: its tokens are discarded.  The zombie
                # KV writes landed in rows past every live reader's masked
                # length, in block-table order that device program order
                # already sequenced before any reallocation's writes.
                continue
            new_tokens: List[int] = []
            finish = None
            for k in range(K):
                token = int(ids_ks[k, s])
                req.num_computed_tokens += 1
                req.output_token_ids.append(token)
                new_tokens.append(token)
                finish = self._check_stop(req, token)
                if finish is not None:
                    break
            # Tokens past a stop are discarded; their KV writes live in
            # already-allocated blocks and are freed with the request.
            self.metrics.generation_tokens.inc(len(new_tokens))
            # The fused block COMPUTED all K steps for this row on
            # device regardless of where the stop landed — all K tokens
            # crossed the EP wire, so all K are charged (generation
            # counts only the kept tokens above).
            self._account_collective_bytes(K)
            if req.last_token_time is not None:
                self.metrics.inter_token_latency.observe(
                    (now - req.last_token_time) / max(1, len(new_tokens)))
            req.last_token_time = now
            self.kv_manager.cache_full_blocks(req)
            outputs.append(RequestOutput(
                req.request_id, new_tokens, finish is not None,
                finish_reason=finish))
            if finish is not None:
                self.scheduler.finish(req, RequestState(finish))
                self._spec_forget(req.request_id)
                self.metrics.request_success.labels(
                    model_name=self.metrics.model_name,
                    finished_reason=finish).inc()
                self.metrics.e2e_request_latency.observe(now - req.arrival_time)
                self._trace_phase(
                    req, "engine.decode", "decode",
                    req.first_token_time or now, now,
                    n_tokens=len(req.output_token_ids), finish=finish)
        self._update_queue_metrics()
        return outputs

    def _ms_try_extend(self, inflight: Dict[str, Any]
                       ) -> Optional[Dict[str, Any]]:
        """Dispatch the in-flight block's successor speculatively (before the
        in-flight tokens are known): last ids come from the device array,
        positions advance by K, fresh blocks are pre-allocated.  Returns the
        new in-flight record, or None when the pipeline must drain (new
        arrivals, rejections, allocation failure, or every request ending
        within the current block)."""
        if self._rejected or self.scheduler.waiting:
            return None
        if self.kv_connector is not None and self.kv_connector.has_pending():
            return None
        scheduled, K = inflight["scheduled"], inflight["K"]
        meta = inflight["meta"]
        rows = inflight["rows"]
        max_len = self.model_config.max_model_len
        live = 0
        for s, sr in zip(rows, scheduled):
            req = sr.request
            if req.state is not RequestState.RUNNING:
                continue
            if req.deadline_expired():
                # Drain the pipeline so the next step's schedule() pass
                # evicts the expired request and frees its blocks.
                return None
            if int(meta["pos0"][s]) + 2 * K >= max_len:
                return None
            if int(meta["gen0"][s]) + K < req.sampling.max_tokens:
                live += 1
        if live == 0:
            return None     # everything finishes within the in-flight block
        # Pre-allocate blocks covering the successor's K tokens.  Requests
        # certain to finish (by length) inside the in-flight block get no
        # allocation — they become pad rows below, so memory pressure from
        # their dying breath can't drain the pipeline.
        finishing = [int(meta["gen0"][s]) + K >= sr.request.sampling.max_tokens
                     for s, sr in zip(rows, scheduled)]
        allocated: List[Tuple[Request, List[int]]] = []
        for (s, sr), fin in zip(zip(rows, scheduled), finishing):
            req = sr.request
            if req.state is not RequestState.RUNNING or fin:
                continue
            ok = self.kv_manager.allocate(req, int(meta["pos0"][s]) + 2 * K)
            if ok is None:
                for r, blocks in reversed(allocated):
                    self.kv_manager.release_tail(r, blocks)
                return None
            allocated.append((req, ok))

        bt = meta["block_tables"]
        next_bt = bt
        next_active = meta["active"]
        for (s, sr), fin in zip(zip(rows, scheduled), finishing):
            if sr.request.state is not RequestState.RUNNING or fin:
                # Requests that stopped in an earlier retire — or that will
                # stop at their length limit in the in-flight block — become
                # pad rows: seq_len 0 (no attention), trash-block writes.
                if next_active is meta["active"]:
                    next_active = next_active.copy()
                next_active[s] = False
                continue
            local = np.asarray(sr.request.block_ids, np.int32) \
                - self._block_offset(sr.request)
            nb = len(local)
            if nb and bt[s, nb - 1] != local[-1]:
                if next_bt is bt:
                    next_bt = bt.copy()
                next_bt[s, :nb] = local
        last_dev = inflight["ids_dev"][K - 1]      # device array, no sync
        next_meta = dict(
            meta,
            last_ids=last_dev,
            pos0=meta["pos0"] + np.int32(K),
            gen0=meta["gen0"] + np.int32(K),
            block_tables=next_bt,
            active=next_active)
        return self._ms_dispatch(next_meta, scheduled, K, rows)

    def _run_multistep(self, sched: SchedulerOutput, K: int) -> List[RequestOutput]:
        meta, ordered, rows = self._ms_meta(sched.scheduled)
        return self._ms_retire(self._ms_dispatch(meta, ordered, K, rows))

    # ---------- speculative decode (MTP draft-and-verify) ----------

    def _spec_lookahead(self, req: Request) -> int:
        """Draft tokens worth scheduling for this decode entry (the
        scheduler's spec callback): fresh drafts only, depth from the
        acceptance tracker's adaptive K, capped so the DISPATCH — all
        num_scheduler_steps fused rounds, each advancing up to k+1
        tokens before the next host look — can neither run past
        max_model_len nor draft beyond the request's own max_tokens
        (those verify FLOPs could never emit).  Logprobs rows draft
        like any other since round 16 (the fused program scores the
        whole verify stride); only do_remote_decode rows demote, and
        that demotion is counted."""
        sp = req.sampling
        if req.do_remote_decode:
            self._disable_feature("spec_decode", "do_remote_decode")
            return 0
        if req.spec_drafts_at != req.num_tokens or not req.spec_drafts:
            return 0                      # stale or absent: plain decode
        rounds = max(1, self.config.num_scheduler_steps)
        k = min(self.spec_tracker.suggest_k(req.request_id),
                len(req.spec_drafts), self.spec_k)
        k = min(k, (self.model_config.max_model_len - req.num_tokens)
                // rounds - 1)
        k = min(k, sp.max_tokens - len(req.output_token_ids) - 1)
        return max(0, k)

    def _build_fused_fn(self, K: int, want_logprobs: bool = False,
                        want_top: bool = False):
        """ONE mixed-round device program: prefill-chunk rows, plain-decode
        rows and K+1 draft-verify rows share a single forward (the ragged
        chunked-prefill batch layout), so a prefill chunk joining a decode
        round rides the SAME per-layer expert-weight stream the decode
        already pays — the HBM weight traffic is amortized over both
        populations (the MoE prefill-MFU lever), and spec decode stays ON
        under continuous prefill traffic.

        Per-row dispatch happens via the batch's fixed [S*(K+1)] verify-
        stride ``sample_idx``: a decode row gathers its 1+nd computed
        positions (tail replicated), so spec_verify accepts/rejects and
        samples the bonus exactly as the pure-spec program did; a prefill
        row replicates its chunk's LAST position into every slot, so
        spec_n=0 makes verification degenerate to classic first-token
        sampling at slot 0 (seeded rows: fold_in(seed, gen0=0) == the
        classic path's fold_in(seed, gen_idx) — byte-identical parity),
        and mid-prefill rows' slot-0 samples are simply discarded host-
        side.  The drafter proposes next-step drafts for EVERY row from
        its accepted position's hidden state — prefill-completing rows
        therefore enter their first decode step already spec-armed.
        Round 16 composition: the same program serves the STACKED
        [dp, S_l] layout (leading dims flattened shard-major before
        verify, exactly like the classic step fn), collects routed
        expert ids for EPLB when it is armed, and scores EVERY verify-
        stride position when logprobs are wanted (verify_logprobs) —
        the host slices the accepted prefix after the fetch, so
        logprobs rows draft like any other and _spec_lookahead's old
        demotion is gone.  ``want_logprobs``/``want_top`` variants are
        cached like _step_fn/_step_fn_top.  Only ids, accepted counts,
        drafts and the optional logprob arrays travel host-ward — in
        the step's one batched fetch, never a new sync."""
        c = self.model_config
        block_size = self.config.block_size
        backend = self.config.attn_backend
        model, mesh = self.model, self.mesh
        moe_opts = self._moe_opts()
        fixed = self.config.spec_fixed_accept
        Qv = K + 1
        collect_routed = self.eplb is not None

        @functools.partial(jax.jit, donate_argnums=(2,))
        def fused_fn(params, draft_params, kv_cache, batch, rng):
            if collect_routed:
                hidden, kv_cache, routed = model.forward(
                    params, kv_cache, batch, c, block_size, backend,
                    mesh=mesh, collect_routed=True, moe_opts=moe_opts)
            else:
                hidden, kv_cache = model.forward(
                    params, kv_cache, batch, c, block_size, backend,
                    mesh=mesh, moe_opts=moe_opts)   # [S*Qv, D]
                routed = None
            logits = model.compute_logits(params, hidden, c)
            if logits.ndim == 3:
                # Stacked (SPMD dp): flatten [dp, S_l*Qv, V] ->
                # [dp*S_l*Qv, V]; the per-row verify fields flatten the
                # same shard-major way, so flat verify row s*Qv + q of
                # flat sequence s = shard*S_l + i stays aligned.
                logits = logits.reshape(-1, logits.shape[-1])
                batch = dict(batch, draft_tokens=(
                    batch["draft_tokens"].reshape(-1, K)), **{
                        k: batch[k].reshape(-1)
                        for k in ("temperature", "top_k", "top_p",
                                  "seeds", "gen0", "spec_n")})
            ids, accepted = sampling_ops.spec_verify(
                logits, batch["draft_tokens"], batch["spec_n"],
                batch["temperature"], batch["top_k"], batch["top_p"],
                rng, seeds=batch["seeds"], gen0=batch["gen0"],
                fixed_accept=fixed, step=batch["spec_step"])
            S = accepted.shape[0]
            h = hidden.reshape(-1, hidden.shape[-1]).reshape(
                S, Qv, hidden.shape[-1])
            h_a = jnp.take_along_axis(
                h, accepted[:, None, None], axis=1)[:, 0]
            bonus = jnp.take_along_axis(ids, accepted[:, None], axis=1)[:, 0]
            drafts = model.draft_propose(
                params, draft_params, h_a, bonus, K, c)
            logprobs = top = None
            if want_top:
                logprobs, top_ids, top_lps = sampling_ops.verify_logprobs(
                    logits, ids, top_n=20)
                top = (top_ids, top_lps)
            elif want_logprobs:
                logprobs = sampling_ops.verify_logprobs(logits, ids)
            return ids, accepted, drafts, logprobs, top, routed, kv_cache

        return fused_fn

    def _empty_fused_np(self, T: int, S: int, Q: int, B: int
                        ) -> Dict[str, np.ndarray]:
        arrs = self._empty_batch_np(T, S, Q, B)
        del arrs["gen_idx"]     # spec_verify consumes gen0 + verify fields
        K = self.spec_k
        arrs["sample_idx"] = np.zeros(S * (K + 1), np.int32)
        arrs["gen0"] = np.zeros(S, np.int32)
        arrs["draft_tokens"] = np.zeros((S, K), np.int32)
        arrs["spec_n"] = np.zeros(S, np.int32)
        return arrs

    def _fill_fused_batch(self, arrs: Dict[str, np.ndarray], scheduled,
                          block_offset: int = 0) -> None:
        """Fill one (shard's) fused mixed-round arrays: the ragged
        chunked-prefill token layout (each row packs its real length — a
        prefill chunk's n tokens, or a decode row's last-accepted token
        + nd drafts) plus a FIXED [S*(K+1)] verify-stride ``sample_idx``
        feeding spec_verify whatever the row mix is, so one compiled
        program per (T, S, Q) bucket covers pure-prefill, pure-decode
        and mixed rounds alike.

        Per-row gather: decode row slots q map to token t0+min(q, nd)
        (its computed positions, tail replicated — consumed slots q <= nd
        always see real logits; slots past nd are masked by spec_n inside
        spec_verify); prefill rows replicate the chunk's LAST token into
        all slots (slot 0 is the classic first-token sample; the rest
        feed nothing).  Padding rows gather token 0 and carry spec_n=0 /
        temperature 0 — their samples are discarded host-side.
        ``block_offset`` rebases global block ids to shard-local ones
        (stacked mode; 0 on the single-mesh path)."""
        cfg = self.config
        K = self.spec_k
        Qv = K + 1
        bs = cfg.block_size
        t = 0
        for s, sr in enumerate(scheduled):
            req, n = sr.request, sr.num_new_tokens
            nd = sr.num_draft_tokens
            n_row = n + nd
            p0 = req.num_computed_tokens
            if nd:
                # Decode row: last accepted token + the live drafts.
                arrs["token_ids"][t] = req.all_token_ids[p0]
                arrs["token_ids"][t + 1:t + n_row] = req.spec_drafts[:nd]
                arrs["draft_tokens"][s, :nd] = req.spec_drafts[:nd]
            else:
                # Plain decode (n == 1) or prefill chunk: real tokens.
                arrs["token_ids"][t:t + n_row] = \
                    req.all_token_ids[p0:p0 + n]
            pos = np.arange(p0, p0 + n_row)
            arrs["positions"][t:t + n_row] = pos
            arrs["token_seq_ids"][t:t + n_row] = s
            arrs["token_qpos"][t:t + n_row] = np.arange(n_row)
            blocks = np.asarray(req.block_ids, np.int32) - block_offset
            arrs["slot_mapping"][t:t + n_row] = \
                blocks[pos // bs] * bs + pos % bs
            arrs["block_tables"][s, :len(blocks)] = blocks
            arrs["seq_lens"][s] = p0 + n_row
            arrs["qtok_idx"][s, :n_row] = np.arange(t, t + n_row)
            if nd:
                arrs["sample_idx"][s * Qv:(s + 1) * Qv] = \
                    t + np.minimum(np.arange(Qv), nd)
            else:
                arrs["sample_idx"][s * Qv:(s + 1) * Qv] = t + n - 1
            sp = req.sampling
            arrs["temperature"][s] = sp.temperature
            arrs["top_k"][s] = sp.top_k
            arrs["top_p"][s] = sp.top_p
            if sp.seed is not None:
                arrs["seeds"][s] = int(sp.seed) & 0x7FFFFFFF
            arrs["gen0"][s] = len(req.output_token_ids)
            arrs["spec_n"][s] = nd
            t += n_row

    def _build_fused_batch(self, scheduled) -> Tuple[
            Dict[str, Any], List, np.ndarray, np.ndarray, int]:
        """Device batch for a fused mixed round, single-mesh or STACKED.

        Returns (batch, scheduled_flat, rows, tok_offs, T_flat):
        ``rows[i]`` is entry i's flat sample-row index (shard*S_l + s in
        stacked mode) and ``tok_offs[i]`` its first flat token index —
        what the retire loop and EPLB's accepted-aware valid mask key
        on.  Stacked mode groups requests by KV shard like
        _build_batch, pads every shard to common [T_l]/[S_l] buckets
        and rebases block ids shard-locally; per-row rollback
        (trim_request) stays shard-local because block ids on the
        request are global and only the device copy is rebased."""
        cfg = self.config
        B = self.max_blocks_per_seq
        max_q = max((sr.num_new_tokens + sr.num_draft_tokens
                     for sr in scheduled), default=1)
        Q = 1 if max_q == 1 else _next_bucket(
            max_q, cfg.min_token_bucket, cfg.max_num_batched_tokens)

        if self.dp == 1:
            S = _next_bucket(len(scheduled),
                             min(cfg.min_seq_bucket, cfg.max_num_seqs),
                             cfg.max_num_seqs)
            total = sum(sr.num_new_tokens + sr.num_draft_tokens
                        for sr in scheduled)
            # Drafts are budgeted like real tokens (scheduler charges
            # n + spec_n), so total <= max_num_batched_tokens holds.
            T = _next_bucket(total, cfg.min_token_bucket,
                             cfg.max_num_batched_tokens)
            arrs = self._empty_fused_np(T, S, Q, B)
            self._fill_fused_batch(arrs, scheduled)
            arrs["spec_step"] = np.int32(self._step_count)
            batch = jax.device_put(arrs, self._replicated)
            offs = np.cumsum([0] + [sr.num_new_tokens + sr.num_draft_tokens
                                    for sr in scheduled[:-1]])
            return (batch, list(scheduled), np.arange(len(scheduled)),
                    offs.astype(np.int64), T)

        per = self._split_by_shard(scheduled)
        T_l = _next_bucket(
            max(sum(sr.num_new_tokens + sr.num_draft_tokens
                    for sr in shard) for shard in per),
            cfg.min_token_bucket, cfg.max_num_batched_tokens)
        S_l = _next_bucket(
            max(len(shard) for shard in per),
            min(cfg.min_seq_bucket, cfg.max_num_seqs), cfg.max_num_seqs)
        B_l = self.kv_manager.blocks_per_region
        shard_arrs = []
        scheduled_flat: List = []
        rows: List[int] = []
        tok_offs: List[int] = []
        for r, shard in enumerate(per):
            arrs = self._empty_fused_np(T_l, S_l, Q, B)
            self._fill_fused_batch(arrs, shard, block_offset=r * B_l)
            shard_arrs.append(arrs)
            scheduled_flat.extend(shard)
            rows.extend(r * S_l + s for s in range(len(shard)))
            t = 0
            for sr in shard:
                tok_offs.append(r * T_l + t)
                t += sr.num_new_tokens + sr.num_draft_tokens
        stacked_np = {k: np.stack([a[k] for a in shard_arrs])
                      for k in shard_arrs[0]}
        stacked_np["spec_step"] = np.int32(self._step_count)
        batch = {k: jax.device_put(
                     v, self._dp_sharded if np.ndim(v) else self._replicated)
                 for k, v in stacked_np.items()}
        return (batch, scheduled_flat, np.asarray(rows, np.int64),
                np.asarray(tok_offs, np.int64), self.dp * T_l)

    def _run_fused(self, sched: SchedulerOutput) -> List[RequestOutput]:
        """One fused mixed-round engine step (ANY row mix once spec decode
        is armed: pure decode, pure prefill, or both in one program).

        Decode rows emit 1..K+1 tokens (accepted drafts + correction/
        bonus) and roll rejected tokens' tail blocks back to the pool the
        same step (kv_cache.trim_request — the prefix cache only ever
        hashes blocks full of ACCEPTED content, so PR 9 restores always
        land on a clean prefix).  Prefill rows advance their chunk with
        the classic bookkeeping (TTFT / prompt / prefix counters, the
        engine.prefill phase, PD-producer finish) and, when the chunk
        completes the prompt, emit slot-0's sampled first token AND store
        the device-proposed drafts — the request enters its first decode
        step already spec-armed, so speculation never blinks across
        prefill joins.  Logprobs rows take the classic sampling epilogue
        (slot-0 logprob arrays from the fused program's variant) without
        demoting any other row."""
        scheduled = sched.scheduled
        want_top = any((sr.request.sampling.logprobs or 0) > 0
                       for sr in scheduled)
        want_lp = any(sr.request.sampling.logprobs is not None
                      for sr in scheduled)
        fn = self._fused_fns.get((want_lp, want_top))
        if fn is None:
            fn = self._build_fused_fn(self.spec_k, want_logprobs=want_lp,
                                      want_top=want_top)
            self._fused_fns[(want_lp, want_top)] = fn
        batch, scheduled, rows, tok_offs, t_flat = \
            self._build_fused_batch(scheduled)
        step_t0 = self._clock.mark("dispatch")
        self._rng, step_key = jax.random.split(self._rng)
        (ids_dev, acc_dev, drafts_dev, lp_dev, top_dev, routed_dev,
         self.kv_cache) = fn(
            self.params, self.draft_params, self.kv_cache, batch, step_key)
        self._dispatch_count += 1
        self.metrics.engine_dispatches.inc()
        # ONE batched fetch, exactly like the classic step's: ids +
        # accepted counts + next drafts (+ optional logprob arrays) in a
        # single host fetch.
        fetch = [ids_dev, acc_dev, drafts_dev] \
            + ([lp_dev] if want_lp else []) \
            + (list(top_dev) if top_dev is not None else [])
        self._clock.mark("fetch")
        # llmd: ignore[JIT] the one intended fused-step host sync (batched)
        fetched = jax.device_get(fetch)
        now = self._clock.mark("post")
        ids = np.asarray(fetched[0])
        accepted = np.asarray(fetched[1])
        drafts = np.asarray(fetched[2])
        logprobs = np.asarray(fetched[3]) if want_lp else None
        top = (np.asarray(fetched[-2]), np.asarray(fetched[-1])) \
            if top_dev is not None else None
        self._step_count += 1
        self.metrics.engine_steps.inc()
        if self.eplb is not None and routed_dev is not None:
            # Accepted-aware valid-token mask: a decode row's verify
            # stride keeps its accepted prefix (+ the bonus slot) only —
            # rejected drafts' routing must not skew the balance stats,
            # exactly as their KV is trimmed — and prefill rows keep
            # their real chunk tokens; shard pad tokens stay masked.
            valid = np.zeros(t_flat, bool)
            for i, sr in enumerate(scheduled):
                off = int(tok_offs[i])
                if sr.num_draft_tokens:
                    a = min(int(accepted[int(rows[i])]),
                            sr.num_draft_tokens)
                    valid[off:off + a + 1] = True
                else:
                    valid[off:off + sr.num_new_tokens] = True
            self.params = self.eplb.on_step(
                routed_dev[:, valid, :], self._step_count,
                self.params, self.mesh)

        outputs: List[RequestOutput] = []
        total_drafted = total_accepted = 0
        for i, sr in enumerate(scheduled):
            s = int(rows[i])
            req, n = sr.request, sr.num_new_tokens
            nd = sr.num_draft_tokens
            # A TRUE decode entry has sampled at least one output token:
            # without the output_token_ids check a 1-token final prefill
            # chunk (1-token prompt, or a prompt that chunks to a 1-token
            # tail) is indistinguishable from decode and would skip the
            # first-token bookkeeping (TTFT, prompt/prefix counters, the
            # engine.prefill trace phase).
            is_decode = (n == 1 and bool(req.output_token_ids)
                         and req.num_computed_tokens == req.num_tokens - 1
                         and not req.do_remote_decode)
            # All n+nd scheduled rows computed (and crossed the EP wire)
            # whatever the verifier kept.
            self._account_collective_bytes(n + nd)
            if not is_decode:
                # ---- prefill chunk (classic bookkeeping) ----
                req.num_computed_tokens += n
                produced_token = req.num_computed_tokens == req.num_tokens
                self.kv_manager.cache_full_blocks(req)
                if not produced_token:
                    continue          # mid-prefill chunk: sample discarded
                if req.num_computed_tokens <= req.num_prompt_tokens:
                    # Prefill just completed.
                    self.metrics.prompt_tokens.inc(req.num_prompt_tokens)
                    if req.num_cached_prompt_tokens:
                        self.metrics.prefix_cache_hits.inc(
                            req.num_cached_prompt_tokens)
                    self.metrics.prefix_cache_queries.inc(
                        req.num_prompt_tokens)
                    if req.first_token_time is None:
                        req.first_token_time = now
                        self.metrics.time_to_first_token.observe(
                            now - req.arrival_time)
                        self._trace_phase(
                            req, "engine.prefill",
                            "first_decode" if req.do_remote_prefill
                            else "prefill",
                            req.first_schedule_time or req.arrival_time,
                            now,
                            cached_tokens=req.num_cached_prompt_tokens
                            or None,
                            resume_offset=req.resume_offset or None,
                            restored_tokens=req.resume_restored_tokens
                            or None)
                    if req.do_remote_decode:
                        # PD producer: stop here, pin blocks, publish
                        # transfer params.
                        outputs.append(self._finish_remote_prefill(
                            req, int(ids[s, 0])))
                        continue
                else:
                    if req.last_token_time is not None:
                        self.metrics.inter_token_latency.observe(
                            now - req.last_token_time)
                req.last_token_time = now
                token = int(ids[s, 0])
                req.output_token_ids.append(token)
                self.metrics.generation_tokens.inc()
                finish = self._check_stop(req, token)
                top_lp = None
                if (req.sampling.logprobs or 0) > 0 and top is not None:
                    n_top = min(int(req.sampling.logprobs),
                                top[0].shape[-1])
                    top_lp = [{int(top[0][s, 0, j]): float(top[1][s, 0, j])
                               for j in range(n_top)}]
                outputs.append(RequestOutput(
                    req.request_id, [token], finish is not None,
                    finish_reason=finish,
                    logprobs=([float(logprobs[s, 0])]
                              if req.sampling.logprobs is not None
                              else None),
                    top_logprobs=top_lp))
                if finish is not None:
                    self.scheduler.finish(req, RequestState(finish))
                    self._spec_forget(req.request_id)
                    self.metrics.request_success.labels(
                        model_name=self.metrics.model_name,
                        finished_reason=finish).inc()
                    self.metrics.e2e_request_latency.observe(
                        now - req.arrival_time)
                    self._trace_phase(
                        req, "engine.decode", "decode",
                        req.first_token_time or now, now,
                        n_tokens=len(req.output_token_ids), finish=finish)
                else:
                    # The fused program drafted from this row's sampled
                    # first token — the request's next (decode) step runs
                    # spec-armed immediately instead of one plain round.
                    req.spec_drafts = [int(tk) for tk in drafts[s]]
                    req.spec_drafts_at = req.num_tokens
                continue
            # ---- decode row (draft-and-verify bookkeeping) ----
            a = min(int(accepted[s]), nd)
            total_drafted += nd
            total_accepted += a
            req.spec_drafted += nd
            req.spec_accepted += a
            if nd:
                self.metrics.spec_draft_tokens.inc(nd)
                if a:
                    self.metrics.spec_accepted_tokens.inc(a)
                self.spec_tracker.observe(req.request_id, nd, a)
            new_tokens: List[int] = []
            finish = None
            for q in range(a + 1):
                token = int(ids[s, q])
                req.num_computed_tokens += 1
                req.output_token_ids.append(token)
                new_tokens.append(token)
                finish = self._check_stop(req, token)
                if finish is not None:
                    break               # tokens past a stop are discarded
            self.metrics.generation_tokens.inc(len(new_tokens))
            if req.last_token_time is not None:
                self.metrics.inter_token_latency.observe(
                    (now - req.last_token_time) / max(1, len(new_tokens)))
            req.last_token_time = now
            # Next step's drafts (device-proposed); the tag invalidates
            # them if any non-spec path appends tokens first.  The
            # adaptive depth is read fresh from the tracker at the next
            # schedule pass (_spec_lookahead), not cached on the request.
            req.spec_drafts = [int(tk) for tk in drafts[s]]
            req.spec_drafts_at = req.num_tokens
            self.kv_manager.cache_full_blocks(req)
            # Per-position logprobs over the verify stride (round 16):
            # a drafting row emits its accepted prefix's logprobs — one
            # float (and one top-N dict) per emitted token — sliced from
            # the [S, K+1] stride arrays the fused program scored; the
            # rejected tail is simply never read.
            top_lp = None
            if (req.sampling.logprobs or 0) > 0 and top is not None:
                n_top = min(int(req.sampling.logprobs), top[0].shape[-1])
                top_lp = [{int(top[0][s, q, j]): float(top[1][s, q, j])
                           for j in range(n_top)}
                          for q in range(len(new_tokens))]
            outputs.append(RequestOutput(
                req.request_id, new_tokens, finish is not None,
                finish_reason=finish,
                logprobs=([float(logprobs[s, q])
                           for q in range(len(new_tokens))]
                          if logprobs is not None
                          and req.sampling.logprobs is not None
                          else None),
                top_logprobs=top_lp))
            if finish is not None:
                self.scheduler.finish(req, RequestState(finish))
                self._spec_forget(req.request_id)
                self.metrics.request_success.labels(
                    model_name=self.metrics.model_name,
                    finished_reason=finish).inc()
                self.metrics.e2e_request_latency.observe(
                    now - req.arrival_time)
                self._trace_phase(
                    req, "engine.decode", "decode",
                    req.first_token_time or now, now,
                    n_tokens=len(req.output_token_ids), finish=finish)
            else:
                # Rejection rollback: tail blocks past the accepted
                # content (plus the pending token's slot) return to the
                # pool THIS step.
                self.kv_manager.trim_request(req, req.num_tokens)
        # Step composition: decode load includes the verify rows (they
        # cost compute like real tokens); everything here is host-side
        # arithmetic over scheduler metadata — no new syncs.
        decode_load = sched.decode_tokens + sched.spec_tokens
        if sched.prefill_tokens:
            self.metrics.step_prefill_tokens.inc(sched.prefill_tokens)
        if decode_load:
            self.metrics.step_decode_tokens.inc(decode_load)
        self.step_time_model.observe(
            sched.prefill_tokens, decode_load, (now - step_t0) * 1e3)
        # (the tokens a row was scheduled, drafts included, ending at the
        # context it kept)
        self._note_step(step_t0, now, [sr.request for sr in scheduled],
                        sched.prefill_tokens, decode_load, fused=True,
                        prefill_ahead_tokens=sched.prefill_ahead_tokens,
                        spec=True, drafted=total_drafted,
                        accepted=total_accepted,
                        kv=self._kv_counts(
                            [sr.request.num_computed_tokens
                             for sr in scheduled],
                            [sr.num_new_tokens for sr in scheduled]))
        self._update_queue_metrics()
        return outputs

    # ---------- fused multistep (N mixed rounds per dispatch) ----------

    def _build_fused_multistep_fn(self, want_logprobs: bool = False,
                                  want_top: bool = False):
        """N fused mixed rounds as ONE device program (a ``lax.scan``
        over the PR 15 mixed round): spec draft state, the per-row
        position (the KV write/rollback head), sampling continuity
        (gen0, per-round fold keys) and chunk progress all carry ON
        DEVICE between rounds, so the engine pays one dispatch and one
        host fetch per N rounds instead of per step (NanoFlow-style:
        keep the resident program fed rather than the host in the
        loop).

        Row layout is the fused round's [S] (or stacked [dp, S_l]) with
        a FIXED per-row token stride for all N rounds: a decode row's
        stride is 1+nd (last accepted token + nd draft slots); a
        prefill row's is its round-0 chunk size — later rounds reuse
        the same slots for the next chunk, and once the prompt
        completes the row's remaining rounds run as decode with up to
        min(K, stride-1) drafts in the same slots (unused slots write
        block-0 trash, the multistep pad idiom).  Everything host-
        knowable is precomputed into ``xs`` [N, ...] (chunk tokens /
        positions / slots, verify sample_idx, per-round spec_n and role
        flags); the device patches only what depends on sampled state —
        decode rows' token ids (carried last token + drafts), their
        positions/slots (from the pos carry) and seq_lens.  KV rollback
        is implicit: a rejected draft's slot is overwritten by the next
        round's write at the same position (slot = f(position) through
        the unchanged block table) and never attended (seq_lens masks
        it); the host reconciles the block list with ONE trim_request
        per row at retire.

        Returns per-round ids/accepted (+ optional verify-stride
        logprobs, + routed ids under EPLB) and the final carry — an
        async successor dispatch starts from the carry without any
        host fetch."""
        c = self.model_config
        block_size = self.config.block_size
        backend = self.config.attn_backend
        model, mesh = self.model, self.mesh
        moe_opts = self._moe_opts()
        fixed = self.config.spec_fixed_accept
        K = self.spec_k
        Qv = K + 1
        collect_routed = self.eplb is not None

        @functools.partial(jax.jit, donate_argnums=(2,))
        def fms_fn(params, draft_params, kv_cache, carry0, sbatch, xs, rng):
            stacked = sbatch["temperature"].ndim == 2
            bt = sbatch["block_tables"]
            slot_row = sbatch["slot_row"]     # [.., T_l] LOCAL row per token
            slot_q = sbatch["slot_q"]         # [.., T_l] slot within stride
            active = sbatch["active"]

            def fr(a):    # flatten rows/tokens: [dp, X, ...] -> [dp*X, ...]
                return a.reshape((-1,) + a.shape[2:]) if stacked else a

            def ur(a, like):    # restore stacked leading dims
                return (a.reshape(like.shape[:2] + a.shape[1:])
                        if stacked else a)

            def one_round(carry, per_round):
                kv_cache, pos, last, drafts, gen0 = carry
                key, x = per_round
                nd = x["spec_n"]
                is_dec = x["is_dec"]
                # Token-level patch: decode rows' content depends on
                # sampled carry; prefill chunks came precomputed in xs.
                # All gathers are along the LOCAL row axis (axis=-1 /
                # -2), so stacked shards never index across each other.
                patch = jnp.take_along_axis(is_dec, slot_row, axis=-1)
                act_t = jnp.take_along_axis(active, slot_row, axis=-1)
                nd_t = jnp.take_along_axis(nd, slot_row, axis=-1)
                last_t = jnp.take_along_axis(last, slot_row, axis=-1)
                drow = jnp.take_along_axis(
                    drafts, slot_row[..., None], axis=-2)  # [.., T_l, K]
                qi = jnp.clip(slot_q - 1, 0, max(K - 1, 0))
                draft_t = jnp.take_along_axis(
                    drow, qi[..., None], axis=-1)[..., 0]
                tok_dec = jnp.where(slot_q == 0, last_t, draft_t)
                pos_row = jnp.take_along_axis(pos, slot_row, axis=-1)
                pos_t = jnp.where(patch, pos_row + slot_q, x["positions"])
                dead = x["dead"] | (patch & (slot_q > nd_t)) | ~act_t
                rowbt = jnp.take_along_axis(
                    bt, slot_row[..., None], axis=-2)      # [.., T_l, B]
                blk = jnp.take_along_axis(
                    rowbt, (pos_t // block_size)[..., None],
                    axis=-1)[..., 0]
                slot = blk * block_size + pos_t % block_size
                slot_mapping = jnp.where(
                    dead, pos_t % block_size,   # block-0 trash writes
                    jnp.where(patch, slot, x["slot_mapping"]))
                seq_lens = jnp.where(is_dec, pos + nd + 1, x["seq_lens"])
                seq_lens = jnp.where(active, seq_lens, 0)
                batch = dict(
                    token_ids=jnp.where(patch, tok_dec, x["token_ids"]),
                    positions=pos_t, token_seq_ids=slot_row,
                    token_qpos=slot_q, slot_mapping=slot_mapping,
                    block_tables=bt, seq_lens=seq_lens,
                    sample_idx=x["sample_idx"], qtok_idx=x["qtok_idx"])
                if collect_routed:
                    hidden, kv_cache, routed = model.forward(
                        params, kv_cache, batch, c, block_size, backend,
                        mesh=mesh, collect_routed=True, moe_opts=moe_opts)
                else:
                    hidden, kv_cache = model.forward(
                        params, kv_cache, batch, c, block_size, backend,
                        mesh=mesh, moe_opts=moe_opts)
                    routed = None
                logits = model.compute_logits(params, hidden, c)
                if logits.ndim == 3:
                    logits = logits.reshape(-1, logits.shape[-1])
                ids, accepted = sampling_ops.spec_verify(
                    logits, fr(drafts), fr(nd),
                    fr(sbatch["temperature"]), fr(sbatch["top_k"]),
                    fr(sbatch["top_p"]), key, seeds=fr(sbatch["seeds"]),
                    gen0=fr(gen0), fixed_accept=fixed,
                    step=x["spec_step"])
                S = accepted.shape[0]
                h = hidden.reshape(-1, hidden.shape[-1]).reshape(
                    S, Qv, hidden.shape[-1])
                h_a = jnp.take_along_axis(
                    h, accepted[:, None, None], axis=1)[:, 0]
                bonus = jnp.take_along_axis(
                    ids, accepted[:, None], axis=1)[:, 0]
                new_drafts = model.draft_propose(
                    params, draft_params, h_a, bonus, K, c)
                # Row-state update (flat rows): a decode row advances by
                # its accepted prefix + bonus; a completing prefill row
                # emits its first token and enters decode spec-armed
                # (fresh device drafts); a mid-prompt row just moves its
                # chunk pointer; inactive rows hold state.
                is_dec_f, comp_f = fr(is_dec), fr(x["completing"])
                act_f = fr(active)
                emitted = jnp.where(
                    act_f & is_dec_f, accepted + 1,
                    jnp.where(act_f & comp_f, 1, 0))
                sampled = act_f & (is_dec_f | comp_f)
                tok_at = jnp.where(is_dec_f, accepted, 0)
                last_new = jnp.where(
                    sampled,
                    jnp.take_along_axis(ids, tok_at[:, None], axis=1)[:, 0],
                    fr(last))
                drafts_new = jnp.where(
                    sampled[:, None], new_drafts, fr(drafts))
                gen0_new = fr(gen0) + emitted
                pos_new = jnp.where(
                    act_f & is_dec_f, fr(pos) + emitted,
                    jnp.where(act_f, fr(x["next_pos"]), fr(pos)))
                carry = (kv_cache, ur(pos_new, pos), ur(last_new, last),
                         ur(drafts_new, drafts), ur(gen0_new, gen0))
                ys = dict(ids=ids, accepted=accepted)
                if want_top:
                    lp, t_ids, t_lps = sampling_ops.verify_logprobs(
                        logits, ids, top_n=20)
                    ys.update(lp=lp, top_ids=t_ids, top_lps=t_lps)
                elif want_logprobs:
                    ys["lp"] = sampling_ops.verify_logprobs(logits, ids)
                if collect_routed:
                    ys["routed"] = routed
                return carry, ys

            N = xs["spec_n"].shape[0]
            keys = jax.random.split(rng, N)
            carry0_full = (kv_cache, carry0["pos"], carry0["last"],
                           carry0["drafts"], carry0["gen0"])
            (kv_cache, pos_f, last_f, drafts_f, gen0_f), ys = jax.lax.scan(
                one_round, carry0_full, (keys, xs))
            carry_out = dict(pos=pos_f, last=last_f, drafts=drafts_f,
                             gen0=gen0_f)
            return ys, carry_out, kv_cache

        return fms_fn

    def _fms_plan(self, sched: SchedulerOutput) -> Optional[Dict[str, Any]]:
        """Plan an N-round fused dispatch from one schedule pass, or None
        to fall back to a single fused round.

        Per row: a decode entry runs N draft-verify rounds at its funded
        depth (stride 1+nd — _spec_lookahead already divided the
        max_model_len headroom by N); a prefill entry consumes its
        prompt in stride-sized chunks (round 0's chunk IS the
        scheduler-funded one, so the decode-priority chunk cap extends
        across all N rounds at the same per-round load) and, once
        complete, continues as decode with up to min(K, stride-1)
        drafts in the same token slots.  The worst-case KV tail (every
        draft accepted every round) is pre-allocated here — shard-local
        under stacked dp, since block ids live globally on the request
        — and reconciled by ONE trim_request per row at retire.  A row
        that cannot be covered (do_remote_decode, a max_model_len
        horizon, pool pressure) bails the whole plan, counted via
        engine_feature_disabled_total, rather than being demoted
        silently."""
        N = self.config.num_scheduler_steps
        scheduled = sched.scheduled
        if N <= 1 or not scheduled:
            return None
        K = self.spec_k
        max_len = self.model_config.max_model_len
        specs: List[Dict[str, Any]] = []
        for sr in scheduled:
            req, n = sr.request, sr.num_new_tokens
            nd = sr.num_draft_tokens
            if req.do_remote_decode:
                self._disable_feature("fused_multistep", "do_remote_decode")
                return None
            is_decode = (n == 1 and bool(req.output_token_ids)
                         and req.num_computed_tokens == req.num_tokens - 1)
            computed = req.num_computed_tokens
            rounds: List[Tuple[str, int]] = []
            if is_decode:
                stride = 1 + nd
                rounds = [("dec", nd)] * N
                cover = computed + N * stride
                min_emit = N
            else:
                stride = max(n, 1)
                nd_post = min(K, stride - 1)
                cover = computed
                min_emit = 0
                done = computed
                for _ in range(N):
                    left = req.num_tokens - done
                    if left > 0:
                        c_r = min(stride, left)
                        rounds.append(("chunk", c_r))
                        done += c_r
                        if done == req.num_tokens:
                            min_emit += 1       # completion emits 1
                        cover = max(cover, done)
                    else:
                        rounds.append(("dec", nd_post))
                        cover = max(cover, done + nd_post + 1)
                        done += nd_post + 1
                        min_emit += 1
            if cover > max_len:
                self._disable_feature("fused_multistep", "max_model_len")
                return None
            specs.append(dict(req=req, active=True, stride=stride,
                              rounds=rounds, cover=cover,
                              min_emit=min_emit,
                              gen0=len(req.output_token_ids)))
        allocated: List[Tuple[Request, Any]] = []
        for spec in specs:
            got = self.kv_manager.allocate(spec["req"], spec["cover"])
            if got is None:
                for r_, blocks in reversed(allocated):
                    self.kv_manager.release_tail(r_, blocks)
                self._disable_feature("fused_multistep", "kv_allocation")
                return None
            allocated.append((spec["req"], got))
        if self.dp > 1:
            shards: List[List] = [[] for _ in range(self.dp)]
            for spec in specs:
                shards[self.kv_manager.region_of_request(
                    spec["req"])].append(spec)
        else:
            shards = [specs]
        return self._fms_build(shards, N, self._step_count)

    def _fms_build(self, shards: List[List], N: int, step_base: int,
                   S_l: Optional[int] = None) -> Dict[str, Any]:
        """Host arrays for an N-round fused dispatch: per-row statics
        (sbatch — sampling params, block tables, the fixed slot_row/
        slot_q token layout), per-round precomputed content (xs,
        leading dim N) and the initial carry.  ``shards`` are per-KV-
        shard spec lists in row order; inactive specs hold their row
        slot (carry shapes are positional — a successor dispatch must
        keep the predecessor's row assignment) but contribute no
        tokens.  ``S_l`` pins the row bucket for successor dispatches
        whose carry rides over on device."""
        cfg = self.config
        K = self.spec_k
        Qv = K + 1
        B = self.max_blocks_per_seq
        bs = cfg.block_size
        dp = self.dp
        B_l = self.kv_manager.blocks_per_region if dp > 1 else 0
        if S_l is None:
            S_l = _next_bucket(max(len(sh) for sh in shards),
                               min(cfg.min_seq_bucket, cfg.max_num_seqs),
                               cfg.max_num_seqs)
        T_l = _next_bucket(
            max(sum(sp_["stride"] for sp_ in sh if sp_["active"])
                for sh in shards) or cfg.min_token_bucket,
            cfg.min_token_bucket, cfg.max_num_batched_tokens)
        max_q = max((sp_["stride"] for sh in shards for sp_ in sh
                     if sp_["active"]), default=1)
        Q = 1 if max_q == 1 else _next_bucket(
            max_q, cfg.min_token_bucket, cfg.max_num_batched_tokens)

        sb_shards, xs_shards, carry_shards = [], [], []
        specs_flat: List[Dict[str, Any]] = []
        rows: List[int] = []
        offs: List[int] = []
        for r, shard in enumerate(shards):
            sb = dict(
                temperature=np.zeros(S_l, np.float32),
                top_k=np.zeros(S_l, np.int32),
                top_p=np.ones(S_l, np.float32),
                seeds=np.full(S_l, -1, np.int32),
                block_tables=np.zeros((S_l, B), np.int32),
                active=np.zeros(S_l, bool),
                slot_row=np.zeros(T_l, np.int32),
                slot_q=np.zeros(T_l, np.int32))
            x = dict(
                token_ids=np.zeros((N, T_l), np.int32),
                positions=np.zeros((N, T_l), np.int32),
                slot_mapping=np.zeros((N, T_l), np.int32),
                dead=np.ones((N, T_l), bool),
                seq_lens=np.zeros((N, S_l), np.int32),
                sample_idx=np.zeros((N, S_l * Qv), np.int32),
                qtok_idx=np.full((N, S_l, Q), T_l, np.int32),
                spec_n=np.zeros((N, S_l), np.int32),
                is_dec=np.zeros((N, S_l), bool),
                completing=np.zeros((N, S_l), bool),
                next_pos=np.zeros((N, S_l), np.int32))
            cr = dict(pos=np.zeros(S_l, np.int32),
                      last=np.zeros(S_l, np.int32),
                      drafts=np.zeros((S_l, K), np.int32),
                      gen0=np.zeros(S_l, np.int32))
            t = 0
            for i, sp_ in enumerate(shard):
                specs_flat.append(sp_)
                rows.append(r * S_l + i)
                offs.append(r * T_l + t)
                if not sp_["active"]:
                    continue
                req = sp_["req"]
                stride = sp_["stride"]
                sampling = req.sampling
                sb["temperature"][i] = sampling.temperature
                sb["top_k"][i] = sampling.top_k
                sb["top_p"][i] = sampling.top_p
                if sampling.seed is not None:
                    sb["seeds"][i] = int(sampling.seed) & 0x7FFFFFFF
                blocks = np.asarray(req.block_ids, np.int32) - r * B_l
                sb["block_tables"][i, :len(blocks)] = blocks
                sb["active"][i] = True
                sb["slot_row"][t:t + stride] = i
                sb["slot_q"][t:t + stride] = np.arange(stride)
                cr["pos"][i] = req.num_computed_tokens
                cr["gen0"][i] = len(req.output_token_ids)
                done = req.num_computed_tokens
                if sp_["rounds"][0][0] == "dec" and req.output_token_ids:
                    cr["last"][i] = req.all_token_ids[done]
                    d = req.spec_drafts[:K]
                    cr["drafts"][i, :len(d)] = d
                for rno, (kind, val) in enumerate(sp_["rounds"]):
                    if kind == "chunk":
                        c_r = val
                        pos = np.arange(done, done + c_r)
                        x["token_ids"][rno, t:t + c_r] = \
                            req.all_token_ids[done:done + c_r]
                        x["positions"][rno, t:t + c_r] = pos
                        x["slot_mapping"][rno, t:t + c_r] = \
                            blocks[pos // bs] * bs + pos % bs
                        x["dead"][rno, t:t + c_r] = False
                        x["seq_lens"][rno, i] = done + c_r
                        x["sample_idx"][rno, i * Qv:(i + 1) * Qv] = \
                            t + c_r - 1
                        x["qtok_idx"][rno, i, :c_r] = np.arange(t, t + c_r)
                        done += c_r
                        if done == req.num_tokens:
                            x["completing"][rno, i] = True
                        x["next_pos"][rno, i] = done
                    else:
                        nd = val
                        used = nd + 1
                        x["dead"][rno, t:t + used] = False
                        x["is_dec"][rno, i] = True
                        x["spec_n"][rno, i] = nd
                        x["sample_idx"][rno, i * Qv:(i + 1) * Qv] = \
                            t + np.minimum(np.arange(Qv), nd)
                        x["qtok_idx"][rno, i, :used] = \
                            np.arange(t, t + used)
                t += stride
            sb_shards.append(sb)
            xs_shards.append(x)
            carry_shards.append(cr)
        if dp == 1:
            sbatch, xs, carry = sb_shards[0], xs_shards[0], carry_shards[0]
        else:
            sbatch = {k: np.stack([sh[k] for sh in sb_shards])
                      for k in sb_shards[0]}
            xs = {k: np.stack([sh[k] for sh in xs_shards], axis=1)
                  for k in xs_shards[0]}
            carry = {k: np.stack([sh[k] for sh in carry_shards])
                     for k in carry_shards[0]}
        xs["spec_step"] = (step_base + np.arange(N)).astype(np.int32)
        return dict(
            kind="fms", N=N, S_l=S_l, T_flat=dp * T_l,
            specs=specs_flat, rows=np.asarray(rows, np.int64),
            offs=np.asarray(offs, np.int64),
            sbatch=sbatch, xs=xs, carry=carry,
            covers={sp_["req"].request_id: sp_["cover"]
                    for sp_ in specs_flat if sp_["active"]})

    def _fms_dispatch(self, plan: Dict[str, Any],
                      carry_dev: Optional[Dict[str, Any]] = None
                      ) -> Dict[str, Any]:
        """Launch one N-round fused dispatch; returns the in-flight
        record WITHOUT synchronizing (per-round ids stay on device
        until retire).  ``carry_dev`` chains a successor straight from
        the predecessor's device carry (async double-buffering)."""
        live = [sp_ for sp_ in plan["specs"] if sp_["active"]]
        want_top = any((sp_["req"].sampling.logprobs or 0) > 0
                       for sp_ in live)
        want_lp = any(sp_["req"].sampling.logprobs is not None
                      for sp_ in live)
        fn = self._fms_fns.get((want_lp, want_top))
        if fn is None:
            fn = self._build_fused_multistep_fn(
                want_logprobs=want_lp, want_top=want_top)
            self._fms_fns[(want_lp, want_top)] = fn
        if self.dp > 1:
            xsh = NamedSharding(self.mesh, P(None, "dp"))
            sbatch = {k: jax.device_put(v, self._dp_sharded)
                      for k, v in plan["sbatch"].items()}
            xs = {k: jax.device_put(
                      v, xsh if np.ndim(v) >= 2 else self._replicated)
                  for k, v in plan["xs"].items()}
            carry0 = (carry_dev if carry_dev is not None
                      else jax.device_put(plan["carry"], self._dp_sharded))
        else:
            sbatch = jax.device_put(plan["sbatch"], self._replicated)
            xs = jax.device_put(plan["xs"], self._replicated)
            carry0 = (carry_dev if carry_dev is not None
                      else jax.device_put(plan["carry"], self._replicated))
        t0 = self._clock.mark("dispatch")
        self._rng, step_key = jax.random.split(self._rng)
        ys, carry_out, self.kv_cache = fn(
            self.params, self.draft_params, self.kv_cache, carry0,
            sbatch, xs, step_key)
        self._dispatch_count += 1
        self.metrics.engine_dispatches.inc()
        return dict(kind="fms", plan=plan, ys=ys, carry=carry_out,
                    want_lp=want_lp, want_top=want_top, t0=t0)

    def _fms_retire(self, rec: Dict[str, Any],
                    successor: Optional[Dict[str, Any]] = None
                    ) -> List[RequestOutput]:
        """Synchronize one in-flight N-round dispatch and replay its
        rounds through the per-request bookkeeping — THE one documented
        host sync per dispatch (N engine steps amortize it).  Mirrors
        _run_fused's per-row logic round by round: chunk rounds advance
        prefill (completion does the classic first-token bookkeeping),
        decode rounds walk the accepted prefix with _check_stop;
        everything computed past a stop is a zombie and is discarded,
        exactly like the classic multistep retire."""
        plan = rec["plan"]
        N = plan["N"]
        ys = rec["ys"]
        K = self.spec_k
        fetch = [ys["ids"], ys["accepted"], rec["carry"]["drafts"]]
        if rec["want_lp"] or rec["want_top"]:
            fetch.append(ys["lp"])
        if rec["want_top"]:
            fetch += [ys["top_ids"], ys["top_lps"]]
        self._clock.mark("fetch")
        # llmd: ignore[JIT] the one intended fused-multistep retire host sync
        fetched = jax.device_get(fetch)
        now = self._clock.mark("post")
        ids = np.asarray(fetched[0])          # [N, S_flat, K+1]
        acc = np.asarray(fetched[1])          # [N, S_flat]
        drafts_f = np.asarray(fetched[2]).reshape(-1, K)
        lp = (np.asarray(fetched[3])
              if rec["want_lp"] or rec["want_top"] else None)
        top = ((np.asarray(fetched[-2]), np.asarray(fetched[-1]))
               if rec["want_top"] else None)
        self._step_count += N
        self.metrics.engine_steps.inc(N)

        outputs: List[RequestOutput] = []
        total_drafted = total_accepted = 0
        pre_toks = dec_toks = 0
        valid = (np.zeros((N, plan["T_flat"]), bool)
                 if self.eplb is not None and "routed" in ys else None)
        for sp_, row, off in zip(plan["specs"], plan["rows"],
                                 plan["offs"]):
            if not sp_["active"]:
                continue
            req = sp_["req"]
            s, off = int(row), int(off)
            # The device computed every round for this row whatever the
            # verifier kept or where a stop lands — charge it all.
            self._account_collective_bytes(
                sum(v if k == "chunk" else v + 1
                    for k, v in sp_["rounds"]))
            pre_toks += sum(v for k, v in sp_["rounds"] if k == "chunk")
            dec_toks += sum(v + 1 for k, v in sp_["rounds"] if k == "dec")
            if req.state is not RequestState.RUNNING:
                continue    # zombie: finished in an earlier retire
            new_tokens: List[int] = []
            lp_list: List[float] = []
            top_at: List[Tuple[int, int]] = []
            finish = None
            for rno, (kind, val) in enumerate(sp_["rounds"]):
                if finish is not None:
                    break
                if kind == "chunk":
                    req.num_computed_tokens += val
                    if valid is not None:
                        valid[rno, off:off + val] = True
                    if req.num_computed_tokens != req.num_tokens:
                        continue          # mid-prompt round
                    if req.num_computed_tokens <= req.num_prompt_tokens:
                        # Prefill just completed.
                        self.metrics.prompt_tokens.inc(
                            req.num_prompt_tokens)
                        if req.num_cached_prompt_tokens:
                            self.metrics.prefix_cache_hits.inc(
                                req.num_cached_prompt_tokens)
                        self.metrics.prefix_cache_queries.inc(
                            req.num_prompt_tokens)
                        if req.first_token_time is None:
                            req.first_token_time = now
                            self.metrics.time_to_first_token.observe(
                                now - req.arrival_time)
                            self._trace_phase(
                                req, "engine.prefill",
                                "first_decode" if req.do_remote_prefill
                                else "prefill",
                                req.first_schedule_time
                                or req.arrival_time, now,
                                cached_tokens=req.num_cached_prompt_tokens
                                or None,
                                resume_offset=req.resume_offset or None,
                                restored_tokens=req.resume_restored_tokens
                                or None)
                    token = int(ids[rno, s, 0])
                    req.output_token_ids.append(token)
                    new_tokens.append(token)
                    if lp is not None:
                        lp_list.append(float(lp[rno, s, 0]))
                    top_at.append((rno, 0))
                    finish = self._check_stop(req, token)
                else:
                    nd = val
                    a = min(int(acc[rno, s]), nd)
                    if valid is not None:
                        # Accepted prefix + bonus slot only: rejected
                        # drafts' routing must not skew EPLB's balance
                        # stats, exactly as their KV is trimmed.
                        valid[rno, off:off + a + 1] = True
                    total_drafted += nd
                    total_accepted += a
                    req.spec_drafted += nd
                    req.spec_accepted += a
                    if nd:
                        self.metrics.spec_draft_tokens.inc(nd)
                        if a:
                            self.metrics.spec_accepted_tokens.inc(a)
                        self.spec_tracker.observe(req.request_id, nd, a)
                    for q in range(a + 1):
                        token = int(ids[rno, s, q])
                        req.num_computed_tokens += 1
                        req.output_token_ids.append(token)
                        new_tokens.append(token)
                        if lp is not None:
                            lp_list.append(float(lp[rno, s, q]))
                        top_at.append((rno, q))
                        finish = self._check_stop(req, token)
                        if finish is not None:
                            break
            self.metrics.generation_tokens.inc(len(new_tokens))
            if new_tokens:
                if req.last_token_time is not None:
                    self.metrics.inter_token_latency.observe(
                        (now - req.last_token_time) / len(new_tokens))
                req.last_token_time = now
            # Next dispatch's drafts come from the FINAL carry.
            req.spec_drafts = [int(tk) for tk in drafts_f[s]]
            req.spec_drafts_at = req.num_tokens
            self.kv_manager.cache_full_blocks(req)
            sampling = req.sampling
            top_lp = None
            if (sampling.logprobs or 0) > 0 and top is not None:
                n_top = min(int(sampling.logprobs), top[0].shape[-1])
                top_lp = [{int(top[0][rno, s, q, j]):
                           float(top[1][rno, s, q, j])
                           for j in range(n_top)}
                          for rno, q in top_at]
            if new_tokens:
                outputs.append(RequestOutput(
                    req.request_id, new_tokens, finish is not None,
                    finish_reason=finish,
                    logprobs=(lp_list if lp is not None
                              and sampling.logprobs is not None
                              else None),
                    top_logprobs=top_lp))
            if finish is not None:
                self.scheduler.finish(req, RequestState(finish))
                self._spec_forget(req.request_id)
                self.metrics.request_success.labels(
                    model_name=self.metrics.model_name,
                    finished_reason=finish).inc()
                self.metrics.e2e_request_latency.observe(
                    now - req.arrival_time)
                self._trace_phase(
                    req, "engine.decode", "decode",
                    req.first_token_time or now, now,
                    n_tokens=len(req.output_token_ids), finish=finish)
            else:
                # ONE rollback per dispatch: trim to the surviving
                # content — or, with a successor already in flight, to
                # ITS worst-case cover (its writes land in blocks
                # allocated past this dispatch's tail).
                keep = req.num_tokens
                if successor is not None:
                    keep = max(keep, successor["plan"]["covers"].get(
                        req.request_id, keep))
                self.kv_manager.trim_request(req, keep)
        if valid is not None:
            routed = jnp.concatenate(
                [ys["routed"][rno][:, np.flatnonzero(valid[rno]), :]
                 for rno in range(N)], axis=1)
            self.params = self.eplb.on_step(
                routed, self._step_count, self.params, self.mesh)
        if pre_toks:
            self.metrics.step_prefill_tokens.inc(pre_toks)
        if dec_toks:
            self.metrics.step_decode_tokens.inc(dec_toks)
        # Amortized per-round sample: pairs with chunk_for(rounds=N) so
        # LLMD_PREFILL_CHUNK=auto sizes chunks against the per-round
        # budget, not the whole dispatch's wall time.
        self.step_time_model.observe(
            pre_toks / N, dec_toks / N, (now - rec["t0"]) * 1e3 / N)
        live = [sp_ for sp_ in plan["specs"] if sp_["active"]]
        self._note_step(
            rec["t0"], now, [sp_["req"] for sp_ in live],
            pre_toks, dec_toks, fused=True, rounds=N, spec=True,
            drafted=total_drafted, accepted=total_accepted,
            kv=self._kv_counts(
                [sp_["req"].num_computed_tokens for sp_ in live],
                [sum(v + (kind == "dec") for kind, v in sp_["rounds"])
                 for sp_ in live]))
        self._update_queue_metrics()
        return outputs

    def _fms_try_extend(self, rec: Dict[str, Any]
                        ) -> Optional[Dict[str, Any]]:
        """Dispatch the in-flight N-round block's successor straight
        from its device carry (pos/last/drafts/gen0 never visit the
        host) — _ms_try_extend's double-buffering contract applied to
        the fused pipeline.  Successors are pure-decode; a row still
        mid-prompt, new arrivals, rejections, expired deadlines, pool
        pressure or a max_model_len horizon all drain the pipeline so
        the next step's schedule() pass re-plans."""
        if self._rejected or self.scheduler.waiting:
            return None
        if self.kv_connector is not None and self.kv_connector.has_pending():
            return None
        plan = rec["plan"]
        N = plan["N"]
        max_len = self.model_config.max_model_len
        next_specs: List[Dict[str, Any]] = []
        live = 0
        for sp_ in plan["specs"]:
            nxt = dict(sp_, active=False)
            next_specs.append(nxt)
            if not sp_["active"]:
                continue
            req = sp_["req"]
            if req.state is not RequestState.RUNNING:
                continue
            if req.deadline_expired():
                return None
            if sp_["rounds"][-1][0] != "dec":
                return None     # still mid-prompt after N rounds
            gen_min = sp_["gen0"] + sp_["min_emit"]
            if gen_min >= req.sampling.max_tokens:
                continue        # certainly finishes in flight: pad row
            nd = sp_["rounds"][-1][1]
            cover = sp_["cover"] + N * (nd + 1)
            if cover > max_len:
                return None
            nxt.update(active=True, stride=nd + 1,
                       rounds=[("dec", nd)] * N, cover=cover,
                       gen0=gen_min, min_emit=N)
            live += 1
        if live == 0:
            return None
        allocated: List[Tuple[Request, Any]] = []
        for nxt in next_specs:
            if not nxt["active"]:
                continue
            got = self.kv_manager.allocate(nxt["req"], nxt["cover"])
            if got is None:
                for r_, blocks in reversed(allocated):
                    self.kv_manager.release_tail(r_, blocks)
                return None
            allocated.append((nxt["req"], got))
        shards: List[List] = [[] for _ in range(self.dp)]
        for nxt, row in zip(next_specs, plan["rows"]):
            shards[int(row) // plan["S_l"]].append(nxt)
        nplan = self._fms_build(shards, N, self._step_count + N,
                                S_l=plan["S_l"])
        return self._fms_dispatch(nplan, carry_dev=rec["carry"])

    # ---------- public API ----------

    def add_request(self, request: Request) -> None:
        if self.block_length and (
                request.do_remote_decode or request.do_remote_prefill
                or request.kv_transfer_params or request.resume_offset):
            # A transfer or a resume would hand over a request in mid-block:
            # neither carries the block's revealed slots (ROADMAP queue B).
            logger.error(
                "request %s asks for a KV transfer or a stream resume, which "
                "a block-diffusion engine does not serve; rejecting",
                request.request_id)
            request.state = RequestState.FINISHED_ABORTED
            self._rejected.append(RequestOutput(
                request.request_id, [], True,
                finish_reason=RequestState.FINISHED_ABORTED.value))
            return
        if request.do_remote_decode and (
                self.kv_connector is None
                or getattr(self.kv_connector, "server", None) is None):
            # Producer contract needs a serving connector: without one the
            # prefill would pin blocks forever (no release pump) or kill the
            # engine loop in register_transfer's consumer-role assert.
            logger.error(
                "request %s asks for remote decode but this engine has no "
                "producer-role KV connector; rejecting", request.request_id)
            request.state = RequestState.FINISHED_ABORTED
            self._rejected.append(RequestOutput(
                request.request_id, [], True,
                finish_reason=RequestState.FINISHED_ABORTED.value))
            return
        if request.kv_transfer_params:
            if self.kv_connector is None:
                # Silent local prefill here would defeat disaggregation while
                # looking healthy; fail the request loudly instead
                # (kv_load_failure_policy:"fail" doctrine, decode.yaml:96).
                logger.error(
                    "request %s carries kv_transfer_params but no KV "
                    "connector is configured; rejecting", request.request_id)
                request.state = RequestState.FINISHED_ABORTED
                self._rejected.append(RequestOutput(
                    request.request_id, [], True,
                    finish_reason=RequestState.FINISHED_ABORTED.value))
                return
            # PD consumer: pull remote KV before the request becomes schedulable.
            self.kv_connector.start_load_kv(self, request)
            return
        self.scheduler.add_request(request)

    def _spec_forget(self, request_id: str) -> None:
        """Drop a finished request's acceptance-tracker state (no-op with
        spec off).  Called on EVERY finish path — spec retire, classic
        and fused retires, scheduler evictions, aborts — so live
        requests' EMA state is never evicted by stale entries hitting
        the tracker's bounded-table cap."""
        if self.spec_tracker is not None:
            self.spec_tracker.forget(request_id)

    def abort_request(self, request_id: str) -> None:
        self.scheduler.abort_request(request_id)
        self._spec_forget(request_id)
        # Aborting a finished remote-prefill (PD producer) must free the
        # pinned blocks, or the usable cache shrinks permanently.
        req = self.pinned_transfers.pop(request_id, None)
        if req is not None:
            self.kv_manager.free(req)
        if self.kv_connector is not None:
            # Consumer side: the request may only exist as an in-flight KV
            # pull; mark it so poll() drops instead of admitting it.
            self.kv_connector.abort(request_id)

    @property
    def step_count(self) -> int:
        """Engine steps run so far: the ``step`` of the last ``engine.step``
        span (``AsyncEngine`` stamps it on ``engine.emit``)."""
        return self._step_count

    def has_work(self) -> bool:
        if self.scheduler.has_work() or self._rejected \
                or self._inflight is not None or self._ahead is not None:
            return True
        return self.kv_connector is not None and self.kv_connector.has_pending()

    def release_pinned(self, request_id: str) -> None:
        """Producer side: transfer complete, free the pinned prefill blocks."""
        req = self.pinned_transfers.pop(request_id, None)
        if req is not None:
            self.kv_manager.free(req)

    @staticmethod
    def _mono_to_epoch(mono: float) -> float:
        """Engine-clock (monotonic) stamp -> epoch, for retroactive trace
        spans (request timestamps live on the monotonic clock)."""
        return time.time() - (time.monotonic() - mono)

    def _trace_phase(self, req: Request, name: str, phase: str,
                     start_mono: float, end_mono: float, **attrs) -> None:
        """Record one per-request phase span (no-op for untraced
        requests) and mirror it into the request_phase histogram."""
        self.metrics.observe_phase(phase, req.criticality,
                                   end_mono - start_mono)
        if req.trace_ctx is None:
            return
        self.tracer.record_span(
            name, self._mono_to_epoch(start_mono),
            self._mono_to_epoch(end_mono), parent=req.trace_ctx,
            request_id=req.request_id, phase=phase, **attrs)

    def kv_bytes_per_token_layer(self) -> int:
        """Bytes one token's KV costs per layer — the byte term bench's
        HBM-roofline accounting streams per decode step (same accounting
        the pool sizing charges)."""
        return kv_bytes_per_token(
            self.model.kv_cache_layout(self.model_config))

    # ---------- batch building ----------

    def _layout(self, T: int, S: int, Q: int, B: Optional[int] = None,
                dp: int = 1) -> BatchLayout:
        """The layout of a classic step's bucket as THIS engine packs it:
        a block's slots, a state pool's fields, a grouped cache's tables."""
        return BatchLayout(
            T, S, Q, self.max_blocks_per_seq if B is None else B, dp=dp,
            R=self.block_length or 1, state=self._has_state,
            groups=bool(self._window_blocks))

    def _empty_batch_np(self, T: int, S: int, Q: int, B: int) -> Dict[str, np.ndarray]:
        """An empty (all padded) batch: views of one fresh packed buffer."""
        layout = self._layout(T, S, Q, B)
        return layout.views(layout.new_buffer())

    def _fill_batch(self, arrs: Dict[str, np.ndarray], scheduled,
                    block_offset: int = 0) -> None:
        """Fill one (shard's) batch arrays from its scheduled requests.
        ``block_offset`` rebases global block ids to shard-local ones
        (stacked mode; 0 for the classic single-mesh path).  Array by
        array, not row by row (one numpy call a field over all rows, as
        ``_fill_block_batch``): since the loop runs a step ahead the engine
        thread computes through most of a step instead of sleeping in the
        fetch, and what it spends here it takes from the server's thread
        (PERF.md section 6, PR 33)."""
        bs = self.config.block_size
        S = len(scheduled)
        reqs = [sr.request for sr in scheduled]
        ns_l = [sr.num_new_tokens for sr in scheduled]
        starts_l = [r.num_computed_tokens for r in reqs]
        ns, starts = np.asarray(ns_l, np.int64), np.asarray(starts_l, np.int64)
        firsts = np.cumsum(ns) - ns         # a row's first flat token
        T = int(ns.sum())
        tables = arrs["block_tables"]
        toks: List[int] = []
        for s, req in enumerate(reqs):
            n, start = ns_l[s], starts_l[s]
            if n == 1:      # a decode row: no list of all its tokens
                toks.append(req.token_at(start))
            else:
                toks += req.all_token_ids[start:start + n]
            tables[s, :len(req.block_ids)] = (
                [b - block_offset for b in req.block_ids] if block_offset
                else req.block_ids)
        if T == S:          # pure decode: one token a row
            row, qpos, pos = np.arange(S), np.zeros(S, np.int64), starts
        else:
            row = np.repeat(np.arange(S), ns)
            qpos = np.arange(T) - firsts[row]
            pos = starts[row] + qpos
        arrs["token_ids"][:T] = toks
        arrs["positions"][:T] = pos
        arrs["token_seq_ids"][:T] = row
        arrs["token_qpos"][:T] = qpos
        arrs["qtok_idx"][row, qpos] = np.arange(T)
        arrs["slot_mapping"][:T] = tables[row, pos // bs] * bs + pos % bs
        arrs["seq_lens"][:S] = starts + ns
        arrs["sample_idx"][:S] = firsts + ns - 1
        sps = [r.sampling for r in reqs]
        arrs["temperature"][:S] = [sp.temperature for sp in sps]
        arrs["top_k"][:S] = [sp.top_k for sp in sps]
        arrs["top_p"][:S] = [sp.top_p for sp in sps]
        # Masked into int32: a 64-bit seed must not OverflowError the batch
        # array (and kill the engine loop for the whole server).
        arrs["seeds"][:S] = [-1 if sp.seed is None
                             else int(sp.seed) & 0x7FFFFFFF for sp in sps]
        # Tokens generated so far, the one being sampled included.
        arrs["gen_idx"][:S] = [r.num_tokens - r.num_prompt_tokens
                               for r in reqs]
        if self._has_state:
            # Each row's slot of the state pool and its chunk's place in
            # the packed batch (ops/ssm.py).
            arrs["state_slot"][:S] = [r.state_slot for r in reqs]
            arrs["query_start"][:S] = firsts
            arrs["query_len"][:S] = ns
        if self._window_blocks:
            # The window group's table, entry for entry beside the full
            # group's (0 where the window has passed), and the write slots
            # in it: a step writes only pages it holds.
            tables_w = arrs["block_tables_w"]
            for s, req in enumerate(reqs):
                tables_w[s, :len(req.window_block_ids)] = req.window_block_ids
            arrs["slot_mapping_w"][:T] = (
                tables_w[row, pos // bs] * bs + pos % bs)
            arrs["kv_group_blocks"][:] = (self.config.num_blocks,
                                          self._window_blocks)

    def _fill_block_batch(self, arrs: Dict[str, np.ndarray],
                          scheduled) -> None:
        """``_fill_batch`` for a block-diffusion engine's rows: prompt
        chunks of whole blocks, and the B slots of an open block (a
        denoising pass: known tokens, slots revealed ahead of a masked one,
        the mask token elsewhere; every slot is sampled, the masked ones may
        be revealed) or of a completed one (its commit pass).  Filled array
        by array, not row by row: 64 rows of four tokens every step would
        pay some twenty numpy calls a row (PERF.md section 6, PR 31: the
        host chain before the launch then outlasts the interpreter's 5 ms
        switch interval, and the server's thread takes turns with it)."""
        c, bs, B = self.model_config, self.config.block_size, self.block_length
        S = len(scheduled)
        reqs = [sr.request for sr in scheduled]
        ns = np.fromiter((sr.num_new_tokens for sr in scheduled), np.int64, S)
        starts = np.fromiter((r.num_computed_tokens for r in reqs),
                             np.int64, S)
        firsts = np.cumsum(ns) - ns         # a row's first flat token
        T = int(ns.sum())
        flat = np.arange(T)
        row = np.repeat(np.arange(S), ns)
        qpos = flat - firsts[row]
        pos = starts[row] + qpos
        toks: List[int] = []
        masked = np.zeros((S, B), np.int32)
        for s, (sr, req) in enumerate(zip(scheduled, reqs)):
            start, n = req.num_computed_tokens, sr.num_new_tokens
            gen = start - req.num_prompt_tokens
            known = (req.output_token_ids[gen:gen + n] if gen >= 0
                     else req.all_token_ids[start:start + n])
            toks += known
            arrs["block_tables"][s, :len(req.block_ids)] = req.block_ids
            if sr.denoise:
                ahead = [req.revealed_ahead.get(start + i)
                         for i in range(len(known), n)]
                toks += [c.mask_token_id if a is None else a[0]
                         for a in ahead]
                masked[s, len(known):] = [a is None for a in ahead]
                arrs["reveal_quota"][s] = c.diffusion_quota(req.denoise_step)
        arrs["token_ids"][:T] = toks
        arrs["positions"][:T] = pos
        arrs["token_seq_ids"][:T] = row
        arrs["token_qpos"][:T] = qpos
        arrs["qtok_idx"][row, qpos] = flat
        arrs["slot_mapping"][:T] = (
            arrs["block_tables"][row, pos // bs] * bs + pos % bs)
        arrs["seq_lens"][:S] = starts + ns
        arrs["slot_masked"][:S] = masked
        den = np.flatnonzero([sr.denoise for sr in scheduled])
        arrs["sample_idx"].reshape(-1, B)[den] = (
            firsts[den, None] + np.arange(B))
        sps = [r.sampling for r in reqs]
        arrs["temperature"][:S] = [sp.temperature for sp in sps]
        arrs["top_k"][:S] = [sp.top_k for sp in sps]
        arrs["top_p"][:S] = [sp.top_p for sp in sps]
        arrs["seeds"][:S] = [-1 if sp.seed is None
                             else int(sp.seed) & 0x7FFFFFFF for sp in sps]
        # Tokens generated before the row's first sampled slot.
        arrs["gen_idx"][:S] = np.maximum(
            starts - [r.num_prompt_tokens for r in reqs], 0)

    def _split_by_shard(self, scheduled) -> List[List]:
        per: List[List] = [[] for _ in range(self.dp)]
        for sr in scheduled:
            per[self.kv_manager.region_of_request(sr.request)].append(sr)
        return per

    def _build_batch(self, out: SchedulerOutput
                     ) -> Tuple[jax.Array, BatchLayout, List, np.ndarray]:
        """Returns (packed device batch, its layout, scheduled list, flat
        sample-row index per scheduled entry).  Stacked mode groups requests
        by their KV shard and pads every shard to common [T_l]/[S_l]
        buckets, one row of the buffer a shard."""
        cfg = self.config
        B = self.max_blocks_per_seq
        max_q = max((sr.num_new_tokens for sr in out.scheduled), default=1)
        # Per-seq query-slot bucket: 1 on pure-decode steps, else pow2.
        Q = 1 if max_q == 1 else _next_bucket(
            max_q, cfg.min_token_bucket, cfg.max_num_batched_tokens)

        if self.dp == 1:
            S_real = len(out.scheduled)
            T = _next_bucket(out.total_tokens, cfg.min_token_bucket,
                             cfg.max_num_batched_tokens)
            S = _next_bucket(S_real, min(cfg.min_seq_bucket, cfg.max_num_seqs),
                             cfg.max_num_seqs)
            layout = self._layout(T, S, Q, B)
            buf = layout.new_buffer()
            views = layout.views(buf)
            (self._fill_block_batch if self.block_length
             else self._fill_batch)(views, out.scheduled)
            scheduled, rows = out.scheduled, np.arange(S_real)
            # Per row: context at the end of the step, tokens of the step.
            ends = views["seq_lens"][:S_real]
            news = np.fromiter((sr.num_new_tokens for sr in scheduled),
                               np.int64, S_real)
        else:
            per = self._split_by_shard(out.scheduled)
            T_l = _next_bucket(
                max(sum(sr.num_new_tokens for sr in shard) for shard in per),
                cfg.min_token_bucket, cfg.max_num_batched_tokens)
            S_l = _next_bucket(
                max(len(shard) for shard in per),
                min(cfg.min_seq_bucket, cfg.max_num_seqs), cfg.max_num_seqs)
            B_l = self.kv_manager.blocks_per_region
            layout = BatchLayout(T_l, S_l, Q, B, dp=self.dp)
            buf = layout.new_buffer()
            scheduled = []
            rows = []
            valid = np.zeros(self.dp * T_l, bool)
            for r, shard in enumerate(per):
                self._fill_batch(layout.views(buf[r]), shard,
                                 block_offset=r * B_l)
                scheduled.extend(shard)
                rows.extend(r * S_l + s for s in range(len(shard)))
                n_real = sum(sr.num_new_tokens for sr in shard)
                valid[r * T_l:r * T_l + n_real] = True
            self._routed_valid = valid     # EPLB: mask pad rows per shard
            rows = np.asarray(rows, np.int32)
            news = [sr.num_new_tokens for sr in scheduled]
            ends = [sr.request.num_computed_tokens + sr.num_new_tokens
                    for sr in scheduled]
        # ONE host-to-device copy a step, made here so that it is booked
        # under ``build``, not hidden in the program call.
        packed = jax.device_put(
            buf, self._replicated if self.dp == 1 else self._dp_sharded)
        self._clock.count("h2d_copies")
        if self._window_blocks:
            kvm = self.kv_manager
            self._step_kv = self._kv_counts(
                ends, news, held_from=cfg.block_size * np.fromiter(
                    (sr.request.window_first_block for sr in scheduled),
                    np.int64, len(scheduled)))
            self._step_kv.update(
                kv_pages_full=kvm.groups[0].pages_held,
                kv_pages_full_total=kvm.groups[0].num_blocks - 1,
                kv_pages_window=kvm.groups[1].pages_held,
                kv_pages_window_total=kvm.groups[1].num_blocks - 1)
        else:
            self._step_kv = self._kv_counts(ends, news)
        if self._has_state:
            self._step_kv.update(self._state_counts(ends, news))
        if self.model_config.mixer_by_layer:
            self._step_kv.update(self._cross_decoder_counts(
                ends, [e == sr.request.num_tokens
                       for e, sr in zip(ends, scheduled)]))
        if Q > 1:
            self._step_kv.update(
                self._attn_q_counts(int(np.sum(news)), layout))
            self._step_kv.update(self._attn_k_counts(ends, news, layout))
        else:
            self._step_kv.update(self._attn_dk_counts(ends, layout))
        self._step_kv.update(self._attn_wk_counts(ends, news, layout))
        self._step_kv.update(self._idx_k_counts(ends, news, layout))
        return packed, layout, scheduled, rows

    # ---------- step ----------

    def _kv_counts(self, ends, news, held_from=None) -> Dict[str, int]:
        """What a dispatch asks of the KV cache (step_clock.py): ``news[r]``
        tokens of row r computed, one after the other or as one causal
        chunk (the same keys either way), ending at context ``ends[r]``.
        ``held_from[r]``: the first token of row r whose page the window
        layers still hold (a cache in groups by layer kind gives the pages
        before it back); None: every layer holds every token."""
        c = self.model_config
        ends = np.asarray(ends, np.int64)
        news = np.minimum(np.asarray(news, np.int64), ends)
        B = c.diffusion_block_length
        if B:
            # Block visibility: each of a block's B queries reads every key
            # to the block's end; rows hold whole blocks.
            def blocks_upto(x):      # sum of (b + 1) B over blocks b < x / B
                return x // B * (x // B + 1) // 2 * B
            full = B * int((blocks_upto(ends)
                            - blocks_upto(ends - news)).sum())
        else:
            # A full layer: queries at positions L - n .. L - 1 read p + 1
            # keys.
            full = int((news * (2 * ends - news + 1)).sum()) // 2
        # Layers whose attention every token of the step goes through, and
        # planes the rows' tokens are held in.  A stack with mixers by layer
        # (models/hybrid_decoder.py): the self-decoder's attention layers;
        # the plane ``cross_kv_layer`` writes is held too, and read by the
        # sampled rows only (``_cross_decoder_counts`` adds those reads).
        attending = planes = c.attending_layers
        if c.mixer_by_layer:
            planes = len(c.layers_of(SLIDING, FULL))
            attending = planes - 1
        counts = {"kv_ctx_tokens": attending * full,
                  "kv_read_tokens": attending * full,
                  "kv_held_tokens": planes * int(ends.sum()),
                  "kv_dead_tokens": 0}
        upto = _keys_seen_upto
        n_window = c.layer_types.count(SLIDING)
        if n_window:
            w = c.sliding_window
            windowed = int((upto(ends, w) - upto(ends - news, w)).sum())
            counts["kv_read_tokens"] -= n_window * (full - windowed)
            held = ends if held_from is None else ends - held_from
            counts["kv_held_tokens"] -= n_window * int((ends - held).sum())
            counts["kv_dead_tokens"] = n_window * int(
                np.maximum(held - w + 1, 0).sum())
        if c.index_topk:
            # Full layers score every visible key (index_pairs) and attend
            # to the index_topk best of them (kv_selected_tokens).
            n_full = c.num_layers - n_window
            k = c.index_topk
            selected = int((upto(ends, k) - upto(ends - news, k)).sum())
            counts["kv_read_tokens"] -= n_full * (full - selected)
            counts["kv_selected_tokens"] = n_full * selected
            counts["index_pairs"] = n_full * full
        return counts

    def _cross_decoder_counts(self, ends, sampled) -> Dict[str, int]:
        """What a dispatch of a decoder-hybrid-decoder stack asks of its
        cross-decoder (step_clock.py): only the rows the step samples from
        (``sampled[r]``) go through ``cross_kv_layer``'s attention and the
        layers after it, and each of those attentions (that layer's own and
        the CROSS layers') reads the row's whole context from the one shared
        plane: added to the step's ``kv_ctx_tokens`` / ``kv_read_tokens``
        here."""
        c = self.model_config
        ends = np.asarray(ends, np.int64)[np.asarray(sampled, bool)]
        reads = (1 + len(c.layers_of(CROSS))) * int(ends.sum())
        kv = self._step_kv
        return {"xdec_rows": len(ends), "xattn_read_tokens": reads,
                "kv_ctx_tokens": kv["kv_ctx_tokens"] + reads,
                "kv_read_tokens": kv["kv_read_tokens"] + reads}

    def _state_counts(self, ends, news) -> Dict[str, int]:
        """What a dispatch asks of the state pool (step_clock.py): rows
        that take the one-token update, tokens that go through the chunked
        scan, rows whose state the program zeroes (a chunk from position
        0)."""
        ends, news = np.asarray(ends, np.int64), np.asarray(news, np.int64)
        resets = int((ends == news).sum())
        self.metrics.ssm_state_resets.inc(resets)
        return {"ssm_decode_rows": int((news == 1).sum()),
                "ssm_prefill_rows": int((news > 1).sum()),
                "ssm_prefill_tokens": int(news[news > 1].sum()),
                "ssm_resets": resets}

    def _moe_counts(self, tokens: int, touched, bucket: int) -> Dict[str, int]:
        """What a retired step asked of the routed experts (step_clock.py):
        ``touched`` is the program's own count, fetched with the step's
        ids; nothing where the program has none.  Which int8 kernel served
        the step follows from its token ``bucket`` alone."""
        if touched is None:
            return {}
        c = self.model_config
        moe_layers = c.num_layers - c.first_dense_layers
        pairs = tokens * c.num_experts_per_tok * moe_layers
        one_pass = self._int8_expert_kernels \
            and moe_ops.int8_kernel(bucket) == "one_pass"
        return {"moe_experts_touched": int(touched),
                "moe_experts_held": moe_layers * c.num_held_experts,
                "moe_pairs": pairs,
                "moe_one_pass_pairs": pairs if one_pass else 0}

    def _attn_q_counts(self, real: int, layout: BatchLayout) -> Dict[str, int]:
        """What a prefill or mixed dispatch hands prefill attention
        (step_clock.py): ``real`` query tokens in the slots its grid holds,
        the Pallas kernels' query tiles or the other paths' [S, Q]
        rectangle."""
        slots = layout.S * layout.Q
        c = self.model_config
        if c.mla_by_kind and not c.linear_by_layer:
            # ops/sparse_mla.py: a layer that selects and a window layer
            # each walk tiles of their own; a mean over the layers.
            from llm_d_tpu.ops import sparse_mla
            from llm_d_tpu.ops.attention import num_query_tiles
            n_window = c.layer_types.count(SLIDING)
            slots = sum(
                n * num_query_tiles(layout.T, layout.S, min(qt, layout.Q))
                * min(qt, layout.Q) for n, qt in (
                    (c.num_layers - n_window, sparse_mla.SELECT_Q_TILE),
                    (n_window, sparse_mla.window_q_tile(
                        c.mla_geometry(SLIDING), self._window_kernel)))
            ) // c.num_layers
        elif self._prefill_tile_dims is not None:
            from llm_d_tpu.ops.attention import (
                num_query_tiles, prefill_q_tile)
            qt = prefill_q_tile(layout.Q, *self._prefill_tile_dims,
                                self.model_config.use_mla)
            slots = num_query_tiles(layout.T, layout.S, qt) * qt
        return {"attn_q_real": real, "attn_q_slots": layout.dp * slots}

    def _attn_k_counts(self, ends, news, layout: BatchLayout) -> Dict[str, int]:
        """The keys the Pallas prefill kernels' inner loop covers for the
        same dispatch (step_clock.py), summed over its query tiles and the
        attention layers: row r's ``news[r]`` queries end at context
        ``ends[r]`` and fill ceil(n / Qt) tiles; a tile walks key blocks
        from the page of the first key its first query sees to the page of
        its last query.  Nothing where another path serves prefill."""
        if self._prefill_tile_dims is None:
            return {}
        from llm_d_tpu.ops.attention import prefill_key_block, prefill_q_tile
        c = self.model_config
        bs = self.config.block_size
        qt = prefill_q_tile(layout.Q, *self._prefill_tile_dims, c.use_mla)
        kb = prefill_key_block(qt, *self._prefill_tile_dims, c.attn_head_dim,
                               bs, c.use_mla)
        q_first, q_last = _tile_spans(ends, news, qt)
        if c.diffusion_block_length:    # the last key its last query sees
            B = c.diffusion_block_length
            q_last = (q_last // B + 1) * B - 1
        real = slots = 0
        n_window = c.layer_types.count(SLIDING)
        # (a stack with mixers by layer: the self-decoder's attention layers;
        # ``cross_kv_layer`` and the layers after it attend a query a row)
        n_full = (len(c.layers_of(FULL)) - 1 if c.mixer_by_layer
                  else c.attending_layers - n_window)
        for window, layers in ((c.sliding_window, n_window),
                               (NO_WINDOW, n_full)):
            if not layers:
                continue
            k_first = np.maximum(q_first - window + 1, 0)
            real += layers * int((q_last + 1 - k_first).sum())
            pages = -(-(q_last + 1) // bs) - k_first // bs
            slots += layers * int((-(-pages * bs // kb)).sum()) * kb
        return {"attn_k_real": real, "attn_k_slots": slots}

    def _attn_wk_counts(self, ends, news, layout: BatchLayout
                        ) -> Dict[str, int]:
        """The keys ``mla_masked_attention`` walks for the MLA layers that
        see a window (step_clock.py), summed over those layers and the
        dispatch's query tiles: a tile of Qt slots (``window_q_tile``, no
        wider than the query bucket) walks whole key blocks from the one
        that holds the first key its first query sees to its last query's
        own.  Nothing where another path serves those layers."""
        if not self._window_kernel:
            return {}
        from llm_d_tpu.ops import sparse_mla
        from llm_d_tpu.ops.pallas.mla_masked import KEY_BLOCK
        c = self.model_config
        g = c.mla_geometry(SLIDING)
        qt = min(sparse_mla.window_q_tile(g, True), layout.Q)
        ends = np.asarray(ends, np.int64)
        news = np.minimum(np.asarray(news, np.int64), ends)
        q_first, q_last = _tile_spans(ends, news, qt)
        blocks = (q_last // KEY_BLOCK + 1
                  - np.maximum(q_first - g.window + 1, 0) // KEY_BLOCK)
        n_window = c.layer_types.count(SLIDING)
        return {"attn_wk_real": n_window * int(
                    (_keys_seen_upto(ends, g.window)
                     - _keys_seen_upto(ends - news, g.window)).sum()),
                "attn_wk_slots": n_window * int(blocks.sum()) * KEY_BLOCK * qt}

    def _idx_k_counts(self, ends, news, layout: BatchLayout
                      ) -> Dict[str, int]:
        """The (query, key) pairs the indexer's kernel scores for the full
        layers that select (step_clock.py), summed over those layers and
        the dispatch's query tiles: a tile of SELECT_Q_TILE slots (no wider
        than the query bucket) walks whole index blocks from key 0 to its
        last query's own, and the kernel's tile always holds its eight
        slots.  Nothing where the XLA form serves those layers."""
        if not self._select_kernel:
            return {}
        from llm_d_tpu.ops import sparse_mla
        from llm_d_tpu.ops.pallas import dsa_index, mla_masked
        c = self.model_config
        bs = self.config.block_size
        ib = dsa_index.index_block(-(-c.max_model_len // bs) * bs,
                                   mla_masked.KEY_BLOCK)
        ends = np.asarray(ends, np.int64)
        news = np.minimum(np.asarray(news, np.int64), ends)
        _, q_last = _tile_spans(
            ends, news, min(sparse_mla.SELECT_Q_TILE, layout.Q))
        n_full = c.num_layers - c.layer_types.count(SLIDING)
        return {"idx_k_real": n_full * (
                    int((news * (2 * ends - news + 1)).sum()) // 2),
                "idx_k_slots": n_full * int((q_last // ib + 1).sum())
                * ib * dsa_index.SLOTS}

    def _attn_dk_counts(self, ends, layout: BatchLayout) -> Dict[str, int]:
        """The keys the MLA decode kernel's inner loop covers for a
        pure-decode dispatch (step_clock.py), summed over its rows and the
        attention layers: the kernel takes the rows of the sequence bucket
        in the order of their contexts ``ends[r]`` (pad rows 0), G to a grid
        program, and a program walks key blocks to its longest row's last.
        Nothing where another path serves decode."""
        c = self.model_config
        if (self._prefill_tile_dims is None or not c.use_mla
                or layout.dp != 1):
            return {}
        from llm_d_tpu.ops.attention import mla_decode_walk
        kb, group = mla_decode_walk(
            layout.S, *self._prefill_tile_dims, self.config.block_size)
        lens = np.zeros(layout.S, np.int64)
        lens[:len(ends)] = ends
        blocks = -(-np.sort(lens).reshape(-1, group).max(axis=1) // kb)
        layers = c.attending_layers
        return {"attn_dk_real": layers * int(lens.sum()),
                "attn_dk_slots": layers * int(blocks.sum()) * group * kb}

    def _note_step(self, t0: float, fetched: float, requests: List[Request],
                   prefill_tokens: int, decode_tokens: int, *,
                   fused: bool, rounds: int = 1, kv: Dict[str, int],
                   run_ahead: int = 0, wasted_rows: int = 0,
                   prefill_ahead_tokens: int = 0, **spec_attrs) -> None:
        """Describe the ``engine.step`` span of the iteration under way:
        the one place all four step paths do, so they share one extent
        (``t0``, the clock read before the RNG split, to tokens
        ``fetched``) and one attribute set.  Only clock reads already
        taken around the one batched fetch: no new sync.  ``rounds`` is
        the engine steps the one dispatch ran."""
        parent = next((r.trace_ctx for r in requests
                       if r.trace_ctx is not None), None)
        self._step_note = (t0, fetched, parent, dict(
            step=self._step_count,
            kind=("decode" if prefill_tokens == 0
                  else "prefill" if decode_tokens == 0 else "mixed"),
            n_seqs=len(requests), prefill_tokens=prefill_tokens,
            prefill_ahead_tokens=prefill_ahead_tokens,
            decode_tokens=decode_tokens, fused=fused, rounds=rounds,
            run_ahead=run_ahead, wasted_rows=wasted_rows,
            **kv, **spec_attrs))

    def step(self) -> List[RequestOutput]:
        """One iteration of the engine loop: at most one step retired, and
        on the classic path at most one launched behind it (module
        docstring).  The phase clock runs over all of it; an iteration that
        fetched tokens writes the ``engine.step`` span of the step it
        retired here, as it returns, with the iteration's phase times
        (parented on the first traced request; none traced, no span).  The
        span runs from the step's launch to its tokens fetched, or, for a
        step launched while its predecessor was on the device
        (``run_ahead``), from the predecessor's fetch: the time the step
        held the pace, so consecutive spans never overlap.  The phases are
        the ITERATION's (``schedule`` / ``build`` / ``dispatch`` of the
        step launched in it, ``fetch`` / ``post`` of the step retired), so
        ``dispatch_ms + fetch_ms`` is the span only where nothing was
        composed ahead."""
        self._clock.enter()
        self._step_note = None
        try:
            # Chaos fault point: simulated engine death (a raised fault
            # propagates exactly like a real step crash — AsyncEngine marks
            # the engine dead, fails all streams, /health turns 500).  No-op
            # dict miss unless rules are installed.  Keyed by model name so
            # a multi-engine chaos harness can kill one replica via match=.
            get_injector().check("engine.step", key=str(self.config.model))
            return self._step()
        finally:
            self._clock.leave(self.scheduler.has_work())
            if self._step_note is not None:
                t0, fetched, parent, attrs = self._step_note
                phases = self._clock.flush()
                if parent is not None:
                    # One conversion for both ends: the duration is exactly
                    # the clock's fetched - t0 (= dispatch_ms + fetch_ms).
                    start = self._mono_to_epoch(t0)
                    self.tracer.record_span(
                        "engine.step", start, start + fetched - t0,
                        parent=parent, **attrs, **phases)

    def _step(self) -> List[RequestOutput]:
        outputs: List[RequestOutput] = []
        if self._rejected:
            outputs.extend(self._rejected)
            self._rejected.clear()
        if self.kv_connector is not None:
            # Pump the connector: admit finished KV pulls, surface failed
            # ones, release producer pins the consumer acknowledged.
            outputs.extend(self.kv_connector.poll(self))
        if self._inflight is not None:
            # Pipelined decode: queue the successor block on the device
            # FIRST, then retire the in-flight one — host-side token
            # processing runs while the device crunches the successor.
            rec = self._inflight
            if isinstance(rec, dict) and rec.get("kind") == "fms":
                nxt = self._fms_try_extend(rec)
                outputs.extend(self._fms_retire(rec, successor=nxt))
            else:
                nxt = self._ms_try_extend(rec)
                outputs.extend(self._ms_retire(rec))
            self._inflight = nxt
            return outputs
        rec, self._ahead = self._ahead, None
        if rec is None:
            sched = self._schedule(outputs)
            if sched.empty:
                self._update_queue_metrics()
                return outputs
            self._clock.mark("build")

            if self._spec_fn is not None:
                # Fused mixed round: whatever this pass scheduled — prefill
                # chunks, plain decodes, draft-verify rows, logprobs rows —
                # runs as ONE device program.  There is no classic fallback
                # anymore (and so no draft-allocation rollback): spec decode
                # stays on under continuous prefill traffic, and a prefill
                # chunk rides the same per-layer expert-weight stream the
                # decodes already pay for.  With num_scheduler_steps > 1 the
                # mixed round becomes the body of an N-round lax.scan — one
                # dispatch + one host fetch per N rounds, double-buffered
                # under async scheduling like the classic multistep path.
                plan = self._fms_plan(sched)
                if plan is not None:
                    rec = self._fms_dispatch(plan)
                    if self.config.async_scheduling:
                        self._inflight = rec
                        return outputs   # this dispatch retires next step
                    outputs.extend(self._fms_retire(rec))
                    return outputs
                outputs.extend(self._run_fused(sched))
                return outputs

            K = self._try_multistep(sched)
            if K is not None:
                if self.config.async_scheduling:
                    meta, ordered, rows = self._ms_meta(sched.scheduled)
                    self._inflight = self._ms_dispatch(meta, ordered, K, rows)
                    return outputs    # this block's tokens arrive next step
                outputs.extend(self._run_multistep(sched, K))
                return outputs

            rec = self._launch(sched, ahead=False)
        # ``rec`` is on the device.  Where no arrival could join the step
        # after it, compose and launch that one now, behind it, and only
        # then wait for ``rec``'s tokens: the device never waits for the
        # host's part of an iteration.  Otherwise today's order.
        if self._may_run_ahead():
            self._clock.mark("schedule")
            sched = self._schedule(outputs)
            if not sched.empty:
                self._clock.mark("build")
                self._ahead = self._launch(sched, ahead=True)
        self._retire(rec, outputs)
        return outputs

    def _schedule(self, outputs: List[RequestOutput]) -> SchedulerOutput:
        """One scheduler pass with its bookkeeping: queue waits observed,
        the requests the scheduler finished itself surfaced."""
        sched = self.scheduler.schedule()
        sched_now = time.monotonic()
        if sched.prefill_ahead_tokens:
            self.metrics.prefill_ahead_tokens.inc(sched.prefill_ahead_tokens)
        for sr in sched.scheduled:
            if sr.is_first_schedule and not sr.request.queue_wait_observed:
                sr.request.queue_wait_observed = True
                sr.request.first_schedule_time = sched_now
                self.metrics.observe_queue_wait(
                    sr.request.criticality,
                    max(0.0, sched_now - sr.request.arrival_time))
                self._trace_phase(
                    sr.request, "engine.queue", "queue",
                    min(sr.request.arrival_time, sched_now), sched_now)
        for req in sched.preempted:      # requests finished by the scheduler
            if req.state is RequestState.FINISHED_DEADLINE:
                self.metrics.inc_deadline_exceeded(req.criticality)
            self._spec_forget(req.request_id)
            outputs.append(RequestOutput(
                req.request_id, [], True, finish_reason=req.state.value))
        return sched

    def _may_run_ahead(self) -> bool:
        """The gate, from the engine's own state once a step is launched:
        may the NEXT step be composed before this one's tokens are known?
        Only where no arrival could have joined it anyway: every sequence
        slot stays taken (a row the step in flight finishes by length, or
        hands to a remote decode, leaves a slot free: the step after a
        finish is composed in today's order and the replacement joins as
        early as today), no KV pull is pending, and the pool holds what
        every running row may ask of the next step, so composing ahead can
        never preempt a row in flight.  A gate that fails costs nothing:
        the iteration is today's."""
        sch = self.scheduler
        if not self._runs_ahead or len(sch.running) < sch.max_num_seqs:
            return False
        if self.kv_connector is not None and self.kv_connector.has_pending():
            return False
        bs, budget = self.config.block_size, sch.max_num_batched_tokens
        need = 0
        for req in sch.running:
            if req.inflight_token_ids and (
                    req.do_remote_decode or self._stops_by_length(
                        req, req.num_tokens - req.num_prompt_tokens)):
                return False
            ask = min(max(req.num_tokens - req.num_computed_tokens, 1),
                      budget)
            need += max(-(-(req.num_computed_tokens + ask) // bs)
                        - len(req.block_ids), 0)
        return self.kv_manager.has_room(need)

    def _launch(self, sched: SchedulerOutput, ahead: bool) -> _LaunchedStep:
        """Build, copy and launch one classic step, and advance its rows as
        the scheduler has to see them next: their tokens computed, and for
        a row the step samples a PLACEHOLDER ``-(row + 1)`` for the token
        not known yet (``Request.inflight_token_ids``).  The next step's
        batch takes it as that row's input like any token and the program
        reads the real id from this step's ids on the device
        (``feed_tokens``); ``_retire`` files the real id in its place.  (A
        block-diffusion step moves its rows when it retires: what a pass
        reveals decides what the next asks.)"""
        packed, layout, scheduled, rows = self._build_batch(sched)
        t0 = self._clock.mark(
            "dispatch", prefill_tokens=sched.prefill_tokens,
            sample_rows=layout.dp * layout.S * layout.R, **self._step_kv)
        # top_logprobs=0 means chosen-token logprob only (no alternatives).
        want_top = any((sr.request.sampling.logprobs or 0) > 0
                       for sr in scheduled)
        if want_top and self._step_fn_top is None:
            self._step_fn_top = self._build_step_fn(
                want_top_logprobs=True, packed=True)
        fn = self._step_fn_top if want_top else self._step_fn
        # ONE launch: the program splits the key and returns its successor.
        ids, logprobs, self.kv_cache, routed, touched, top, self._rng = fn(
            self.params, self.kv_cache, packed, self._rng, *self._fed,
            layout)
        if self._fed:
            self._fed = (ids,)
        self._clock.count("launches")
        self._dispatch_count += 1
        self.metrics.engine_dispatches.inc()
        if ahead:
            self.metrics.run_ahead_steps.inc()
        # ONE batched fetch: each device_get is a blocking PCIe transfer
        # that drains the dispatch queue, and chosen-token logprobs are
        # only materialized when some request asked for them.
        fetch = {"ids": ids}
        if any(sr.request.sampling.logprobs is not None for sr in scheduled):
            fetch["logprobs"] = logprobs
        if top is not None:
            fetch["top"] = top
        if touched is not None:
            fetch["touched"] = touched
        samples: List[Tuple[bool, bool, bool]] = []
        released = 0
        if not self.block_length:
            bs = self.config.block_size
            for i, sr in enumerate(scheduled):
                req, before = sr.request, sr.request.num_computed_tokens
                req.num_computed_tokens += sr.num_new_tokens
                if self._window_blocks:
                    # The step is launched: the pages its row's window has
                    # passed go back (kv_cache.py, ``release_passed``).
                    released += self.kv_manager.release_passed(req)
                sampled = req.num_computed_tokens == req.num_tokens
                samples.append((
                    sampled,
                    sampled and req.num_computed_tokens
                    <= req.num_prompt_tokens,
                    req.num_computed_tokens // bs > before // bs))
                if sampled:
                    req.inflight_token_ids.append(-1 - int(rows[i]))
        if self._window_blocks:
            self._step_kv["kv_window_pages_released"] = released
            self.metrics.kv_window_pages_released.inc(released)
        return _LaunchedStep(
            sched, scheduled, rows, samples, fetch, routed,
            self._routed_valid, self._step_kv, layout.T, t0, ahead)

    def _retire(self, rec: _LaunchedStep,
                outputs: List[RequestOutput]) -> None:
        """Fetch a launched step's tokens and file them: placeholders
        replaced, full blocks of confirmed tokens hashed, stops checked,
        metrics counted, outputs emitted.  A row whose request stopped
        while this step was in flight (its predecessor's token was an EOS
        or closed a stop string, it was aborted, its deadline passed) is
        dropped whole: nothing is emitted after a stop, and its blocks
        went back when it stopped.  The device runs programs in order, so
        a block handed on since is written by its new owner after this
        step's stale write and never read before."""
        sched, scheduled, rows = rec.sched, rec.scheduled, rec.rows
        self._clock.mark("fetch")
        # llmd: ignore[JIT] the one intended per-step host sync (batched)
        fetched = jax.device_get(rec.fetch)
        moe = self._moe_counts(sched.total_tokens, fetched.get("touched"),
                               rec.bucket)
        now = self._clock.mark("post", **moe)
        ids = np.asarray(fetched["ids"])
        logprobs = (np.asarray(fetched["logprobs"])
                    if "logprobs" in fetched else None)
        top = (tuple(np.asarray(a) for a in fetched["top"])
               if "top" in fetched else None)
        self._step_count += 1
        self.metrics.engine_steps.inc()
        # One extent a step: a step that ran ahead held the pace only from
        # the instant its predecessor's tokens were fetched.
        t0 = max(rec.t0, self._fetched_at) if rec.ahead else rec.t0
        self._fetched_at = now
        wasted = sum(sr.request.state is not RequestState.RUNNING
                     for sr in scheduled)
        if wasted:
            self.metrics.run_ahead_wasted_rows.inc(wasted)
        self._note_step(t0, now, [sr.request for sr in scheduled],
                        sched.prefill_tokens, sched.decode_tokens,
                        fused=False, kv={**rec.kv, **moe},
                        run_ahead=int(rec.ahead), wasted_rows=wasted,
                        prefill_ahead_tokens=sched.prefill_ahead_tokens,
                        **(self._block_pass_counts(scheduled, ids)
                           if self.block_length else {}))
        if self.eplb is not None:
            # Record routed logical ids (sampled; padding rows excluded so
            # the zero-embedding's favorite expert doesn't skew the stats)
            # and rebalance the physical placement on the interval.
            routed = rec.routed
            if routed is not None:
                if rec.routed_valid is not None:   # stacked: ragged pads
                    routed = routed[:, rec.routed_valid, :]
                else:
                    routed = routed[:, :sched.total_tokens, :]
            self.params = self.eplb.on_step(
                routed, self._step_count, self.params, self.mesh)

        if self.block_length:
            self._retire_block_rows(scheduled, rows, ids, logprobs, top, now,
                                    outputs)
            scheduled = ()      # nothing is left for the one-token rows below
        n_tokens, at = 0, len(outputs)
        first_outputs: List[RequestOutput] = []
        for i, sr in enumerate(scheduled):
            req, n = sr.request, sr.num_new_tokens
            if req.state is not RequestState.RUNNING:
                # Stopped while the step was in flight: nothing of the row
                # is kept, a placeholder least of all.
                req.inflight_token_ids.clear()
                continue
            s = int(rows[i])
            self._account_collective_bytes(n)
            sampled, first, block_done = rec.samples[i]
            if block_done:      # only then is there a new block to hash
                self.kv_manager.cache_full_blocks(req)
            if not sampled:
                continue                  # mid-prefill chunk: no sampling yet
            # The oldest placeholder is this step's (the next step's may
            # wait behind it).
            req.inflight_token_ids.pop(0)
            token = int(ids[s])
            if first:
                # Prefill just completed.
                self.metrics.prompt_tokens.inc(req.num_prompt_tokens)
                if req.num_cached_prompt_tokens:
                    self.metrics.prefix_cache_hits.inc(req.num_cached_prompt_tokens)
                self.metrics.prefix_cache_queries.inc(req.num_prompt_tokens)
                if req.first_token_time is None:
                    req.first_token_time = now
                    self.metrics.time_to_first_token.observe(
                        now - req.arrival_time)
                    # PD consumer admissions only recompute the last
                    # prompt token locally — that IS the first-decode
                    # leg of the PD TTFT decomposition; everything else
                    # is an ordinary prefill (a resume admission's
                    # prompt+generated recompute included).
                    self._trace_phase(
                        req, "engine.prefill",
                        "first_decode" if req.do_remote_prefill
                        else "prefill",
                        req.first_schedule_time or req.arrival_time, now,
                        cached_tokens=req.num_cached_prompt_tokens or None,
                        resume_offset=req.resume_offset or None,
                        restored_tokens=req.resume_restored_tokens or None)
                if req.do_remote_decode:
                    # PD producer: stop here, pin blocks, publish transfer
                    # params (the first token travels in them).
                    outputs.append(self._finish_remote_prefill(req, token))
                    continue
            else:
                if req.last_token_time is not None:
                    self.metrics.inter_token_latency.observe(
                        now - req.last_token_time)
            req.last_token_time = now

            req.output_token_ids.append(token)
            n_tokens += 1
            finish = self._check_stop(req, token)
            top_lp = None
            if (req.sampling.logprobs or 0) > 0 and top is not None:
                n = min(int(req.sampling.logprobs), top[0].shape[1])
                top_lp = [{int(top[0][s, j]): float(top[1][s, j])
                           for j in range(n)}]
            # A first token goes out ahead of the step's other frames: the
            # server writes a step's frames one after the other.
            (first_outputs if first else outputs).append(RequestOutput(
                req.request_id, [token], finish is not None,
                finish_reason=finish,
                logprobs=([float(logprobs[s])]
                          if req.sampling.logprobs is not None else None),
                top_logprobs=top_lp))
            if finish is not None:
                # A row of the step composed behind this one is wasted; its
                # placeholder goes now, so that ``num_tokens`` is the
                # request's own count when the server reads its usage.
                req.inflight_token_ids.clear()
                self._finish_request(req, finish, now)

        outputs[at:at] = first_outputs
        if n_tokens:
            self.metrics.generation_tokens.inc(n_tokens)

        # Step composition counters + the step-latency model's sample,
        # all from scheduler metadata and the clock reads already taken
        # around the one batched fetch — no new host syncs.
        if sched.prefill_tokens:
            self.metrics.step_prefill_tokens.inc(sched.prefill_tokens)
        if sched.decode_tokens:
            self.metrics.step_decode_tokens.inc(sched.decode_tokens)
        self.step_time_model.observe(
            sched.prefill_tokens, sched.decode_tokens, (now - t0) * 1e3)
        self._update_queue_metrics()

    def _finish_request(self, req: Request, finish: str, now: float) -> None:
        """A classic step's row stopped (``_check_stop``): out of the
        scheduler, into the request metrics and the trace."""
        self.scheduler.finish(req, RequestState(finish))
        self._spec_forget(req.request_id)
        self.metrics.request_success.labels(
            model_name=self.metrics.model_name,
            finished_reason=finish).inc()
        self.metrics.e2e_request_latency.observe(now - req.arrival_time)
        self._trace_phase(
            req, "engine.decode", "decode",
            req.first_token_time or now, now,
            n_tokens=len(req.output_token_ids), finish=finish)

    # ---------- generation by diffusion over blocks ----------

    def _block_pass_counts(self, scheduled, ids: np.ndarray) -> Dict[str, int]:
        """What a block-diffusion step did (``engine.step`` attributes, and
        the /metrics counters): rows that ran a denoising pass and rows that
        committed a block, the slots those passes forwarded (masked and
        revealed, commit passes included: what the device computed), and
        the tokens the denoising passes revealed (``ids`` is the fetched
        [S, B], -1 where nothing was revealed, padding included)."""
        denoise = sum(sr.denoise for sr in scheduled)
        commit = sum(sr.commit for sr in scheduled)
        revealed = int(np.count_nonzero(ids >= 0))
        self.metrics.add_diffusion_passes("denoise", denoise)
        self.metrics.add_diffusion_passes("commit", commit)
        self.metrics.diffusion_revealed_tokens.inc(revealed)
        return {"denoise_rows": denoise, "commit_rows": commit,
                "denoise_slots": (denoise + commit) * self.block_length,
                "denoise_revealed": revealed}

    def _retire_block_rows(self, scheduled, rows, ids, logprobs, top,
                           now: float, outputs: List[RequestOutput]) -> None:
        """The rows of a block-diffusion step.  A chunk's or a commit
        pass's keys are final: the request's computed tokens advance, full
        pages join the prefix cache.  A denoising pass's ``ids[row]`` holds
        the slots it revealed; those that continue the request's known
        tokens without a gap are its output of this step (0..B tokens, each
        with the logprob of the pass that revealed it), the others wait in
        ``revealed_ahead``.  A stop is checked token by token as the prefix
        grows, so an EOS counts once every slot before it is revealed and
        whatever was revealed behind it is dropped."""
        B = self.block_length
        for i, sr in enumerate(scheduled):
            s = int(rows[i])
            req, n = sr.request, sr.num_new_tokens
            self._account_collective_bytes(n)
            if not sr.denoise:
                req.num_computed_tokens += n
                self.kv_manager.cache_full_blocks(req)
                continue
            start = req.num_computed_tokens
            want_lp = req.sampling.logprobs is not None
            n_top = min(int(req.sampling.logprobs or 0),
                        top[0].shape[1] if top is not None else 0)
            for j in np.flatnonzero(ids[s] >= 0):
                r = s * B + int(j)
                req.revealed_ahead[start + int(j)] = (
                    int(ids[s, j]),
                    float(logprobs[s, j]) if want_lp else None,
                    {int(top[0][r, m]): float(top[1][r, m])
                     for m in range(n_top)} if n_top else None)
            req.denoise_step += 1
            new: List[Any] = []
            finish = None
            while finish is None and req.num_tokens in req.revealed_ahead:
                new.append(req.revealed_ahead.pop(req.num_tokens))
                req.output_token_ids.append(new[-1][0])
                finish = self._check_stop(req, new[-1][0])
            if req.num_tokens >= start + B:
                req.reset_block()      # complete: the next pass commits it
            if not new:
                continue               # revealed nothing that can stream yet
            if req.first_token_time is None:
                req.first_token_time = now
                self.metrics.prompt_tokens.inc(req.num_prompt_tokens)
                if req.num_cached_prompt_tokens:
                    self.metrics.prefix_cache_hits.inc(
                        req.num_cached_prompt_tokens)
                self.metrics.prefix_cache_queries.inc(req.num_prompt_tokens)
                self.metrics.time_to_first_token.observe(
                    now - req.arrival_time)
                self._trace_phase(
                    req, "engine.prefill", "prefill",
                    req.first_schedule_time or req.arrival_time, now,
                    cached_tokens=req.num_cached_prompt_tokens or None)
            elif req.last_token_time is not None:
                gap = (now - req.last_token_time) / len(new)
                for _ in new:
                    self.metrics.inter_token_latency.observe(gap)
            req.last_token_time = now
            self.metrics.generation_tokens.inc(len(new))
            outputs.append(RequestOutput(
                req.request_id, [t for t, _, _ in new], finish is not None,
                finish_reason=finish,
                logprobs=[lp for _, lp, _ in new] if want_lp else None,
                top_logprobs=[tp for _, _, tp in new] if n_top else None))
            if finish is not None:
                self._finish_request(req, finish, now)

    def _finish_remote_prefill(self, req: Request, first_token: int) -> RequestOutput:
        req.state = RequestState.FINISHED_REMOTE_PREFILL
        self.scheduler.running.remove(req)
        self.pinned_transfers[req.request_id] = req
        if self.kv_connector is not None:
            # Stage the pinned blocks' KV to host and serve them under the
            # request uuid (consumer address comes from kv_transfer_params).
            self.kv_connector.register_transfer(self, req)
        params: Dict[str, Any] = {
            "remote_block_ids": list(req.block_ids),
            "remote_host": getattr(self.kv_connector, "host", "localhost"),
            "remote_port": getattr(self.kv_connector, "port", 0),
            "uuid": req.request_id,
            "first_token": first_token,
        }
        req.kv_transfer_params = params
        return RequestOutput(
            req.request_id, [first_token], True,
            finish_reason=RequestState.FINISHED_REMOTE_PREFILL.value,
            kv_transfer_params=params)

    def _check_stop(self, req: Request, token: int) -> Optional[str]:
        sp = req.sampling
        if not sp.ignore_eos and self.eos_token_id is not None \
                and token == self.eos_token_id \
                and len(req.output_token_ids) >= sp.min_tokens:
            return RequestState.FINISHED_STOPPED.value
        # Engine-side stop strings: decode a tail window (a stop string can
        # span token boundaries) and terminate generation promptly instead of
        # decoding to max_tokens and truncating in the server.
        if sp.stop and self.tokenizer is not None \
                and len(req.output_token_ids) >= sp.min_tokens:
            max_stop = max(len(s) for s in sp.stop)
            window = req.output_token_ids[-(max_stop + 8):]
            tail = self.tokenizer.decode(window)
            if any(s in tail for s in sp.stop):
                return RequestState.FINISHED_STOPPED.value
        if self._stops_by_length(req, len(req.output_token_ids)):
            return RequestState.FINISHED_LENGTH.value
        return None

    def _stops_by_length(self, req: Request, n_out: int) -> bool:
        """``_check_stop``'s tests that need no token, at ``n_out`` output
        tokens: known as soon as the step that samples the last of them is
        launched (the gate reads it)."""
        return (n_out >= req.sampling.max_tokens
                or req.num_prompt_tokens + n_out
                >= self.model_config.max_model_len)

    def _account_collective_bytes(self, n_tokens: int) -> None:
        """Charge ``n_tokens`` computed tokens' EP exchange bytes to
        llmd_tpu:collective_bytes_total (no-op off the multi-device MoE
        path)."""
        if self._collective_wire is None or not n_tokens:
            return
        for phase, b in self._a2a_token_bytes.items():
            self.metrics.add_collective_bytes(
                phase, self._collective_wire, n_tokens * b)

    def _update_queue_metrics(self) -> None:
        if self.host_tier is not None:
            # One batched device->host copy for all blocks cached this step.
            self.host_tier.flush()
        self.metrics.num_requests_waiting.set(self.scheduler.num_waiting)
        self.metrics.num_requests_running.set(self.scheduler.num_running)
        self.metrics.kv_cache_usage_perc.set(self.kv_manager.usage)
        if self._has_state:
            self.metrics.ssm_state_slots_in_use.set(
                self.kv_manager.state_slots_in_use)
        if self._window_blocks:
            kvm = self.kv_manager
            for g in kvm.groups:
                self.metrics.set_group_pages(g.name, g.pages_held)
                for seen, now, add in (
                        (self._group_evictions, g.eviction_count,
                         self.metrics.add_group_evictions),
                        (self._group_hit_lost, kvm.hit_tokens_lost[g.name],
                         self.metrics.add_prefix_hit_lost)):
                    if now > seen.get(g.name, 0):
                        add(g.name, now - seen.get(g.name, 0))
                        seen[g.name] = now
        if self.kv_manager.eviction_count > self._last_evictions:
            self.metrics.kv_cache_evictions.inc(
                self.kv_manager.eviction_count - self._last_evictions)
            self._last_evictions = self.kv_manager.eviction_count
        if self.scheduler.num_preemptions > self._last_preemptions:
            self.metrics.preemptions.inc(
                self.scheduler.num_preemptions - self._last_preemptions)
            self._last_preemptions = self.scheduler.num_preemptions

    # ---------- convenience (tests / bench) ----------

    def generate(self, requests: List[Request], max_steps: int = 10000
                 ) -> Dict[str, List[int]]:
        """Run requests to completion synchronously; returns output ids."""
        for r in requests:
            self.add_request(r)
        for _ in range(max_steps):
            if not self.has_work():
                break
            self.step()
            if not self.scheduler.has_work() and self.has_work():
                time.sleep(0.001)   # only async connector work pending
        return {r.request_id: list(r.output_token_ids) for r in requests}
