"""Model configurations and presets.

One config type covers the dense (Llama/Qwen) and MoE (Mixtral/DeepSeek
-style) families; ``num_experts == 0`` means dense.  Presets mirror the
models the reference's well-lit paths deploy: Qwen3-0.6B
(inference-scheduling), Llama-3.3-70B (pd-disaggregation), DeepSeek-R1
(wide-ep-lws), Qwen3-32B (tiered-prefix-cache), Mixtral-8x22B
(predicted-latency) — reference: SURVEY.md §2.1, BASELINE.json configs.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional, Tuple

import jax.numpy as jnp

SLIDING, FULL = "sliding_attention", "full_attention"
# Mixers that are not self-attention over a layer's own keys (a stack that
# names any of them is walked by models/hybrid_decoder.py): a Mamba-1
# selective state-space mixer; a gated memory unit, which gates the memory
# that ``gmu_memory_layer``'s mixer produced for the same token; attention
# that projects a query only and reads ``cross_kv_layer``'s keys and values.
MAMBA, GMU, CROSS = "mamba", "gmu", "cross_attention"
# A linear-attention mixer in place of a layer's attention, inside a stack
# of MLA layers and routed experts (models/moe.py walks it as one more layer
# kind; models/linear_attention.py): a delta rule under a per-channel gate
# over a recurrent state a head, no keys in pages and no rotary embedding.
LINEAR = "linear_attention"
LAYER_KINDS = (SLIDING, FULL, MAMBA, GMU, CROSS, LINEAR)
# The window of a full-attention layer: one that never binds (above any
# position, with room to add a position without overflowing int32).
NO_WINDOW = 1 << 30
# Which masked slots of a block a denoising pass reveals, q the pass's quota:
# the first q from the left; the q of highest confidence; every slot whose
# confidence passes the threshold if they are at least q, else the q highest.
DIFFUSION_REMASKING = ("sequential", "low_confidence_static",
                       "low_confidence_dynamic")


class RopeRule(NamedTuple):
    """The rotary tables of one layer kind (``ModelConfig.rope_rules``).
    ``factor`` 0: plain tables of ``theta``.  Otherwise YaRN as published:
    the inverse frequencies theta^(-2i/d) are blended with the same over
    ``factor`` by a linear ramp between the correction dimensions of
    ``beta_fast`` and ``beta_slow`` rotations over ``original_max`` positions
    (dimensions that turn often keep theirs, slow ones are interpolated),
    and cos and sin are multiplied by ``attention_factor``."""
    theta: float
    factor: float = 0.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


class MLAGeometry(NamedTuple):
    """Latent attention as one layer kind runs it (``mla_geometry``)."""
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    window: int            # keys a query sees, itself included; 0 = all
    index_topk: int        # keys the indexer keeps for a query; 0 = all

    @property
    def row_width(self) -> int:
        """The cached row: latent | rotary key, padded to whole lanes."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "custom"
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 16
    num_heads: int = 16
    num_kv_heads: int = 8
    head_dim: Optional[int] = None          # default hidden/heads
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    attention_bias: bool = False            # Qwen2: True
    qk_norm: bool = False                   # Qwen3: True
    max_model_len: int = 32000              # reference: ms-pd/values.yaml:41-42
    dtype: str = "bfloat16"
    # --- MoE (0 experts = dense) ---
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    num_shared_experts: int = 0             # DeepSeek shared expert(s)
    first_dense_layers: int = 0             # DeepSeek: first k layers dense
    moe_renormalize: bool = True
    n_group: int = 0                        # DeepSeek group-limited routing (0=off)
    topk_group: int = 0
    routed_scaling_factor: float = 1.0
    # "softmax" (Mixtral/Qwen-MoE) or "sigmoid" (DeepSeek-V3/R1: sigmoid
    # scores + e_score_correction_bias used for selection only).
    scoring_func: str = "softmax"
    # --- MLA (multi-head latent attention; 0 = classic MHA/GQA) ---
    # DeepSeek-V3/R1 compress KV into a rank-512 latent + one shared 64-d
    # RoPE key per token: the serving cache holds 576 values/token instead
    # of num_heads * head_dim * 2 (the reason wide-EP decode fits).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- mixed attention stacks (GQA path; empty = every layer full) ---
    # Kind of each layer: SLIDING layers see the last ``sliding_window``
    # keys only, FULL layers the whole context.
    layer_types: Tuple[str, ...] = ()
    sliding_window: int = 0
    # False: FULL layers carry no rotary embedding (SLIDING ones always do).
    rope_on_full_attention: bool = True
    # The rotary rule by layer kind, as a published ``rope_parameters``
    # gives it: {kind: {"rope_type": "default" | "yarn", "rope_theta", and
    # for yarn "factor", "original_max_position_embeddings", "beta_fast",
    # "beta_slow", "attention_factor"}} (kept as a tuple of items, so the
    # config stays hashable).  Empty: ``rope_theta`` plain on every layer
    # that rotates, the tables every other stack has always had.
    rope_parameters: Tuple = ()
    # sigmoid(h W_gate) multiplies the attention output before o_proj.
    attn_output_gate: bool = False
    # RMS norms on the attention and MLP outputs too, before the residual.
    sandwich_norm: bool = False
    embed_scale: float = 1.0                # multiplies the token embedding
    # --- mixed stacks on the MLA path ---
    # With ``layer_types`` on an MLA model the SLIDING layers are latent
    # attention of a geometry of their own (``mla_geometry``): every
    # ``swa_*`` field left at 0 takes the full layers' value.  Parameter
    # shapes and cache rows then differ by layer kind, so each kind has its
    # parameter stacks and its cache buffers (models/moe.py).
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    # The normed query and key-value latents are multiplied by
    # sqrt(hidden_size / rank), each kind by its own ranks; the cached row
    # is the rescaled one.
    mla_lora_rescale: bool = False
    # sigmoid(h W_g), one scalar a head, multiplies each head's attention
    # output before o_proj (MLA path; the GQA path's gate is elementwise:
    # ``attn_output_gate``).
    attn_head_gate: bool = False
    # --- learned key selection in FULL MLA layers (0 = attend to all) ---
    # An indexer scores every visible key for every query from
    # ``index_n_heads`` small heads on the query latent and one cached index
    # key a token (``index_head_dim`` wide, a cache buffer of its own), and
    # the layer attends to the ``index_topk`` best keys only (all of them
    # while fewer are visible).  ops/sparse_mla.py.
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    # --- one rank's share of a wider expert-parallel deployment ---
    # The router scores all ``num_experts``; this process holds
    # ``num_local_experts`` of them (0 = all), ids ``first_local_expert``
    # onwards, and a token's slots routed elsewhere add nothing here.
    num_local_experts: int = 0
    first_local_expert: int = 0
    # --- generation by diffusion over blocks (0 = autoregressive) ---
    # Attention is block-causal (the query at position p sees key j iff
    # j // B <= p // B) and a block of B tokens is generated by denoising
    # passes over its B slots, revealed or holding ``mask_token_id``, then
    # one commit pass whose keys and values are kept.  The model's
    # generation settings, not flags of the server: pass s of a block
    # reveals B // steps masked slots (+1 in the first B % steps passes),
    # chosen by ``diffusion_remasking`` (DIFFUSION_REMASKING).
    diffusion_block_length: int = 0
    mask_token_id: int = 0
    diffusion_steps: int = 0                # 0 = one pass a slot (B)
    diffusion_remasking: str = "sequential"
    diffusion_confidence_threshold: float = 0.9
    # --- a state-space mixer beside attention in every layer (0 = none) ---
    # Mamba-2: the layer's normed input goes through attention AND through
    # a selective state-space mixer, and both outputs join the residual.
    # A sequence then owns, besides its keys and values in pages, a
    # recurrent state per layer that every token overwrites in place:
    # ``ssm_num_heads`` x ``ssm_state_size`` x ``ssm_head_dim`` float32
    # values and the last ``ssm_conv_kernel - 1`` inputs of a depthwise
    # causal convolution over ``ssm_inner_size + 2 * groups * state``
    # channels (engine: the state pool; ops/ssm.py).
    ssm_state_size: int = 0                 # N
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0                   # P
    ssm_num_groups: int = 1                 # heads of a group share B and C
    ssm_conv_kernel: int = 4
    ssm_chunk_size: int = 128               # tokens a piece of the scan
    ssm_inner_size: int = 0                 # 0: heads x head size
    # > 0: the mixer is Mamba-1 (a MAMBA layer of ``layer_types``, in place
    # of a layer's attention and not beside it): the decay is A[N, inner]
    # by channel and state, dt comes from a rank-``ssm_dt_rank`` bottleneck
    # and the recurrent state is [N, inner] float32 a layer and slot, with
    # no heads or groups (ops/ssm.py, the ``ssm1_*`` forms).
    ssm_dt_rank: int = 0
    # --- a decoder-hybrid-decoder stack (``layer_types`` names mixers) ---
    # The layers after ``cross_kv_layer`` keep no state of their own: CROSS
    # layers attend over that FULL layer's cache plane, GMU layers read the
    # memory ``gmu_memory_layer`` (a MAMBA layer, just before it) computed
    # for the same token.  So only the rows a step samples from go through
    # ``cross_kv_layer``'s attention and everything after it.  -1 = none.
    cross_kv_layer: int = -1
    gmu_memory_layer: int = -1
    # Differential attention: heads pair up (neighbours), a pair's output is
    # softmax(q1 k1) V - lambda softmax(q2 k2) V over the pair's two value
    # heads side by side, RMS-normed and scaled by 1 - lambda_init(layer).
    diff_attention: bool = False
    norm_kind: str = "rms"                  # "layer": LayerNorm, weight + bias
    use_rope: bool = True                   # False: no positional encoding
    attention_out_bias: bool = False        # a bias on o_proj too
    # --- muP multipliers, applied where the published code applies them ---
    # (``embed_scale`` above is the embedding's.)
    lm_head_multiplier: float = 1.0         # on the logits
    attention_in_multiplier: float = 1.0    # on the attention block's input
    attention_out_multiplier: float = 1.0   # on its output
    key_multiplier: float = 1.0             # on the keys, before the rotary
    ssm_in_multiplier: float = 1.0          # on the mixer's input
    ssm_out_multiplier: float = 1.0         # on its output
    # On the five segments of the mixer's input projection: gate, x, B, C, dt.
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5
    # On the MLP's gate pre-activation and on its output.
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)
    # --- LINEAR layers: gated delta-rule linear attention (0 heads = none) ---
    # q, k, v = SiLU(causal conv(x W)) a head, q and k L2-normed; the state
    # S [key, value] float32 a head decays by exp(g) a KEY CHANNEL, g =
    # ``lin_gate_floor`` * sigmoid(exp(A_log) (x W_f + dt_bias)) in
    # (floor, 0), then takes the delta-rule write beta k (v - S^T k)^T; the
    # output S^T q is RMS-normed a head and gated by sigmoid(x W_g), one
    # scalar a head.  The state and the convolutions' tails live in the
    # engine's state pool (ops/linear_attention.py).
    lin_num_heads: int = 0
    lin_key_dim: int = 0                    # K, a head
    lin_value_dim: int = 0                  # V, a head
    lin_conv_kernel: int = 4
    lin_gate_floor: float = -5.0            # the log-decay's lower bound

    @property
    def use_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def num_held_experts(self) -> int:
        """Routed experts whose weights this process holds."""
        return self.num_local_experts or self.num_experts

    @property
    def mla_layer_kinds(self) -> Tuple[str, ...]:
        """The kinds of an MLA stack whose layers differ, in the order of
        their cache buffers; empty where one geometry serves every layer."""
        if not (self.use_mla and self.layer_types):
            return ()
        return tuple(k for k in (FULL, SLIDING) if k in self.layer_types)

    @property
    def mla_by_kind(self) -> bool:
        """Latent attention that ops/sparse_mla.py serves over cache
        buffers by layer kind: two geometries, or a selection of keys."""
        return bool(self.mla_layer_kinds or self.index_topk)

    def mla_geometry(self, kind: str = FULL) -> "MLAGeometry":
        """Latent attention as the layers of ``kind`` run it."""
        if kind == SLIDING:
            def own(name, full):
                return getattr(self, "swa_" + name) or full
            return MLAGeometry(
                own("num_heads", self.num_heads),
                own("q_lora_rank", self.q_lora_rank),
                own("kv_lora_rank", self.kv_lora_rank),
                own("qk_nope_head_dim", self.qk_nope_head_dim),
                own("qk_rope_head_dim", self.qk_rope_head_dim),
                own("v_head_dim", self.v_head_dim),
                own("rope_theta", self.rope_theta),
                self.sliding_window, 0)
        return MLAGeometry(
            self.num_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rope_theta, 0, self.index_topk)

    @property
    def rope_rules(self) -> Tuple[RopeRule, ...]:
        """The distinct rotary rules of the stack's layer kinds, in the
        order of ``rope_parameters``; empty where one plain table serves."""
        return tuple(dict.fromkeys(rule for _, rule in self.rope_parameters))

    @property
    def layer_rope_rule(self) -> Tuple[int, ...]:
        """Per layer, its kind's place in ``rope_rules``."""
        rules, by_kind = self.rope_rules, dict(self.rope_parameters)
        return tuple(rules.index(by_kind[t]) for t in self.layer_types)

    @property
    def kv_cache_groups(self) -> Tuple[str, ...]:
        """The layer kinds whose pages the engine MAY manage as groups of
        their own (engine/kv_cache.py): a GQA stack of window and full
        layers holds a window of keys for the former and the whole context
        for the latter, where the engine's limits make that smaller than
        one pool (``engine.derive_group_blocks``).  Empty: one group, every layer holds every token
        (one kind of layer; or cache buffers that go by kind already, a
        state pool beside the pages, blocks of queries: ROADMAP B10)."""
        if (self.use_mla or self.mixer_by_layer or self.has_recurrent_state
                or self.diffusion_block_length
                or set(self.layer_types) != {FULL, SLIDING}):
            return ()
        return (FULL, SLIDING)

    @property
    def has_recurrent_state(self) -> bool:
        """A sequence's state is not keys and values alone."""
        return self.ssm_state_size > 0 or self.linear_by_layer

    @property
    def linear_by_layer(self) -> bool:
        """``layer_types`` names LINEAR layers: linear-attention mixers
        among the MLA layers of a MoE stack (models/moe.py)."""
        return LINEAR in self.layer_types

    @property
    def attending_layers(self) -> int:
        """Layers with attention over keys of their own in the paged cache
        (a LINEAR layer keeps none)."""
        return self.num_layers - self.layer_types.count(LINEAR)

    @property
    def lin_conv_channels(self) -> int:
        """What a LINEAR layer's causal convolutions run over: q, k and v
        side by side."""
        return self.lin_num_heads * (2 * self.lin_key_dim
                                     + self.lin_value_dim)

    @property
    def mixer_by_layer(self) -> bool:
        """``layer_types`` names mixers that are not self-attention: the
        stack is a decoder-hybrid-decoder (models/hybrid_decoder.py)."""
        return bool(set(self.layer_types) & {MAMBA, GMU, CROSS})

    def layers_of(self, *kinds: str) -> Tuple[int, ...]:
        """The layers of ``layer_types`` of the given kinds, in order."""
        return tuple(i for i, t in enumerate(self.layer_types) if t in kinds)

    @property
    def ssm_inner_size_(self) -> int:
        return self.ssm_inner_size or self.ssm_num_heads * self.ssm_head_dim

    @property
    def ssm_conv_channels(self) -> int:
        """What the mixer's causal convolution runs over: x, B and C
        (Mamba-2), x alone (Mamba-1)."""
        if self.ssm_dt_rank:
            return self.ssm_inner_size_
        return (self.ssm_inner_size_
                + 2 * self.ssm_num_groups * self.ssm_state_size)

    @property
    def attn_head_dim(self) -> int:
        """The head size the attention kernels and the cache row see:
        differential attention feeds them a PAIR of heads as one."""
        return self.head_dim_ * (2 if self.diff_attention else 1)

    def diff_lambda_init(self, layer: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * layer)

    def __post_init__(self):
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(
                f"scoring_func must be 'softmax' or 'sigmoid', "
                f"got {self.scoring_func!r}")
        # A JSON list arrives here: a tuple keeps the config hashable.  And
        # a JSON integer: a rope_theta of 1e11 does not fit the int32 a
        # weakly typed Python int becomes inside a traced function.
        object.__setattr__(self, "rope_theta", float(self.rope_theta))
        object.__setattr__(self, "swa_rope_theta", float(self.swa_rope_theta))
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "rope_parameters",
                           self._rope_parameters(self.rope_parameters))
        kinds = self.layer_types
        if kinds:
            if len(kinds) != self.num_layers or set(kinds) - set(LAYER_KINDS):
                raise ValueError(
                    f"layer_types must name {self.num_layers} layers, each "
                    f"one of {LAYER_KINDS}, got {len(kinds)}: {kinds}")
            if SLIDING in kinds and self.sliding_window < 1:
                raise ValueError("sliding layers need sliding_window >= 1")
        self._check_hybrid_decoder()
        self._check_linear_attention()
        if self.use_mla and (self.attn_output_gate or self.sandwich_norm
                             or not self.rope_on_full_attention):
            raise ValueError(
                "attn_output_gate, sandwich_norm and rope_on_full_attention "
                "belong to the GQA attention block; the MLA path gates by "
                "head (attn_head_gate) and has neither of the others")
        if not self.use_mla and (
                self.attn_head_gate or self.mla_lora_rescale
                or self.index_topk
                or any(getattr(self, f.name) for f in
                       dataclasses.fields(self)
                       if f.name.startswith("swa_"))):
            raise ValueError(
                "swa_*, attn_head_gate, mla_lora_rescale and index_topk "
                "belong to the MLA attention block (kv_lora_rank > 0)")
        if self.index_topk and (self.index_n_heads < 1
                                or self.index_head_dim < 1
                                or self.q_lora_rank < 1):
            raise ValueError(
                "index_topk needs index_n_heads, index_head_dim and a "
                "query latent (q_lora_rank) for the indexer to read")
        E_loc = self.num_local_experts
        if E_loc or self.first_local_expert:
            if not (0 < E_loc and 0 <= self.first_local_expert
                    and self.first_local_expert + E_loc
                    <= self.num_experts):
                raise ValueError(
                    f"experts {self.first_local_expert}.."
                    f"{self.first_local_expert + E_loc - 1} are no share "
                    f"of {self.num_experts}")

        for name in ("ssm_multipliers", "mlp_multipliers"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.ssm_state_size > 0:
            # What a state-space mixer's state is not served with, one
            # refusal a field.
            for field, on in (("kv_lora_rank", self.use_mla),
                              ("diffusion_block_length",
                               bool(self.diffusion_block_length)),
                              ("num_experts", self.is_moe)):
                if on:
                    raise ValueError(
                        f"ssm_state_size with {field}: a state-space mixer "
                        f"is served in a dense autoregressive stack with "
                        f"GQA attention; {field} is not")
            if kinds and not self.mixer_by_layer:
                raise ValueError(
                    "ssm_state_size with layer_types of attention kinds "
                    "only: a mixer BESIDE attention is served with every "
                    "layer full; name the mixers' layers (\"mamba\") for a "
                    "mixer in place of attention")
            H, G = self.ssm_num_heads, self.ssm_num_groups
            if self.ssm_dt_rank:
                if not self.mixer_by_layer or H or self.ssm_head_dim \
                        or self.ssm_inner_size < 1:
                    raise ValueError(
                        "ssm_dt_rank (Mamba-1) needs MAMBA layers in "
                        "layer_types and ssm_inner_size, and has neither "
                        "ssm_num_heads nor ssm_head_dim")
            elif (H < 1 or self.ssm_head_dim < 1 or G < 1 or H % G
                    or self.ssm_inner_size_ != H * self.ssm_head_dim):
                raise ValueError(
                    f"ssm_num_heads {H} x ssm_head_dim {self.ssm_head_dim} "
                    f"must be ssm_inner_size {self.ssm_inner_size_}, in "
                    f"{G} equal groups")
            if self.ssm_conv_kernel < 2 or self.ssm_chunk_size < 1:
                raise ValueError(
                    "ssm_conv_kernel must be at least 2 and ssm_chunk_size "
                    "at least 1")
            if len(self.ssm_multipliers) != 5 \
                    or len(self.mlp_multipliers) != 2:
                raise ValueError(
                    "ssm_multipliers names five segments (gate, x, B, C, "
                    "dt) and mlp_multipliers two (gate, output)")

        B = self.diffusion_block_length
        if B:
            if self.use_mla or kinds:
                raise ValueError(
                    "block diffusion is served by the GQA attention block "
                    "with every layer full; MLA and layer_types are not")
            if not 1 <= (self.diffusion_steps or B) <= B:
                raise ValueError(
                    f"diffusion_steps must lie in 1..{B} (a pass reveals at "
                    f"least one slot), got {self.diffusion_steps}")
            if self.diffusion_remasking not in DIFFUSION_REMASKING:
                raise ValueError(
                    f"diffusion_remasking must be one of "
                    f"{DIFFUSION_REMASKING}, got {self.diffusion_remasking!r}")
            if not 0 <= self.mask_token_id < self.vocab_size:
                raise ValueError(
                    f"mask_token_id {self.mask_token_id} is outside the "
                    f"vocabulary of {self.vocab_size}")

    def _rope_parameters(self, given) -> Tuple:
        """``rope_parameters`` as ((kind, RopeRule), ...), from the
        published mapping by kind or from that form itself."""
        out = []
        for kind, rule in (given.items() if isinstance(given, dict)
                           else given):
            if isinstance(rule, dict):
                kind_of = rule.get("rope_type", "default")
                if kind_of not in ("default", "yarn"):
                    raise ValueError(
                        f"rope_parameters[{kind!r}]: rope_type {kind_of!r} "
                        f"is not served ('default' and 'yarn' are)")
                theta = float(rule.get("rope_theta", self.rope_theta))
                if kind_of == "default":
                    rule = RopeRule(theta)
                else:
                    factor = float(rule["factor"])
                    rule = RopeRule(
                        theta, factor,
                        int(rule["original_max_position_embeddings"]),
                        float(rule.get("beta_fast") or 32.0),
                        float(rule.get("beta_slow") or 1.0),
                        float(rule.get("attention_factor")
                              or 0.1 * math.log(factor) + 1.0))
            out.append((kind, rule))
        if out and (self.use_mla or self.mixer_by_layer
                    or not self.rope_on_full_attention
                    or set(self.layer_types) - {k for k, _ in out}
                    or not self.layer_types):
            raise ValueError(
                "rope_parameters gives the GQA attention block a rotary "
                "rule for every kind of layer_types; MLA, the hybrid "
                "decoder and rope_on_full_attention=False have their own")
        return tuple(out)

    def _check_linear_attention(self) -> None:
        """The one form of a stack with LINEAR layers that models/moe.py
        walks (MLA layers of one geometry among them, routed experts), each
        refusal by its field."""
        lin_fields = (("lin_num_heads", self.lin_num_heads),
                      ("lin_key_dim", self.lin_key_dim),
                      ("lin_value_dim", self.lin_value_dim))
        if not self.linear_by_layer:
            for field, value in lin_fields:
                if value:
                    raise ValueError(
                        f"{field} belongs to a stack whose layer_types name "
                        f"{LINEAR!r} layers")
            return
        for field, value in lin_fields:
            if value < 1:
                raise ValueError(
                    f"{LINEAR!r} layers need {field} (heads, and a key and "
                    f"a value size a head)")
        for field, off in (
                ("kv_lora_rank", not self.use_mla),
                ("num_experts", not self.is_moe)):
            if off:
                raise ValueError(
                    f"{LINEAR!r} layers without {field}: a linear-attention "
                    f"mixer is served among the MLA layers of a MoE stack "
                    f"(models/moe.py)")
        for field, on in (
                ("ssm_state_size", self.ssm_state_size > 0),
                ("diffusion_block_length",
                 bool(self.diffusion_block_length)),
                ("sliding_window", SLIDING in self.layer_types),
                ("index_topk", bool(self.index_topk)),
                ("mixers in layer_types", self.mixer_by_layer)):
            if on:
                raise ValueError(
                    f"{LINEAR!r} layers with {field}: the other layers of "
                    f"such a stack are full MLA attention of one geometry; "
                    f"{field} is not served there")
        if FULL not in self.layer_types:
            raise ValueError(
                f"{LINEAR!r} layers alone: the stack's paged cache is its "
                f"{FULL!r} layers' latent rows, and it has none")
        if self.lin_conv_kernel < 2 or not -10.0 <= self.lin_gate_floor < 0:
            raise ValueError(
                "lin_conv_kernel must be at least 2 and lin_gate_floor in "
                "[-10, 0): half a sub-block's decay must stay inside "
                "float32 (ops/linear_attention.py)")

    def _check_hybrid_decoder(self) -> None:
        """The one form of a stack with mixers by layer that
        models/hybrid_decoder.py walks, each refusal by its field."""
        kinds = self.layer_types
        hybrid_only = (
            ("cross_kv_layer", self.cross_kv_layer >= 0),
            ("gmu_memory_layer", self.gmu_memory_layer >= 0),
            ("diff_attention", self.diff_attention),
            ("norm_kind", self.norm_kind != "rms"),
            ("use_rope", not self.use_rope),
            ("attention_out_bias", self.attention_out_bias),
            ("ssm_dt_rank", self.ssm_dt_rank > 0))
        if self.norm_kind not in ("rms", "layer"):
            raise ValueError(
                f"norm_kind must be 'rms' or 'layer', got {self.norm_kind!r}")
        if not self.mixer_by_layer:
            for field, on in hybrid_only:
                if on:
                    raise ValueError(
                        f"{field} belongs to a stack whose layer_types name "
                        f"mixers ({MAMBA!r}, {GMU!r}, {CROSS!r})")
            return
        for field, on in (("kv_lora_rank", self.use_mla),
                          ("num_experts", self.is_moe),
                          ("diffusion_block_length",
                           bool(self.diffusion_block_length)),
                          ("attn_output_gate", self.attn_output_gate),
                          ("sandwich_norm", self.sandwich_norm),
                          ("qk_norm", self.qk_norm)):
            if on:
                raise ValueError(
                    f"{field} with mixers in layer_types: the hybrid "
                    f"decoder is dense GQA attention, Mamba-1 and gated "
                    f"memory units; {field} is not served there")
        if self.use_rope:
            raise ValueError(
                "use_rope with mixers in layer_types: the hybrid decoder's "
                "attention carries no positional encoding (the recurrent "
                "layers carry the order)")
        if not self.ssm_dt_rank or self.ssm_state_size < 1:
            raise ValueError(
                "MAMBA layers need ssm_dt_rank, ssm_state_size and "
                "ssm_inner_size (Mamba-1)")
        m, x = self.gmu_memory_layer, self.cross_kv_layer
        if not (0 <= m and x == m + 1 and x < self.num_layers
                and m % 2 == 0 and self.num_layers % 2 == 0):
            raise ValueError(
                f"gmu_memory_layer {m} and cross_kv_layer {x} must be an "
                f"even layer and the one after it: the stack is walked in "
                f"pairs (mixer, attention)")
        want = tuple(
            (MAMBA if li <= m else GMU) if li % 2 == 0
            else (FULL if li == x else None if li < x else CROSS)
            for li in range(self.num_layers))
        for li, (got, w) in enumerate(zip(kinds, want)):
            if got != w and not (w is None and got in (SLIDING, FULL)):
                raise ValueError(
                    f"layer_types[{li}] is {got!r}: with gmu_memory_layer "
                    f"{m} and cross_kv_layer {x} the hybrid decoder serves "
                    f"{MAMBA!r} on even layers up to {m}, {SLIDING!r} or "
                    f"{FULL!r} on odd layers before {x}, {FULL!r} at {x}, "
                    f"then {GMU!r} / {CROSS!r} alternating")
        if self.diff_attention and (self.num_heads % 2 or self.num_kv_heads % 2
                                    or (self.num_heads // 2)
                                    % (self.num_kv_heads // 2)):
            raise ValueError(
                f"diff_attention pairs neighbouring heads: {self.num_heads} "
                f"query and {self.num_kv_heads} key-value heads are not "
                f"whole pairs in whole groups")

    def diffusion_quota(self, step: int) -> int:
        """Masked slots the ``step``-th denoising pass of a block reveals."""
        B, steps = self.diffusion_block_length, (
            self.diffusion_steps or self.diffusion_block_length)
        return max(B // steps + (step < B % steps), 1)

    @property
    def layer_windows(self) -> Tuple[int, ...]:
        """Per layer, how many keys a query sees (itself included)."""
        return tuple(self.sliding_window if t == SLIDING else NO_WINDOW
                     for t in self.layer_types)

    @property
    def layer_rope(self) -> Tuple[bool, ...]:
        return tuple(t == SLIDING or self.rope_on_full_attention
                     for t in self.layer_types)

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def jax_dtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                "float16": jnp.float16}[self.dtype]

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0


# ---- Presets (architecture dims from the public model cards) ----

PRESETS = {
    # Tiny configs for tests / CI (CPU-friendly).
    "tiny": ModelConfig(
        name="tiny", vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, rope_theta=10000.0,
        max_model_len=512),
    "tiny-moe": ModelConfig(
        name="tiny-moe", vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, rope_theta=10000.0,
        max_model_len=512, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=96, num_shared_experts=1, first_dense_layers=1),
    # inference-scheduling default model (reference: ms-inference-scheduling values).
    "qwen3-0.6b": ModelConfig(
        name="qwen3-0.6b", vocab_size=151936, hidden_size=1024,
        intermediate_size=3072, num_layers=28, num_heads=16, num_kv_heads=8,
        head_dim=128, rope_theta=1000000.0, qk_norm=True,
        tie_word_embeddings=True, max_model_len=32768),
    # tiered-prefix-cache flagship (reference: Qwen/Qwen3-32B, tiered
    # cpu/README.md benchmark model; offloading-connector TP=2).
    "qwen3-32b": ModelConfig(
        name="qwen3-32b", vocab_size=151936, hidden_size=5120,
        intermediate_size=25600, num_layers=64, num_heads=64, num_kv_heads=8,
        head_dim=128, rope_theta=1000000.0, qk_norm=True,
        max_model_len=32768),
    "llama3-8b": ModelConfig(
        name="llama3-8b", vocab_size=128256, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        rope_theta=500000.0, max_model_len=32000),
    "llama3-70b": ModelConfig(
        name="llama3-70b", vocab_size=128256, hidden_size=8192,
        intermediate_size=28672, num_layers=80, num_heads=64, num_kv_heads=8,
        rope_theta=500000.0, max_model_len=32000),
    # Single-chip bench model (fits one v5e's HBM in bf16).
    "llama3-1b": ModelConfig(
        name="llama3-1b", vocab_size=128256, hidden_size=2048,
        intermediate_size=8192, num_layers=16, num_heads=32, num_kv_heads=8,
        head_dim=64, rope_theta=500000.0, max_model_len=8192),
    # Qwen3 MoE (no shared expert, softmax routing, qk-norm).
    "qwen3-30b-a3b": ModelConfig(
        name="qwen3-30b-a3b", vocab_size=151936, hidden_size=2048,
        intermediate_size=6144, num_layers=48, num_heads=32, num_kv_heads=4,
        head_dim=128, rope_theta=1000000.0, qk_norm=True, max_model_len=32768,
        num_experts=128, num_experts_per_tok=8, moe_intermediate_size=768),
    # SDAR: Qwen3-MoE's weight tree, generated by diffusion over blocks of 4
    # (block length, passes a block and mask id are the family's generation
    # settings: benchmarks/configs/sdar-30b-a3b.json, ``assumed``).
    "sdar-30b-a3b": ModelConfig(
        name="sdar-30b-a3b", vocab_size=151936, hidden_size=2048,
        intermediate_size=6144, num_layers=48, num_heads=32, num_kv_heads=4,
        head_dim=128, rope_theta=1000000.0, rms_norm_eps=1e-6, qk_norm=True,
        max_model_len=32768, num_experts=128, num_experts_per_tok=8,
        moe_intermediate_size=768, diffusion_block_length=4,
        diffusion_steps=4, mask_token_id=151669),
    # Falcon-H1: a Mamba-2 mixer in parallel with GQA attention in every
    # layer, dense, muP multipliers as published (the benchmark's file:
    # benchmarks/configs/falcon-h1-34b.json, where ``assumed`` lists what
    # is not a key of the published config).
    "falcon-h1-34b": ModelConfig(
        name="falcon-h1-34b", vocab_size=261120, hidden_size=5120,
        intermediate_size=21504, num_layers=72, num_heads=20, num_kv_heads=4,
        head_dim=128, rope_theta=1e11, rms_norm_eps=1e-5,
        max_model_len=32768, ssm_state_size=256, ssm_num_heads=32,
        ssm_head_dim=128, ssm_num_groups=2, ssm_conv_kernel=4,
        ssm_chunk_size=128, ssm_inner_size=4096,
        embed_scale=5.656854249492381, lm_head_multiplier=0.0078125,
        attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
        key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284)),
    # Phi-4-mini-flash-reasoning (SambaY, arXiv:2507.06607): a self-decoder
    # of Mamba-1 and window-512 differential attention layers alternating,
    # one full-attention layer (17) whose keys and values the seven cross
    # layers after it read, gated memory units on layer 16's memory between
    # them; LayerNorm, no positional encoding.  What is no key of the
    # published config is listed under ``assumed`` in
    # benchmarks/configs/phi4-mini-flash.json.
    "phi4-mini-flash": ModelConfig(
        name="phi4-mini-flash", vocab_size=200064, hidden_size=2560,
        intermediate_size=10240, num_layers=32, num_heads=40,
        num_kv_heads=20, head_dim=64, rms_norm_eps=1e-5,
        tie_word_embeddings=True, attention_bias=True,
        attention_out_bias=True, max_model_len=32768,
        layer_types=(MAMBA, SLIDING) * 8 + (MAMBA, FULL) + (GMU, CROSS) * 7,
        sliding_window=512, cross_kv_layer=17, gmu_memory_layer=16,
        ssm_state_size=16, ssm_inner_size=5120, ssm_dt_rank=160,
        ssm_conv_kernel=4, ssm_chunk_size=128, diff_attention=True,
        norm_kind="layer", use_rope=False),
    "mixtral-8x22b": ModelConfig(
        name="mixtral-8x22b", vocab_size=32768, hidden_size=6144,
        intermediate_size=16384, num_layers=56, num_heads=48, num_kv_heads=8,
        rope_theta=1000000.0, max_model_len=32000,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=16384),
    # DeepSeek-V3/R1-class MoE with MLA-proper: the KV cache holds the
    # rank-512 latent + shared 64-d RoPE key (576/token vs 32768 for the
    # round-3 GQA stand-in — the memory profile wide-EP decode relies on).
    "deepseek-v3": ModelConfig(
        name="deepseek-v3", vocab_size=129280, hidden_size=7168,
        intermediate_size=18432, num_layers=61, num_heads=128, num_kv_heads=1,
        head_dim=128, rope_theta=10000.0, max_model_len=32000,
        num_experts=256, num_experts_per_tok=8, moe_intermediate_size=2048,
        num_shared_experts=1, first_dense_layers=3, n_group=8, topk_group=4,
        routed_scaling_factor=2.5, scoring_func="sigmoid",
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128),
    # Single-chip MoE bench model: DeepSeek-V3's serving-relevant structure
    # (MLA latent cache, sigmoid+bias group-limited routing, shared expert,
    # first layer dense, top-8 of 64 routed experts) scaled so the full
    # bf16 expert set (~6 GB) fits one v5e chip's 16 GB HBM next to the KV
    # cache.  This is the model behind the north-star MoE bench number
    # (BASELINE.md: DeepSeek-R1 wide-EP >= 2.2k tok/s/chip,
    # /root/reference/README.md:20) — same per-chip serving regime (HBM
    # dominated by expert weights, all experts touched every decode step at
    # batch >= E/k), one chip instead of 32.
    "deepseek-v3-bench": ModelConfig(
        name="deepseek-v3-bench", vocab_size=32768, hidden_size=2048,
        intermediate_size=8192, num_layers=16, num_heads=16, num_kv_heads=1,
        rope_theta=10000.0, max_model_len=8192,
        num_experts=64, num_experts_per_tok=8, moe_intermediate_size=512,
        num_shared_experts=1, first_dense_layers=1, n_group=8, topk_group=4,
        routed_scaling_factor=2.5, scoring_func="sigmoid",
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128),
    # Tiny mixed-stack MoE for CPU tests: sliding and full layers 3:1, a
    # window that is no multiple of the block size, full layers without a
    # rotary embedding, gated attention, four norms a layer, scaled
    # embedding, sigmoid routing with a shared expert, two dense layers.
    "tiny-swa-moe": ModelConfig(
        name="tiny-swa-moe", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=8, num_heads=4, num_kv_heads=2,
        rope_theta=10000.0, max_model_len=512, qk_norm=True,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=96,
        num_shared_experts=1, first_dense_layers=2, scoring_func="sigmoid",
        routed_scaling_factor=2.0,
        layer_types=(SLIDING, SLIDING, SLIDING, FULL) * 2, sliding_window=48,
        rope_on_full_attention=False, attn_output_gate=True,
        sandwich_norm=True, embed_scale=8.0),
    # Tiny window / full GQA MoE for CPU tests: mellum2-12b-a2.5b's
    # mechanisms (every layer sparse, softmax top-2 of 8 renormalised, no
    # shared expert, per-head q/k norm, YaRN on the full layers only over
    # an original 64 positions, so that the tests' prompts pass it), a
    # window that is no multiple of the block size.  Its pages go by layer
    # kind (``kv_cache_groups``).
    "tiny-mellum": ModelConfig(
        name="tiny-mellum", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=8, num_heads=4, num_kv_heads=2,
        head_dim=16, rms_norm_eps=1e-6, max_model_len=512, qk_norm=True,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=96,
        layer_types=(SLIDING, SLIDING, SLIDING, FULL) * 2, sliding_window=48,
        rope_parameters={
            FULL: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
                   "original_max_position_embeddings": 64, "beta_fast": 32,
                   "beta_slow": 1, "attention_factor": 1.1386294361119891},
            SLIDING: {"rope_type": "default", "rope_theta": 10000.0}}),
    # Tiny block-diffusion MoE for CPU tests: sdar-30b-a3b's mechanisms
    # (per-head q/k norm, softmax top-2 of 8, no shared expert, blocks of 4).
    "tiny-sdar": ModelConfig(
        name="tiny-sdar", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        rope_theta=10000.0, max_model_len=512, qk_norm=True, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=96,
        diffusion_block_length=4, diffusion_steps=4, mask_token_id=511),
    # Tiny hybrid for CPU tests: a Mamba-2 mixer beside GQA attention in
    # every layer, two groups, a query group of 3 (no power of two), a scan
    # piece of 8 tokens so short prompts cross several, every multiplier
    # set and none equal to another.
    "tiny-ssm": ModelConfig(
        name="tiny-ssm", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=6, num_kv_heads=2,
        head_dim=16, rope_theta=10000.0, max_model_len=512,
        ssm_state_size=16, ssm_num_heads=4, ssm_head_dim=8,
        ssm_num_groups=2, ssm_conv_kernel=4, ssm_chunk_size=8,
        embed_scale=5.5, lm_head_multiplier=0.125,
        attention_in_multiplier=1.25, attention_out_multiplier=0.0625,
        key_multiplier=0.25, ssm_in_multiplier=0.5,
        ssm_out_multiplier=0.09375,
        ssm_multipliers=(0.375, 0.25, 0.1875, 0.5, 0.3125),
        mlp_multipliers=(0.1875, 0.03125)),
    # Tiny decoder-hybrid-decoder for CPU tests: all five layer kinds, a
    # window (24) under the tests' prompts, a scan piece of 8 tokens, an
    # inner width that is not twice the hidden one, 4 states, a query group
    # of 2 pairs a key-value pair.
    "tiny-hybrid-decoder": ModelConfig(
        name="tiny-hybrid-decoder", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=10, num_heads=8, num_kv_heads=4,
        head_dim=8, max_model_len=512, tie_word_embeddings=True,
        attention_bias=True, attention_out_bias=True,
        layer_types=(MAMBA, SLIDING) * 2 + (MAMBA, FULL) + (GMU, CROSS) * 2,
        sliding_window=24, cross_kv_layer=5, gmu_memory_layer=4,
        ssm_state_size=4, ssm_inner_size=96, ssm_dt_rank=6,
        ssm_conv_kernel=4, ssm_chunk_size=8, diff_attention=True,
        norm_kind="layer", use_rope=False),
    # Tiny mixed MLA stack for CPU tests: kinds F F S S S F with a leading
    # dense layer, two latent geometries (heads, ranks, head sizes and rotary
    # base all differ), a top-k and a window both under the tests' contexts,
    # headwise gates, rescaled latents, a quarter of 8 experts held.
    "tiny-sparse-mla": ModelConfig(
        name="tiny-sparse-mla", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=6, num_heads=4, num_kv_heads=4,
        rope_theta=10000.0, max_model_len=512, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=96,
        num_shared_experts=1, first_dense_layers=1, scoring_func="sigmoid",
        num_local_experts=2, first_local_expert=2,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        layer_types=(FULL, FULL, SLIDING, SLIDING, SLIDING, FULL),
        sliding_window=21, swa_num_heads=2, swa_q_lora_rank=24,
        swa_kv_lora_rank=48, swa_qk_nope_head_dim=24,
        swa_qk_rope_head_dim=8, swa_v_head_dim=16, swa_rope_theta=500.0,
        mla_lora_rescale=True, attn_head_gate=True,
        index_topk=16, index_n_heads=4, index_head_dim=16),
    # Tiny linear-attention / MLA MoE for CPU tests: kinds L L L L F L L
    # behind one leading dense layer (one period of 5 : 1 and a layer), a
    # key size that is not the value size, a group-limited sigmoid router
    # of which a quarter of 8 experts is held, no query latent.
    "tiny-linear-moe": ModelConfig(
        name="tiny-linear-moe", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=7, num_heads=4, num_kv_heads=1,
        rope_theta=10000.0, rms_norm_eps=1e-6, max_model_len=512,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=96,
        num_shared_experts=1, first_dense_layers=1, scoring_func="sigmoid",
        n_group=4, topk_group=2, routed_scaling_factor=2.5,
        num_local_experts=2, first_local_expert=0,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16,
        layer_types=(LINEAR,) * 4 + (FULL,) + (LINEAR,) * 2,
        lin_num_heads=4, lin_key_dim=16, lin_value_dim=8),
    # Tiny MLA+MoE config for CPU tests.
    "tiny-mla": ModelConfig(
        name="tiny-mla", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=1,
        rope_theta=10000.0, max_model_len=512, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=96,
        num_shared_experts=1, first_dense_layers=1,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16),
}


def get_config(name: str) -> ModelConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown model preset '{name}' (have {sorted(PRESETS)})")
    return PRESETS[name]
