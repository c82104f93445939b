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
from typing import Optional, Tuple

import jax.numpy as jnp

SLIDING, FULL = "sliding_attention", "full_attention"
# The window of a full-attention layer: one that never binds (above any
# position, with room to add a position without overflowing int32).
NO_WINDOW = 1 << 30
# Which masked slots of a block a denoising pass reveals, q the pass's quota:
# the first q from the left; the q of highest confidence; every slot whose
# confidence passes the threshold if they are at least q, else the q highest.
DIFFUSION_REMASKING = ("sequential", "low_confidence_static",
                       "low_confidence_dynamic")


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
    # sigmoid(h W_gate) multiplies the attention output before o_proj.
    attn_output_gate: bool = False
    # RMS norms on the attention and MLP outputs too, before the residual.
    sandwich_norm: bool = False
    embed_scale: float = 1.0                # multiplies the token embedding
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

    @property
    def use_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def has_recurrent_state(self) -> bool:
        """A sequence's state is not keys and values alone."""
        return self.ssm_state_size > 0

    @property
    def ssm_inner_size_(self) -> int:
        return self.ssm_inner_size or self.ssm_num_heads * self.ssm_head_dim

    @property
    def ssm_conv_channels(self) -> int:
        """x, B and C: what the mixer's causal convolution runs over."""
        return (self.ssm_inner_size_
                + 2 * self.ssm_num_groups * self.ssm_state_size)

    def __post_init__(self):
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(
                f"scoring_func must be 'softmax' or 'sigmoid', "
                f"got {self.scoring_func!r}")
        # A JSON list arrives here: a tuple keeps the config hashable.  And
        # a JSON integer: a rope_theta of 1e11 does not fit the int32 a
        # weakly typed Python int becomes inside a traced function.
        object.__setattr__(self, "rope_theta", float(self.rope_theta))
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        kinds = self.layer_types
        if kinds:
            if len(kinds) != self.num_layers or set(kinds) - {SLIDING, FULL}:
                raise ValueError(
                    f"layer_types must name {self.num_layers} layers as "
                    f"{SLIDING!r} or {FULL!r}, got {len(kinds)}: {kinds}")
            if SLIDING in kinds and self.sliding_window < 1:
                raise ValueError("sliding layers need sliding_window >= 1")
        if self.use_mla and (kinds or self.attn_output_gate
                             or self.sandwich_norm):
            raise ValueError(
                "layer_types, attn_output_gate and sandwich_norm belong to "
                "the GQA attention block; the MLA path has none of them")

        for name in ("ssm_multipliers", "mlp_multipliers"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.has_recurrent_state:
            if (self.use_mla or kinds or self.diffusion_block_length
                    or self.is_moe):
                raise ValueError(
                    "a state-space mixer is served beside the GQA attention "
                    "block of a dense autoregressive stack with every layer "
                    "full; MLA, layer_types, block diffusion and experts "
                    "are not")
            H, G = self.ssm_num_heads, self.ssm_num_groups
            if (H < 1 or self.ssm_head_dim < 1 or G < 1 or H % G
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
