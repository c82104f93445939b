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

    @property
    def use_mla(self) -> bool:
        return self.kv_lora_rank > 0

    def __post_init__(self):
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(
                f"scoring_func must be 'softmax' or 'sigmoid', "
                f"got {self.scoring_func!r}")
        # A JSON list arrives here: a tuple keeps the config hashable.
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
