"""MoE decoder-only transformer (Mixtral / DeepSeek-V3 families).

Same functional design as ``models.llama`` (stacked layers + ``lax.scan``)
with the MLP replaced by shared + routed experts.  DeepSeek-style models run
their first ``first_dense_layers`` layers dense, so the stack scans two
parameter groups: ``dense_layers`` then ``moe_layers`` (the KV cache is one
[L, slots, KVH*D] buffer split at the boundary).

This is the model half of the wide-EP path (reference:
guides/wide-ep-lws/manifests/modelserver/base/decode.yaml:76-132 — EP flags,
EPLB, DeepEP backends; the engine equivalents live in ``ops.moe``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from llm_d_tpu.models.config import ModelConfig
from llm_d_tpu.models.llama import (  # noqa: F401  (re-exports: the MoE
    # model shares the dense family's logits head and MTP drafter — the
    # drafter reads only embed/lm_head from the target params, which both
    # families carry identically)
    attention_block, compute_logits, dense_layer, draft_propose,
    embed_tokens, init_draft_params, mlp_out, sampled_hidden)
from llm_d_tpu.ops import layers as L
from llm_d_tpu.ops import moe as moe_ops
from llm_d_tpu.ops.attention import (
    with_block_visibility, with_query_tiles)
from llm_d_tpu.ops.parts import part
from llm_d_tpu.parallel.mesh import AXIS_EP

Params = Dict[str, Any]


def _attn_params(c: ModelConfig, n: int, key, dt) -> Params:
    dh = c.head_dim_
    k = iter(jax.random.split(key, 8))

    def stacked(shape, kk):
        return (jax.random.normal(kk, (n, *shape), jnp.float32)
                * (shape[0] ** -0.5)).astype(dt)

    p = {
        "input_norm": jnp.ones((n, c.hidden_size), dt),
        "q_proj": stacked((c.hidden_size, c.num_heads * dh), next(k)),
        "k_proj": stacked((c.hidden_size, c.num_kv_heads * dh), next(k)),
        "v_proj": stacked((c.hidden_size, c.num_kv_heads * dh), next(k)),
        "o_proj": stacked((c.num_heads * dh, c.hidden_size), next(k)),
        "post_attn_norm": jnp.ones((n, c.hidden_size), dt),
    }
    if c.attention_bias:
        p["q_bias"] = jnp.zeros((n, c.num_heads * dh), dt)
        p["k_bias"] = jnp.zeros((n, c.num_kv_heads * dh), dt)
        p["v_bias"] = jnp.zeros((n, c.num_kv_heads * dh), dt)
    if c.qk_norm:
        p["q_norm"] = jnp.ones((n, dh), dt)
        p["k_norm"] = jnp.ones((n, dh), dt)
    if c.attn_output_gate:
        p["attn_gate"] = stacked((c.hidden_size, c.num_heads * dh), next(k))
    if c.sandwich_norm:
        p["attn_out_norm"] = jnp.ones((n, c.hidden_size), dt)
        p["mlp_out_norm"] = jnp.ones((n, c.hidden_size), dt)
    return p


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    c = config
    dt = c.jax_dtype
    Ld = c.first_dense_layers
    Lm = c.num_layers - Ld
    E, Im = c.num_experts, c.moe_intermediate_size
    Ish = Im * c.num_shared_experts
    k = iter(jax.random.split(key, 16))

    def w(shape, kk):
        return (jax.random.normal(kk, shape, jnp.float32)
                * (shape[-2] ** -0.5)).astype(dt)

    def attn_params(n, kk):
        if c.use_mla:
            from llm_d_tpu.models.mla import init_mla_params
            p = init_mla_params(c, n, kk, dt)
            p["input_norm"] = jnp.ones((n, c.hidden_size), dt)
            p["post_attn_norm"] = jnp.ones((n, c.hidden_size), dt)
            return p
        return _attn_params(c, n, kk, dt)

    dense = attn_params(Ld, next(k))
    dense.update({
        "gate_proj": w((Ld, c.hidden_size, c.intermediate_size), next(k)),
        "up_proj": w((Ld, c.hidden_size, c.intermediate_size), next(k)),
        "down_proj": w((Ld, c.intermediate_size, c.hidden_size), next(k)),
    })
    moe = attn_params(Lm, next(k))
    moe.update({
        "router": w((Lm, c.hidden_size, E), next(k)).astype(jnp.float32),
        "w_gate": w((Lm, E, c.hidden_size, Im), next(k)),
        "w_up": w((Lm, E, c.hidden_size, Im), next(k)),
        "w_down": w((Lm, E, Im, c.hidden_size), next(k)),
    })
    if c.scoring_func == "sigmoid":
        moe["e_bias"] = jnp.zeros((Lm, E), jnp.float32)
    if c.num_shared_experts > 0:
        moe.update({
            "shared_gate": w((Lm, c.hidden_size, Ish), next(k)),
            "shared_up": w((Lm, c.hidden_size, Ish), next(k)),
            "shared_down": w((Lm, Ish, c.hidden_size), next(k)),
        })
    params: Params = {
        "embed": w((c.vocab_size, c.hidden_size), next(k)),
        "dense_layers": dense,
        "moe_layers": moe,
        "final_norm": jnp.ones((c.hidden_size,), dt),
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = w((c.hidden_size, c.vocab_size), next(k))
    return params


def forward(
    params: Params,
    kv_cache: Dict[str, jax.Array],   # {"k","v": [L, slots, KVH*dh]}
    batch: Dict[str, jax.Array],
    config: ModelConfig,
    block_size: int,
    attn_backend: str = "auto",
    mesh: Optional[Mesh] = None,
    collect_routed: bool = False,   # also return [Lm, T, k] routed ids (EPLB)
    moe_opts: Optional[Dict] = None,   # {"dbo_{decode,prefill}_min_tokens"}
    collect_moe_trace: bool = False,   # also return per-MoE-layer dispatch
                                       # inputs (the collective accuracy
                                       # harness's real-trace capture)
    count_touched: bool = False,    # also return, last, the distinct routed
                                    # experts the step's real token rows
                                    # select, summed over the MoE layers
):
    c = config
    Ld = c.first_dense_layers
    stacked = batch["token_ids"].ndim == 2
    x = embed_tokens(params, batch["token_ids"], c)   # [T, D] / [dp, T_l, D]
    cache_keys = ("kv",) if c.use_mla else ("k", "v")
    # Once a step program, outside both layer scans: a block-diffusion
    # model's visibility limits, and the query tile list the Pallas prefill
    # kernels walk in every layer.
    with part("tiles"):
        batch = with_query_tiles(
            with_block_visibility(batch, c.diffusion_block_length),
            c.num_heads, kv_cache[cache_keys[0]].shape[-1], attn_backend,
            mesh, mla=c.use_mla)
        real = None
        if count_touched:
            # The token bucket's real rows lie first; a padded query slot
            # names row T.
            T = batch["token_ids"].shape[-1]
            real = jnp.arange(T) < jnp.sum(batch["qtok_idx"] < T)
    # DBO threshold by phase: the program's query width is static under jit,
    # and Q == 1 holds exactly for pure-decode programs (single-step or
    # fused).  None (no opts) lets the op consult its standalone env vars;
    # -1 disables DBO outright.
    is_decode = batch["qtok_idx"].shape[-1] == 1
    dbo_min_tokens = (moe_opts or {}).get(
        "dbo_decode_min_tokens" if is_decode else "dbo_prefill_min_tokens")

    def attend_local(lp, hn, caches, ab, li):
        """Attention dispatch: MLA (single latent buffer) or classic GQA."""
        if c.use_mla:
            from llm_d_tpu.models.mla import mla_attention_block
            a, kv = mla_attention_block(
                lp, c, hn, ab, caches[0], block_size, attn_backend,
                layer=li, mesh=mesh)
            return a, (kv,)
        return attention_block(
            lp, c, hn, ab, caches, block_size, attn_backend, layer=li,
            mesh=mesh)

    def attend(lp, hn, caches, li):
        """Stacked mode: per-dp-shard attention (manual dp, auto tp) —
        the dp half of the wide-EP regime; see parallel.dp_attention."""
        if stacked:
            from llm_d_tpu.parallel.dp_attention import dp_attend
            return dp_attend(attend_local, mesh, lp, hn, caches, batch, li)
        return attend_local(lp, hn, caches, batch, li)

    def moe_tokens(hn):
        """[dp, T_l, D] -> [dp*T_l, D] for EP dispatch: the merged token
        dim stays dp-sharded (row-major reshape is shard-local), so the
        a2a's in_specs re-slice only within each dp group."""
        return hn.reshape(-1, hn.shape[-1]) if stacked else hn

    # Full stacked KV cache rides both scans' carries; each layer updates its
    # plane in place (see models.llama.forward) — no split/concat copies.
    def dense_body(carry, lp):
        h, caches, li = carry
        h, caches = dense_layer(
            lp, c, h, lambda hn: attend(lp, hn, caches, li))
        return (h, caches, li + 1), None

    def moe_body(carry, lp):
        h, caches, li = carry
        with part("attn.proj"):
            hn = L.rms_norm(h, lp["input_norm"], c.rms_norm_eps)
        a, caches = attend(lp, hn, caches, li)
        with part("router"):
            h = h + a
            hn = L.rms_norm(h, lp["post_attn_norm"], c.rms_norm_eps)
            ht = moe_tokens(hn)                   # [T, D] (dp-sharded rows)
            weights, idx = moe_ops.route(
                jnp.dot(ht.astype(jnp.float32), lp["router"]), c,
                e_bias=lp.get("e_bias"))
            touched = (moe_ops.experts_touched(idx, real, c.num_experts)
                       if real is not None else None)
            phys_idx = idx
            if "replica_table" in lp:
                # EPLB: route to a physical replica of the logical expert
                # (round-robin over its replicas; parallel.eplb plans the
                # table per layer — the layer index phases the walk so every
                # layer doesn't start on replica 0).
                phys_idx = moe_ops.to_physical_experts(
                    idx, lp["replica_table"], lp["num_replicas"],
                    phase=li - Ld)
        if quant_stacked is not None:
            # int8 payloads travel to the op STACKED (closure, not scan
            # xs — a scan slice feeding pallas_call would materialize a
            # per-layer copy) with the MoE-layer plane index; on TPU
            # they reach the Pallas int8 kernel family without a
            # materialized dequant — dense streaming / fused-routing
            # routed / chunk-streamed by batch regime on one device
            # (ops/pallas/moe_int8.py, moe_routed.py,
            # moe_routed_stream.py), and the chunk-streamed kernel per
            # dispatch chunk on the a2a EP mesh path.
            quant = dict(quant_stacked, layer=li - Ld)
            w_gate = w_up = w_down = None
        else:
            quant = None
            w_gate, w_up, w_down = lp["w_gate"], lp["w_up"], lp["w_down"]
        with part("experts"):
            m = moe_ops.expert_ffn(
                ht, weights, phys_idx, w_gate, w_up, w_down, mesh=mesh,
                dbo_min_tokens=dbo_min_tokens, quant=quant)
            if stacked:
                m = m.reshape(hn.shape)
        if "shared_gate" in lp:
            with part("shared"):
                m = m + L.swiglu_mlp(hn, lp["shared_gate"], lp["shared_up"],
                                     lp["shared_down"])
        with part("mlp"):
            h = h + mlp_out(lp, c, m)
        if collect_moe_trace:
            # The EXACT operands the EP dispatch ships: the rms-normed
            # hidden rows plus the routing the combine applies — what the
            # collective accuracy harness measures quantization against
            # (ops/collective_accuracy.py).
            return (h, caches, li + 1), ({
                "x": ht, "weights": weights, "idx": phys_idx}, touched)
        return (h, caches, li + 1), (idx, touched)

    ml = params["moe_layers"]
    quant_keys = ("w_gate_q", "w_gate_s", "w_up_q", "w_up_s",
                  "w_down_q", "w_down_s")
    quant_stacked = ({k: ml[k] for k in quant_keys}
                     if "w_gate_q" in ml else None)
    moe_scan_params = ({k: v for k, v in ml.items() if k not in quant_keys}
                       if quant_stacked is not None else ml)

    caches0 = tuple(kv_cache[k] for k in cache_keys)
    with part("scan"):
        (x, caches, li), _ = jax.lax.scan(
            dense_body, (x, caches0, jnp.int32(0)), params["dense_layers"])
        (x, caches, _), (routed, touched) = jax.lax.scan(
            moe_body, (x, caches, li), moe_scan_params)

    out = (sampled_hidden(params, x, batch, c, stacked),
           dict(zip(cache_keys, caches)))
    if collect_moe_trace or collect_routed:
        # The harness's real routed trace {"x": [Lm, T, H], "weights":
        # [Lm, T, k], "idx": [Lm, T, k]} (see moe_body), or the [Lm, T, k]
        # logical ids for the engine's EPLB LoadTracker.
        out += (routed,)
    if count_touched:
        with part("router"):
            out += (jnp.sum(touched),)
    return out


def sharding_rules(config: ModelConfig):
    """TP for attention/shared experts (Megatron layout), EP over the
    flattened (dp, sp, tp) axes for routed experts — the wide-EP regime
    ("TPxDP in attention, EP in MoE layers"; reference decode.yaml:76,87)."""
    rules = [
        (r"embed", P(None, "tp")),
        (r"layers/((q|k|v)_proj|attn_gate)", P(None, None, "tp")),
        (r"layers/(q|k|v)_bias", P(None, "tp")),
        (r"layers/o_proj", P(None, "tp", None)),
        (r"dense_layers/(gate|up)_proj", P(None, None, "tp")),
        (r"dense_layers/down_proj", P(None, "tp", None)),
        (r"moe_layers/router", P()),
        (r"moe_layers/w_(gate|up|down)", P(None, AXIS_EP)),
        (r"moe_layers/shared_(gate|up)", P(None, None, "tp")),
        (r"moe_layers/shared_down", P(None, "tp", None)),
        (r"lm_head", P(None, "tp")),
    ]
    if config.use_mla:
        from llm_d_tpu.models.mla import mla_sharding_rules
        rules = mla_sharding_rules() + rules
    return rules


def kv_cache_layout(config: ModelConfig) -> Dict[str, int]:
    """Per-buffer cache row widths.  MLA caches ONE latent row per token
    (kv_lora_rank + rope, lane-padded) — for V3 that is 640 values vs
    32768 for materialized heads, the memory profile wide-EP decode
    relies on.

    The MLA row ALWAYS lane-pads to a multiple of 128 (V3: 576 -> 640,
    +11%): the Pallas decode kernel's page DMAs need the alignment, zero
    columns are score-neutral (models/mla.py), and deriving the width
    from config alone keeps the PD KV-transfer wire format identical
    across backends (a CPU prefiller can feed a TPU decoder)."""
    if config.use_mla:
        w = config.kv_lora_rank + config.qk_rope_head_dim
        return {"kv": -(-w // 128) * 128}
    return {"k": config.num_kv_heads * config.head_dim_,
            "v": config.num_kv_heads * config.head_dim_}


def kv_cache_spec(config: Optional[ModelConfig] = None) -> Dict[str, P]:
    if config is not None and config.use_mla:
        # The latent row is shared by all (tp-sharded) heads: replicate.
        return {"kv": P()}
    return {"k": P(None, None, "tp"), "v": P(None, None, "tp")}
