"""Dense decoder-only transformer (Llama / Qwen2 / Qwen3 families).

Functional forward over a plain parameter pytree with layers *stacked* on a
leading axis and iterated with ``lax.scan`` — one traced layer body instead
of L inlined copies keeps XLA compile time flat in depth (important under
continuous batching where several batch buckets each compile).

This is the model half of the vLLM-equivalent engine (reference:
docker/Dockerfile.cuda:61-63 pins the fork of vLLM this replaces).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from llm_d_tpu.models.config import NO_WINDOW, SLIDING, ModelConfig
from llm_d_tpu.ops import layers as L
from llm_d_tpu.ops.attention import (
    attention_with_kv_update, with_block_visibility, with_query_tiles)
from llm_d_tpu.ops.parts import attn_part, part

Params = Dict[str, Any]


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random-init parameters (tests / benchmarks); HF checkpoints load via
    ``llm_d_tpu.models.loader``."""
    c = config
    dh = c.head_dim_
    dt = c.jax_dtype
    k = iter(jax.random.split(key, 16))

    def w(shape, kk):
        return (jax.random.normal(kk, shape, jnp.float32)
                * (shape[0] ** -0.5)).astype(dt)

    Lc = c.num_layers

    def stacked(shape, kk):
        return (jax.random.normal(kk, (Lc, *shape), jnp.float32)
                * (shape[0] ** -0.5)).astype(dt)

    params: Params = {
        "embed": w((c.vocab_size, c.hidden_size), next(k)),
        "layers": {
            "input_norm": jnp.ones((Lc, c.hidden_size), dt),
            "q_proj": stacked((c.hidden_size, c.num_heads * dh), next(k)),
            "k_proj": stacked((c.hidden_size, c.num_kv_heads * dh), next(k)),
            "v_proj": stacked((c.hidden_size, c.num_kv_heads * dh), next(k)),
            "o_proj": stacked((c.num_heads * dh, c.hidden_size), next(k)),
            "post_attn_norm": jnp.ones((Lc, c.hidden_size), dt),
            "gate_proj": stacked((c.hidden_size, c.intermediate_size), next(k)),
            "up_proj": stacked((c.hidden_size, c.intermediate_size), next(k)),
            "down_proj": stacked((c.intermediate_size, c.hidden_size), next(k)),
        },
        "final_norm": jnp.ones((c.hidden_size,), dt),
    }
    if c.attention_bias:
        params["layers"]["q_bias"] = jnp.zeros((Lc, c.num_heads * dh), dt)
        params["layers"]["k_bias"] = jnp.zeros((Lc, c.num_kv_heads * dh), dt)
        params["layers"]["v_bias"] = jnp.zeros((Lc, c.num_kv_heads * dh), dt)
    if c.qk_norm:
        params["layers"]["q_norm"] = jnp.ones((Lc, dh), dt)
        params["layers"]["k_norm"] = jnp.ones((Lc, dh), dt)
    if c.attn_output_gate:
        params["layers"]["attn_gate"] = stacked(
            (c.hidden_size, c.num_heads * dh), next(k))
    if c.sandwich_norm:
        params["layers"]["attn_out_norm"] = jnp.ones((Lc, c.hidden_size), dt)
        params["layers"]["mlp_out_norm"] = jnp.ones((Lc, c.hidden_size), dt)
    if not c.tie_word_embeddings:
        params["lm_head"] = w((c.hidden_size, c.vocab_size), next(k))
    return params


def with_layer_tables(batch, config: ModelConfig):
    """Once a step program, outside the layer scan, what a layer looks up
    by its traced index: the rotary tables of each rule of a stack whose
    rotary rule goes by layer kind (``rope_tables``: cos and sin
    [R, T, D/2]), and for a cache in groups by layer kind each layer's
    first page in the one buffer (``layer_page0`` [L]: layer l's region
    holds the pages of its kind's group, ``kv_group_blocks`` of them, after
    those of the layers before it).  Whether a window stack's pages go by
    groups is the ENGINE's answer, from its limits and what it was asked
    for (``engine.derive_group_blocks``), so the config alone cannot say:
    the engine's static ``BatchLayout.groups`` is what brings
    ``block_tables_w``, and with it this form of the program.  A batch and
    a config with neither come back as they are."""
    c = config
    if c.rope_rules:
        with part("attn.proj"):
            batch = dict(batch, rope_tables=L.rope_tables(
                batch["positions"], c.head_dim_, c.rope_rules))
    if "block_tables_w" in batch:
        with part("tiles"):
            pages = batch["kv_group_blocks"][jnp.asarray(
                [t == SLIDING for t in c.layer_types], jnp.int32)]
            batch = dict(batch, layer_page0=jnp.cumsum(pages) - pages)
    return batch


def attention_block(
    lp: Params, config: ModelConfig, x: jax.Array, batch: Dict[str, jax.Array],
    caches: Tuple[jax.Array, jax.Array], block_size: int, attn_backend: str,
    layer: jax.Array = None, mesh=None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Shared by dense and MoE models. Returns (attn_out, caches').

    ``caches`` is (k, v).  With ``layer`` the caches are the full stacked
    [L, slots, F] buffers updated in place (see
    ops.attention.attention_with_kv_update).

    A mixed stack (``config.layer_types``) runs every layer through this
    one traced body: the window and the rotary rule of layer ``layer`` are
    looked up in per-layer tables by the traced index."""
    c = config
    dh = c.head_dim_
    T = x.shape[0]

    with part("attn.proj"):
        q = L.linear(x, lp["q_proj"], lp.get("q_bias")).reshape(
            T, c.num_heads, dh)
        kx = L.linear(x, lp["k_proj"], lp.get("k_bias")).reshape(
            T, c.num_kv_heads, dh)
        vx = L.linear(x, lp["v_proj"], lp.get("v_bias")).reshape(
            T, c.num_kv_heads, dh)
        if c.qk_norm:
            q = L.rms_norm(q, lp["q_norm"], c.rms_norm_eps)
            kx = L.rms_norm(kx, lp["k_norm"], c.rms_norm_eps)
        if c.key_multiplier != 1.0:
            kx = (kx * c.key_multiplier).astype(kx.dtype)

        if c.rope_rules:
            # The rule of the layer's kind (YaRN on some): the tables are
            # the step program's (``with_layer_tables``).
            tables = batch.get("rope_tables")
            if tables is None:
                tables = L.rope_tables(batch["positions"], dh, c.rope_rules)
            cos, sin = (t[jnp.asarray(c.layer_rope_rule, jnp.int32)[layer]]
                        for t in tables)
        else:
            cos, sin = L.rope_cos_sin(batch["positions"], dh, c.rope_theta)
        window = None
        if c.layer_types:
            window = jnp.asarray(c.layer_windows, jnp.int32)[layer]
        plane = layer       # of the stacked cache: the layer's own
        if "block_tables_w" in batch:
            # Pages in groups by layer kind (engine/kv_cache.py): the table
            # and the write slots of the layer's group, in the layer's own
            # region of the one buffer, which has one plane.
            own = window < NO_WINDOW        # a window layer
            page0 = batch["layer_page0"][layer]
            batch = dict(
                batch,
                block_tables=jnp.where(own, batch["block_tables_w"],
                                       batch["block_tables"]) + page0,
                slot_mapping=jnp.where(own, batch["slot_mapping_w"],
                                       batch["slot_mapping"])
                + page0 * block_size)
            plane = jnp.zeros_like(layer)
        if all(c.layer_rope):
            q = L.apply_rope(q, cos, sin)
            kx = L.apply_rope(kx, cos, sin)
        else:
            rope = jnp.asarray(c.layer_rope)[layer]
            q = jnp.where(rope, L.apply_rope(q, cos, sin), q)
            kx = jnp.where(rope, L.apply_rope(kx, cos, sin), kx)

    with part(attn_part(batch)):
        attn, *new_caches = attention_with_kv_update(
            q, kx, vx, caches[0], caches[1], batch,
            block_size=block_size, backend=attn_backend, layer=plane,
            mesh=mesh, window=window)
    with part("attn.proj"):
        attn = attn.reshape(T, c.num_heads * dh)
        if "attn_gate" in lp:
            attn = attn * jax.nn.sigmoid(L.linear(x, lp["attn_gate"]))
        out = L.linear(attn, lp["o_proj"])
        if "attn_out_norm" in lp:
            out = L.rms_norm(out, lp["attn_out_norm"], c.rms_norm_eps)
    return out, tuple(new_caches)


def embed_tokens(params: Params, token_ids: jax.Array,
                 config: ModelConfig) -> jax.Array:
    with part("embed"):
        x = params["embed"][token_ids]
        if config.embed_scale != 1.0:
            x = (x * config.embed_scale).astype(x.dtype)
    return x


def mlp_out(lp: Params, config: ModelConfig, m: jax.Array) -> jax.Array:
    """What the MLP (dense or experts) adds to the residual stream."""
    if "mlp_out_norm" in lp:
        return L.rms_norm(m, lp["mlp_out_norm"], config.rms_norm_eps)
    return m


def dense_mlp(lp: Params, config: ModelConfig, h: jax.Array) -> jax.Array:
    x = L.rms_norm(h, lp["post_attn_norm"], config.rms_norm_eps)
    gate_mult, out_mult = config.mlp_multipliers
    if (gate_mult, out_mult) == (1.0, 1.0):
        return mlp_out(lp, config, L.swiglu_mlp(
            x, lp["gate_proj"], lp["up_proj"], lp["down_proj"]))
    # muP: the gate's pre-activation and the MLP's output are scaled.
    gate = (L.linear(x, lp["gate_proj"]) * gate_mult).astype(x.dtype)
    m = L.linear(jax.nn.silu(gate) * L.linear(x, lp["up_proj"]),
                 lp["down_proj"])
    return mlp_out(lp, config, (m * out_mult).astype(m.dtype))


def dense_layer(lp: Params, config: ModelConfig, h: jax.Array, attend):
    """One dense layer around ``attend(normed input) -> (out, aux)``, each
    operation in its part (ops/parts.py): the norm that feeds attention is
    ``attn.proj``'s, the residual additions are ``mlp``'s.  Returns (h',
    aux)."""
    with part("attn.proj"):
        hn = L.rms_norm(h, lp["input_norm"], config.rms_norm_eps)
    a, aux = attend(hn)
    with part("mlp"):
        h = h + a
        return h + dense_mlp(lp, config, h), aux


def sampled_hidden(params: Params, x: jax.Array, batch, config: ModelConfig,
                   stacked: bool = False) -> jax.Array:
    """The final norm and the rows the head runs on: only sampling
    positions need logits."""
    with part("head"):
        x = L.rms_norm(x, params["final_norm"], config.rms_norm_eps)
        if stacked:
            return jnp.take_along_axis(
                x, batch["sample_idx"][..., None], axis=1)   # [dp, S_l, D]
        return x[batch["sample_idx"]]                        # [S, D]


def forward(
    params: Params,
    kv_cache: Dict[str, jax.Array],   # {"k","v": [L, num_slots, KVH*dh]}
    batch: Dict[str, jax.Array],
    config: ModelConfig,
    block_size: int,
    attn_backend: str = "auto",
    mesh=None,                        # Pallas attention runs per tp shard
    moe_opts=None,                    # unused (MoE dispatch knobs)
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One engine step over a ragged batch.

    Returns (hidden states for sampling positions [S, D], updated kv cache);
    a block-diffusion model's batch names every slot of every row's block in
    ``sample_idx``, so [S * B, D].

    SPMD dp (stacked mode): when batch arrays carry a leading [dp] dim
    (``token_ids.ndim == 2``), attention runs per dp shard under
    ``parallel.dp_attention.dp_attend`` and the sample gather is batched —
    returns [dp, S_l, D].  Everything else is shape-polymorphic over the
    leading dim.
    """
    c = config
    stacked = batch["token_ids"].ndim == 2
    x = embed_tokens(params, batch["token_ids"], c)  # [T, D] / [dp, T_l, D]

    caches0 = (kv_cache["k"], kv_cache["v"])
    # Once a step program, outside the layer scan: a block-diffusion model's
    # visibility limits, and the query tile list the Pallas prefill kernels
    # walk in every layer.
    with part("tiles"):
        batch = with_query_tiles(
            with_block_visibility(batch, c.diffusion_block_length),
            c.num_heads, caches0[0].shape[-1], attn_backend, mesh)
    batch = with_layer_tables(batch, c)

    # The FULL stacked KV cache rides the scan carry and each layer updates
    # its plane in place (Pallas aliasing / scatter-at-layer): slicing the
    # cache into per-layer xs/ys moved 2x the whole cache through HBM every
    # step (~10 ms at 1B scale) — the dominant decode cost before this.
    def attend(lp, hn, caches, ab, li):
        return attention_block(
            lp, c, hn, ab, caches, block_size, attn_backend, layer=li,
            mesh=mesh)

    def layer_body(carry, lp):
        h, caches, li = carry

        def attend_normed(hn):
            if stacked:
                from llm_d_tpu.parallel.dp_attention import dp_attend
                return dp_attend(attend, mesh, lp, hn, caches, batch, li)
            return attend(lp, hn, caches, batch, li)

        h, caches = dense_layer(lp, c, h, attend_normed)
        return (h, caches, li + 1), None

    with part("scan"):
        (x, caches, _), _ = jax.lax.scan(
            layer_body, (x, caches0, jnp.int32(0)), params["layers"])

    return (sampled_hidden(params, x, batch, c, stacked),
            dict(zip(("k", "v"), caches)))


def compute_logits(params: Params, hidden: jax.Array, config: ModelConfig) -> jax.Array:
    with part("head"):
        head = params.get("lm_head")
        if head is None:                              # tied embeddings
            head = params["embed"].T
        logits = jnp.dot(hidden, head, preferred_element_type=jnp.float32)
        if config.lm_head_multiplier != 1.0:
            logits = logits * config.lm_head_multiplier
    return logits


def init_draft_params(config: ModelConfig, key: jax.Array) -> Params:
    """MTP-style drafter head (DeepSeek-V3 multi-token prediction shape,
    scaled to one module): combine the last hidden state with the
    embedding of the token just sampled through a ``[2D, D]`` projection
    plus one SwiGLU MLP, share the target's embedding / lm_head for the
    draft logits, and reuse the SAME module at every draft depth.  Kept
    OUTSIDE the target param tree (separate pytree in the engine) so
    quantization, EPLB, PD weight paths and HF loading never see it."""
    c = config
    dt = c.jax_dtype
    D, I = c.hidden_size, c.intermediate_size
    k = iter(jax.random.split(key, 4))

    def w(shape, kk):
        return (jax.random.normal(kk, shape, jnp.float32)
                * (shape[0] ** -0.5)).astype(dt)

    return {
        "h_norm": jnp.ones((D,), dt),
        "e_norm": jnp.ones((D,), dt),
        "proj": w((2 * D, D), next(k)),
        "mlp_norm": jnp.ones((D,), dt),
        "gate_proj": w((D, I), next(k)),
        "up_proj": w((D, I), next(k)),
        "down_proj": w((I, D), next(k)),
    }


def draft_propose(params: Params, draft_params: Params, hidden: jax.Array,
                  last_ids: jax.Array, K: int,
                  config: ModelConfig) -> jax.Array:
    """Greedy MTP rollout: propose ``K`` draft ids from the last hidden
    state + the just-sampled token.

    ``hidden`` [S, D] is the target trunk's output at the position that
    sampled ``last_ids`` [S] — each depth folds the previous draft's
    embedding back in (h, t) -> h' -> shared-head logits -> argmax.
    Drafts are greedy regardless of the request's sampling params: the
    verifier only ever compares them against the target's own samples,
    so draft sampling noise would cost acceptance and buy nothing."""
    c = config
    dp = draft_params

    def one(carry, _):
        h, tok = carry
        e = params["embed"][tok].astype(h.dtype)
        x = jnp.concatenate(
            [L.rms_norm(h, dp["h_norm"], c.rms_norm_eps),
             L.rms_norm(e, dp["e_norm"], c.rms_norm_eps)], axis=-1)
        h2 = jnp.dot(x, dp["proj"])
        h2 = h2 + L.swiglu_mlp(
            L.rms_norm(h2, dp["mlp_norm"], c.rms_norm_eps),
            dp["gate_proj"], dp["up_proj"], dp["down_proj"])
        nxt = jnp.argmax(compute_logits(params, h2, c),
                         axis=-1).astype(jnp.int32)
        return (h2, nxt), nxt

    (_, _), ids = jax.lax.scan(one, (hidden, last_ids.astype(jnp.int32)),
                               None, length=K)
    return jnp.swapaxes(ids, 0, 1)                   # [K, S] -> [S, K]


def sharding_rules(config: ModelConfig):
    """(path-regex, PartitionSpec) table for TP over the mesh's ``tp`` axis.

    Column-parallel q/k/v/gate/up (+ lm_head), row-parallel o/down — the
    Megatron layout the reference gets from vLLM's NCCL TP, expressed as
    sharding annotations for XLA to lower onto ICI.
    Stacked layer weights carry a leading L dim (hence leading None).
    """
    return [
        (r"embed", P(None, "tp")),
        (r"layers/((q|k|v)_proj|attn_gate)", P(None, None, "tp")),
        (r"layers/(q|k|v)_bias", P(None, "tp")),
        (r"layers/(gate|up)_proj", P(None, None, "tp")),
        (r"layers/o_proj", P(None, "tp", None)),
        (r"layers/down_proj", P(None, "tp", None)),
        (r"lm_head", P(None, "tp")),
        # norms replicate (matched by default rule)
    ]


def kv_cache_layout(config: ModelConfig) -> Dict[str, int]:
    """Per-buffer cache row widths (folded [KVH*D] layout)."""
    w = config.num_kv_heads * config.head_dim_
    return {"k": w, "v": w}


def kv_cache_layers(config: ModelConfig) -> Dict[str, int]:
    """Layers each cache buffer holds rows of: every buffer all of them."""
    return dict.fromkeys(kv_cache_layout(config), config.num_layers)


def kv_cache_spec(config: ModelConfig = None) -> Dict[str, P]:
    """KV cache sharding: folded head dim over tp (per-head D-blocks stay
    contiguous when tp divides num_kv_heads), slots replicated."""
    return {"k": P(None, None, "tp"), "v": P(None, None, "tp")}
