"""Plain reference of the Qwen3 / Qwen3-MoE family: grouped-query attention
with an RMS norm over each head of q and k (``qk_norm``) before the rotary
embedding, as published (modeling_qwen3_moe.py)."""

from __future__ import annotations

import jax.numpy as jnp

import references.plain as plain
from references.plain import F32


def attention(lp, c, x, pos):
    T, H, KVH, D = x.shape[0], c.num_heads, c.num_kv_heads, c.head_dim_
    q = (x @ lp["q_proj"].astype(F32)).reshape(T, H, D)
    k = (x @ lp["k_proj"].astype(F32)).reshape(T, KVH, D)
    v = (x @ lp["v_proj"].astype(F32)).reshape(T, KVH, D)
    if c.qk_norm:
        q = plain.rms(q, lp["q_norm"], c.rms_norm_eps)
        k = plain.rms(k, lp["k_norm"], c.rms_norm_eps)
    q = plain.rope(q, pos, c.rope_theta)
    k = plain.rope(k, pos, c.rope_theta)
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    out = plain.causal_attention(q, k, v, D ** -0.5)
    return out.reshape(T, H * D) @ lp["o_proj"].astype(F32)


def tail_logprobs(params, config, tokens, k):
    return plain.decoder(params, config, tokens, k, attention)
