"""Plain reference of the DeepSeek-V2/V3 family (kanana-2 among them):
multi-head latent attention in its UNABSORBED form, as published: the latent
is expanded to per-head keys and values.  The program serves the
weight-absorbed form over a latent cache, so the two share no code path."""

from __future__ import annotations

import jax.numpy as jnp

import references.plain as plain
from references.plain import F32


def attention(lp, c, x, pos):
    T, H = x.shape[0], c.num_heads
    nope, rope, vd, R = (c.qk_nope_head_dim, c.qk_rope_head_dim,
                         c.v_head_dim, c.kv_lora_rank)
    if "q_a_proj" in lp:
        cq = plain.rms(x @ lp["q_a_proj"].astype(F32), lp["q_a_norm"],
                       c.rms_norm_eps)
        q = cq @ lp["q_b_proj"].astype(F32)
    else:
        q = x @ lp["q_proj"].astype(F32)
    q = q.reshape(T, H, nope + rope)
    kv_a = x @ lp["kv_a_proj"].astype(F32)
    c_kv = plain.rms(kv_a[:, :R], lp["kv_a_norm"], c.rms_norm_eps)
    k_pe = plain.rope(kv_a[:, R:].reshape(T, 1, rope), pos, c.rope_theta)
    q_pe = plain.rope(q[..., nope:], pos, c.rope_theta)
    kv = (c_kv @ lp["kv_b_proj"].astype(F32)).reshape(T, H, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (T, H, rope))], -1)
    qq = jnp.concatenate([q[..., :nope], q_pe], -1)
    out = plain.causal_attention(qq, k, kv[..., nope:],
                                 (nope + rope) ** -0.5)
    return out.reshape(T, H * vd) @ lp["o_proj"].astype(F32)


def tail_logprobs(params, config, tokens, k):
    return plain.decoder(params, config, tokens, k, attention)
