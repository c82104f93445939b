"""Plain reference of the afmoe family (Trinity-Mini among them): a stack that
mixes sliding-window and full attention layers, as published
(modeling_afmoe.py, followed from its description; there is no network here):

  - the token embedding is multiplied by ``embed_scale`` (sqrt(hidden) under
    ``mup_enabled``);
  - grouped-query attention with an RMS norm over each head of q and k; the
    rotary embedding is applied on ``sliding_attention`` layers only, and those
    see keys j with i - sliding_window < j <= i, ``full_attention`` layers see
    all j <= i and carry no positional embedding;
  - the attention output is multiplied by sigmoid(h W_gate) before o_proj;
  - four norms a layer: before and after the attention, before and after the
    MLP (dense SwiGLU in the leading layers, shared + routed experts after).

Its own decoder loop and its own attention; of ``plain.py`` it takes the
norm, the rotary embedding, SwiGLU and the routed experts.  It reads the
program's parameter tree and ``ModelConfig`` and nothing else of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import references.plain as plain
from references.plain import F32


def masked_attention(q, k, v, mask, scale):
    """q, k, v [T, H, D], mask [T, T] -> [T, H, D], one head at a time."""

    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.where(mask, (qh @ kh.T) * scale, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    out = jax.lax.map(head, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    return jnp.swapaxes(out, 0, 1)


def attention(lp, c, h, pos, sliding):
    """One layer's attention on the normed input ``h``; ``sliding`` is the
    layer's kind (a traced bool under the MoE layers' scan)."""
    T, H, KVH, D = h.shape[0], c.num_heads, c.num_kv_heads, c.head_dim_
    q = plain.rms((h @ lp["q_proj"].astype(F32)).reshape(T, H, D),
                  lp["q_norm"], c.rms_norm_eps)
    k = plain.rms((h @ lp["k_proj"].astype(F32)).reshape(T, KVH, D),
                  lp["k_norm"], c.rms_norm_eps)
    v = (h @ lp["v_proj"].astype(F32)).reshape(T, KVH, D)
    rotate = sliding | c.rope_on_full_attention
    q = jnp.where(rotate, plain.rope(q, pos, c.rope_theta), q)
    k = jnp.where(rotate, plain.rope(k, pos, c.rope_theta), k)
    i, j = pos[:, None], pos[None, :]
    mask = (j <= i) & (~sliding | (j > i - c.sliding_window))
    a = masked_attention(q, jnp.repeat(k, H // KVH, axis=1),
                         jnp.repeat(v, H // KVH, axis=1), mask, D ** -0.5)
    a = a.reshape(T, H * D) * jax.nn.sigmoid(h @ lp["attn_gate"].astype(F32))
    return a @ lp["o_proj"].astype(F32)


def layer(lp, c, x, pos, sliding, mlp):
    eps = c.rms_norm_eps
    x = x + plain.rms(attention(lp, c, plain.rms(x, lp["input_norm"], eps),
                                pos, sliding), lp["attn_out_norm"], eps)
    return x + plain.rms(mlp(plain.rms(x, lp["post_attn_norm"], eps)),
                         lp["mlp_out_norm"], eps)


def tail_logprobs(params, config, tokens, k):
    c = config
    sliding = jnp.asarray([t == "sliding_attention" for t in c.layer_types])
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = params["embed"][tokens].astype(F32) * c.embed_scale
        nd = c.first_dense_layers
        for i in range(nd):
            lp = {name: leaf[i] for name, leaf in
                  params["dense_layers"].items()}
            x = layer(lp, c, x, pos, sliding[i], lambda h: plain.swiglu(
                h, lp["gate_proj"], lp["up_proj"], lp["down_proj"]))

        # A scan only to bound memory, as in plain.decoder.
        def moe_layer(x, xs):
            lp, kind = xs
            return layer(lp, c, x, pos, kind,
                         lambda h: plain.experts(lp, c, h)), None

        x, _ = jax.lax.scan(moe_layer, x,
                            (params["moe_layers"], sliding[nd:]))
        h = plain.rms(x[-k:], params["final_norm"], c.rms_norm_eps)
        return jax.nn.log_softmax(h @ params["lm_head"].astype(F32))
