"""The equations that the benchmark's plain references share.

Straightforward ``jax.numpy`` in float32
(``jax.default_matmul_precision("highest")``): no kernels, no cache, no
batching, one sequence.  It reads nothing of the program but its parameter
tree (weights are data; the int8 experts are dequantised as ``q * scale``,
which is their meaning):

  - RMS norm, rotary embedding in the rotate-half layout, causal softmax
    attention one head at a time;
  - SwiGLU MLP in leading dense layers, routed experts elsewhere: softmax or
    sigmoid scores (+ selection bias), top-k, renormalised, scaled; shared
    experts added;
  - the decoder stack around a family's attention, untied output head.

Departures from the published models, all forced by random weights: none in
the equations.  ``rope_interleave`` checkpoints store q/k rope columns
interleaved; with seeded weights that is a permutation of columns.

A family's module (``qwen3_moe.py``, ``deepseek_mla_moe.py``) supplies the
attention of one layer and calls :func:`decoder`.
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x, positions, theta):
    """x [T, H, D]; rotate-half pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    f = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, scale):
    """q [T, H, Dq], k [T, H, Dq], v [T, H, Dv] -> [T, H, Dv].  One head at
    a time (``lax.map``) only to bound memory: a [H, T, T] score tensor of a
    2,300-token prompt would not fit beside a served engine."""
    T = q.shape[0]
    mask = jnp.tril(jnp.ones((T, T), bool))

    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.where(mask, (qh @ kh.T) * scale, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    out = jax.lax.map(head, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    return jnp.swapaxes(out, 0, 1)


def swiglu(x, g, u, d):
    return (jax.nn.silu(x @ g.astype(F32)) * (x @ u.astype(F32))) \
        @ d.astype(F32)


def experts(lp, c, x):
    """Routed experts of one MoE layer, all tokens through every expert with
    a [T, E] combine matrix: wasteful and plain."""
    logits = x @ lp["router"].astype(F32)
    if c.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores + (lp["e_bias"].astype(F32)[None]
                           if "e_bias" in lp else 0.0)
    else:
        scores = jax.nn.softmax(logits, -1)
        choice = scores
    _, idx = jax.lax.top_k(choice, c.num_experts_per_tok)
    w = jnp.take_along_axis(scores, idx, 1)
    if c.moe_renormalize:
        w = w / w.sum(-1, keepdims=True)
    w = w * c.routed_scaling_factor
    combine = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], idx].add(w)          # [T, E]

    def weight(name, e):
        if f"{name}_q" in lp:
            return lp[f"{name}_q"][e].astype(F32) * lp[f"{name}_s"][e]
        return lp[name][e].astype(F32)

    def one(acc, e):
        y = swiglu(x, weight("w_gate", e), weight("w_up", e),
                   weight("w_down", e))
        return acc + combine[:, e][:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          jnp.arange(c.num_experts))
    if "shared_gate" in lp:
        out = out + swiglu(x, lp["shared_gate"], lp["shared_up"],
                           lp["shared_down"])
    return out


def decoder(params, c, tokens, k: int,
            attn: Callable[[Dict[str, Any], Any, jax.Array, jax.Array],
                           jax.Array]) -> jax.Array:
    """The decoder stack around ``attn(layer_params, config, x, positions)``:
    float32 log-probabilities [k, V] of the token after each of the last
    ``k`` positions of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = params["embed"][tokens].astype(F32)
        dense = params["dense_layers"] if c.is_moe else params["layers"]
        for i in range(c.first_dense_layers if c.is_moe else c.num_layers):
            lp = {name: leaf[i] for name, leaf in dense.items()}
            x = x + attn(lp, c, rms(x, lp["input_norm"], c.rms_norm_eps),
                         pos)
            x = x + swiglu(rms(x, lp["post_attn_norm"], c.rms_norm_eps),
                           lp["gate_proj"], lp["up_proj"], lp["down_proj"])
        if c.is_moe:
            # A scan, not a Python loop, only to bound memory: unrolled,
            # XLA keeps a copy of every layer's expert slices alive at once
            # (4.7 GB at 8 layers, beside a chip filled by the server).
            def moe_layer(x, lp):
                x = x + attn(lp, c, rms(x, lp["input_norm"],
                                        c.rms_norm_eps), pos)
                return x + experts(lp, c, rms(x, lp["post_attn_norm"],
                                              c.rms_norm_eps)), None

            x, _ = jax.lax.scan(moe_layer, x, params["moe_layers"])
        h = rms(x[-k:], params["final_norm"], c.rms_norm_eps)
        head = params.get("lm_head")
        logits = h @ (head.astype(F32) if head is not None
                      else params["embed"].astype(F32).T)
        return jax.nn.log_softmax(logits)
