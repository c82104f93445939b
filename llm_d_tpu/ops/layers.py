"""Transformer building blocks (functional, shard-friendly).

Pure functions over explicit parameter dicts: no framework modules, so
pjit/shard_map see plain pytrees and XLA fuses elementwise work into the
surrounding matmuls (MXU-friendly: keep matmuls in bf16 with f32
accumulation via ``preferred_element_type``).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def rope_cos_sin(
    positions: jax.Array,      # [T] i32
    head_dim: int,
    theta: float = 10000.0,
    scaling_factor: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """Rotary embedding tables for the given absolute positions: [T, D/2]."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    pos = positions.astype(jnp.float32) / scaling_factor
    freqs = pos[:, None] * inv_freq[None, :]
    return jnp.cos(freqs), jnp.sin(freqs)


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float, beta_slow: float):
    """YaRN's inverse frequencies [D/2] (numpy, float64: constants of the
    step program).  Dimension i turns ``original_max / (2 pi theta^(2i/d))``
    times over the original context; one that turns ``beta_fast`` times or
    more keeps theta^(-2i/d), one that turns ``beta_slow`` times or fewer
    takes it over ``factor``, and between the two correction dimensions
    (floor and ceiling, held inside 0 .. d/2 - 1) the blend is linear."""
    import math

    import numpy as np

    def correction_dim(rotations: float) -> float:
        return (head_dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001           # the published guard against a 0 / 0
    pos_freqs = theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                          / head_dim)
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    return (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)


def rope_tables(positions: jax.Array, head_dim: int, rules
                ) -> Tuple[jax.Array, jax.Array]:
    """cos and sin [R, T, D/2] of each of the ``rules``
    (``models.config.RopeRule``), computed once a step program; a layer
    takes the pair of its kind by its traced index."""
    pos = positions.astype(jnp.float32)
    cos, sin = [], []
    for rule in rules:
        if not rule.factor:
            c, s = rope_cos_sin(positions, head_dim, rule.theta)
        else:
            inv_freq = jnp.asarray(yarn_inv_freq(
                head_dim, rule.theta, rule.factor, rule.original_max,
                rule.beta_fast, rule.beta_slow), jnp.float32)
            freqs = pos[:, None] * inv_freq[None, :]
            c = jnp.cos(freqs) * rule.attention_factor
            s = jnp.sin(freqs) * rule.attention_factor
        cos.append(c)
        sin.append(s)
    return jnp.stack(cos), jnp.stack(sin)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (HF 'half-rotation' convention). x: [T, H, D]."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos = cos[:, None, :].astype(x1.dtype)
    sin = sin[:, None, :].astype(x1.dtype)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def linear(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    """x: [..., in], w: [in, out] (row-major for clean TP column sharding)."""
    y = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def swiglu_mlp(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               w_down: jax.Array) -> jax.Array:
    gate = linear(x, w_gate)
    up = linear(x, w_up)
    return linear(jax.nn.silu(gate) * up, w_down)
