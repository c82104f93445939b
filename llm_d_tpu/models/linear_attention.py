"""The LINEAR layer's mixer: gated delta-rule linear attention in place of a
layer's attention, inside the MLA + MoE stack that ``models.moe`` walks
(``ModelConfig.layer_types`` names the layers; the Kimi Delta Attention
form, arXiv:2510.26692).

On the layer's normed input ``x`` [T, D], with H heads of key size K and
value size V:

    q | k | v = silu(causal_conv(x W_qkv))       (depthwise, no bias)
    q = q / |q| K^-1/2,   k = k / |k|             (per head)
    g = floor sigmoid(exp(A_log[h]) (x W_f + dt_bias))   in (floor, 0)
    beta = sigmoid(x W_b)                         (per head)
    S <- Diag(exp g) S;  S <- S + beta k (v - S^T k)^T;  o = S^T q
    y = (rms_norm(o; weight [V]) sigmoid(x W_g)) W_o

The recurrent state [H, K, V] float32 and the convolution's last inputs
live in the engine's state pool, two more entries of the ``kv_cache`` dict
(``ssm``, ``conv``), a plane a LINEAR layer; ops/linear_attention.py has the
recurrence, its chunked form and the pool's life cycle.  No rotary
embedding: the recurrence carries the order.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from llm_d_tpu.models.config import LINEAR, ModelConfig
from llm_d_tpu.models.ssm import STATE_DTYPE, STATE_KEYS  # noqa: F401
from llm_d_tpu.ops import layers as L
from llm_d_tpu.ops import linear_attention as lin_ops
from llm_d_tpu.ops import ssm as ssm_ops
from llm_d_tpu.ops.parts import part

F32 = jnp.float32
Params = Dict[str, jax.Array]
# Under the square root of the q / k L2 norms.
L2_EPS = 1e-6


def state_pool_shapes(c: ModelConfig, slots: int
                      ) -> Dict[str, jax.ShapeDtypeStruct]:
    """The state pool of ``slots`` sequence slots (slot 0 the trash slot
    included): per LINEAR layer and slot the recurrent state [H, K, V] and
    the last kernel - 1 inputs of the convolution over q | k | v."""
    n = c.layer_types.count(LINEAR)
    return {
        "ssm": jax.ShapeDtypeStruct(
            (n, slots, c.lin_num_heads, c.lin_key_dim, c.lin_value_dim),
            STATE_DTYPE),
        "conv": jax.ShapeDtypeStruct(
            (n, slots, c.lin_conv_kernel - 1, c.lin_conv_channels),
            c.jax_dtype)}


def param_shapes(c: ModelConfig, n: int) -> Dict[str, Tuple[int, ...]]:
    H, K, V, D = c.lin_num_heads, c.lin_key_dim, c.lin_value_dim, c.hidden_size
    return {
        "lin_qkv_proj": (n, D, c.lin_conv_channels),    # q | k | v
        "lin_conv_w": (n, c.lin_conv_channels, c.lin_conv_kernel),
        "lin_f_proj": (n, D, H * K),
        "lin_dt_bias": (n, H * K),
        "lin_A_log": (n, H),
        "lin_b_proj": (n, D, H),
        "lin_g_proj": (n, D, H),                # the output gate, a head
        "lin_o_norm": (n, V),
        "lin_o_proj": (n, H * V, D),
    }


def init_params(c: ModelConfig, n: int, key: jax.Array, dt) -> Params:
    """Random weights of ``n`` LINEAR mixers, every matrix MADE in the
    model's dtype, fan-in scaled.  The decay's three leaves are drawn so
    that a token's log-decay spans about -0.001 to -0.5 over heads and
    channels (half-lives from a few tokens to thousands), as a trained
    model's does: on plain normals g is about floor / 2 everywhere, the
    state forgets within two tokens and nothing a check reads would depend
    on what a chunk or a slot carried."""
    shapes = param_shapes(c, n)
    H, K = c.lin_num_heads, c.lin_key_dim
    ks = iter(jax.random.split(key, 8))

    def w(name, scale=1.0, fan_in=None):
        shape = shapes[name]
        fan_in = fan_in or shape[-2]
        return (jax.random.normal(next(ks), shape, dt)
                * jnp.asarray(scale * fan_in ** -0.5, dt)).astype(dt)

    rate = jax.random.uniform(next(ks), (n, H), F32, np.log(0.5), np.log(2.0))
    share = jnp.exp(jax.random.uniform(
        next(ks), (n, H, K), F32, np.log(1e-3), np.log(0.5))) \
        / -c.lin_gate_floor                 # sigmoid(.) that gives the decay
    bias = (jnp.log(share) - jnp.log1p(-share)) / jnp.exp(rate)[..., None]
    return {
        "lin_qkv_proj": w("lin_qkv_proj"),
        "lin_conv_w": w("lin_conv_w", fan_in=c.lin_conv_kernel),
        "lin_f_proj": w("lin_f_proj", 0.5),
        "lin_dt_bias": bias.reshape(n, H * K),
        "lin_A_log": rate,
        "lin_b_proj": w("lin_b_proj"),
        "lin_g_proj": w("lin_g_proj"),
        "lin_o_norm": jnp.ones(shapes["lin_o_norm"], dt),
        "lin_o_proj": w("lin_o_proj"),
    }


def mixer_block(lp: Params, config: ModelConfig, x: jax.Array,
                batch: Dict[str, jax.Array],
                state: Tuple[jax.Array, jax.Array], layer: jax.Array,
                backend: str
                ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One LINEAR layer's mixer on its normed input ``x`` [T, D].  ``state``
    is the whole pool (``ssm``, ``conv``); plane ``layer`` (of the LINEAR
    layers) is updated in place.  Returns (out [T, D], state')."""
    c = config
    T = x.shape[0]
    H, K, V = c.lin_num_heads, c.lin_key_dim, c.lin_value_dim
    ssm, conv = state
    with part("lin.proj"):
        qkv = L.linear(x, lp["lin_qkv_proj"])
        # The decay and the write strength stay in float32 from the dot on:
        # a bf16 pre-activation of -8 is +-0.03, 3 % of a decay.
        f = jnp.dot(x, lp["lin_f_proj"], preferred_element_type=F32)
        rate = jnp.exp(lp["lin_A_log"].astype(F32))[None, :, None]
        g = c.lin_gate_floor * jax.nn.sigmoid(
            rate * (f + lp["lin_dt_bias"].astype(F32)).reshape(T, H, K))
        beta = jax.nn.sigmoid(
            jnp.dot(x, lp["lin_b_proj"], preferred_element_type=F32))
        gate = jax.nn.sigmoid(
            jnp.dot(x, lp["lin_g_proj"], preferred_element_type=F32))
    with part("lin.state"):
        qkv, conv = ssm_ops.causal_conv(
            qkv, lp["lin_conv_w"], jnp.zeros((qkv.shape[1],), F32), conv,
            batch, layer)
        q, k, v = (a.reshape(T, H, -1).astype(F32) for a in jnp.split(
            qkv, (H * K, 2 * H * K), axis=1))

        def unit(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)

        o, ssm = lin_ops.state_update(
            unit(q) * K ** -0.5, unit(k), v, g, beta, ssm, batch, layer,
            lin_ops.PIECE, backend)
    with part("lin.proj"):
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + c.rms_norm_eps)
        o = o * lp["lin_o_norm"].astype(F32) * gate[..., None]
        return (L.linear(o.reshape(T, H * V).astype(x.dtype),
                         lp["lin_o_proj"]), (ssm, conv))
