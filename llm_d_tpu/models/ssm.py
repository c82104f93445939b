"""Dense decoder with a state-space mixer beside attention in every layer
(the Falcon-H1 family: Mamba-2 in parallel with GQA attention).

    x = norm(h);  h = h + attn(x * a_in) * a_out + mixer(x * s_in) * s_out
    h = h + mlp(norm(h))

The attention block, the MLP, the embedding and the output head are
``models.llama``'s (with the muP multipliers ``ModelConfig`` carries); this
module adds the mixer and the layer that joins the two.  The mixer's
recurrent state and its convolution's tail ride the layer scan's carry
beside the paged cache, as two more entries of the ``kv_cache`` dict
(``ssm``, ``conv``: the engine's state pool, ops/ssm.py), updated in place.

The mixer of one layer, on its input ``x`` [T, D]:

    u = (x W_in) * mup_vector       -> gate z, x, B, C, dt  (five segments)
    xBC = silu(causal_conv1d(u[x, B, C]))
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t;  y_t = S_t C_t + D x_t
    y = rms_norm_by_group(y * silu(z)) * norm_weight;   out = y W_out
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from llm_d_tpu.models import llama
from llm_d_tpu.models.config import ModelConfig
from llm_d_tpu.models.llama import (  # noqa: F401  (the model interface)
    Params, compute_logits, draft_propose, init_draft_params,
    kv_cache_layers, kv_cache_layout, kv_cache_spec, sharding_rules)
from llm_d_tpu.ops import layers as L
from llm_d_tpu.ops import ssm as ssm_ops
from llm_d_tpu.ops.attention import with_query_tiles
from llm_d_tpu.ops.parts import part

F32 = jnp.float32
# Keys of the state pool in the ``kv_cache`` dict, beside ``k`` and ``v``.
STATE_KEYS = ("ssm", "conv")
# The pool's dtype is ONE, not an option: float32.  A bf16 state rounds the
# whole state again at every token (benchmarks/tools/ssm_mechanism_check.py).
STATE_DTYPE = jnp.float32


def mup_vector(c: ModelConfig) -> np.ndarray:
    """The published multipliers over the five segments of the mixer's
    input projection (gate, x, B, C, dt), as one vector of its width."""
    gn = c.ssm_num_groups * c.ssm_state_size
    widths = (c.ssm_inner_size_, c.ssm_inner_size_, gn, gn, c.ssm_num_heads)
    return np.concatenate([np.full(w, m, np.float32)
                           for w, m in zip(widths, c.ssm_multipliers)])


def state_pool_shapes(c: ModelConfig, slots: int
                      ) -> Dict[str, jax.ShapeDtypeStruct]:
    """The state pool of ``slots`` sequence slots (slot 0 the trash slot
    included): per layer and slot the recurrent state [H, N, P] and the
    convolution's last K - 1 inputs."""
    return {
        "ssm": jax.ShapeDtypeStruct(
            (c.num_layers, slots, c.ssm_num_heads, c.ssm_state_size,
             c.ssm_head_dim), STATE_DTYPE),
        "conv": jax.ShapeDtypeStruct(
            (c.num_layers, slots, c.ssm_conv_kernel - 1,
             c.ssm_conv_channels), c.jax_dtype)}


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random weights for tests and benchmarks, every leaf MADE in the
    model's dtype (a float32 copy of a 2.7 B embedding and head would not
    fit beside the finished weights on one chip).  Fan-in scaled normals,
    and each matrix a multiplier follows is scaled by the multiplier's
    inverse, so that what comes out of it is of order one as in the trained
    model: with ``lm_head_multiplier`` 1/128 on plain fan-in weights every
    logit would be 0.01 and every log-probability log(1 / V)."""
    c = config
    dt, dh, Lc, D = c.jax_dtype, c.head_dim_, c.num_layers, c.hidden_size
    di, H = c.ssm_inner_size_, c.ssm_num_heads
    k = iter(jax.random.split(key, 24))

    def w(shape, scale=1.0, fan_in=None):
        fan_in = fan_in or shape[-2]
        return (jax.random.normal(next(k), shape, dt)
                * jnp.asarray(scale * fan_in ** -0.5, dt)).astype(dt)

    a_in, a_out, s_in, s_out = (
        c.attention_in_multiplier, c.attention_out_multiplier,
        c.ssm_in_multiplier, c.ssm_out_multiplier)
    in_scale = jnp.asarray(1.0 / (s_in * mup_vector(c)), dt)
    layers = {
        "input_norm": jnp.ones((Lc, D), dt),
        "q_proj": w((Lc, D, c.num_heads * dh), 1.0 / a_in),
        "k_proj": w((Lc, D, c.num_kv_heads * dh),
                    1.0 / (a_in * c.key_multiplier)),
        "v_proj": w((Lc, D, c.num_kv_heads * dh), 1.0 / a_in),
        "o_proj": w((Lc, c.num_heads * dh, D), 1.0 / a_out),
        "post_attn_norm": jnp.ones((Lc, D), dt),
        "gate_proj": w((Lc, D, c.intermediate_size),
                       1.0 / c.mlp_multipliers[0]),
        "up_proj": w((Lc, D, c.intermediate_size)),
        "down_proj": w((Lc, c.intermediate_size, D),
                       1.0 / c.mlp_multipliers[1]),
        "ssm_in_proj": (w((Lc, D, in_scale.shape[0])) * in_scale).astype(dt),
        "ssm_conv_w": w((Lc, c.ssm_conv_channels, c.ssm_conv_kernel),
                        fan_in=c.ssm_conv_kernel),
        "ssm_conv_b": w((Lc, c.ssm_conv_channels), 0.1, fan_in=1),
        # dt from 0.001 to 0.1 and A from -1 to -16, as Mamba-2 starts them:
        # some heads forget within ten tokens, some remember thousands.
        "ssm_dt_bias": _inverse_softplus(jnp.exp(jax.random.uniform(
            next(k), (Lc, H), F32, np.log(1e-3), np.log(1e-1)))),
        "ssm_A_log": jnp.log(jax.random.uniform(
            next(k), (Lc, H), F32, 1.0, 16.0)),
        "ssm_D": jnp.ones((Lc, H), F32),
        "ssm_norm": jnp.ones((Lc, di), dt),
        "ssm_out_proj": w((Lc, di, D), 1.0 / s_out),
    }
    return {
        "embed": w((c.vocab_size, D), 1.0 / c.embed_scale, fan_in=1),
        "layers": layers,
        "final_norm": jnp.ones((D,), dt),
        "lm_head": w((D, c.vocab_size), 1.0 / c.lm_head_multiplier),
    }


def _inverse_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


def mixer_block(lp: Params, config: ModelConfig, x: jax.Array,
                batch: Dict[str, jax.Array], state: Tuple[jax.Array, jax.Array],
                layer: jax.Array, backend: str
                ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One layer's mixer on its (scaled) input ``x`` [T, D].  ``state`` is
    the whole pool (``ssm``, ``conv``); plane ``layer`` is updated in
    place.  Returns (out [T, D], state')."""
    c = config
    T = x.shape[0]
    H, P, N, G = (c.ssm_num_heads, c.ssm_head_dim, c.ssm_state_size,
                  c.ssm_num_groups)
    di, gn = c.ssm_inner_size_, G * N
    ssm, conv = state
    with part("ssm.proj"):
        u = (L.linear(x, lp["ssm_in_proj"])
             * jnp.asarray(mup_vector(c), x.dtype)).astype(x.dtype)
        z, xbc, dt = (u[:, :di], u[:, di:2 * di + 2 * gn],
                      u[:, 2 * di + 2 * gn:])
    with part("ssm.state"):
        xbc, conv = ssm_ops.causal_conv(
            xbc, lp["ssm_conv_w"], lp["ssm_conv_b"], conv, batch, layer)
        xs = xbc[:, :di].reshape(T, H, P)
        B = xbc[:, di:di + gn].reshape(T, G, N)
        C = xbc[:, di + gn:].reshape(T, G, N)
        dt = jax.nn.softplus(dt.astype(F32) + lp["ssm_dt_bias"].astype(F32))
        y, ssm = ssm_ops.state_update(
            xs, dt, -jnp.exp(lp["ssm_A_log"].astype(F32)), B, C, lp["ssm_D"],
            ssm, batch, layer, c.ssm_chunk_size, backend)
    with part("ssm.proj"):
        # Gate, then an RMS norm over each group's share of the inner width.
        y = y.reshape(T, di).astype(F32) * jax.nn.silu(z.astype(F32))
        yg = y.reshape(T, G, di // G)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(yg * yg, axis=-1, keepdims=True) + c.rms_norm_eps)
        y = (yg.reshape(T, di) * lp["ssm_norm"].astype(F32)).astype(x.dtype)
        return L.linear(y, lp["ssm_out_proj"]), (ssm, conv)


def forward(
    params: Params,
    kv_cache: Dict[str, jax.Array],   # k, v [L, slots, KVH*dh]; ssm, conv
    batch: Dict[str, jax.Array],
    config: ModelConfig,
    block_size: int,
    attn_backend: str = "auto",
    mesh=None,
    moe_opts=None,                    # unused
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One engine step over a ragged batch, as ``models.llama.forward``:
    (hidden states of the sampling positions [S, D], the cache and the
    state pool updated)."""
    c = config
    x = llama.embed_tokens(params, batch["token_ids"], c)
    with part("tiles"):
        batch = with_query_tiles(
            batch, c.num_heads, kv_cache["k"].shape[-1], attn_backend, mesh)

    def scaled(h, m):
        return h if m == 1.0 else (h * m).astype(h.dtype)

    def layer_body(carry, lp):
        h, caches, state, li = carry
        with part("attn.proj"):
            hn = L.rms_norm(h, lp["input_norm"], c.rms_norm_eps)
            ha = scaled(hn, c.attention_in_multiplier)
        a, caches = llama.attention_block(
            lp, c, ha, batch, caches, block_size, attn_backend, layer=li,
            mesh=mesh)
        with part("ssm.proj"):
            hs = scaled(hn, c.ssm_in_multiplier)
        m, state = mixer_block(lp, c, hs, batch, state, li, attn_backend)
        with part("mlp"):
            h = h + scaled(a, c.attention_out_multiplier) \
                + scaled(m, c.ssm_out_multiplier)
            h = h + llama.dense_mlp(lp, c, h)
        return (h, caches, state, li + 1), None

    with part("scan"):
        (x, caches, state, _), _ = jax.lax.scan(
            layer_body,
            (x, (kv_cache["k"], kv_cache["v"]),
             tuple(kv_cache[name] for name in STATE_KEYS), jnp.int32(0)),
            params["layers"])
    return llama.sampled_hidden(params, x, batch, c), dict(
        zip(("k", "v") + STATE_KEYS, caches + state))
