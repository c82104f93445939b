"""Decoder-hybrid-decoder (the SambaY family: Phi-4-mini-flash-reasoning):
a stack whose layers are NOT alike in the state they hold.

    every layer:  h = x + Mixer(LN1(x));   x' = h + MLP(LN2(h))

with the mixer by layer kind (``ModelConfig.layer_types``):

  self-decoder, layers 0 .. gmu_memory_layer (even: MAMBA, odd: SLIDING or
  FULL differential attention), walked as ONE ``lax.scan`` over (mixer,
  attention) PAIRS:
    MAMBA    [a, z] = W_in u;  c = silu(conv1d(a) + b);  [r, B, C] = W_x c;
             dt = softplus(W_dt r + b_dt);  A = -exp(A_log) [N, inner];
             S_t = exp(dt_t A) S_{t-1} + B_t (x) (dt_t c_t);
             y_t = C_t . S_t + D c_t;  out = W_out (y * silu(z)).
             A slot of the state pool holds S [N, inner] float32 and the
             convolution's last K - 1 inputs (ops/ssm.py).
    SLIDING  differential attention over the last ``sliding_window`` keys;
             the layer's keys and values go to its plane of the paged cache.
  ``gmu_memory_layer`` (MAMBA) also hands on the MEMORY m_t = y_t, before
  the gate.  ``cross_kv_layer`` (FULL, the next one) writes the keys and
  values of EVERY token of the step to its plane, the cross-decoder's cache.

  cross-decoder, from ``cross_kv_layer``'s own attention on: ONLY THE ROWS
  THE STEP SAMPLES FROM (``sample_idx``; a prompt chunk that samples
  nothing puts nothing useful through it).  Nothing after that layer's
  keys and values writes state, so this is exact: each of these attentions
  is ONE query a row over ``cross_kv_layer``'s cached keys up to the row's
  position, the step's own chunk included.  One ``lax.scan`` over (GMU,
  CROSS) pairs:
    GMU      out = W_2 (m_t * silu(W_1 u)),  m_t the memory of the SAME token
    CROSS    q = W_q u only; differential attention of q over
             ``cross_kv_layer``'s plane; W_o.  No key, value or cache.

Differential attention (``ops.attention.diff_pair_queries``): neighbouring
heads pair, a pair is fed to the GQA kernels as one head of twice the size,
o = P_1 V - lambda P_2 V, lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
lambda_init(layer), then RMSNorm over the pair's 2 D values times (1 -
lambda_init).  No positional encoding anywhere; LayerNorm with bias.

Parameters go by kind, each group stacked over its layers: ``mamba_layers``
and ``attn_layers`` (the self-decoder's pairs), ``memory_layer`` and
``kv_layer`` (one layer each, unstacked), ``gmu_layers`` and
``cross_layers``.  The cache buffers hold a plane a layer that WRITES one
(``kv_cache_layers``), the state pool a plane a MAMBA layer.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from llm_d_tpu.models import llama
from llm_d_tpu.models.config import FULL, MAMBA, NO_WINDOW, SLIDING, ModelConfig
from llm_d_tpu.models.llama import (  # noqa: F401  (the model interface)
    Params, compute_logits, draft_propose, init_draft_params)
from llm_d_tpu.models.ssm import STATE_DTYPE, STATE_KEYS, _inverse_softplus
from llm_d_tpu.ops import layers as L
from llm_d_tpu.ops import ssm as ssm_ops
from llm_d_tpu.ops.attention import (
    attention_one_query, attention_with_kv_update, diff_combine,
    diff_pair_queries, with_query_tiles, write_kv)
from llm_d_tpu.ops.parts import attn_part, part

F32 = jnp.float32


def self_pairs(c: ModelConfig) -> int:
    """(MAMBA, attention) pairs in front of the memory layer's."""
    return c.gmu_memory_layer // 2


def cross_pairs(c: ModelConfig) -> int:
    return (c.num_layers - c.cross_kv_layer - 1) // 2


def kv_cache_layout(config: ModelConfig) -> Dict[str, int]:
    """Cache row widths: the folded [KVH * D] keys and values (to the
    kernels KVH / 2 heads of 2 D: a pair is one head)."""
    w = config.num_kv_heads * config.head_dim_
    return {"k": w, "v": w}


def kv_cache_layers(config: ModelConfig) -> Dict[str, int]:
    """A plane a layer that writes keys and values: the self-decoder's
    attention layers and ``cross_kv_layer``."""
    return dict.fromkeys(("k", "v"), len(config.layers_of(SLIDING, FULL)))


def kv_cache_spec(config: ModelConfig = None) -> Dict[str, P]:
    return {"k": P(), "v": P()}         # one device (engine: tp refused)


def sharding_rules(config: ModelConfig):
    return []


def state_pool_shapes(c: ModelConfig, slots: int
                      ) -> Dict[str, jax.ShapeDtypeStruct]:
    """The state pool of ``slots`` sequence slots (slot 0 the trash slot
    included): a plane a MAMBA layer, per slot the state [N, inner]
    (channels on the lanes) and the convolution's last K - 1 inputs."""
    planes = len(c.layers_of(MAMBA))
    return {
        "ssm": jax.ShapeDtypeStruct(
            (planes, slots, c.ssm_state_size, c.ssm_inner_size), STATE_DTYPE),
        "conv": jax.ShapeDtypeStruct(
            (planes, slots, c.ssm_conv_kernel - 1, c.ssm_inner_size),
            c.jax_dtype)}


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random weights for tests and benchmarks, every leaf MADE in the
    model's dtype (no float32 copy of the embedding).  Fan-in scaled
    normals; biases small and not zero, so that a dropped bias shows; the
    tied embedding at hidden ** -0.5, so that the logits are of order one;
    dt from 0.001 to 0.1 and A from -1 to -16, as Mamba starts them; the
    lambda vectors at 0.1, as differential attention starts them."""
    c = config
    dt, dh, D, I = c.jax_dtype, c.head_dim_, c.hidden_size, c.intermediate_size
    di, N, R, K = (c.ssm_inner_size, c.ssm_state_size, c.ssm_dt_rank,
                   c.ssm_conv_kernel)
    H, KVH = c.num_heads, c.num_kv_heads
    keys = iter(jax.random.split(key, 128))

    def w(shape, scale=1.0, fan_in=None):
        fan_in = fan_in or shape[-2]
        return (jax.random.normal(next(keys), shape, dt)
                * jnp.asarray(scale * fan_in ** -0.5, dt)).astype(dt)

    def small(shape):
        return w(shape, 0.1, fan_in=1)

    def common(n):
        """What every layer has: two LayerNorms and the MLP."""
        return {
            "input_norm": jnp.ones(n + (D,), dt),
            "input_norm_b": small(n + (D,)),
            "post_attn_norm": jnp.ones(n + (D,), dt),
            "post_attn_norm_b": small(n + (D,)),
            "gate_proj": w(n + (D, I)), "up_proj": w(n + (D, I)),
            "down_proj": w(n + (I, D))}

    def mamba(n):
        return dict(common(n), **{
            "in_proj": w(n + (D, 2 * di)),
            "conv_w": w(n + (di, K), fan_in=K), "conv_b": small(n + (di,)),
            "x_proj": w(n + (di, R + 2 * N)), "dt_proj": w(n + (R, di)),
            "dt_bias": _inverse_softplus(jnp.exp(jax.random.uniform(
                next(keys), n + (di,), F32, np.log(1e-3), np.log(1e-1)))),
            "A_log": jnp.log(jax.random.uniform(
                next(keys), n + (N, di), F32, 1.0, 16.0)),
            "D": jnp.ones(n + (di,), F32),
            "out_proj": w(n + (di, D))})

    def attention(n, cross=False):
        lp = dict(common(n), **{
            "q_proj": w(n + (D, H * dh)), "q_bias": small(n + (H * dh,)),
            "o_proj": w(n + (H * dh, D)),
            "lambda_q1": small(n + (dh,)), "lambda_k1": small(n + (dh,)),
            "lambda_q2": small(n + (dh,)), "lambda_k2": small(n + (dh,)),
            "subln": jnp.ones(n + (2 * dh,), dt)})
        if c.attention_out_bias:
            lp["o_bias"] = small(n + (D,))
        if not cross:
            lp.update({
                "k_proj": w(n + (D, KVH * dh)), "k_bias": small(n + (KVH * dh,)),
                "v_proj": w(n + (D, KVH * dh)), "v_bias": small(n + (KVH * dh,))})
        if not c.attention_bias:
            for name in ("q_bias", "k_bias", "v_bias"):
                lp.pop(name, None)
        return lp

    def gmu(n):
        return dict(common(n), **{
            "gmu_in": w(n + (D, di)), "gmu_out": w(n + (di, D))})

    ns, nx = (self_pairs(c),), (cross_pairs(c),)
    return {
        "embed": w((c.vocab_size, D), fan_in=D),
        "mamba_layers": mamba(ns), "attn_layers": attention(ns),
        "memory_layer": mamba(()), "kv_layer": attention(()),
        "gmu_layers": gmu(nx), "cross_layers": attention(nx, cross=True),
        "final_norm": jnp.ones((D,), dt), "final_norm_b": small((D,)),
    }


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float):
    xf = x.astype(F32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return (xc * jax.lax.rsqrt(var + eps) * w.astype(F32)
            + b.astype(F32)).astype(x.dtype)


def input_norm(lp: Params, c: ModelConfig, h: jax.Array) -> jax.Array:
    return layer_norm(h, lp["input_norm"], lp["input_norm_b"], c.rms_norm_eps)


def mlp_residual(lp: Params, c: ModelConfig, h: jax.Array, mixed: jax.Array):
    """h + mixer output, then the MLP on its LayerNorm: the layer's end."""
    with part("mlp"):
        h = h + mixed
        x = layer_norm(h, lp["post_attn_norm"], lp["post_attn_norm_b"],
                       c.rms_norm_eps)
        return h + L.swiglu_mlp(x, lp["gate_proj"], lp["up_proj"],
                                lp["down_proj"])


def mamba_mixer(lp: Params, c: ModelConfig, u: jax.Array,
                batch: Dict[str, jax.Array],
                state: Tuple[jax.Array, jax.Array], plane: jax.Array,
                backend: str):
    """One MAMBA layer's mixer on its normed input ``u`` [T, D]; plane
    ``plane`` of the pool is updated in place.  Returns (out [T, D], state',
    the memory y [T, inner] before the gate)."""
    di, N, R = c.ssm_inner_size, c.ssm_state_size, c.ssm_dt_rank
    ssm, conv = state
    with part("ssm.proj"):
        az = L.linear(u, lp["in_proj"])
        a, z = az[:, :di], az[:, di:]
    with part("ssm.state"):
        cx, conv = ssm_ops.causal_conv(
            a, lp["conv_w"], lp["conv_b"], conv, batch, plane)
    with part("ssm.proj"):
        rbc = L.linear(cx, lp["x_proj"])
        dt = jax.nn.softplus(
            L.linear(rbc[:, :R], lp["dt_proj"]).astype(F32)
            + lp["dt_bias"].astype(F32))
    with part("ssm.state"):
        y, ssm = ssm_ops.ssm1_state_update(
            cx, dt, -jnp.exp(lp["A_log"].astype(F32)), rbc[:, R:R + N],
            rbc[:, R + N:], lp["D"], ssm, batch, plane, c.ssm_chunk_size,
            backend)
    with part("ssm.proj"):
        y = y.astype(u.dtype)
        gated = (y.astype(F32) * jax.nn.silu(z.astype(F32))).astype(u.dtype)
        return L.linear(gated, lp["out_proj"]), (ssm, conv), y


def diff_lambda(lp: Params, lam_init) -> jax.Array:
    def dot(a, b):
        return jnp.sum(lp[a].astype(F32) * lp[b].astype(F32), axis=-1)
    return (jnp.exp(dot("lambda_q1", "lambda_k1"))
            - jnp.exp(dot("lambda_q2", "lambda_k2")) + lam_init)


def diff_output(lp: Params, c: ModelConfig, out: jax.Array, lam_init):
    """The paired heads' attention [T, H, 2D] -> the layer's attention
    output [T, D_model]: the subtraction, the norm over each pair's 2 D
    values, the factor 1 - lambda_init, W_o."""
    T = out.shape[0]
    o = diff_combine(out, diff_lambda(lp, lam_init))
    o = L.rms_norm(o, lp["subln"], c.rms_norm_eps) * (1.0 - lam_init)
    return L.linear(o.reshape(T, -1).astype(out.dtype), lp["o_proj"],
                    lp.get("o_bias"))


def paired_queries(lp: Params, c: ModelConfig, u: jax.Array) -> jax.Array:
    q = L.linear(u, lp["q_proj"], lp.get("q_bias"))
    return diff_pair_queries(q.reshape(u.shape[0], c.num_heads, c.head_dim_))


def paired_kv(lp: Params, c: ModelConfig, u: jax.Array):
    """Keys and values [T, KVH / 2, 2 D]: a pair of heads side by side."""
    T = u.shape[0]
    shape = (T, c.num_kv_heads // 2, c.attn_head_dim)
    return (L.linear(u, lp["k_proj"], lp.get("k_bias")).reshape(shape),
            L.linear(u, lp["v_proj"], lp.get("v_bias")).reshape(shape))


def forward(
    params: Params,
    kv_cache: Dict[str, jax.Array],   # k, v [planes, slots, KVH*dh]; ssm, conv
    batch: Dict[str, jax.Array],
    config: ModelConfig,
    block_size: int,
    attn_backend: str = "auto",
    mesh=None,
    moe_opts=None,                    # unused
    every_row: bool = False,          # tests: the cross-decoder on every
                                      # token's row (see ``cross_decoder``)
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One engine step over a ragged batch, as ``models.llama.forward``:
    (hidden states of the sampling positions [S, D], the cache and the
    state pool updated)."""
    c = config
    scale = c.head_dim_ ** -0.5
    ns = self_pairs(c)
    x = llama.embed_tokens(params, batch["token_ids"], c)
    with part("tiles"):
        batch = with_query_tiles(
            batch, c.num_heads, kv_cache["k"].shape[-1], attn_backend, mesh)
    attn_at = c.layers_of(SLIDING, FULL)        # plane -> layer
    windows = jnp.asarray(
        [c.sliding_window if c.layer_types[li] == SLIDING else NO_WINDOW
         for li in attn_at], jnp.int32)
    lam_inits = jnp.asarray(
        [c.diff_lambda_init(li) for li in range(c.num_layers)], F32)

    def mamba_layer(lp, h, state, plane):
        with part("ssm.proj"):
            u = input_norm(lp, c, h)
        out, state, mem = mamba_mixer(lp, c, u, batch, state, plane,
                                      attn_backend)
        return mlp_residual(lp, c, h, out), state, mem

    def self_pair(carry, lps):
        h, caches, state, i = carry
        lm, la = lps
        h, state, _ = mamba_layer(lm, h, state, i)
        with part("attn.proj"):
            u = input_norm(la, c, h)
            q = paired_queries(la, c, u)
            kx, vx = paired_kv(la, c, u)
        with part(attn_part(batch)):
            out, *caches = attention_with_kv_update(
                q, kx, vx, *caches, batch, block_size=block_size,
                scale=scale, backend=attn_backend, layer=i, mesh=mesh,
                window=windows[i])
        with part("attn.proj"):
            a = diff_output(la, c, out, lam_inits[2 * i + 1])
        return (mlp_residual(la, c, h, a), tuple(caches), state, i + 1), None

    state = tuple(kv_cache[name] for name in STATE_KEYS)
    with part("scan"):
        (x, caches, state, _), _ = jax.lax.scan(
            self_pair, (x, (kv_cache["k"], kv_cache["v"]), state,
                        jnp.int32(0)),
            (params["mamba_layers"], params["attn_layers"]))
    plane = jnp.int32(ns)
    x, state, mem = mamba_layer(params["memory_layer"], x, state, plane)

    # ``cross_kv_layer``: the keys and values of every token to its plane.
    lk = params["kv_layer"]
    with part("attn.proj"):
        u = input_norm(lk, c, x)
        kx, vx = paired_kv(lk, c, u)
    with part(attn_part(batch)):
        caches = write_kv(*caches, kx, vx, batch["slot_mapping"], layer=plane)

    hidden = cross_decoder(
        params, c, x, u, mem, caches, batch, block_size, attn_backend,
        lam_inits, every_row)
    return hidden, dict(zip(("k", "v") + STATE_KEYS, caches + state))


def cross_decoder(params: Params, c: ModelConfig, x, u_kv, mem, caches,
                  batch, block_size: int, backend: str, lam_inits,
                  every_row: bool) -> jax.Array:
    """``cross_kv_layer``'s own attention and every layer after it, on the
    rows the step samples from ([S] of them; ``every_row``: on every token's
    row, at the token's own position, for the test that holds the two
    against each other), then the final norm.  Each attention is one query
    a row over ``cross_kv_layer``'s plane, which holds every key up to the
    row's position."""
    scale = c.head_dim_ ** -0.5
    plane = jnp.int32(self_pairs(c))
    with part("head"):      # the gather ``sampled_hidden`` made, moved up
        if every_row:
            rows = jnp.arange(x.shape[0])
            seq = batch["token_seq_ids"]
            view = {"block_tables": batch["block_tables"][seq],
                    "seq_lens": jnp.where(
                        batch["seq_lens"][seq] > 0,
                        batch["positions"] + 1, 0)}
        else:
            rows, view = batch["sample_idx"], batch
        h, u, m = x[rows], u_kv[rows], mem[rows]

    def attend(lp, u, li):
        with part("attn.proj"):
            q = paired_queries(lp, c, u)
        with part("attn.cross"):
            out = attention_one_query(
                q, *caches, view, block_size, scale=scale, backend=backend,
                layer=plane)
        with part("attn.proj"):
            return diff_output(lp, c, out, lam_inits[li])

    lk = params["kv_layer"]
    h = mlp_residual(lk, c, h, attend(lk, u, c.cross_kv_layer))

    def cross_pair(carry, lps):
        h, li = carry
        lg, lc = lps
        with part("gmu"):
            g = L.linear(input_norm(lg, c, h), lg["gmu_in"])
            g = L.linear(
                (m.astype(F32) * jax.nn.silu(g.astype(F32))).astype(h.dtype),
                lg["gmu_out"])
        h = mlp_residual(lg, c, h, g)
        with part("attn.proj"):
            u = input_norm(lc, c, h)
        h = mlp_residual(lc, c, h, attend(lc, u, li + 1))
        return (h, li + 2), None

    with part("scan"):
        (h, _), _ = jax.lax.scan(
            cross_pair, (h, jnp.int32(c.cross_kv_layer + 1)),
            (params["gmu_layers"], params["cross_layers"]))
    with part("head"):
        return layer_norm(h, params["final_norm"], params["final_norm_b"],
                          c.rms_norm_eps)
