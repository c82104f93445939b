"""Plain reference of the falcon_h1 family (Falcon-H1-34B-Instruct among
them): a Mamba-2 state-space mixer in parallel with grouped-query attention
in every layer, muP multipliers throughout.  From the published
``config.json`` and from ``modeling_falcon_h1.py`` as the builder knows it
(there is no network here; what is not a key of the config is listed under
``assumed`` in the configuration's file):

    h0 = embed(tokens) * embedding_multiplier
    x  = RMSNorm_in(h)
    a  = Attn(x * attention_in_multiplier) * attention_out_multiplier
           q, k, v = x W_q, x W_k * key_multiplier, x W_v   (no bias, no q/k
           norm); rotary embedding on all of the head, rotate-half; causal
           softmax(q k^T / sqrt(d)) v; W_o
    m  = Mamba2(x * ssm_in_multiplier) * ssm_out_multiplier
           u = (x W_in) * mup_vector;  W_in -> gate z | x | B | C | dt, and
           mup_vector = ssm_multipliers[0..4] over those five segments
           xBC = silu(causal_conv1d(u[x, B, C], kernel K, bias))   depthwise
           dt = softplus(u[dt] + dt_bias);  A = -exp(A_log)
           S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t     per head; the
           heads of a group share B and C
           y_t = S_t C_t + D x_t
           y = RMSNorm_groups(y * silu(z)) * w   (norm_before_gate false)
           m = y W_out
    h  = h + a + m
    h  = h + (silu(x' W_gate * mlp_multipliers[0]) * (x' W_up)) W_down
             * mlp_multipliers[1],                      x' = RMSNorm_ff(h)
    logits = RMSNorm_f(h) W_head * lm_head_multiplier

Float32 under ``jax.default_matmul_precision("highest")``; the recurrence is
a plain ``lax.scan`` over tokens from a zero state: no chunks, no cache, no
slots.  Of ``plain.py`` it takes the norm, the rotary embedding and causal
attention.  It reads the program's parameter tree and ``ModelConfig`` and
nothing else of the program.  Only to bound memory beside a served engine:
the MLP and the output head are computed in blocks of their width (a
float32 copy of a 5120 x 21,504 matrix is 0.4 GiB, of the head 5 GiB), and
the layers run under a scan.

``FAULTS`` (empty in every served comparison) switches ONE thing wrong at a
time for ``benchmarks/tools/ssm_mechanism_check.py``: the state carried in
bf16, the convolution's tail zeroed at a chunk boundary, the mup_vector or
the key multiplier left out, B and C of the groups swapped, no softplus on
dt, the MLP's and the head's weights rounded to int8.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import references.plain as plain
from references.plain import F32

# Names of mechanisms to get wrong (tools/ssm_mechanism_check.py).
FAULTS: set = set()
# "zero_conv_tail": the tokens a step of the served engine takes (its
# --max-num-batched-tokens), so that a prompt's chunk boundaries fall here
# where they fall there.
FAULT_CHUNK = 2048
MLP_BLOCKS, HEAD_BLOCKS = 4, 15


def mup_vector(c):
    gn = c.ssm_num_groups * c.ssm_state_size
    di = c.ssm_num_heads * c.ssm_head_dim
    mult = ((1.0,) * 5 if "no_mup_vector" in FAULTS else c.ssm_multipliers)
    return jnp.asarray(np.concatenate([
        np.full(w, m, np.float32)
        for w, m in zip((di, di, gn, gn, c.ssm_num_heads), mult)]))


def causal_conv(u, w, b):
    """u [T, C], w [C, K], b [C]: out[t] = sum_k w[:, k] u[t - (K-1) + k]."""
    T, K = u.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
    out = b.astype(F32)[None, :]
    t = jnp.arange(T)
    for k in range(K):
        inp = padded[k:k + T]
        if "zero_conv_tail" in FAULTS:      # inputs before a chunk's start
            inp = jnp.where((t - (K - 1) + k >= t // FAULT_CHUNK
                             * FAULT_CHUNK)[:, None], inp, 0.0)
        out = out + inp * w.astype(F32)[:, k][None, :]
    return out


def mixer(lp, c, x):
    """One layer's Mamba-2 mixer on its (scaled) input x [T, D]."""
    T = x.shape[0]
    H, P, N, G = (c.ssm_num_heads, c.ssm_head_dim, c.ssm_state_size,
                  c.ssm_num_groups)
    di, gn = H * P, G * N
    u = (x @ lp["ssm_in_proj"].astype(F32)) * mup_vector(c)[None, :]
    z, xbc, dt = u[:, :di], u[:, di:2 * di + 2 * gn], u[:, 2 * di + 2 * gn:]
    xbc = jax.nn.silu(causal_conv(xbc, lp["ssm_conv_w"], lp["ssm_conv_b"]))
    xs = xbc[:, :di].reshape(T, H, P)
    B = xbc[:, di:di + gn].reshape(T, G, N)
    C = xbc[:, di + gn:].reshape(T, G, N)
    if "swap_groups" in FAULTS:
        B, C = B[:, ::-1], C[:, ::-1]
    dt = dt + lp["ssm_dt_bias"].astype(F32)[None, :]
    if "no_softplus" not in FAULTS:
        dt = jax.nn.softplus(dt)
    A = -jnp.exp(lp["ssm_A_log"].astype(F32))
    Bh = jnp.repeat(B, H // G, axis=1)                      # [T, H, N]
    Ch = jnp.repeat(C, H // G, axis=1)
    carry_dtype = jnp.bfloat16 if "bf16_state" in FAULTS else F32

    def token(S, inp):
        x_t, b_t, c_t, dt_t = inp                           # [H,P] [H,N] [H]
        S = S.astype(F32) * jnp.exp(dt_t * A)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        S = S.astype(carry_dtype)
        return S, jnp.einsum("hpn,hn->hp", S.astype(F32), c_t)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), carry_dtype),
                        (xs, Bh, Ch, dt))
    y = y + lp["ssm_D"].astype(F32)[None, :, None] * xs
    y = y.reshape(T, di) * jax.nn.silu(z)
    yg = y.reshape(T, G, di // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                            + c.rms_norm_eps)
    y = yg.reshape(T, di) * lp["ssm_norm"].astype(F32)[None, :]
    return y @ lp["ssm_out_proj"].astype(F32)


def attention(lp, c, x, pos):
    T, H, KVH, D = x.shape[0], c.num_heads, c.num_kv_heads, c.head_dim_
    q = (x @ lp["q_proj"].astype(F32)).reshape(T, H, D)
    k = (x @ lp["k_proj"].astype(F32)).reshape(T, KVH, D)
    if "no_key_multiplier" not in FAULTS:
        k = k * c.key_multiplier
    v = (x @ lp["v_proj"].astype(F32)).reshape(T, KVH, D)
    q, k = plain.rope(q, pos, c.rope_theta), plain.rope(k, pos, c.rope_theta)
    a = plain.causal_attention(q, jnp.repeat(k, H // KVH, axis=1),
                               jnp.repeat(v, H // KVH, axis=1), D ** -0.5)
    return a.reshape(T, H * D) @ lp["o_proj"].astype(F32)


def block_of(w, i, n, axis):
    """Block ``i`` of ``n`` equal ones of ``w`` along ``axis``, in float32:
    only one block of a wide matrix is ever held as a float32 copy."""
    width = w.shape[axis] // n
    blk = jax.lax.dynamic_slice_in_dim(w, i * width, width, axis).astype(F32)
    if "int8_weights" in FAULTS:    # per output column, as a quantiser would
        scale = jnp.max(jnp.abs(blk), axis=0, keepdims=True) / 127.0
        blk = jnp.round(blk / scale) * scale
    return blk


def mlp(lp, c, x):
    n = MLP_BLOCKS if c.intermediate_size % MLP_BLOCKS == 0 else 1
    gate_mult, out_mult = c.mlp_multipliers

    def block(acc, i):
        hidden = jax.nn.silu((x @ block_of(lp["gate_proj"], i, n, 1))
                             * gate_mult) \
            * (x @ block_of(lp["up_proj"], i, n, 1))
        return acc + hidden @ block_of(lp["down_proj"], i, n, 0), None

    out, _ = jax.lax.scan(block, jnp.zeros_like(x), jnp.arange(n))
    return out * out_mult


def tail_logprobs(params, config, tokens, k):
    c = config
    eps = c.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        h = params["embed"][tokens].astype(F32) * c.embed_scale

        def layer(h, lp):
            x = plain.rms(h, lp["input_norm"], eps)
            h = h + attention(lp, c, x * c.attention_in_multiplier, pos) \
                * c.attention_out_multiplier \
                + mixer(lp, c, x * c.ssm_in_multiplier) \
                * c.ssm_out_multiplier
            return h + mlp(lp, c, plain.rms(h, lp["post_attn_norm"], eps)), \
                None

        h, _ = jax.lax.scan(layer, h, params["layers"])
        h = plain.rms(h[-k:], params["final_norm"], eps)
        n = HEAD_BLOCKS if c.vocab_size % HEAD_BLOCKS == 0 else 1
        logits = jax.lax.map(
            lambda i: h @ block_of(params["lm_head"], i, n, 1),
            jnp.arange(n))                                  # [n, k, V / n]
        logits = jnp.moveaxis(logits, 0, 1).reshape(k, c.vocab_size)
        return jax.nn.log_softmax(logits * c.lm_head_multiplier)
