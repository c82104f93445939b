"""Plain reference of the phi4flash family (Phi-4-mini-flash-reasoning: the
SambaY decoder-hybrid-decoder of arXiv:2507.06607 with differential
attention, arXiv:2410.05258).  From the published ``config.json`` and from
``modeling_phi4flash.py`` / the papers as the builder knows them (there is
no network here; what is not a key of the config is listed under
``assumed`` in the configuration's file).  Every layer l:

    h = x + Mixer_l(LN1_l(x));   x' = h + W_down (silu(g) * p),
    [g, p] = W_gate_up LN2_l(h)              (LayerNorm: weight and bias)

    MAMBA (even l up to gmu_memory_layer)
        [a, z] = W_in u;  c = silu(conv1d_causal_depthwise(a, K) + b)
        [r, B_t, C_t] = W_x c;  dt = softplus(W_dt r + b_dt)
        S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t c_t) (x) B_t,  A = -exp(A_log)
        y_t = S_t C_t + D c_t;  out = W_out (y * silu(z))
        gmu_memory_layer also hands on the memory m_t = y_t
    SLIDING / FULL (odd l up to cross_kv_layer): differential attention
        over keys s with t - window < s <= t (FULL: all s <= t)
    GMU (even l after):  out = W_2 (m_t * silu(W_1 u))
    CROSS (odd l after): q = W_q u; differential attention of q over
        cross_kv_layer's k, v (all s <= t); W_o

    differential attention: heads pair up as neighbours, (q1, q2) =
    heads (2i, 2i + 1), (k1, k2), (v1, v2) likewise, query pair i reads
    key-value pair i // (query pairs a key-value pair);
    P_s = softmax(q_s k_s^T / sqrt(D));  o = P_1 [v1, v2] - lambda P_2 [v1, v2]
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
    lambda_init = 0.8 - 0.6 exp(-0.3 l);
    o <- RMSNorm_2D(o) * w * (1 - lambda_init);  concat -> W_o (+ bias)

    logits = LN_f(x) E^T  (tied embedding).  No positional encoding.

Float32 under ``jax.default_matmul_precision("highest")``, a Python loop
over the layers, ALL positions through ALL layers (it knows nothing of
sampled rows, caches, slots, chunks or kernels); the recurrence is a plain
``lax.scan`` over tokens from a zero state, attention a masked softmax over
every key.  Of ``plain.py`` it takes ``F32`` only.  It reads the program's
parameter tree and ``ModelConfig`` and nothing else of the program.

Departures from the published code, all to bound memory beside a served
engine and none in the equations: wide matrices are multiplied a block of
columns (or rows) at a time, so that only one block is ever held as a
float32 copy (the tied embedding is 2 GB in float32); attention runs a pair
of heads at a time over query blocks of 1,024, so that the scores of 6,200
tokens are 25 MB and not 6 GB.  The program's tree stores ``A_log``
transposed ([N, inner]) and the fused published matrices (Wqkv, gate_up,
in_proj's halves) split or as one by name; with random weights from
``--seed`` that is a renaming.

``FAULTS`` (empty in every served comparison) switches ONE thing wrong at a
time for ``benchmarks/tools/phi4flash_mechanism_check.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from references.plain import F32

# Names of mechanisms to get wrong (tools/phi4flash_mechanism_check.py):
# int8_weights, no_diff_term, window_off_by_one, gmu_reads_gated,
# cross_misses_chunk, bf16_state.
FAULTS: set = set()
# "cross_misses_chunk": the tokens a step of the served engine takes (its
# --max-num-batched-tokens), so that chunk boundaries fall here as there.
FAULT_CHUNK = 2048
COLUMN_BLOCKS, HEAD_BLOCKS, QUERY_BLOCK = 4, 24, 1024


class Leaf:
    """A layer's leaf of the program's tree: the stacked leaf of its group
    and the layer's index there (None: a layer that is stored alone).  A
    wide matrix is never sliced out whole: ``block_of`` takes a block of one
    layer straight from the stack."""

    def __init__(self, stack, index=None):
        self.stack, self.index = stack, index
        self.shape = stack.shape if index is None else stack.shape[1:]

    def all(self):
        w = self.stack if self.index is None else self.stack[self.index]
        return w.astype(F32)


def block_of(w, i, n, axis):
    """Block ``i`` of ``n`` equal ones of ``w`` along ``axis``, in float32."""
    width = w.shape[axis] // n
    if isinstance(w, Leaf) and w.index is not None:
        start = [w.index, 0, 0]
        start[1 + axis] = i * width
        size = [1, *w.shape]
        size[1 + axis] = width
        blk = jax.lax.dynamic_slice(w.stack, start, size)[0].astype(F32)
    else:
        stack = w.stack if isinstance(w, Leaf) else w
        blk = jax.lax.dynamic_slice_in_dim(
            stack, i * width, width, axis).astype(F32)
    if "int8_weights" in FAULTS:    # per output column, as a quantiser would
        scale = jnp.max(jnp.abs(blk), axis=0, keepdims=True) / 127.0
        blk = jnp.round(blk / jnp.maximum(scale, 1e-30)) * scale
    return blk


def dense(x, w, b=None):
    """x [T, in] @ w [in, out] (+ b), a block of output columns at a time."""
    n = COLUMN_BLOCKS if w.shape[1] % COLUMN_BLOCKS == 0 else 1
    y = jax.lax.map(lambda i: x @ block_of(w, i, n, 1), jnp.arange(n))
    y = jnp.moveaxis(y, 0, 1).reshape(x.shape[0], w.shape[1])
    return y if b is None else y + b.all()[None, :]


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w.all() \
        + b.all()


def mlp(lp, x):
    n = COLUMN_BLOCKS if lp["gate_proj"].shape[1] % COLUMN_BLOCKS == 0 else 1

    def block(acc, i):
        hidden = jax.nn.silu(x @ block_of(lp["gate_proj"], i, n, 1)) \
            * (x @ block_of(lp["up_proj"], i, n, 1))
        return acc + hidden @ block_of(lp["down_proj"], i, n, 0), None

    return jax.lax.scan(block, jnp.zeros_like(x), jnp.arange(n))[0]


def causal_conv(a, w, b):
    """a [T, C], w [C, K], b [C]: out[t] = sum_k w[:, k] a[t - (K-1) + k]."""
    T, K = a.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, a.shape[1]), F32), a])
    out = b.all()[None, :]
    for k in range(K):
        out = out + padded[k:k + T] * w.all()[:, k][None, :]
    return out


def mamba(lp, c, u):
    """One Mamba-1 mixer on its normed input u [T, D] -> (out, memory y)."""
    di, N, R = c.ssm_inner_size, c.ssm_state_size, c.ssm_dt_rank
    az = dense(u, lp["in_proj"])
    a, z = az[:, :di], az[:, di:]
    cx = jax.nn.silu(causal_conv(a, lp["conv_w"], lp["conv_b"]))
    rbc = dense(cx, lp["x_proj"])
    r, B, C = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]
    dt = jax.nn.softplus(dense(r, lp["dt_proj"], lp["dt_bias"]))
    A = -jnp.exp(lp["A_log"].all())                   # [N, inner]
    carry_dtype = jnp.bfloat16 if "bf16_state" in FAULTS else F32

    def token(S, inp):
        dt_t, c_t, b_t, c_out = inp              # [inner] [inner] [N] [N]
        S = jnp.exp(dt_t[None, :] * A) * S.astype(F32) \
            + b_t[:, None] * (dt_t * c_t)[None, :]
        S = S.astype(carry_dtype)
        return S, jnp.sum(S.astype(F32) * c_out[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros((N, di), carry_dtype),
                        (dt, cx, B, C))
    y = y + lp["D"].all()[None, :] * cx
    gated = y * jax.nn.silu(z)
    return dense(gated, lp["out_proj"]), (
        gated if "gmu_reads_gated" in FAULTS else y)


def visible(qpos, kpos, window, cross):
    """[Tq, Tk] bool: the keys each query sees."""
    m = kpos[None, :] <= qpos[:, None]
    if window:
        w = window + 1 if "window_off_by_one" in FAULTS else window
        m &= kpos[None, :] > qpos[:, None] - w
    if cross and "cross_misses_chunk" in FAULTS:
        # nothing of the query's own chunk but itself
        m &= (kpos[None, :] < (qpos // FAULT_CHUNK * FAULT_CHUNK)[:, None]) \
            | (kpos[None, :] == qpos[:, None])
    return m


def diff_attention(lp, c, q, k, v, layer, window=0, cross=False):
    """q [T, H, D], k, v [T, KVH, D] -> the layer's attention output
    [T, D_model], pairs of heads written out."""
    T, H, D = q.shape
    q1, q2 = q[:, 0::2], q[:, 1::2]                         # [T, H/2, D]
    k1, k2 = k[:, 0::2], k[:, 1::2]                         # [T, KVH/2, D]
    vv = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)  # [T, KVH/2, 2D]
    group = (H // 2) // k1.shape[1]
    k1, k2, vv = (jnp.repeat(a, group, axis=1) for a in (k1, k2, vv))
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * layer)

    def dot(a, b):
        return jnp.sum(lp[a].all() * lp[b].all())

    lam = jnp.exp(dot("lambda_q1", "lambda_k1")) \
        - jnp.exp(dot("lambda_q2", "lambda_k2")) + lam_init
    if "no_diff_term" in FAULTS:
        lam = 0.0
    pos = jnp.arange(T)
    scale = D ** -0.5

    def pair(args):
        qa, qb, ka, kb, vp = args
        outs = []
        for s in range(0, T, QUERY_BLOCK):
            m = visible(pos[s:s + QUERY_BLOCK], pos, window, cross)

            def probs(qs, ks):
                return jax.nn.softmax(
                    jnp.where(m, (qs @ ks.T) * scale, -jnp.inf), axis=-1)

            outs.append(probs(qa[s:s + QUERY_BLOCK], ka) @ vp
                        - lam * (probs(qb[s:s + QUERY_BLOCK], kb) @ vp))
        return jnp.concatenate(outs)

    o = jax.lax.map(pair, tuple(jnp.swapaxes(a, 0, 1)
                                for a in (q1, q2, k1, k2, vv)))
    o = jnp.swapaxes(o, 0, 1)                               # [T, H/2, 2D]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + c.rms_norm_eps) * lp["subln"].all()
    o = o * (1.0 - lam_init)
    return dense(o.reshape(T, H * D), lp["o_proj"], lp.get("o_bias"))


def heads(lp, c, u, name, n):
    return dense(u, lp[name + "_proj"], lp.get(name + "_bias")).reshape(
        u.shape[0], n, c.head_dim_)


def layer_params(params, c, li):
    """The program's tree goes by kind (models/hybrid_decoder.py): the
    self-decoder's pairs stacked, the memory and the key-value layer alone,
    the cross-decoder's pairs stacked."""
    m, x = c.gmu_memory_layer, c.cross_kv_layer
    if li in (m, x):
        return {name: Leaf(leaf) for name, leaf in params[
            "memory_layer" if li == m else "kv_layer"].items()}
    if li < m:
        group, i = ("mamba_layers", "attn_layers")[li % 2], li // 2
    else:
        group, i = ("gmu_layers", "cross_layers")[(li - x - 1) % 2], \
            (li - x - 1) // 2
    return {name: Leaf(leaf, i) for name, leaf in params[group].items()}


def tail_logprobs(params, config, tokens, k):
    c = config
    eps = c.rms_norm_eps
    H, KVH = c.num_heads, c.num_kv_heads
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        memory = shared_kv = None
        for li, kind in enumerate(c.layer_types):
            lp = layer_params(params, c, li)
            u = layer_norm(x, lp["input_norm"], lp["input_norm_b"], eps)
            if kind == "mamba":
                out, y = mamba(lp, c, u)
                if li == c.gmu_memory_layer:
                    memory = y
            elif kind == "gmu":
                out = dense(memory * jax.nn.silu(dense(u, lp["gmu_in"])),
                            lp["gmu_out"])
            elif kind == "cross_attention":
                out = diff_attention(lp, c, heads(lp, c, u, "q", H),
                                     *shared_kv, li, cross=True)
            else:
                kv = (heads(lp, c, u, "k", KVH), heads(lp, c, u, "v", KVH))
                if li == c.cross_kv_layer:
                    shared_kv = kv
                out = diff_attention(
                    lp, c, heads(lp, c, u, "q", H), *kv, li,
                    window=(c.sliding_window
                            if kind == "sliding_attention" else 0),
                    cross=li == c.cross_kv_layer)
            h = x + out
            x = h + mlp(lp, layer_norm(h, lp["post_attn_norm"],
                                       lp["post_attn_norm_b"], eps))
        h = layer_norm(x[-k:], Leaf(params["final_norm"]),
                       Leaf(params["final_norm_b"]), eps)
        n = HEAD_BLOCKS if c.vocab_size % HEAD_BLOCKS == 0 else 1
        logits = jax.lax.map(
            lambda i: h @ block_of(params["embed"], i, n, 0).T,
            jnp.arange(n))                                  # [n, k, V / n]
        logits = jnp.moveaxis(logits, 0, 1).reshape(k, c.vocab_size)
        return jax.nn.log_softmax(logits)
