"""Plain reference of a stack of linear-attention (KDA: Kimi Delta Attention,
arXiv:2510.26692) and latent-attention (MLA) layers over routed experts, as
``ling-3.0-flash-vl``'s published ``config.json`` describes its language
model; what is not a key's value is listed under ``assumed`` in the
configuration's file.

    h = RMSNorm(x), pre-norm residuals, final RMSNorm, untied head.

  LINEAR layer (``layer_types[l] == "linear_attention"``), H heads of key
  size K and value size V, token by token:
    q | k | v = SiLU(conv(h W_qkv))     depthwise causal, kernel 4, no bias
    q = q / |q| K^-1/2,  k = k / |k|     per head
    g = floor sigmoid(exp(A_log[h]) (h W_f + dt_bias))   per head, channel
    beta = sigmoid(h W_b)                per head
    S <- Diag(exp g) S;  S <- S + beta k (v - S^T k)^T;  o = S^T q
    y = (RMSNorm_head(o; weight [V]) sigmoid(h W_g)[head]) W_o
  no rotary embedding.
  FULL layer: DeepSeek-V2's latent attention with a direct query,
    q = h W_q -> per head q_nope | q_pe, RoPE(q_pe);  [c | k_pe] = h W_kva,
    c = RMSNorm(c), RoPE(k_pe) (one for all heads);  k_h = [c W_uk,h | k_pe],
    v_h = c W_uv,h (UNABSORBED; the program serves the absorbed form over a
    latent cache), causal softmax at scale (nope + rope)^-1/2.
  Feed-forward: the leading layers dense SwiGLU; the others
    s = sigmoid(h W_r) over ALL experts; on s + bias a group's score is the
    sum of its two best, the best ``topk_group`` of ``n_group`` groups stay,
    the top-k inside them; weights s_e / sum s_e x routed_scaling_factor;
    plus the shared expert.  Of the routed experts ids first_local_expert
    .. + num_local_experts - 1 are held: a token's slots routed elsewhere
    add nothing.

Float32 under ``jax.default_matmul_precision("highest")``; no cache, no
kernels, no chunks, no batching, one sequence.  It reads the program's
parameter tree and ``ModelConfig`` fields and nothing else of the program.
Only to bound memory beside a served engine: attention runs one head at a
time, the experts one at a time out of the stacked leaves in place, the
dense MLP in blocks of its width.

``FAULTS`` (empty in every served comparison) switches ONE thing wrong at a
time for ``benchmarks/tools/kda_mechanism_check.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import references.plain as plain
from references.plain import F32

# The mechanisms to get wrong, one at a time (tools/kda_mechanism_check.py
# reads this table from the reference a configuration names): the fault's
# name in ``FAULTS``, what it is, and whether the configuration's limits
# must refuse it.
FAULT_TABLE = (
    ("no_decay", "no decay (g = 0)", True),
    ("no_delta", "no delta correction (S += beta k v^T, nothing taken back)",
     True),
    ("beta_one", "beta = 1", True),
    # The convolution forgets its inputs at every multiple of CHUNK tokens.
    ("conv_tail_zeroed", "the convolution's tail zeroed at a chunk boundary",
     True),
    # The recurrence starts from the state another sequence left.
    ("stale_state", "the state not zeroed on a reused slot", True),
    ("no_out_gate", "the output gate left out", True),
    ("no_group_limit", "the group limit left out of the router (the top-k "
     "over every group)", True),
    ("int8_weights", "every weight matrix rounded to int8 a column", True),
)
FAULTS: set = set()
CHUNK = 2048                # the served deployment's --max-num-batched-tokens
MLP_BLOCKS = 4
LINEAR = "linear_attention"
L2_EPS = 1e-6


def _w(x):
    """A weight as the reference reads it."""
    x = x.astype(F32)
    if "int8_weights" in FAULTS and x.ndim >= 2:
        scale = jnp.max(jnp.abs(x), -2, keepdims=True) / 127.0
        x = jnp.round(x / jnp.maximum(scale, 1e-30)) * scale
    return x


def causal_conv(u, w):
    """Depthwise causal convolution, no bias: ``u`` [T, C], ``w`` [C, kernel]
    (``w[:, kernel - 1]`` meets the token itself)."""
    T, kernel = u.shape[0], w.shape[1]
    pos = jnp.arange(T)
    out = 0.0
    for d in range(kernel):
        seen = pos >= d
        if "conv_tail_zeroed" in FAULTS:
            seen = seen & (pos % CHUNK >= d)
        past = jnp.where(seen[:, None], u[jnp.maximum(pos - d, 0)], 0.0)
        out = out + past * w[:, kernel - 1 - d]
    return out


def recurrence(q, k, v, g, beta, s0):
    """The delta rule under a per-channel decay, token by token: ``q``,
    ``k``, ``g`` [T, H, K], ``v`` [T, H, V], ``beta`` [T, H], ``s0`` [H, K,
    V].  Returns (o [T, H, V], the last state)."""
    def token(s, inp):
        qt, kt, vt, gt, bt = inp
        s = s * jnp.exp(gt)[..., None]
        seen = jnp.einsum("hkv,hk->hv", s, kt)
        if "no_delta" in FAULTS:
            seen = 0.0
        s = s + kt[..., None] * (bt[:, None] * (vt - seen))[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    last, o = jax.lax.scan(token, s0, (q, k, v, g, beta))
    return o, last


def linear_attention(lp, c, x):
    T = x.shape[0]
    H, K, V = c.lin_num_heads, c.lin_key_dim, c.lin_value_dim
    qkv = jax.nn.silu(causal_conv(x @ _w(lp["lin_qkv_proj"]),
                                  lp["lin_conv_w"].astype(F32)))
    q = qkv[:, :H * K].reshape(T, H, K)
    k = qkv[:, H * K:2 * H * K].reshape(T, H, K)
    v = qkv[:, 2 * H * K:].reshape(T, H, V)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)

    q, k = unit(q) * K ** -0.5, unit(k)
    rate = jnp.exp(lp["lin_A_log"].astype(F32))[None, :, None]
    g = c.lin_gate_floor * jax.nn.sigmoid(rate * (
        x @ _w(lp["lin_f_proj"]) + lp["lin_dt_bias"].astype(F32)
    ).reshape(T, H, K))
    if "no_decay" in FAULTS:
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(x @ _w(lp["lin_b_proj"]))
    if "beta_one" in FAULTS:
        beta = jnp.ones_like(beta)
    s0 = jnp.zeros((H, K, V), F32)
    if "stale_state" in FAULTS:
        # What another sequence of 64 tokens leaves in the slot: this
        # sequence's own last tokens, read as a stranger's.
        _, s0 = recurrence(q[-64:], k[-64:], v[-64:], g[-64:], beta[-64:], s0)
    o, _ = recurrence(q, k, v, g, beta, s0)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + c.rms_norm_eps) * lp["lin_o_norm"].astype(F32)
    if "no_out_gate" not in FAULTS:
        o = o * jax.nn.sigmoid(x @ _w(lp["lin_g_proj"])).reshape(T, H, -1)
    return o.reshape(T, H * V) @ _w(lp["lin_o_proj"])


def latent_attention(lp, c, x, pos):
    T = x.shape[0]
    H, R = c.num_heads, c.kv_lora_rank
    nope, rope, vd = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    q = (x @ _w(lp["q_proj"])).reshape(T, H, nope + rope)
    kv_a = x @ _w(lp["kv_a_proj"])
    c_kv = plain.rms(kv_a[:, :R], lp["kv_a_norm"], c.rms_norm_eps)
    k_pe = plain.rope(kv_a[:, R:].reshape(T, 1, rope), pos,
                      c.rope_theta)[:, 0]
    q_pe = plain.rope(q[..., nope:], pos, c.rope_theta)
    w_kb = _w(lp["kv_b_proj"]).reshape(R, H, nope + vd)
    scale = (nope + rope) ** -0.5
    seen = jnp.tril(jnp.ones((T, T), bool))

    def head(args):
        qn, qp, wh = args              # [T, nope], [T, rope], [R, nope+vd]
        kvh = c_kv @ wh                               # [T, nope + vd]
        s = (qn @ kvh[:, :nope].T + qp @ k_pe.T) * scale
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return p @ kvh[:, nope:]

    out = jax.lax.map(head, (jnp.swapaxes(q[..., :nope], 0, 1),
                             jnp.swapaxes(q_pe, 0, 1),
                             jnp.swapaxes(w_kb, 0, 1)))     # [H, T, vd]
    return jnp.swapaxes(out, 0, 1).reshape(T, H * vd) @ _w(lp["o_proj"])


def dense_mlp(x, group, i):
    """SwiGLU in blocks of its width."""
    I = group["gate_proj"].shape[-1]
    n = MLP_BLOCKS if I % MLP_BLOCKS == 0 else 1
    out = 0.0
    for b in range(n):
        cols = slice(b * I // n, (b + 1) * I // n)
        out = out + (jax.nn.silu(x @ _w(group["gate_proj"][i][:, cols]))
                     * (x @ _w(group["up_proj"][i][:, cols]))
                     ) @ _w(group["down_proj"][i][cols])
    return out


def combine_weights(group, i, c, x):
    """[T, E] f32: each token's weight on every routed expert (0 on those
    it did not choose), under the group-limited selection."""
    scores = jax.nn.sigmoid(x @ group["router"][i].astype(F32))
    choice = scores + (group["e_bias"][i].astype(F32)[None]
                       if "e_bias" in group else 0.0)
    T, E = scores.shape
    if c.n_group and "no_group_limit" not in FAULTS:
        by_group = choice.reshape(T, c.n_group, E // c.n_group)
        best2 = jax.lax.top_k(by_group, 2)[0].sum(-1)           # [T, groups]
        _, kept = jax.lax.top_k(best2, c.topk_group)
        keep = jnp.zeros((T, c.n_group), bool).at[
            jnp.arange(T)[:, None], kept].set(True)
        choice = jnp.where(jnp.repeat(keep, E // c.n_group, axis=1), choice,
                           -jnp.inf)
    _, idx = jax.lax.top_k(choice, c.num_experts_per_tok)
    w = jnp.take_along_axis(scores, idx, 1)
    if c.moe_renormalize:
        w = w / w.sum(-1, keepdims=True)
    return jnp.zeros_like(scores).at[jnp.arange(T)[:, None], idx].add(
        w * c.routed_scaling_factor)


def experts(group, i, c, x, share=None, shared=True):
    """Routed experts of the group's layer ``i``: the router over all of
    them, the held ones computed one by one out of the stacked leaves, the
    shared expert added.  ``share`` (first id, count) replaces the config's
    own share and ``shared`` False leaves the shared expert out (the share
    test sums all the shares and counts the shared expert once)."""
    combine = combine_weights(group, i, c, x)
    e0, held = share if share is not None else (
        c.first_local_expert, c.num_local_experts or c.num_experts)

    def one(acc, e):
        y = plain.swiglu(x, _w(group["w_gate"][i, e]),
                         _w(group["w_up"][i, e]), _w(group["w_down"][i, e]))
        return acc + jnp.take(combine, e0 + e, axis=1)[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    if shared and "shared_gate" in group:
        out = out + plain.swiglu(x, _w(group["shared_gate"][i]),
                                 _w(group["shared_up"][i]),
                                 _w(group["shared_down"][i]))
    return out


def layers_of(params, c):
    """(kind, group, index within the group, has routed experts) of every
    layer, in the stack's order."""
    seen = {}
    for li in range(c.num_layers):
        kind = c.layer_types[li]
        moe = li >= c.first_dense_layers
        name = (("lin_" if kind == LINEAR else "")
                + ("moe_layers" if moe else "dense_layers"))
        i = seen.get(name, 0)
        seen[name] = i + 1
        yield kind, params[name], i, moe


def hidden_states(params, c, tokens):
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(F32)
    for kind, group, i, moe in layers_of(params, c):
        lp = {name: leaf[i] for name, leaf in group.items()
              if leaf.ndim <= 3}
        hn = plain.rms(x, lp["input_norm"], c.rms_norm_eps)
        x = x + (linear_attention(lp, c, hn) if kind == LINEAR
                 else latent_attention(lp, c, hn, pos))
        hn = plain.rms(x, lp["post_attn_norm"], c.rms_norm_eps)
        x = x + (experts(group, i, c, hn) if moe
                 else dense_mlp(hn, group, i))
    return x


def tail_logprobs(params, config, tokens, k):
    """float32 log-probabilities [k, V] of the token after each of the last
    ``k`` positions of ``tokens``."""
    c = config
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, c, tokens)
        h = plain.rms(x[-k:], params["final_norm"], c.rms_norm_eps)
        return jax.nn.log_softmax(h @ _w(params["lm_head"]))
