"""Plain reference of the dots3_note family (dots3-note-prev among them):
latent attention of two geometries in one stack, FULL layers that attend to
the keys a learned indexer selects, SLIDING layers that see a window, a
headwise sigmoid gate on both, rescaled latents, and routed experts of which
one rank's share is held.  From the published ``config.json``; what is not a
key's value is listed under ``assumed`` in the configuration's file.

    h = RMSNorm(x), pre-norm residuals, final RMSNorm, untied head.

  FULL layer (``layer_types[l] == "full_attention"``):
    c_q = a_q RMSNorm(h W_qa),  q = c_q W_qb -> per head q_nope | q_pe,
    RoPE(q_pe);  [c_kv | k_pe] = h W_kva,  c_kv = a_kv RMSNorm(c_kv),
    RoPE(k_pe) (one for all heads);  k_h = [c_kv W_uk,h | k_pe],
    v_h = c_kv W_uv,h (UNABSORBED; the program serves the absorbed form
    over a latent cache);  a_q = sqrt(hidden / q_lora_rank),
    a_kv = sqrt(hidden / kv_lora_rank).
    Indexer: qI = c_q W_Iq (Hi heads of Di, RoPE on the first rope columns
    of each), kI = LayerNorm(h W_Ik) (Di, RoPE on the same columns),
    w = h W_Iw (Hi);
        I(t, s) = Hi^-1/2 Di^-1/2 sum_j w[t, j] relu(qI[t, j] . kI[s])
    S_t = the index_topk keys s <= t of largest I(t, s), all while
    t < index_topk.
        o[t, h] = sum_{s in S_t} softmax_s(q[t, h] . k[s, h] / sqrt(d_qk)) v[s, h]
    gate g = sigmoid(h W_g) (one scalar a head), o[t, h] *= g[t, h];
    output concat_h(o) W_o.
  SLIDING layer: the same block without the indexer at the ``swa_*``
    widths, keys t - window < s <= t.
  Feed-forward: the leading layers dense SwiGLU; the others
    s = sigmoid(h W_r) over ALL experts, the top-k of s + bias, weights
    s_e / sum s_e x routed_scaling_factor, plus the shared expert.  Of the
    routed experts ids first_local_expert .. + num_local_experts - 1 are
    held: a token's slots routed elsewhere add nothing.

Float32 under ``jax.default_matmul_precision("highest")``; no cache, no
kernels, no batching, one sequence.  It reads the program's parameter tree
and ``ModelConfig`` fields and nothing else of the program.  Only to bound
memory beside a served engine: attention runs one head at a time (a
[T, T] score plane, not [H, T, T]; the head's keys and values expanded
inside the loop), the index scores one index head at a time, the experts
one at a time read out of the stacked leaves in place, the dense MLP in
blocks of its width.

``FAULTS`` (empty in every served comparison) switches ONE thing wrong at a
time for ``benchmarks/tools/dsa_mechanism_check.py``.  ``selection_margins``
gives the gap between the last key selected and the first left out: with
random weights the scores near rank ``index_topk`` lie close together, so
bf16 rounding flips a few members of a set as it flips expert sets.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

import references.plain as plain
from references.plain import F32

# Names of mechanisms to get wrong (tools/dsa_mechanism_check.py):
# "no_rescale", "no_gate", "dense_full" (every visible key attended to),
# "window_plus_one", "int8_weights" (every matrix rounded to int8 a
# column), "int8_kv" (latent rows rounded to int8 before expansion).
FAULTS: set = set()
MLP_BLOCKS = 4
SLIDING = "sliding_attention"


def _w(x):
    """A weight as the reference reads it."""
    x = x.astype(F32)
    if "int8_weights" in FAULTS and x.ndim >= 2:
        scale = jnp.max(jnp.abs(x), -2, keepdims=True) / 127.0
        x = jnp.round(x / scale) * scale
    return x


def _geometry(c, kind):
    """(heads, q_lora, kv_lora, nope, rope, v, theta, window, topk)."""
    full = (c.num_heads, c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim, c.rope_theta)
    if kind != SLIDING:
        return full + (0, c.index_topk)
    own = (c.swa_num_heads, c.swa_q_lora_rank, c.swa_kv_lora_rank,
           c.swa_qk_nope_head_dim, c.swa_qk_rope_head_dim, c.swa_v_head_dim,
           c.swa_rope_theta)
    return tuple(o or f for o, f in zip(own, full)) + (c.sliding_window, 0)


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * w.astype(F32) + b.astype(F32))


def rope_first(x, pos, theta, rope):
    """x [T, H, D]: the rotary embedding on the first ``rope`` columns."""
    return jnp.concatenate(
        [plain.rope(x[..., :rope], pos, theta), x[..., rope:]], -1)


def index_scores(lp, c, h, cq, pos, theta, rope):
    """I [T, T] f32, -inf where s > t."""
    T = h.shape[0]
    Hi, Di = c.index_n_heads, c.index_head_dim
    qI = rope_first((cq @ _w(lp["index_q_proj"])).reshape(T, Hi, Di),
                    pos, theta, rope)
    kI = layer_norm(h @ _w(lp["index_k_proj"]), lp["index_k_norm"],
                    lp["index_k_norm_bias"], c.rms_norm_eps)
    kI = rope_first(kI[:, None, :], pos, theta, rope)[:, 0]
    w = (h @ _w(lp["index_w_proj"])) * (Hi ** -0.5) * (Di ** -0.5)

    def head(acc, qw):
        qj, wj = qw                                   # [T, Di], [T]
        return acc + wj[:, None] * jax.nn.relu(qj @ kI.T), None

    scores, _ = jax.lax.scan(
        head, jnp.zeros((T, T), F32),
        (jnp.swapaxes(qI, 0, 1), jnp.swapaxes(w, 0, 1)))
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -jnp.inf)


def selected(scores, topk: int):
    """[T, T] bool: the ``topk`` best visible keys of each query (all of
    them while fewer are visible), and the margin [T] between the last one
    kept and the first one left out (inf where none is left out)."""
    T = scores.shape[0]
    if T <= topk:
        return scores > -jnp.inf, jnp.full((T,), jnp.inf, F32)
    # Equal scores (a ReLU leaves exact zeros): the lower position first,
    # as ``lax.top_k`` orders them.
    best, at = jax.lax.top_k(scores, topk + 1)
    keep = jnp.zeros((T, T), bool).at[
        jnp.arange(T)[:, None], at[:, :topk]].set(True)
    return keep & (scores > -jnp.inf), jnp.where(
        best[:, topk] > -jnp.inf, best[:, topk - 1] - best[:, topk], jnp.inf)


def attention(lp, c, x, pos, kind, margins: List = None):
    T = x.shape[0]
    H, _, R, nope, rope, vd, theta, window, topk = _geometry(c, kind)
    rescale = c.mla_lora_rescale and "no_rescale" not in FAULTS
    cq = plain.rms(x @ _w(lp["q_a_proj"]), lp["q_a_norm"], c.rms_norm_eps)
    if rescale:
        cq = cq * (c.hidden_size / cq.shape[-1]) ** 0.5
    q = (cq @ _w(lp["q_b_proj"])).reshape(T, H, nope + rope)
    kv_a = x @ _w(lp["kv_a_proj"])
    c_kv = plain.rms(kv_a[:, :R], lp["kv_a_norm"], c.rms_norm_eps)
    if rescale:
        c_kv = c_kv * (c.hidden_size / R) ** 0.5
    k_pe = plain.rope(kv_a[:, R:].reshape(T, 1, rope), pos, theta)[:, 0]
    q_pe = plain.rope(q[..., nope:], pos, theta)
    if "int8_kv" in FAULTS:
        from tools.mechanism_check import int8_rows
        c_kv, k_pe = int8_rows(c_kv), int8_rows(k_pe)

    seen = jnp.tril(jnp.ones((T, T), bool))
    if window:
        w = window + ("window_plus_one" in FAULTS)
        seen = seen & (pos[None, :] > pos[:, None] - w)
    if topk and "dense_full" not in FAULTS:
        keep, margin = selected(
            index_scores(lp, c, x, cq, pos, theta, rope), topk)
        seen = seen & keep
        if margins is not None:
            margins.append(margin)

    w_kb = _w(lp["kv_b_proj"]).reshape(R, H, nope + vd)
    scale = (nope + rope) ** -0.5

    def head(args):
        qn, qp, wh = args              # [T, nope], [T, rope], [R, nope+vd]
        kvh = c_kv @ wh                               # [T, nope + vd]
        s = (qn @ kvh[:, :nope].T + qp @ k_pe.T) * scale
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return p @ kvh[:, nope:]

    out = jax.lax.map(head, (jnp.swapaxes(q[..., :nope], 0, 1),
                             jnp.swapaxes(q_pe, 0, 1),
                             jnp.swapaxes(w_kb, 0, 1)))     # [H, T, vd]
    out = jnp.swapaxes(out, 0, 1)
    if "head_gate" in lp and "no_gate" not in FAULTS:
        out = out * jax.nn.sigmoid(x @ _w(lp["head_gate"]))[:, :, None]
    return out.reshape(T, H * vd) @ _w(lp["o_proj"])


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


def experts(group, i, c, x, share=None):
    """Routed experts of the group's layer ``i``: the router over all of
    them, the held ones computed one by one out of the stacked leaves, the
    shared expert added.  ``share`` (first id, count) replaces the config's
    own share (the share test sums all the shares)."""
    scores = jax.nn.sigmoid(x @ group["router"][i].astype(F32))
    choice = scores + (group["e_bias"][i].astype(F32)[None]
                       if "e_bias" in group else 0.0)
    _, idx = jax.lax.top_k(choice, c.num_experts_per_tok)
    w = jnp.take_along_axis(scores, idx, 1)
    if c.moe_renormalize:
        w = w / w.sum(-1, keepdims=True)
    w = w * c.routed_scaling_factor
    combine = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], idx].add(w)          # [T, E]
    e0, held = share if share is not None else (
        c.first_local_expert, c.num_local_experts or c.num_experts)

    def one(acc, e):
        y = plain.swiglu(x, _w(group["w_gate"][i, e]),
                         _w(group["w_up"][i, e]), _w(group["w_down"][i, e]))
        return acc + jnp.take(combine, e0 + e, axis=1)[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    if "shared_gate" in group:
        out = out + plain.swiglu(x, _w(group["shared_gate"][i]),
                                 _w(group["shared_up"][i]),
                                 _w(group["shared_down"][i]))
    return out


def layers_of(params, c):
    """(kind, group, index within the group, has routed experts) of every
    layer, in the stack's order."""
    seen = {}
    for li in range(c.num_layers):
        kind = c.layer_types[li] if c.layer_types else "full_attention"
        moe = li >= c.first_dense_layers
        name = (("swa_" if kind == SLIDING else "")
                + ("moe_layers" if moe else "dense_layers"))
        i = seen.get(name, 0)
        seen[name] = i + 1
        yield kind, params[name], i, moe


def hidden_states(params, c, tokens, margins: List = None):
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(F32)
    for kind, group, i, moe in layers_of(params, c):
        lp = {name: leaf[i] for name, leaf in group.items()
              if leaf.ndim <= 3}
        x = x + attention(lp, c, plain.rms(x, lp["input_norm"],
                                           c.rms_norm_eps), pos, kind,
                          margins)
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


def selection_margins(params, config, tokens):
    """[full layers that select, T] f32: per query the gap between the
    index score of the last key selected and the first left out (inf while
    nothing is left out)."""
    margins: List = []
    with jax.default_matmul_precision("highest"):
        hidden_states(params, config, tokens, margins)
    return jnp.stack(margins)
