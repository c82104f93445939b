"""Plain reference of the ``mellum`` family (Mellum2-12B-A2.5B among them): a
stack that mixes sliding-window and full attention layers 3:1 over sparse
experts in every layer, followed from the published ``config.json`` (there is
no network here, so no modelling code was read):

  - grouped-query attention, an RMS norm over each head of q and k before
    the rotary embedding;
  - ``sliding_attention`` layers see keys j with i - sliding_window < j <= i
    and rotate by ``rope_parameters["sliding_attention"]``: plain tables,
    inverse frequencies theta^(-2i/d);
  - ``full_attention`` layers see all j <= i and rotate by
    ``rope_parameters["full_attention"]``: YaRN.  With d the head size,
    dimension i turns r(i) = L0 / (2 pi theta^(2i/d)) times over the original
    L0 = ``original_max_position_embeddings`` positions; the correction
    dimensions are where r = ``beta_fast`` and r = ``beta_slow``,
    c(b) = d ln(L0 / (2 pi b)) / (2 ln theta), rounded down and up and held
    inside 0 .. d - 1; the inverse frequency of dimension i is
    (1 - g) theta^(-2i/d) + g theta^(-2i/d) / factor with the linear ramp
    g = clip((i - low) / (high - low), 0, 1); cos and sin are multiplied by
    ``attention_factor`` (so the scores by its square);
  - every layer's MLP is routed experts: softmax over all of them, top-k,
    renormalised (``norm_topk_prob``), no shared expert, no dense layer.

Departures from the published model: the q/k norm is the convention of the
family whose config keys these are (Qwen3-MoE's), since ``config.json`` has
no key for it; ``intermediate_size`` is unused (every ``mlp_layer_types``
entry is ``sparse``); the multi-token-prediction head has no key in the
config and is not here.  Weights are random from the run's seed.

Its own attention and its own YaRN tables; of ``plain.py`` it takes the
norm, the rotate-half rotation's layout, the routed experts and nothing of
the program but its parameter tree and ``ModelConfig``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import references.plain as plain
from references.plain import F32


def inv_freq(rule, d: int):
    """Inverse frequencies [d / 2] of one kind's rotary rule and the factor
    on cos and sin: (theta, factor, original_max, beta_fast, beta_slow,
    attention_factor), ``factor`` 0 for plain tables."""
    theta, factor, original_max, beta_fast, beta_slow, attention_factor = rule
    i = jnp.arange(0, d, 2, dtype=F32)
    plain_freq = theta ** (-i / d)
    if not factor:
        return plain_freq, 1.0

    def correction(turns):
        return d * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return (1 - ramp) * plain_freq + ramp * plain_freq / factor, \
        attention_factor


def rotate(x, positions, freq, mscale):
    """x [T, H, D], rotate-half pairs (i, i + D/2), cos and sin scaled."""
    f = positions.astype(F32)[:, None] * freq[None, :]
    cos = (jnp.cos(f) * mscale)[:, None, :]
    sin = (jnp.sin(f) * mscale)[:, None, :]
    d = x.shape[-1]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def masked_attention(q, k, v, mask, scale):
    """q, k, v [T, H, D], mask [T, T] -> [T, H, D], one head at a time, so
    that a 6,200-token prompt's scores fit beside a served engine."""

    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.where(mask, (qh @ kh.T) * scale, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    out = jax.lax.map(head, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    return jnp.swapaxes(out, 0, 1)


def attention(lp, c, h, pos, sliding, rules):
    """One layer's attention on the normed input ``h``; ``sliding`` is the
    layer's kind (a traced bool under the layers' scan), ``rules`` the
    rotary rule of (full layers, sliding layers)."""
    T, H, KVH, D = h.shape[0], c.num_heads, c.num_kv_heads, c.head_dim_
    q = plain.rms((h @ lp["q_proj"].astype(F32)).reshape(T, H, D),
                  lp["q_norm"], c.rms_norm_eps)
    k = plain.rms((h @ lp["k_proj"].astype(F32)).reshape(T, KVH, D),
                  lp["k_norm"], c.rms_norm_eps)
    v = (h @ lp["v_proj"].astype(F32)).reshape(T, KVH, D)
    (f_full, m_full), (f_win, m_win) = (inv_freq(r, D) for r in rules)
    q = jnp.where(sliding, rotate(q, pos, f_win, m_win),
                  rotate(q, pos, f_full, m_full))
    k = jnp.where(sliding, rotate(k, pos, f_win, m_win),
                  rotate(k, pos, f_full, m_full))
    i, j = pos[:, None], pos[None, :]
    mask = (j <= i) & (~sliding | (j > i - c.sliding_window))
    a = masked_attention(q, jnp.repeat(k, H // KVH, axis=1),
                         jnp.repeat(v, H // KVH, axis=1), mask, D ** -0.5)
    return a.reshape(T, H * D) @ lp["o_proj"].astype(F32)


def rope_rules(c):
    """The config's rotary rule of (full layers, sliding layers), each as
    ``inv_freq`` takes it."""
    by_kind = dict(c.rope_parameters)
    return tuple(tuple(by_kind[kind]) for kind in
                 ("full_attention", "sliding_attention"))


def tail_logprobs(params, config, tokens, k, rules=None):
    """float32 log-probabilities [k, V] of the token after each of the last
    ``k`` positions of ``tokens``.  ``rules``: another rotary rule by kind
    than the config's (what ``tools/mellum_mechanism_check.py`` gets wrong
    on purpose)."""
    c = config
    rules = rules or rope_rules(c)
    sliding = jnp.asarray([t == "sliding_attention" for t in c.layer_types])
    eps = c.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = params["embed"][tokens].astype(F32)

        # A scan only to bound memory, as in plain.decoder.
        def layer(x, xs):
            lp, kind = xs
            x = x + attention(lp, c, plain.rms(x, lp["input_norm"], eps),
                              pos, kind, rules)
            return x + plain.experts(
                lp, c, plain.rms(x, lp["post_attn_norm"], eps)), None

        x, _ = jax.lax.scan(layer, x, (params["moe_layers"], sliding))
        h = plain.rms(x[-k:], params["final_norm"], eps)
        return jax.nn.log_softmax(h @ params["lm_head"].astype(F32))
