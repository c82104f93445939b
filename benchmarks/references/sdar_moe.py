"""Plain reference of the SDAR-MoE family (SDAR-30B-A3B-Chat among them):
Qwen3-MoE's weight tree, generated by diffusion over blocks of B tokens.

Weights and every per-token operation are ``qwen3_moe``'s: grouped-query
attention with an RMS norm over each head of q and k, the rotary embedding
in the rotate-half layout at the true positions, softmax top-k routing
renormalised, no shared expert, an untied head.  Two things differ.

1. Attention is block-causal.  With block length B the query at absolute
   position p sees key j iff ``j // B <= p // B``: its whole block and every
   block before it.  Blocks are aligned on absolute positions, the prompt's
   included.

2. Generation of a request with a prompt of n tokens (greedy):
   - the prompt's whole blocks ``x[0 : n // B * B]`` are forwarded under the
     block mask and their keys and values are final; its tail opens the
     first generated block as revealed slots;
   - for each block k from ``n // B`` on, every slot at ``[kB, (k+1)B)`` is
     revealed or holds ``mask_token_id``.  While a slot is masked: forward
     the B slots against the blocks before and themselves (nothing of the
     pass is kept); at every masked slot i ``x0_i = argmax logits_i`` (the
     logits AT position i predict the token AT position i: no shift) and
     ``conf_i = softmax(logits_i)[x0_i]``; some masked slots are revealed
     and take their x0 (``reveal_rule``).  Pass s of a block has the quota
     ``B // steps`` (+1 for the first ``B % steps`` passes).  A revealed
     slot is never masked again.  When no slot is masked the block's final
     tokens are forwarded once more and those keys and values are kept (the
     commit pass): with B = steps = 4, five forwards a block of four tokens;
   - the answer is positions ``n .. n + max_tokens - 1``; a token counts
     once it and every slot before it are revealed; an EOS ends the answer
     at the first revealed EOS whose predecessors are all revealed; the
     last block stops as soon as the asked prefix is revealed.  A token's
     logprob is ``log softmax(logits_i)[x0_i]`` of the pass that revealed
     it.

No cache here: a pass is one forward over every token so far, which under
the block mask gives the earlier blocks the keys the commit pass gave them.

Departures from the published model: none in the equations.  Block length,
passes a block, the mask token's id, "no shift" and the three strategies are
the family's generation settings as the builder knows them (there is no
network here; the configuration's ``assumed`` lists them) and are read from
``ModelConfig``.  Equal confidences (a tie ``low_confidence_*`` can meet)
go to the leftmost slot.  ``temperature > 0`` (x0 sampled) is not rendered.

Of ``plain.py`` it takes the norm, the rotary embedding and the routed
experts.  It reads the program's parameter tree and ``ModelConfig`` and
nothing else of the program.

``WRONG`` names one thing to get wrong, for ``tools/sdar_mechanism_check.py``
(what the comparison that decides ``correct`` must refuse); "" is the
reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import references.plain as plain
from references.plain import F32

# One of: "" (the reference), "causal_mask" (a causal mask in place of the
# block mask), "stale_pass" (the slot before the one a pass reveals still
# masked), "denoise_keys" (earlier generated blocks' keys taken from their
# last denoising pass, whose last slot was still masked, not from the
# commit pass).  Read when a function here is traced.
WRONG = ""


def masked_attention(q, k, v, mask, scale):
    """q, k, v [T, H, D], mask [T, T] -> [T, H, D], one head at a time
    (``lax.map``) only to bound memory."""

    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.where(mask, (qh @ kh.T) * scale, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    out = jax.lax.map(head, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    return jnp.swapaxes(out, 0, 1)


def attention(lp, c, x, pos):
    T, H, KVH, D = x.shape[0], c.num_heads, c.num_kv_heads, c.head_dim_
    B = c.diffusion_block_length
    q = plain.rms((x @ lp["q_proj"].astype(F32)).reshape(T, H, D),
                  lp["q_norm"], c.rms_norm_eps)
    k = plain.rms((x @ lp["k_proj"].astype(F32)).reshape(T, KVH, D),
                  lp["k_norm"], c.rms_norm_eps)
    v = (x @ lp["v_proj"].astype(F32)).reshape(T, KVH, D)
    q = plain.rope(q, pos, c.rope_theta)
    k = plain.rope(k, pos, c.rope_theta)
    i, j = pos[:, None], pos[None, :]
    mask = j <= i if WRONG == "causal_mask" else j // B <= i // B
    a = masked_attention(q, jnp.repeat(k, H // KVH, axis=1),
                         jnp.repeat(v, H // KVH, axis=1), mask, D ** -0.5)
    return a.reshape(T, H * D) @ lp["o_proj"].astype(F32)


def hidden_states(params, c, tokens):
    """The decoder stack over ``tokens`` [T] under the block mask: the final
    norm's output [T, hidden].  Every layer is attention + routed experts."""
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(F32)

    # A scan only to bound memory, as in plain.decoder.
    def layer(x, lp):
        x = x + attention(lp, c, plain.rms(x, lp["input_norm"],
                                           c.rms_norm_eps), pos)
        return x + plain.experts(lp, c, plain.rms(
            x, lp["post_attn_norm"], c.rms_norm_eps)), None

    x, _ = jax.lax.scan(layer, x, params["moe_layers"])
    return plain.rms(x, params["final_norm"], c.rms_norm_eps)


def log_probs(params, h):
    return jax.nn.log_softmax(h @ params["lm_head"].astype(F32))


def tail_logprobs(params, config, tokens, k):
    """For each of the last ``k`` positions j of ``tokens`` + the one after
    them (the generated positions of a request whose ``tokens`` are its
    prompt and all but the last of its answer): the log-probabilities [k, V]
    of the token AT j, from the pass that revealed it under ``sequential``.
    That pass saw every token before j and the mask token from j to the end
    of j's block; what lies behind j's block is invisible to it, so each
    pass is one forward over one padded length.  k forwards, no cache."""
    c = config
    B = c.diffusion_block_length
    L0 = tokens.shape[0]
    n = L0 + 1 - k                       # the first generated position
    L = -(-(L0 + 1) // B) * B
    at = jnp.arange(L)
    toks = jnp.concatenate(
        [tokens, jnp.full((L - L0,), c.mask_token_id, tokens.dtype)])

    def one(j):
        start = j // B * B
        # The first masked slot: j itself; "stale_pass": the generated slot
        # before it in its block too.
        first = jnp.maximum(j - 1, jnp.maximum(start, n)) \
            if WRONG == "stale_pass" else j
        x = jnp.where(at < first, toks, c.mask_token_id)
        if WRONG == "denoise_keys":
            x = jnp.where((at >= n) & (at < start) & (at % B == B - 1),
                          c.mask_token_id, x)
        h = hidden_states(params, c, x)
        return log_probs(params, jax.lax.dynamic_slice_in_dim(h, j, 1))[0]

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, n + jnp.arange(k))


def reveal_rule(conf, masked, quota: int, strategy: str, threshold: float):
    """Which masked slots of one block a pass reveals (numpy bools [B]).
    ``conf`` [B] the slots' confidences, ``masked`` [B] bools."""
    conf, masked = np.asarray(conf, np.float64), np.asarray(masked, bool)
    idx = np.flatnonzero(masked)
    if strategy == "sequential":
        pick = idx[:quota]
    else:
        # the highest confidences, the leftmost of equal ones first
        pick = idx[np.argsort(-conf[idx], kind="stable")[:quota]]
        if strategy == "low_confidence_dynamic":
            sure = idx[conf[idx] > threshold]
            if len(sure) >= quota:
                pick = sure
        elif strategy != "low_confidence_static":
            raise ValueError(f"unknown remasking strategy {strategy!r}")
    out = np.zeros(len(masked), bool)
    out[pick] = True
    return out


def generate(params, config, prompt, max_tokens: int, eos=None):
    """The generation loop of the equations above, greedy: (token ids,
    the logprob each was revealed with).  One full forward a pass, no cache;
    for the tiny presets of the tests."""
    c = config
    B, steps = c.diffusion_block_length, (
        c.diffusion_steps or c.diffusion_block_length)
    n = len(prompt)
    L = -(-(n + max_tokens) // B) * B

    @jax.jit
    def forward(p, x):
        with jax.default_matmul_precision("highest"):
            return log_probs(p, hidden_states(p, c, x))

    toks = np.full(L, c.mask_token_id, np.int32)
    toks[:n] = prompt
    known = np.arange(L) < n
    lps = np.zeros(L, np.float64)
    emitted = n                 # tokens that count: revealed without a gap

    def done():
        return emitted >= n + max_tokens or (
            eos is not None and emitted > n and toks[emitted - 1] == eos)

    for start in range(n // B * B, L, B):
        sl = slice(start, start + B)
        for s in range(steps):
            if known[sl].all() or done():
                break
            lp = np.asarray(forward(params, jnp.asarray(toks)))[sl]
            x0 = lp.argmax(-1)
            own = lp[np.arange(B), x0]
            now = reveal_rule(
                np.exp(own), ~known[sl], B // steps + (s < B % steps),
                c.diffusion_remasking, c.diffusion_confidence_threshold)
            toks[sl] = np.where(now, x0, toks[sl])
            lps[sl] = np.where(now, own, lps[sl])
            known[sl] |= now
            while emitted < L and known[emitted] and not done():
                emitted += 1
        if done():
            break
    return toks[n:emitted].tolist(), lps[n:emitted].tolist()
