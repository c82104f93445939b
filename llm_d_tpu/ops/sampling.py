"""On-device batched sampling: greedy / temperature / top-k / top-p.

All sequences in a step sample in one vectorized op with per-sequence
parameters (static shapes; data-dependent k/p handled by masking over the
sorted vocabulary, not dynamic slicing).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from llm_d_tpu.models.config import DIFFUSION_REMASKING


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration (OpenAI API surface)."""
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0              # 0 = disabled
    max_tokens: int = 16
    min_tokens: int = 0
    stop: tuple = ()
    seed: Optional[int] = None
    ignore_eos: bool = False
    logprobs: Optional[int] = None

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


# Sampling truncates to the top TOPK_MAX logits before applying top-k/top-p
# (a full-vocab sort costs ~100 ms/step on TPU; mass beyond the top 64 of an
# LLM distribution is negligible — same truncation vLLM's TPU backend uses).
TOPK_MAX = 64


def sample(
    logits: jax.Array,        # [S, V] f32
    temperature: jax.Array,   # [S] f32 (0 = greedy)
    top_k: jax.Array,         # [S] i32 (0 = off)
    top_p: jax.Array,         # [S] f32 (1 = off)
    key: jax.Array,           # PRNG key for this step
    seeds: Optional[jax.Array] = None,     # [S] i32, -1 = unseeded
    gen_idx: Optional[jax.Array] = None,   # [S] i32 tokens generated so far
) -> jax.Array:               # [S] i32 sampled token ids
    """Batched sampling with per-request seeded reproducibility.

    Rows with ``seeds[s] >= 0`` draw from ``fold_in(fold_in(zero_key,
    seed), gen_idx)`` — deterministic for a given (seed, position)
    regardless of batch composition or engine step count (the vLLM
    ``SamplingParams.seed`` contract). Unseeded rows derive from the
    engine's per-step key folded with the row index.
    """
    S, V = logits.shape
    greedy_ids = jnp.argmax(logits, axis=-1)
    K = min(TOPK_MAX, V)

    def row_keys():
        rows = jnp.arange(S)
        unseeded = jax.vmap(lambda i: jax.random.fold_in(key, i))(rows)
        if seeds is None:
            return unseeded
        base = jax.random.PRNGKey(0)
        gi = gen_idx if gen_idx is not None else jnp.zeros(S, jnp.int32)
        seeded = jax.vmap(lambda s, g: jax.random.fold_in(
            jax.random.fold_in(base, jnp.maximum(s, 0)), g))(seeds, gi)
        pick = (seeds >= 0)[:, None]
        return jnp.where(pick, seeded, unseeded)

    def do_sample(_):
        vals, idxs = jax.lax.top_k(logits, K)                # [S, K]
        temp = jnp.maximum(temperature, 1e-6)[:, None]
        v = vals / temp
        ranks = jnp.arange(K)[None, :]
        k_eff = jnp.where(top_k <= 0, K, jnp.minimum(top_k, K))[:, None]
        keep_k = ranks < k_eff
        probs = jax.nn.softmax(v, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep tokens until cumulative prob (exclusive) exceeds p; rank 0
        # always survives.
        keep_p = (cum - probs) < top_p[:, None]
        masked = jnp.where(keep_k & keep_p, v, -jnp.inf)
        gumbel = jax.vmap(
            lambda k: jax.random.gumbel(k, (K,), jnp.float32))(row_keys())
        choice = jnp.argmax(masked + gumbel, axis=-1)        # [S]
        return jnp.take_along_axis(idxs, choice[:, None], axis=-1)[:, 0]

    # Scalar predicate: all-greedy batches skip the top-k machinery entirely.
    sampled_ids = jax.lax.cond(
        jnp.any(temperature > 0.0), do_sample, lambda _: greedy_ids, None)
    return jnp.where(temperature <= 0.0, greedy_ids, sampled_ids)


def spec_verify(
    logits: jax.Array,        # [S*(K+1), V] f32 (position-major per seq)
    draft_tokens: jax.Array,  # [S, K] i32 drafted ids fed at q slots 1..K
    spec_n: jax.Array,        # [S] i32 live drafts per seq (0 = plain decode)
    temperature: jax.Array,   # [S] f32
    top_k: jax.Array,         # [S] i32
    top_p: jax.Array,         # [S] f32
    key: jax.Array,
    seeds: jax.Array,         # [S] i32, -1 = unseeded
    gen0: jax.Array,          # [S] i32 output tokens emitted before this step
    fixed_accept: Optional[float] = None,   # bench: seeded acceptance rate
    step: Optional[jax.Array] = None,       # scalar i32 (fixed_accept key)
) -> tuple:                   # (ids [S, K+1], accepted [S] in 0..K)
    """On-device draft verification + bonus-token sampling.

    Every query position samples the TARGET model's token with the same
    per-position randomness the non-spec engine uses — seeded rows via
    ``fold_in(fold_in(zero_key, seed), gen0 + q)`` (the vLLM seed
    contract, so position q's draw is identical whether it was reached
    speculatively or one step at a time), greedy rows via argmax.  A
    draft is accepted while it EQUALS the target's own sample at that
    position; the first mismatch position's target sample is the
    correction token, and a fully-accepted row's last position yields
    the bonus token — so the emitted prefix ``ids[:, :accepted+1]`` is
    byte-identical to non-spec decode for greedy and seeded sampling,
    whatever the drafter proposed.  Drafter quality moves throughput
    only, never output.

    ``fixed_accept`` (bench/diagnostics only):
    replace the equality check with a SEEDED per-draft coin at this rate
    keyed on (step, row) — deterministic accepted-length schedules for
    the accepted-tok/s bench metric.  Changes model output (accepted
    drafts are emitted verbatim); never used on the serving path.
    """
    S, K = draft_tokens.shape
    Q = K + 1

    def rep(x):
        return jnp.repeat(x, Q)

    gen_idx = (gen0[:, None] + jnp.arange(Q, dtype=jnp.int32)[None, :]
               ).reshape(-1)
    ids = sample(logits, rep(temperature), rep(top_k), rep(top_p), key,
                 seeds=rep(seeds), gen_idx=gen_idx).reshape(S, Q)
    if fixed_accept is not None:
        fk = jax.random.fold_in(
            jax.random.PRNGKey(0x5BEC),
            step if step is not None else jnp.int32(0))
        match = jax.random.uniform(fk, (S, K)) < fixed_accept
    else:
        match = draft_tokens == ids[:, :K]
    live = jnp.arange(K, dtype=jnp.int32)[None, :] < spec_n[:, None]
    accepted = jnp.cumprod((match & live).astype(jnp.int32),
                           axis=1).sum(axis=1)
    return ids, accepted


def reveal(
    logits: jax.Array,      # [S*B, V] f32, a row's B slots side by side
    x0: jax.Array,          # [S*B] i32 the token each slot would take
    masked: jax.Array,      # [S, B] bool slots that hold the mask token
    quota: jax.Array,       # [S] i32 slots this pass reveals (0: a row that
                            # does not generate)
    strategy: str,          # models.config.DIFFUSION_REMASKING
    threshold: float,
) -> tuple:                 # (ids [S, B] i32, -1 = not revealed by this
                            #  pass; logprobs [S, B] of x0)
    """One denoising pass's reveal rule of a block-diffusion model, on the
    device: which masked slots take their candidate ``x0`` now.  A slot's
    confidence is ``softmax(logits)[x0]``.

      sequential              the first ``quota`` masked slots from the left
      low_confidence_static   the ``quota`` masked slots of highest
                              confidence (equal confidences: the leftmost)
      low_confidence_dynamic  every masked slot whose confidence passes
                              ``threshold`` if they are at least ``quota``,
                              else as low_confidence_static

    A quota above the row's masked slots reveals them all.  B is a handful,
    so ranks are counted pairwise: no sort."""
    if strategy not in DIFFUSION_REMASKING:
        raise ValueError(f"unknown remasking strategy {strategy!r}")
    S, B = masked.shape
    logprobs = compute_logprobs(logits, x0).reshape(S, B)
    if strategy == "sequential":
        rank = jnp.cumsum(masked, axis=1, dtype=jnp.int32) - 1   # from left
    else:
        conf = jnp.where(masked, jnp.exp(logprobs), -1.0)
        ahead = (conf[:, None, :] > conf[:, :, None]) | (
            (conf[:, None, :] == conf[:, :, None])
            & (jnp.arange(B)[None, None, :] < jnp.arange(B)[None, :, None]))
        rank = jnp.sum(ahead, axis=2, dtype=jnp.int32)   # slots ranked before
    now = masked & (rank < quota[:, None])
    if strategy == "low_confidence_dynamic":
        sure = masked & (conf > threshold) & (quota[:, None] > 0)
        now = jnp.where(
            jnp.sum(sure, axis=1, keepdims=True) >= quota[:, None], sure, now)
    return jnp.where(now, x0.reshape(S, B), -1).astype(jnp.int32), logprobs


def compute_logprobs(logits: jax.Array, token_ids: jax.Array) -> jax.Array:
    """Log-probability of the chosen tokens. logits [S, V], ids [S]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, token_ids[:, None], axis=-1)[:, 0]


def verify_logprobs(logits: jax.Array, ids: jax.Array,
                    top_n: int = 0):
    """Per-position logprobs over the K+1 verify stride, on device.

    ``logits`` [S*(K+1), V] is the verify-stride layout ``spec_verify``
    consumes; ``ids`` [S, K+1] are its sampled tokens.  Returns
    ``lp [S, K+1]`` (and, when ``top_n > 0``, ``top_ids [S, K+1, n]`` /
    ``top_lps [S, K+1, n]``) — EVERY stride position is scored so the
    host can slice the accepted prefix ``[:accepted+1]`` after the fused
    fetch without a second device round trip.  Rejected-draft positions
    are computed and discarded (they share the already-materialized
    log-softmax); a row whose stride replicates one chunk-last token
    (prefill rows in the mixed round) just repeats position 0's value.
    This is what lets logprobs rows ride the spec path instead of
    demoting to the classic epilogue."""
    S, Q = ids.shape
    flat = ids.reshape(-1)
    if top_n <= 0:
        return compute_logprobs(logits, flat).reshape(S, Q)
    chosen, top_ids, top_lps = compute_top_logprobs(logits, flat, top_n)
    return (chosen.reshape(S, Q), top_ids.reshape(S, Q, top_n),
            top_lps.reshape(S, Q, top_n))


def compute_top_logprobs(logits: jax.Array, token_ids: jax.Array,
                         n: int = 20):   # OpenAI chat's top_logprobs max
    """Chosen-token logprobs plus the top-``n`` alternatives.

    Returns (chosen [S], top_ids [S, n], top_logprobs [S, n]) — the data
    the OpenAI ``logprobs`` response field needs (vLLM returns the same
    per-position top list).  ``n`` is static: one extra ``lax.top_k`` over
    the already-materialized log-softmax."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    chosen = jnp.take_along_axis(logp, token_ids[:, None], axis=-1)[:, 0]
    top_lps, top_ids = jax.lax.top_k(logp, n)
    return chosen, top_ids.astype(jnp.int32), top_lps
