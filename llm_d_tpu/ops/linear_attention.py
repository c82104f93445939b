"""Gated delta-rule linear attention over the engine's state pool.

A LINEAR layer (``ModelConfig.layer_types``) keeps, in place of keys and
values in pages, one recurrent state a head that every token overwrites:

  S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
  o_t = S_t^T q_t

(per head: ``S`` [K, V] float32, ``q``, ``k`` [K] L2-normed, ``v`` [V],
``g`` [K] the log-decay a KEY CHANNEL, in (floor, 0), ``beta`` in (0, 1)):
the state forgets by channel, then the delta rule replaces what it holds
under the key ``k_t`` by ``v_t``.  The states live in the engine's STATE
POOL beside the paged cache, ``ssm`` [L, slots, H, K, V] float32 with the
convolutions' tails ``conv`` [L, slots, kernel - 1, channels], under the
slot life cycle of ops/ssm.py (slot 0 the trash slot; a row whose chunk
starts at position 0 starts from zero, zeroed by the PROGRAM; ``fresh_rows``,
``scan_pieces`` and ``causal_conv`` are that module's).

Two device computations (``state_update`` picks by the step's shape):

  - rows of one token: the update above, in place on the pool
    (``ops.pallas.delta_update`` where the geometry allows);
  - rows of more: the CHUNKED form over ``scan_pieces``.  With ``G`` the
    running sum of ``g`` inside a piece and ``u_t = beta_t (v_t - (Diag(exp
    g_t) S_{t-1})^T k_t)`` the write of token t, ``S_t = Diag(exp g_t)
    S_{t-1} + k_t u_t^T``, so for the piece that starts from ``S_0``

      (I + A) U = beta (V - K+ S_0),   A[t, j] = beta_t sum_c k_tc k_jc
                                       exp(G_tc - G_jc)   (j < t)
      O = Q+ S_0 + P U,                P[t, j] = sum_c q_tc k_jc
                                       exp(G_tc - G_jc)   (j <= t)
      S_C = Diag(exp G_C) S_0 + Kend^T U

    with ``K+ = k exp(G)``, ``Q+ = q exp(G)``, ``Kend = k exp(G_C - G)``.
    ``piece_terms`` computes what does not depend on ``S_0`` for every piece
    at once: ``W = (I + A)^-1 beta K+``, ``U0 = (I + A)^-1 beta V``, ``P``,
    ``Q+``, ``Kend``, ``exp G_C``; the walk over the pieces (``chunk_scan``,
    or ``ops.pallas.delta_scan`` with the state in VMEM from a row's first
    piece to its last) is four dots a piece and head.

Exactness: ``exp(G_t - G_j)`` is never formed as ``exp(G_t) / exp(G_j)``
over a whole piece (``exp(-G_j)`` overflows float32 after 18 tokens at the
floor of -5).  A piece is cut into sub-blocks of ``SUB`` = 16 tokens and
every exponent is taken against a sub-block's middle token ``M_b``: inside
a sub-block ``|G - M_b| <= 8 x 5``, across sub-blocks ``G_t - M_b <= 0``.
The unit lower-triangular ``I + A`` is solved by sub-block: the diagonal
blocks' inverses by doubling, ``(I + N)^-1 = (I - N)(I + N^2)(I + N^4)
(I + N^8)`` for a strictly lower ``N`` of 16 (dots, no sequential loop),
then a block forward substitution over the piece's sub-blocks.  Every dot
that meets the state or a decay runs in float32 (``HIGHEST``).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from llm_d_tpu.ops.attention import resolve_backend
from llm_d_tpu.ops.ssm import fresh_rows, scan_pieces

F32 = jnp.float32
EXACT = jax.lax.Precision.HIGHEST
# Tokens a sub-block: SUB / 2 x |floor| must stay under ln(float32 max) =
# 88.7 (``ModelConfig`` refuses a floor that does not).
SUB = 16
# Tokens a piece of the chunked form as the model runs it: four sub-blocks,
# the program's choice and no key of a model.
PIECE = 64


def decode_update_reference(q, k, v, g, beta, pool, layer, slot, fresh):
    """The one-token update in XLA: ``q``, ``k``, ``g`` [S, H, K] float32,
    ``v`` [S, H, V], ``beta`` [S, H], ``pool`` [L, slots, H, K, V].
    Returns (o [S, H, V] float32, pool)."""
    s0 = jnp.where(fresh[:, None, None, None], 0.0, pool[layer, slot])
    s = s0 * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=2))
    s1 = s + k[..., None] * u[:, :, None, :]
    return jnp.sum(s1 * q[..., None], axis=2), pool.at[layer, slot].set(s1)


def pallas_ineligible_reason(H: int, K: int, V: int,
                             chunk: int = PIECE) -> str:
    """Why the Pallas state kernels cannot serve a geometry ('' = they
    can): they hold a head's state as whole [K, V] tiles of 128 lanes and
    step eight heads a grid program."""
    if K % 128 or V % 128:
        return f"state {K} x {V} a head: not whole 128 x 128 tiles"
    if H % 8 or chunk % 8:
        return f"{H} heads, pieces of {chunk}: no multiples of 8"
    return ""


def _unit_lower_inverse(n):
    """(I + N)^-1 of strictly lower ``n`` [..., SUB, SUB] by doubling."""
    eye = jnp.eye(SUB, dtype=F32)

    def mm(a, b):
        return jnp.matmul(a, b, precision=EXACT)

    inv, power = eye - n, n
    for _ in range(SUB.bit_length() - 2):       # N^2, N^4, N^8
        power = mm(power, power)
        inv = mm(inv, eye + power)
    return inv


def piece_terms(q, k, v, g, beta):
    """What a piece's recurrence needs beside the state it starts from, for
    every piece and head at once: ``q``, ``k``, ``g`` [..., C, K] float32,
    ``v`` [..., C, V], ``beta`` [..., C]; tokens past a piece's length come
    with ``g`` = 0 and ``beta`` = 0 (they leave the state as it is).
    Returns (W [..., C, K], U0 [..., C, V], P [..., C, C], Q+ [..., C, K],
    Kend [..., C, K], exp G_C [..., K])."""
    C, K = q.shape[-2:]
    n = C // SUB
    lead = q.shape[:-2]
    G = jnp.cumsum(g, axis=-2)                                  # <= 0
    M = G.reshape(*lead, n, SUB, K)[..., SUB // 2 - 1, :]       # [.., n, K]
    block = jnp.arange(C) // SUB
    # Exponent of token t against sub-block b's middle: inside b within
    # +-SUB/2 x floor, after b below 0; before b it is not needed.
    e = jnp.where((block[:, None] < jnp.arange(n)[None, :])[:, :, None], 0.0,
                  G[..., :, None, :] - M[..., None, :, :])      # [.., C, n, K]
    left = jnp.exp(e)
    km = (k * jnp.exp(jnp.repeat(M, SUB, axis=-2) - G)).reshape(
        *lead, n, SUB, K)

    def against_keys(x):
        """sum_c x_tc k_jc exp(G_tc - G_jc) for j's sub-block <= t's."""
        return jnp.einsum("...tbk,...bjk->...tbj", x[..., :, None, :] * left,
                          km, precision=EXACT).reshape(*lead, C, C)

    t, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    A = jnp.where(j < t, against_keys(k), 0.0) * beta[..., None]
    P = jnp.where(j <= t, against_keys(q), 0.0)
    # (I + A) X = beta [K+ | V] by sub-block.
    decay = jnp.exp(G)
    rhs = jnp.concatenate([k * decay, v], axis=-1) * beta[..., None]
    Ab = A.reshape(*lead, n, SUB, C)
    diag = jnp.stack([Ab[..., b, :, b * SUB:(b + 1) * SUB]
                      for b in range(n)], axis=-3)
    inv = _unit_lower_inverse(diag)                     # [.., n, SUB, SUB]
    xs = []
    for b in range(n):
        r = rhs[..., b * SUB:(b + 1) * SUB, :]
        if b:
            r = r - jnp.matmul(Ab[..., b, :, :b * SUB],
                               jnp.concatenate(xs, axis=-2), precision=EXACT)
        xs.append(jnp.matmul(inv[..., b, :, :], r, precision=EXACT))
    X = jnp.concatenate(xs, axis=-2)
    end = G[..., -1:, :]
    return (X[..., :K], X[..., K:], P, q * decay, k * jnp.exp(end - G),
            jnp.exp(end[..., 0, :]))


def _by_piece(q, k, v, g, beta, pc, chunk: int):
    """The step's tokens laid out by piece and head, [NT, H, chunk, ...],
    dead tokens with ``g`` = ``beta`` = 0, and their ``piece_terms``."""
    T = q.shape[0]
    within = jnp.arange(chunk)[None, :]
    idx = jnp.clip(pc["start"][:, None] + within, 0, T - 1)     # [NT, c]
    live = within < pc["length"][:, None]

    def lay(a, mask=False):
        a = a[idx].astype(F32)                                  # [NT, c, H..]
        if mask:
            a = jnp.where(live.reshape(live.shape + (1,) * (a.ndim - 2)),
                          a, 0.0)
        return jnp.moveaxis(a, 2, 1)                            # [NT, H, c..]

    return piece_terms(lay(q), lay(k), lay(v), lay(g, True), lay(beta, True))


def chunk_scan(q, k, v, g, beta, pool, layer, batch, chunk: int):
    """The chunked form in XLA over the rows of more than one token: ``q``,
    ``k``, ``g`` [T, H, K], ``v`` [T, H, V], ``beta`` [T, H], ``pool`` [L,
    slots, H, K, V].  The terms of every piece at once (``piece_terms``),
    then the state from piece to piece, and from this step to the row's
    next, through the row's slot; only as many pieces as the rows hold are
    walked.  Returns (o [T, H, V] float32 with rows of one token left
    unwritten, pool)."""
    T, H, V = v.shape
    pc = scan_pieces(batch, T, chunk)
    W, U0, P, Qp, Kend, gam = _by_piece(q, k, v, g, beta, pc, chunk)

    def dot(spec, a, b):
        return jnp.einsum(spec, a, b, precision=EXACT)

    def piece(i, carry):
        pool, o = carry
        sl = pc["slot"][i]
        s0 = jnp.where(pc["first"][i] & pc["fresh"][i], 0.0, pool[layer, sl])
        u = U0[i] - dot("hck,hkv->hcv", W[i], s0)
        oi = dot("hck,hkv->hcv", Qp[i], s0) + dot("hcj,hjv->hcv", P[i], u)
        s1 = gam[i][..., None] * s0 + dot("hck,hcv->hkv", Kend[i], u)
        return (pool.at[layer, sl].set(s1),
                jax.lax.dynamic_update_slice_in_dim(o, oi[None], i, axis=0))

    pool, o = jax.lax.fori_loop(
        0, pc["count"], piece,
        (pool, jnp.zeros((W.shape[0], H, chunk, V), F32)))
    return o[pc["tok_piece"], :, pc["tok_off"]], pool


def chunk_scan_pallas(q, k, v, g, beta, pool, layer, batch, chunk: int):
    """``chunk_scan`` with the walk over the pieces in
    ``ops.pallas.delta_scan.delta_chunk_scan``: XLA computes every piece's
    terms and takes the kernel's output back to the packed batch."""
    from llm_d_tpu.ops.pallas.delta_scan import delta_chunk_scan
    pc = scan_pieces(batch, q.shape[0], chunk)
    o, pool = delta_chunk_scan(
        *_by_piece(q, k, v, g, beta, pc, chunk), pool, layer, pc["slot"],
        pc["first"], pc["fresh"], pc["live"])
    return o[pc["tok_piece"], :, pc["tok_off"]], pool


def state_update(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                 beta: jax.Array, pool: jax.Array,
                 batch: Dict[str, jax.Array], layer: jax.Array, chunk: int,
                 backend: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """Run the recurrence over a step's packed batch: ``q``, ``k`` [T, H,
    K] (normed, ``q`` scaled), ``v`` [T, H, V], ``g`` [T, H, K] float32 the
    log-decay, ``beta`` [T, H] float32.  Rows of one token take the
    one-token update, rows of more the chunked form; a pure decode step
    (``qtok_idx`` one column wide: static) holds no scan at all; ``chunk``
    is the piece, whole sub-blocks of ``SUB`` (the model passes ``PIECE``).
    Returns (o [T, H, V] float32, pool)."""
    T, H, K = q.shape
    V = v.shape[-1]
    rows = batch["token_seq_ids"]
    single = batch["query_len"] == 1
    # Rows of one token, gathered to [S, ...]; the others update the trash.
    tok = jnp.clip(batch["query_start"], 0, T - 1)
    slot1 = jnp.where(single, batch["state_slot"], 0)
    kernels = (resolve_backend(backend) == "pallas"
               and not pallas_ineligible_reason(H, K, V, chunk))
    update = decode_update_reference
    if kernels:
        from llm_d_tpu.ops.pallas.delta_update import (
            delta_decode_update as update)
    o1, pool = update(q[tok].astype(F32), k[tok].astype(F32),
                      v[tok].astype(F32), g[tok], beta[tok], pool, layer,
                      slot1, fresh_rows(batch))
    o = o1[rows]
    if batch["qtok_idx"].shape[1] > 1:
        on, pool = (chunk_scan_pallas if kernels else chunk_scan)(
            q, k, v, g, beta, pool, layer, batch, chunk)
        o = jnp.where(single[rows][:, None, None], o, on)
    return o, pool
