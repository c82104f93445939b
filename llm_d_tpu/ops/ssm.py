"""The selective state-space mixer (Mamba-2) over the engine's state pool.

A sequence of a stack with such a mixer owns, besides its keys and values
in pages, a recurrent state per layer that every token overwrites:

  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t        y_t = S_t C_t + D x_t

(per head: ``S`` [N, P] float32, ``x`` [P], ``B``, ``C`` [N] shared by the
heads of a group, ``dt`` and ``A`` < 0 scalars) and the last K - 1 inputs of
the depthwise causal convolution in front of it.  Both live in the engine's
STATE POOL, device arrays donated through the step program beside the paged
cache: ``ssm`` [L, slots, H, N, P] float32 and ``conv`` [L, slots, K - 1, C]
in the activations' dtype, one slot a running sequence.  Slot 0 is the
trash slot (padded rows of a batch write there, as padded tokens write to
block 0 of the paged cache).

A step brings each row a CHUNK of its tokens (``query_len``; 1 for a decode
row) that starts where the row's last chunk ended, so the state carries
from chunk to chunk through the slot and a chunk boundary may fall
anywhere.  A row whose chunk starts at position 0 (``seq_lens ==
query_len``: a new request, or one preempted and recomputed) starts from
zero, whatever its slot held: the PROGRAM zeroes, so a slot that a finished
or dropped row leaves behind needs no cleaning, and a step launched ahead
of its predecessor's retire may advance a slot that is about to be reused.

Two device computations (``state_update`` picks by the step's shape):

  - rows of one token: the one-token update, in place on the pool
    (``ops.pallas.ssm_update.ssm_decode_update`` where the geometry allows:
    rows address their slot by scalar prefetch, the pool is aliased in and
    out; a gather, an update and a scatter in XLA would move the rows'
    states three times);
  - rows of more: the chunked scan (SSD), a row's chunk in pieces of
    ``chunk`` tokens, the part inside a piece as dots, the state carried
    from piece to piece through the slot.

The batch names what both need per row: ``state_slot``, ``query_start`` (the
row's first token in the packed batch), ``query_len``, ``seq_lens``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from llm_d_tpu.ops.attention import resolve_backend
from llm_d_tpu.ops.parts import part

F32 = jnp.float32


def fresh_rows(batch: Dict[str, jax.Array]) -> jax.Array:
    """[S] bool: the row's chunk starts at position 0, its state at zero."""
    return (batch["seq_lens"] == batch["query_len"]) \
        & (batch["query_len"] > 0)


def causal_conv(u: jax.Array, w: jax.Array, b: jax.Array, tails: jax.Array,
                batch: Dict[str, jax.Array], layer: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution of kernel K over the packed batch, then
    SiLU: ``u`` [T, C] the step's inputs, ``w`` [C, K] (``w[:, K - 1]``
    meets the token itself), ``b`` [C], ``tails`` [L, slots, K - 1, C] each
    slot's last K - 1 inputs, oldest first.  A token's earlier inputs come
    from its own row's chunk or, before the chunk's start, from the row's
    tail (zeros for a fresh row).  Returns (activations [T, C], tails with
    every row's new last K - 1 inputs written)."""
    T, C = u.shape
    K = w.shape[1]
    rows, qpos = batch["token_seq_ids"], batch["token_qpos"]
    slot, qlen = batch["state_slot"], batch["query_len"]
    old = jnp.where(fresh_rows(batch)[:, None, None], 0,
                    tails[layer, slot])                     # [S, K - 1, C]
    wf = w.astype(F32)
    acc = u.astype(F32) * wf[:, K - 1]
    t = jnp.arange(T)
    for k in range(1, K):
        inp = jnp.where((qpos >= k)[:, None], u[jnp.maximum(t - k, 0)],
                        old[rows, jnp.clip(K - 1 - k + qpos, 0, K - 2)])
        acc = acc + inp.astype(F32) * wf[:, K - 1 - k]
    out = jax.nn.silu(acc + b.astype(F32)).astype(u.dtype)
    # A row's new tail: the last K - 1 of (old tail, chunk).
    rel = qlen[:, None] - (K - 1) + jnp.arange(K - 1)[None, :]   # in chunk
    new = jnp.where(
        (rel >= 0)[:, :, None],
        u[jnp.clip(batch["query_start"][:, None] + rel, 0, T - 1)],
        jnp.take_along_axis(
            old, jnp.clip(rel + K - 1, 0, K - 2)[:, :, None], axis=1))
    tails = tails.at[layer, jnp.where(qlen > 0, slot, 0)].set(
        new.astype(tails.dtype))
    return out, tails


def decode_update_reference(xdt, dA, B, C, pool, layer, slot, fresh):
    """The one-token update in XLA: ``xdt`` [S, H, P] float32 (dt x),
    ``dA`` [S, H] (exp(dt A)), ``B``, ``C`` [S, G, N], ``pool`` [L, slots,
    H, N, P].  Returns (S_t C_t [S, H, P], pool)."""
    H = xdt.shape[1]
    hpg = H // B.shape[1]
    s0 = jnp.where(fresh[:, None, None, None], 0.0, pool[layer, slot])
    bh = jnp.repeat(B.astype(F32), hpg, axis=1)             # [S, H, N]
    ch = jnp.repeat(C.astype(F32), hpg, axis=1)
    s1 = s0 * dA[:, :, None, None] + bh[..., None] * xdt[:, :, None, :]
    y = jnp.sum(s1 * ch[..., None], axis=2)
    return y, pool.at[layer, slot].set(s1)


def pallas_ineligible_reason(H: int, P: int, N: int, G: int,
                             chunk: int) -> str:
    """Why the Pallas state kernels cannot serve a geometry ('' = they
    can): they hold a head's state as whole [N, P] tiles of 128 lanes, step
    eight heads of one group a grid program, and walk pieces of whole
    128-token tiles."""
    if P % 128 or N % 128 or chunk % 128:
        return (f"state {N} x head {P}, scan pieces of {chunk}: not whole "
                f"128 x 128 tiles")
    if (H // G) % 8:
        return f"{H // G} heads a group is no multiple of 8"
    return ""


def scan_pieces(batch: Dict[str, jax.Array], T: int, chunk: int):
    """The list of PIECES the chunked scan walks: every row of more than one
    token cut into ceil(n / chunk) pieces of ``chunk`` tokens, rows in the
    batch's order, a row's pieces one after the other.  ``NT`` = ceil(T /
    chunk) + S entries always hold them; the dead ones past ``count`` name
    row S - 1 with no live token and the trash slot.  Returns a dict: per
    piece ``start`` (its first token in the packed batch), ``length`` (live
    tokens, 0 when dead), ``slot``,
    ``first`` (of its row), ``fresh`` (that row starts from zero), ``live``;
    per token
    ``tok_piece`` and ``tok_off`` (where a token of such a row lies);
    ``count``."""
    with part("tiles"):     # the same list in every layer
        qstart, qlen = batch["query_start"], batch["query_len"]
        S = qlen.shape[0]
        pieces = jnp.where(qlen > 1, -(-qlen // chunk), 0)
        ends = jnp.cumsum(pieces)
        NT = -(-T // chunk) + S
        i = jnp.arange(NT)
        row = jnp.minimum(jnp.searchsorted(ends, i, side="right"), S - 1)
        live = i < ends[-1]
        off = (i - (ends - pieces)[row]) * chunk
        rows, qpos = batch["token_seq_ids"], batch["token_qpos"]
        return {
            "start": qstart[row] + off,
            "length": jnp.where(live, jnp.clip(qlen[row] - off, 0, chunk), 0),
            "slot": jnp.where(live, batch["state_slot"][row], 0),
            "first": live & (off == 0), "fresh": fresh_rows(batch)[row],
            "live": live,
            "tok_piece": (ends - pieces)[rows] + qpos // chunk,
            "tok_off": qpos % chunk, "count": ends[-1]}


def chunk_scan_pallas(x, dt, A, B, C, pool, layer, batch, chunk: int):
    """``chunk_scan`` through ``ops.pallas.ssm_scan.ssm_chunk_scan``: XLA
    lays the step's tokens out by piece and takes the kernel's output back
    to the packed batch."""
    from llm_d_tpu.ops.pallas.ssm_scan import ssm_chunk_scan
    T = x.shape[0]
    pc = scan_pieces(batch, T, chunk)
    within = jnp.arange(chunk)[None, :]
    idx = jnp.clip(pc["start"][:, None] + within, 0, T - 1)     # [NT, c]
    dts = jnp.where((within < pc["length"][:, None])[:, :, None],
                    dt[idx], 0.0)                               # [NT, c, H]
    xdt = (x[idx].astype(F32) * dts[..., None]).astype(x.dtype)
    cum = jnp.cumsum(dts * A.astype(F32), axis=1)
    y, pool = ssm_chunk_scan(xdt, B[idx], C[idx], cum, pool, layer,
                             pc["slot"], pc["first"], pc["fresh"],
                             pc["live"])
    return y[pc["tok_piece"], pc["tok_off"]], pool


def chunk_scan(x, dt, A, B, C, pool, layer, batch, chunk: int):
    """The chunked scan in XLA over the rows of more than one token: ``x``
    [T, H, P], ``dt`` [T, H] float32, ``A`` [H] < 0, ``B``, ``C`` [T, G,
    N], ``pool`` [L, slots, H, N, P].  A row's chunk is walked in pieces of
    ``chunk`` tokens (``scan_pieces``; the last one shorter): inside a piece
    the recurrence is three dots (scores C B^T under the decay mask times
    dt x; C against the state the piece starts from; B^T against the
    decayed dt x for the state it leaves), and the state goes from piece to
    piece, and from this step to the row's next, through the row's slot.
    Only as many pieces as the rows hold are walked (a while loop).  Returns
    (S_t C_t [T, H, P] float32 with rows of one token left unwritten,
    pool)."""
    T, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    hpg = H // G
    pc = scan_pieces(batch, T, chunk)

    def padded(a):      # a piece read at the batch's end stays in bounds
        return jnp.pad(a, ((0, chunk),) + ((0, 0),) * (a.ndim - 1))

    xp, dtp, bp, cp = padded(x), padded(dt), padded(B), padded(C)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    af = A.astype(F32)

    def piece(i, carry):
        pool, y = carry
        t0, n, sl = pc["start"][i], pc["length"][i], pc["slot"][i]

        def take(a):
            return jax.lax.dynamic_slice_in_dim(a, t0, chunk, axis=0)

        live = (jnp.arange(chunk) < n)[:, None]
        dts = jnp.where(live, take(dtp), 0.0)               # [c, H]
        xs, bs, cs = take(xp), take(bp), take(cp)
        cum = jnp.cumsum(dts * af, axis=0)                  # [c, H], <= 0
        xdt = (xs.astype(F32) * dts[:, :, None]).astype(x.dtype)
        s0 = jnp.where(pc["first"][i] & pc["fresh"][i], 0.0, pool[layer, sl])
        s0g = s0.reshape(G, hpg, N, P)
        # Inside the piece: token i takes from token j <= i.
        scores = jnp.einsum("ign,jgn->gij", cs, bs,
                            preferred_element_type=F32)     # [G, c, c]
        decay = jnp.where(tri[:, :, None],
                          jnp.exp(cum[:, None, :] - cum[None, :, :]), 0.0)
        w = (decay.reshape(chunk, chunk, G, hpg)
             * scores.transpose(1, 2, 0)[..., None]).astype(x.dtype)
        ys = jnp.einsum("ijgh,jghp->ighp", w,
                        xdt.reshape(chunk, G, hpg, P),
                        preferred_element_type=F32)
        # From the state the piece starts from.
        ys = ys + jnp.exp(cum).reshape(chunk, G, hpg, 1) * jnp.einsum(
            "ign,ghnp->ighp", cs.astype(F32), s0g,
            preferred_element_type=F32)
        # The state it leaves.
        to_end = jnp.exp(cum[-1][None, :] - cum)            # [c, H]
        xw = (xdt.astype(F32) * to_end[:, :, None]).astype(x.dtype)
        s1 = s0g * jnp.exp(cum[-1]).reshape(G, hpg, 1, 1) + jnp.einsum(
            "jgn,jghp->ghnp", bs, xw.reshape(chunk, G, hpg, P),
            preferred_element_type=F32)
        pool = pool.at[layer, sl].set(s1.reshape(H, N, P))
        y = jax.lax.dynamic_update_slice_in_dim(
            y, ys.reshape(chunk, H, P), t0, axis=0)
        return pool, y

    pool, y = jax.lax.fori_loop(
        0, pc["count"], piece, (pool, jnp.zeros((T + chunk, H, P), F32)))
    return y[:T], pool


def state_update(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                 C: jax.Array, D: jax.Array, pool: jax.Array,
                 batch: Dict[str, jax.Array], layer: jax.Array, chunk: int,
                 backend: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """Run the recurrence over a step's packed batch: ``x`` [T, H, P],
    ``dt`` [T, H] float32 (after softplus), ``A`` [H] < 0, ``B``, ``C``
    [T, G, N], ``D`` [H].  Rows of one token take the one-token update, rows
    of more the chunked scan; a pure decode step (``qtok_idx`` one column
    wide: static) holds no scan at all.  Returns (y [T, H, P] in ``x``'s
    dtype, pool)."""
    T, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    rows = batch["token_seq_ids"]
    qlen = batch["query_len"]
    single = qlen == 1
    # Rows of one token, gathered to [S, ...]; the others update the trash.
    tok = jnp.clip(batch["query_start"], 0, T - 1)
    slot1 = jnp.where(single, batch["state_slot"], 0)
    dt1 = dt[tok]
    xdt1 = x[tok].astype(F32) * dt1[:, :, None]
    dA1 = jnp.exp(dt1 * A.astype(F32))
    kernels = (resolve_backend(backend) == "pallas"
               and not pallas_ineligible_reason(H, P, N, G, chunk))
    update = decode_update_reference
    if kernels:
        from llm_d_tpu.ops.pallas.ssm_update import (
            ssm_decode_update as update)
    y1, pool = update(xdt1, dA1, B[tok], C[tok], pool, layer, slot1,
                      fresh_rows(batch))
    y = y1[rows]
    if batch["qtok_idx"].shape[1] > 1:
        yn, pool = (chunk_scan_pallas if kernels else chunk_scan)(
            x, dt, A, B, C, pool, layer, batch, chunk)
        y = jnp.where(single[rows][:, None, None], y, yn)
    y = y + D.astype(F32)[None, :, None] * x.astype(F32)
    return y.astype(x.dtype), pool


# --------------------------------------------------------------------------
# Mamba-1: the decay is A[N, inner] by channel and state, so there are no
# heads and no dots; the state of a slot is [N, inner] float32 (channels on
# the lanes) and a token's update is elementwise over it:
#
#   S_t = exp(dt_t A) * S_{t-1} + B_t (outer) (dt_t x_t)     y_t = C_t . S_t
#
# with ``dt`` [T, inner] by channel and ``B``, ``C`` [T, N] shared by all
# channels.  The same pool life cycle, the same list of pieces, the same two
# computations as above (``ops.pallas.ssm1_scan``).
# --------------------------------------------------------------------------

def ssm1_pallas_ineligible_reason(inner: int, N: int, chunk: int) -> str:
    """Why the Mamba-1 kernels cannot serve a geometry ('' = they can): a
    grid program holds whole 128-lane blocks of channels and whole float32
    sublane tiles of states and of tokens."""
    from llm_d_tpu.ops.pallas.ssm1_scan import (
        TOKENS_PER_GROUP, channel_block)
    if not channel_block(inner):
        return f"inner width {inner} is no multiple of 128 lanes"
    if N % 8 or chunk % TOKENS_PER_GROUP:
        return (f"{N} states, scan pieces of {chunk}: not whole float32 "
                f"sublane tiles")
    return ""


def ssm1_decode_update_reference(dt, xdt, A, B, C, pool, layer, slot, fresh):
    """The one-token update in XLA: ``dt``, ``xdt`` [S, inner] float32,
    ``A`` [N, inner], ``B``, ``C`` [S, N], ``pool`` [L, slots, N, inner].
    Returns (S_t C_t [S, inner], pool)."""
    s0 = jnp.where(fresh[:, None, None], 0.0, pool[layer, slot])
    s1 = jnp.exp(dt[:, None, :] * A[None]) * s0 \
        + B.astype(F32)[:, :, None] * xdt[:, None, :]
    y = jnp.sum(s1 * C.astype(F32)[:, :, None], axis=1)
    return y, pool.at[layer, slot].set(s1)


def ssm1_chunk_scan_pallas(dt, xdt, A, B, C, pool, layer, batch, chunk: int):
    """``ssm1_chunk_scan`` through the kernel: XLA lays the step's tokens
    out by piece and takes the kernel's output back to the packed batch."""
    from llm_d_tpu.ops.pallas.ssm1_scan import ssm1_chunk_scan as kernel
    T = dt.shape[0]
    pc = scan_pieces(batch, T, chunk)
    within = jnp.arange(chunk)[None, :]
    idx = jnp.clip(pc["start"][:, None] + within, 0, T - 1)     # [NT, c]
    live = (within < pc["length"][:, None])[:, :, None]
    y, pool = kernel(
        jnp.where(live, dt[idx], 0.0), jnp.where(live, xdt[idx], 0.0), A,
        B[idx], C[idx], pool, layer, pc["slot"], pc["first"], pc["fresh"],
        pc["live"])
    return y[pc["tok_piece"], pc["tok_off"]], pool


def ssm1_chunk_scan(dt, xdt, A, B, C, pool, layer, batch, chunk: int):
    """The selective scan in XLA over the rows of more than one token:
    ``dt``, ``xdt`` [T, inner] float32, ``A`` [N, inner], ``B``, ``C`` [T,
    N], ``pool`` [L, slots, N, inner].  A row's chunk is walked in pieces
    (``scan_pieces``), a piece token by token (``lax.scan``), the state
    going from piece to piece and from this step to the row's next through
    the row's slot.  Returns (S_t C_t [T, inner] float32 with rows of one
    token left unwritten, pool)."""
    T, inner = dt.shape
    pc = scan_pieces(batch, T, chunk)

    def padded(a):      # a piece read at the batch's end stays in bounds
        return jnp.pad(a, ((0, chunk),) + ((0, 0),) * (a.ndim - 1))

    dtp, xp = padded(dt), padded(xdt)
    bp, cp = padded(B.astype(F32)), padded(C.astype(F32))

    def piece(i, carry):
        pool, y = carry
        t0, n, sl = pc["start"][i], pc["length"][i], pc["slot"][i]

        def take(a):
            return jax.lax.dynamic_slice_in_dim(a, t0, chunk, axis=0)

        live = (jnp.arange(chunk) < n)[:, None]
        s0 = jnp.where(pc["first"][i] & pc["fresh"][i], 0.0, pool[layer, sl])

        def token(s, inp):
            dt_t, x_t, b_t, c_t = inp
            s = jnp.exp(dt_t[None, :] * A) * s + b_t[:, None] * x_t[None, :]
            return s, jnp.sum(s * c_t[:, None], axis=0)

        s1, ys = jax.lax.scan(token, s0, (
            jnp.where(live, take(dtp), 0.0), jnp.where(live, take(xp), 0.0),
            take(bp), take(cp)))
        return (pool.at[layer, sl].set(s1),
                jax.lax.dynamic_update_slice_in_dim(y, ys, t0, axis=0))

    pool, y = jax.lax.fori_loop(
        0, pc["count"], piece, (pool, jnp.zeros((T + chunk, inner), F32)))
    return y[:T], pool


def ssm1_state_update(x: jax.Array, dt: jax.Array, A: jax.Array,
                      B: jax.Array, C: jax.Array, D: jax.Array,
                      pool: jax.Array, batch: Dict[str, jax.Array],
                      layer: jax.Array, chunk: int, backend: str = "auto"
                      ) -> Tuple[jax.Array, jax.Array]:
    """``state_update`` for Mamba-1: ``x`` [T, inner], ``dt`` [T, inner]
    float32 (after softplus), ``A`` [N, inner] < 0, ``B``, ``C`` [T, N],
    ``D`` [inner].  Returns (y [T, inner] float32, D x added, pool)."""
    T, inner = x.shape
    rows = batch["token_seq_ids"]
    single = batch["query_len"] == 1
    xf = x.astype(F32)
    xdt = xf * dt
    A = A.astype(F32)
    tok = jnp.clip(batch["query_start"], 0, T - 1)
    slot1 = jnp.where(single, batch["state_slot"], 0)
    kernels = (resolve_backend(backend) == "pallas"
               and not ssm1_pallas_ineligible_reason(inner, A.shape[0], chunk))
    update = ssm1_decode_update_reference
    if kernels:
        from llm_d_tpu.ops.pallas.ssm1_scan import (
            ssm1_decode_update as update)
    y1, pool = update(dt[tok], xdt[tok], A, B[tok], C[tok], pool, layer,
                      slot1, fresh_rows(batch))
    y = y1[rows]
    if batch["qtok_idx"].shape[1] > 1:
        yn, pool = (ssm1_chunk_scan_pallas if kernels else ssm1_chunk_scan)(
            dt, xdt, A, B, C, pool, layer, batch, chunk)
        y = jnp.where(single[rows][:, None], y, yn)
    return y + D.astype(F32)[None, :] * xf, pool
