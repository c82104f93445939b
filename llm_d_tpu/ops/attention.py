"""Ragged paged attention: the engine's single attention entry point.

One op serves mixed prefill+decode batches over the paged KV cache — the
TPU-native counterpart of the reference's FlashInfer attention path
(reference: docker/Dockerfile.cuda:57-58) married to vLLM's paged KV.  A
single static-shape op keeps XLA tracing happy under continuous batching:
the engine buckets the total token count T and the max sequence count S, so
recompiles are bounded regardless of batch composition.

Batch layout (all padded to bucketed sizes):
  q:              [T, H, D]     query vectors for every token in this step
  token_seq_ids:  [S_max] rows; token t belongs to sequence token_seq[t]
  positions:      [T]           absolute position of each token in its seq
  kv cache slots: [num_slots, KVH*D] per layer/side; slot = block*bs + off
                  (heads folded into the lane dim: keeps DMA slices 128-
                   aligned on TPU and scatter rows contiguous)
  block_tables:   [S, B]        physical block ids per sequence (0 = null)
  seq_lens:       [S]           total context length per sequence (0 = pad row)

Block 0 is the reserved null/trash block: padding tokens write there and
null table entries read from it (always masked out).

The jnp reference implementation below is the correctness oracle and CPU
path; ``llm_d_tpu.ops.pallas.paged_attention`` provides the TPU kernel and
this module dispatches on backend.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _gather_rows(cache: jax.Array, idx: jax.Array,
                 layer: Optional[jax.Array]):
    """f32 rows ``idx`` of ``cache`` ``[num_slots, W]`` (or of plane
    ``layer`` of a stacked ``[L, slots, W]``)."""
    rows = cache[idx] if layer is None else cache[layer, idx]
    return rows.astype(jnp.float32)


def ragged_paged_attention_reference(
    q: jax.Array,              # [T, H, D]
    k_cache: jax.Array,        # [num_slots, KVH*D] (this layer, new KV written)
    v_cache: jax.Array,        # [num_slots, KVH*D]
    token_seq_ids: jax.Array,  # [T] i32, sequence row per token (pad -> 0)
    positions: jax.Array,      # [T] i32
    block_tables: jax.Array,   # [S, B] i32
    seq_lens: jax.Array,       # [S] i32
    block_size: int,
    scale: Optional[float] = None,
    soft_cap: Optional[float] = None,
    layer: Optional[jax.Array] = None,
    window: Optional[jax.Array] = None,    # i32: keys a query sees (None=all)
    limits: Optional[jax.Array] = None,    # [T] i32 last key a query sees
                                           # (None: its own position)
) -> jax.Array:               # [T, H, D]
    T, H, D = q.shape
    S, B = block_tables.shape
    KVH = k_cache.shape[-1] // D
    G = H // KVH
    scale = scale if scale is not None else D ** -0.5

    # Gather each sequence's context from the paged cache: [S, C, KVH, D].
    slot_ids = (block_tables[:, :, None] * block_size
                + jnp.arange(block_size)[None, None, :]).reshape(S, B * block_size)
    C = B * block_size
    k_seq = _gather_rows(k_cache, slot_ids, layer).reshape(S, C, KVH, D)
    v_seq = _gather_rows(v_cache, slot_ids, layer).reshape(S, C, KVH, D)

    # Per-token context: [T, C, KVH, D].
    k_tok = k_seq[token_seq_ids]
    v_tok = v_seq[token_seq_ids]

    qf = q.astype(jnp.float32).reshape(T, KVH, G, D)
    scores = jnp.einsum("tkgd,tckd->tkgc", qf * scale,
                        k_tok.astype(jnp.float32))  # [T, KVH, G, C]
    if soft_cap is not None:
        scores = soft_cap * jnp.tanh(scores / soft_cap)

    # Causal + length mask. key position c is valid for token t iff
    # c <= positions[t] (its visibility limit, where one is given) and
    # c < seq_lens[seq(t)].
    key_pos = jnp.arange(C)[None, :]                       # [1, C]
    last = positions if limits is None else limits
    valid = (key_pos <= last[:, None]) & (
        key_pos < seq_lens[token_seq_ids][:, None])        # [T, C]
    if window is not None:
        valid &= key_pos > positions[:, None] - window
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("tkgc,tckd->tkgd", probs, v_tok.astype(jnp.float32))
    return out.reshape(T, H, D).astype(q.dtype)


def write_kv(
    k_cache: jax.Array,      # [num_slots, KVH*D] or stacked [L, slots, KVH*D]
    v_cache: jax.Array,
    k_new: jax.Array,        # [T, KVH, D]
    v_new: jax.Array,
    slot_mapping: jax.Array,  # [T] i32 target slot per token (pad -> slot in block 0)
    layer: Optional[jax.Array] = None,   # i32 plane of a stacked cache
):
    """Scatter this step's KV into the paged cache (donated buffers).

    Rows are contiguous KVH*D vectors -> each scatter row is one 1 KB burst.
    With ``layer`` the scatter targets one plane of the full stacked cache
    in place (no per-layer slice copies).  The decode hot path bypasses this
    entirely: the Pallas kernel fuses the row update into attention
    (see attention_with_kv_update).
    """
    T = k_new.shape[0]
    if layer is None:
        k_cache = k_cache.at[slot_mapping].set(
            k_new.reshape(T, -1).astype(k_cache.dtype))
        v_cache = v_cache.at[slot_mapping].set(
            v_new.reshape(T, -1).astype(v_cache.dtype))
    else:
        k_cache = k_cache.at[layer, slot_mapping].set(
            k_new.reshape(T, -1).astype(k_cache.dtype))
        v_cache = v_cache.at[layer, slot_mapping].set(
            v_new.reshape(T, -1).astype(v_cache.dtype))
    return k_cache, v_cache


def _flash_over_kv_chunks(
    qs: jax.Array,        # [S, Q, H, D] padded per-seq queries
    q_pos: jax.Array,     # [S, Q] absolute positions (pad -> -1)
    slot_ids: jax.Array,  # [S, C] gather indices into the cache
    seq_lens: jax.Array,  # [S]
    k_cache: jax.Array, v_cache: jax.Array,
    kv_chunk: int, scale: float, soft_cap: Optional[float],
    layer: Optional[jax.Array] = None,
    window: Optional[jax.Array] = None,
    q_lim: Optional[jax.Array] = None,   # [S, Q] last visible key (None: q_pos)
) -> jax.Array:           # [S, Q, H, D]
    """Online-softmax attention scanning the context in kv_chunk slices.

    Flash-attention recurrence expressed in XLA (lax.scan over KV chunks):
    peak memory is O(S*Q*H*kv_chunk) instead of O(S*Q*H*C).  The Pallas
    kernel supersedes this on TPU for the decode regime.
    """
    S, Q, H, D = qs.shape
    KVH = k_cache.shape[-1] // D
    G = H // KVH
    C = slot_ids.shape[1]
    n_chunks = C // kv_chunk
    qf = qs.astype(jnp.float32).reshape(S, Q, KVH, G, D) * scale

    max_len = jnp.max(seq_lens)   # skip chunks past the longest context
    q_last = q_pos if q_lim is None else q_lim

    def compute_chunk(carry, ci):
        m, l, acc = carry
        sl = jax.lax.dynamic_slice_in_dim(slot_ids, ci * kv_chunk, kv_chunk, 1)
        k = _gather_rows(k_cache, sl, layer).reshape(S, kv_chunk, KVH, D)
        v = _gather_rows(v_cache, sl, layer).reshape(S, kv_chunk, KVH, D)
        s = jnp.einsum("sqkgd,sckd->sqkgc", qf, k)   # [S, Q, KVH, G, kc]
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        key_pos = ci * kv_chunk + jnp.arange(kv_chunk)
        valid = (key_pos[None, None, :] <= q_last[:, :, None]) & (
            key_pos[None, None, :] < seq_lens[:, None, None])
        if window is not None:
            valid &= key_pos[None, None, :] > q_pos[:, :, None] - window
        s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
        # Clamp the running max to a finite floor so fully-masked rows/chunks
        # yield p = exp(NEG_INF - floor) = 0 instead of exp(0) = 1.
        m_new = jnp.maximum(jnp.maximum(m, jnp.max(s, axis=-1)), -1e29)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "sqkgc,sckd->sqkgd", p, v)
        return m_new, l_new, acc_new

    # Only chunks below the longest live context execute: a while_loop with
    # a data-dependent trip count, NOT a scan of per-chunk lax.conds — the
    # skipped-branch conds copied the full (m, l, acc) carry (~17 MB at the
    # 64x128 prefill shape) once per dead chunk, which measured ~40% of the
    # whole prefill step on v5e.  HBM traffic now tracks actual context
    # length with no dead-chunk cost at all.
    n_live = jnp.minimum(
        (max_len + kv_chunk - 1) // kv_chunk, n_chunks).astype(jnp.int32)

    def chunk_step(carry):
        ci, m, l, acc = carry
        m, l, acc = compute_chunk((m, l, acc), ci)
        return ci + 1, m, l, acc

    # Under a window the walk starts at the chunk that holds the oldest key
    # any live query still sees.
    first = jnp.int32(0) if window is None else jnp.maximum(
        jnp.min(jnp.where(q_pos >= 0, q_pos, jnp.iinfo(jnp.int32).max))
        - window + 1, 0) // kv_chunk
    init = (first.astype(jnp.int32),
            jnp.full((S, Q, KVH, G), -1e29, jnp.float32),
            jnp.zeros((S, Q, KVH, G), jnp.float32),
            jnp.zeros((S, Q, KVH, G, D), jnp.float32))
    _, m, l, acc = jax.lax.while_loop(
        lambda c: c[0] < n_live, chunk_step, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(S, Q, H, D).astype(qs.dtype)


def _chunk_size_for(C: int, target: int = 512) -> int:
    kc = min(target, C)
    while C % kc:
        kc //= 2
    return max(kc, 1)


# Peak f32 elements allowed in one flash score tensor [S, Qc, H, kv_chunk]
# (~128 MB). Both chunk dims shrink to honor it, so prefill memory stays
# bounded whatever the (S, Q) bucket combination.
_FLASH_SCORE_BUDGET = 1 << 25


def _flash_batched_q_chunks(
    qs: jax.Array,        # [S, Q, H, D]
    q_pos: jax.Array,     # [S, Q]
    slot_ids: jax.Array,  # [S, C]
    seq_lens: jax.Array,  # [S]
    k_cache: jax.Array, v_cache: jax.Array,
    scale: float, soft_cap: Optional[float],
    layer: Optional[jax.Array] = None,
    window: Optional[jax.Array] = None,
    q_lim: Optional[jax.Array] = None,
) -> jax.Array:           # [S, Q, H, D]
    """All-sequences-batched prefill attention.

    The flash recurrence runs over KV chunks with ALL sequences in one
    program (MXU-sized matmuls, no per-sequence serialization); an outer
    ``lax.scan`` over query chunks bounds peak memory for large Q buckets.
    Replaces the round-2 per-sequence ``lax.map`` (≈1% MFU: 64 serial tiny
    flashes per step).
    """
    S, Q, H, D = qs.shape
    C = slot_ids.shape[1]
    kv_chunk = _chunk_size_for(C)
    qc = Q
    while qc > 8 and (S * qc * H * kv_chunk > _FLASH_SCORE_BUDGET
                      or Q % qc) and qc % 2 == 0:
        qc //= 2
    while kv_chunk > 16 and S * qc * H * kv_chunk > _FLASH_SCORE_BUDGET \
            and kv_chunk % 2 == 0 and C % (kv_chunk // 2) == 0:
        kv_chunk //= 2
    if Q % qc:      # non-pow2 Q bucket: no clean split, single chunk
        qc = Q

    if qc == Q:
        return _flash_over_kv_chunks(
            qs, q_pos, slot_ids, seq_lens, k_cache, v_cache,
            kv_chunk, scale, soft_cap, layer=layer, window=window,
            q_lim=q_lim)

    def one_q_chunk(_, qi):
        qs_i = jax.lax.dynamic_slice_in_dim(qs, qi * qc, qc, 1)
        qp_i = jax.lax.dynamic_slice_in_dim(q_pos, qi * qc, qc, 1)
        ql_i = None if q_lim is None else jax.lax.dynamic_slice_in_dim(
            q_lim, qi * qc, qc, 1)
        out_i = _flash_over_kv_chunks(
            qs_i, qp_i, slot_ids, seq_lens, k_cache, v_cache,
            kv_chunk, scale, soft_cap, layer=layer, window=window,
            q_lim=ql_i)
        return None, out_i

    _, outs = jax.lax.scan(one_q_chunk, None,
                           jnp.arange(Q // qc))     # [nq, S, qc, H, D]
    return jnp.moveaxis(outs, 0, 1).reshape(S, Q, H, D)


def gather_per_seq_queries(q, positions, qtok_idx):
    """[T, H, D] ragged queries -> ([S, Q, H, D], [S, Q] positions).

    qtok_idx's pad sentinel is T: one zero query row / -1 position is
    appended so pad slots gather a fully-masked row.  The rectangle of the
    chunked XLA path; the Pallas prefill kernels take ``gather_query_tiles``."""
    T, H, D = q.shape
    q_pad = jnp.concatenate([q, jnp.zeros((1, H, D), q.dtype)])
    pos_pad = jnp.concatenate(
        [positions, jnp.full((1,), -1, positions.dtype)])
    return q_pad[qtok_idx], pos_pad[qtok_idx]


def with_block_visibility(batch, block_length: int):
    """``batch`` plus ``vis_limit`` [T], the last key each query sees under
    block-causal attention with blocks of ``block_length`` aligned on
    absolute positions: ``(p // B + 1) * B - 1`` (a query sees its whole
    block and every block before it).  Derived once a step program, by the
    models' ``forward``; every backend masks by it in place of the query's
    own position, which stays what the rotary embedding and a window use.
    ``block_length`` 0 (an autoregressive model) adds nothing: the step
    programs are the ones without the operand."""
    if not block_length:
        return batch
    B = block_length
    return dict(batch, vis_limit=(batch["positions"] // B + 1) * B - 1)


# What ``query_tiles`` derives from a batch (``ATTN_BATCH_KEYS`` carries
# them to the shard-local attention call).
QUERY_TILE_KEYS = ("tile_seq", "tile_tok", "tile_pos", "tok_tile", "tok_slot")


def prefill_q_tile(Q: int, H: int, F: int, mla: bool = False) -> int:
    """Query slots a tile of the Pallas prefill kernels holds, for a step
    of query bucket ``Q`` and ``H`` heads over cache rows ``F`` wide as ONE
    shard sees them (``ops.pallas.flash_prefill.pick_q_tile``)."""
    if mla:
        from llm_d_tpu.ops.pallas.mla_prefill import _pick_q_tile
    else:
        from llm_d_tpu.ops.pallas.flash_prefill import _pick_q_tile
    return _pick_q_tile(Q, H, F)


def prefill_key_block(q_tile: int, H: int, F: int, D: int, block_size: int,
                      mla: bool = False) -> int:
    """Keys one step of a Pallas prefill kernel's inner loop covers for
    tiles of ``q_tile`` slots, with ``H`` heads of size ``D`` over cache
    rows ``F`` wide: a block of several pages, by the rows of a dot (the GQA
    kernel's ``pick_key_block``, the MLA kernel's ``_pick_key_block``)."""
    if mla:
        from llm_d_tpu.ops.pallas.mla_prefill import _pick_key_block
        return _pick_key_block(block_size, F, q_tile * H)
    from llm_d_tpu.ops.pallas.flash_prefill import dot_rows, pick_key_block
    return pick_key_block(block_size, F, dot_rows(q_tile, H, F // D, D))


@functools.lru_cache(maxsize=None)
def mla_decode_walk(S: int, H: int, F: int, block_size: int):
    """(keys a step of the MLA decode kernel's inner loop covers for a
    sequence, sequences a grid program owns) for ``S`` rows of ``H`` heads
    over cache rows ``F`` wide as ONE shard sees them: the kernel's own
    picks (``ops.pallas.mla_attention``)."""
    from llm_d_tpu.ops.pallas.mla_attention import (
        decode_key_block, decode_seq_group)
    kb = decode_key_block(H, F, block_size)
    return kb, decode_seq_group(S, H, F, kb)


def num_query_tiles(T: int, S: int, q_tile: int) -> int:
    """Tiles that hold any ``S`` rows of ``T`` tokens together: a row of n
    tokens fills ceil(n / q_tile), so the sum stays UNDER this count and
    the last tile is always dead."""
    return -(-T // q_tile) + S


def query_tiles(batch, q_tile: int):
    """The compact list of query tiles the Pallas prefill kernels walk in
    place of the padded [S, Q] rectangle, from the batch's ``qtok_idx``
    [S, Q] (token per (row, slot), T = pad; a row's n queries are its
    leading n entries), ``token_seq_ids``, ``token_qpos`` and ``positions``
    [T].  A row with n queries takes ceil(n / q_tile) tiles, rows in order,
    dead tiles at the end.

      tile_seq [NT]      row a tile belongs to (a dead tile: the last row)
      tile_tok [NT, Qt]  flat token index per slot, T = pad (as qtok_idx)
      tile_pos [NT, Qt]  the last key it sees: its position, or its
                         ``vis_limit`` where the batch has one; pad -> -1
      tok_tile, tok_slot [T]  where each token's output lands; a token that
                         is in no row's list (padding, a fused round's dead
                         slot) reads the dead last tile: zeros

    A few integer ops on [S]-, [T]- and [NT, Qt]-sized arrays, derived once
    a step program (``with_query_tiles``), not once a layer."""
    qtok_idx, seq, qpos = (batch[k] for k in (
        "qtok_idx", "token_seq_ids", "token_qpos"))
    S, Q = qtok_idx.shape
    T = seq.shape[0]
    NT = num_query_tiles(T, S, q_tile)
    n_row = jnp.sum(qtok_idx < T, axis=1, dtype=jnp.int32)          # [S]
    row_tiles = -(-n_row // q_tile)
    ends = jnp.cumsum(row_tiles)                 # tiles of rows 0 .. s
    starts = ends - row_tiles
    n = jnp.arange(NT, dtype=jnp.int32)
    tile_seq = jnp.minimum(
        jnp.sum(n[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), S - 1)
    slot = ((n - starts[tile_seq])[:, None] * q_tile
            + jnp.arange(q_tile, dtype=jnp.int32)[None, :])   # [NT, Qt] in row
    live = (n < ends[-1])[:, None] & (slot < Q)
    tile_tok = jnp.where(
        live, qtok_idx[tile_seq[:, None], jnp.minimum(slot, Q - 1)], T)
    pos = batch.get("vis_limit", batch["positions"])
    pos_pad = jnp.concatenate([pos, jnp.full((1,), -1, pos.dtype)])
    real = qpos < n_row[seq]
    return dict(
        tile_seq=tile_seq, tile_tok=tile_tok, tile_pos=pos_pad[tile_tok],
        tok_tile=jnp.where(real, starts[seq] + qpos // q_tile, NT - 1),
        tok_slot=jnp.where(real, qpos % q_tile, 0))


def with_query_tiles(batch, num_heads: int, row_width: int, backend: str,
                     mesh=None, mla: bool = False):
    """``batch`` plus its query tile list (``QUERY_TILE_KEYS``) where the
    Pallas prefill kernels will serve it: the models' ``forward`` calls this
    before the layer scan, so the list is derived once a step program.
    ``num_heads`` and ``row_width`` are the model's; a tp shard sees its
    slice of the heads (and of the folded GQA row; the MLA latent row is
    replicated).  Stacked dp batches get one list per shard."""
    qtok_idx = batch.get("qtok_idx")
    if (qtok_idx is None or qtok_idx.shape[-1] == 1
            or resolve_backend(backend) != "pallas"):
        return batch
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    derive = functools.partial(query_tiles, q_tile=prefill_q_tile(
        qtok_idx.shape[-1], num_heads // tp,
        row_width if mla else row_width // tp, mla))
    if qtok_idx.ndim == 3:
        derive = jax.vmap(derive)
    return dict(batch, **derive({k: batch[k] for k in (
        "qtok_idx", "token_seq_ids", "token_qpos", "positions", "vis_limit")
        if k in batch}))


def gather_query_tiles(q, batch, row_width: int, mla: bool = False):
    """[T, H, D] ragged queries -> (tiles [NT, Qt, H, D], the batch with
    its tile list).  A batch that did not come through ``with_query_tiles``
    (a caller outside the models' ``forward``) gets its list derived here,
    from the shapes this shard sees."""
    T, H, D = q.shape
    if "tile_tok" not in batch:
        batch = dict(batch, **query_tiles(batch, prefill_q_tile(
            batch["qtok_idx"].shape[1], H, row_width, mla)))
    q_pad = jnp.concatenate([q, jnp.zeros((1, H, D), q.dtype)])
    return q_pad[batch["tile_tok"]], batch


def ragged_paged_attention_chunked(
    q: jax.Array,              # [T, H, D]
    k_cache: jax.Array, v_cache: jax.Array,
    token_seq_ids: jax.Array, positions: jax.Array,
    block_tables: jax.Array, seq_lens: jax.Array,
    qtok_idx: jax.Array,       # [S, Q] token index per (seq, q slot); T = pad
    token_qpos: jax.Array,     # [T] q slot of each token within its seq
    block_size: int, scale=None, soft_cap=None,
    layer: Optional[jax.Array] = None,
    window: Optional[jax.Array] = None,
    limits: Optional[jax.Array] = None,    # [T] last key a query sees
) -> jax.Array:
    """Memory-bounded ragged attention (XLA flash recurrence).

    Decode steps (Q == 1) batch all sequences through one flash pass;
    prefill/mixed steps map over sequences to bound the score tensor.
    """
    T, H, D = q.shape
    S, B = block_tables.shape
    Q = qtok_idx.shape[1]
    scale = scale if scale is not None else D ** -0.5
    C = B * block_size

    qs, q_pos = gather_per_seq_queries(q, positions, qtok_idx)
    q_lim = None if limits is None else gather_per_seq_queries(
        q, limits, qtok_idx)[1]
    slot_ids = (block_tables[:, :, None] * block_size
                + jnp.arange(block_size)[None, None, :]).reshape(S, C)

    if Q == 1:
        out = _flash_over_kv_chunks(
            qs, q_pos, slot_ids, seq_lens, k_cache, v_cache,
            _chunk_size_for(C), scale, soft_cap, layer=layer,
            window=window, q_lim=q_lim)                        # [S, 1, H, D]
    else:
        out = _flash_batched_q_chunks(
            qs, q_pos, slot_ids, seq_lens, k_cache, v_cache,
            scale, soft_cap, layer=layer, window=window, q_lim=q_lim)

    return out[token_seq_ids, token_qpos]       # [T, H, D]


def resolve_backend(backend: str) -> str:
    """'auto' -> the platform's preferred implementation."""
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    return backend


def pallas_ineligible_reason(block_size: int,
                             row_width: int) -> Optional[str]:
    """Why the Pallas attention kernels (dense and MLA, decode and prefill)
    cannot serve a cache geometry; None when they can.  TPU DMA slices need
    sublane-aligned pages and 128-lane-aligned rows — an ineligible shape
    would fail Mosaic compilation, so the chunked XLA path serves it.  The
    answer is static per engine: ``EngineCore`` asks ONCE at construction
    and says so (log + ``engine_feature_disabled_total``) instead of
    leaving the drop to be discovered from a profile."""
    if block_size % 16:
        return (f"block_size {block_size} is not a multiple of 16 "
                f"(bf16 sublane tile)")
    if row_width % 128:
        return (f"cache row width {row_width} is not a multiple of 128 "
                f"lanes")
    return None


def manual_over_mesh(fn, mesh, in_specs, out_specs):
    """Mosaic kernels cannot be partitioned automatically ("wrap the call
    in a shard_map"): run ``fn`` MANUAL over every mesh axis that is not
    manual already.  Under ``dp_attend`` the dp axis already is, so this
    nests and takes the rest (tp, sp); on a plain TP mesh it takes all
    three.  Head-sharded operands name ``"tp"`` in their specs; each shard
    then sees exactly the local shapes the kernels handle on one chip."""
    if mesh is None or mesh.devices.size == 1:
        return fn
    ctx = jax.sharding.get_abstract_mesh()
    return jax.shard_map(
        fn, mesh=None if ctx.manual_axes else mesh,
        in_specs=in_specs, out_specs=out_specs,
        axis_names=set(mesh.axis_names) - set(ctx.manual_axes),
        check_vma=False)


# Batch arrays attention consumes (replicated over tp under a TP mesh).
ATTN_BATCH_KEYS = ("positions", "token_seq_ids", "token_qpos",
                   "slot_mapping", "block_tables", "seq_lens", "qtok_idx",
                   "vis_limit", *QUERY_TILE_KEYS)


def attention_with_kv_update(
    q: jax.Array,            # [T, H, D]
    k_new: jax.Array,        # [T, KVH, D] this step's K rows
    v_new: jax.Array,
    k_cache: jax.Array,      # [num_slots, KVH*D] or stacked [L, slots, KVH*D]
    v_cache: jax.Array,
    batch,                   # dict with the ragged-batch index arrays
    block_size: int,
    scale=None,
    soft_cap=None,
    backend: str = "auto",
    layer: Optional[jax.Array] = None,   # i32 plane of a stacked cache
    mesh=None,               # multi-device mesh: Pallas runs per tp shard
    window: Optional[jax.Array] = None,   # i32 scalar (traced per layer):
                                          # keys a query sees; None = all
):
    """Write this step's KV into the paged cache and attend over it.

    One entry point for every backend so kernels may FUSE the update with
    attention (the Pallas decode kernel does: single-row HBM scatters are
    not DMA-alignable on TPU, so the row is spliced into the last page in
    VMEM and the page written back).

    With ``layer`` the caches are the engine's full stacked [L, slots, F]
    buffers and every read/write addresses one plane in place — the model's
    layer loop then carries the whole cache through ``lax.scan`` with zero
    per-layer slice/copy traffic (measured ~10 ms/step of pure HBM copies
    at 1B scale otherwise).

    Returns (attn_out, k_cache', v_cache').

    On a multi-device ``mesh`` the Pallas backend runs per tp shard under
    ``manual_over_mesh``: heads (and the folded cache rows) split over
    ``tp``, every shard attends its own heads with no cross-shard traffic.

    Prefill and mixed steps hand the Pallas kernel the step's query TILES
    (``gather_query_tiles``: Qt slots of one row each, only the tiles that
    hold a real query), not the padded [S, Q] rectangle; the chunked XLA
    path keeps the rectangle.

    A batch with ``vis_limit`` (``with_block_visibility``: a block-diffusion
    model) is masked by it: query i sees keys j <= vis_limit[i].  The tile
    list already carries the limits as its ``tile_pos``; the reference and
    chunked paths take them as an operand.  The one-query decode kernel
    knows no limit and is never reached: every row of such a model brings
    a whole block of queries.

    ``window``: query i sees keys j with i - window < j <= i.  Every
    backend masks by it, and the kernels and the chunked path start their
    walk at the first page (chunk) that holds a visible key.  The cache is
    written whole all the same.  ``None`` lowers to the programs without
    the operand; a full layer of a mixed stack passes a window that never
    binds (``models.config.NO_WINDOW``).
    """
    backend = resolve_backend(backend)
    if backend == "pallas" and mesh is not None and mesh.devices.size > 1:
        ab = {k: batch[k] for k in ATTN_BATCH_KEYS if k in batch}
        heads = P(None, "tp", None)
        rows = P(*(None,) * (k_cache.ndim - 1), "tp")
        # The replicated scalars, those that are given.
        largs = {name: v for name, v in (("layer", layer), ("window", window))
                 if v is not None}

        def local(q, k_new, v_new, k_cache, v_cache, ab, *rest):
            return attention_with_kv_update(
                q, k_new, v_new, k_cache, v_cache, ab, block_size,
                scale=scale, soft_cap=soft_cap, backend=backend,
                **dict(zip(largs, rest)))

        return manual_over_mesh(
            local, mesh,
            in_specs=(heads, heads, heads, rows, rows,
                      {k: P() for k in ab}) + (P(),) * len(largs),
            out_specs=(heads, rows, rows),
        )(q, k_new, v_new, k_cache, v_cache, ab, *largs.values())
    T, H, D = q.shape
    F = k_cache.shape[-1]

    qtok_idx = batch.get("qtok_idx")
    # An ineligible cache geometry takes the chunked XLA path; the engine
    # announced that at construction.
    kernel_ok = (backend == "pallas" and qtok_idx is not None
                 and pallas_ineligible_reason(block_size, F) is None)
    if kernel_ok and soft_cap is None and qtok_idx.shape[1] == 1:
        from llm_d_tpu.ops.pallas.paged_attention import (
            paged_attention_decode_update)
        rows = qtok_idx[:, 0].clip(0, T - 1)
        out, k_cache, v_cache = paged_attention_decode_update(
            q[rows], k_new.reshape(T, F)[rows].astype(k_cache.dtype),
            v_new.reshape(T, F)[rows].astype(v_cache.dtype),
            k_cache, v_cache, batch["block_tables"], batch["seq_lens"],
            block_size=block_size,
            num_kv_heads=F // D, scale=scale, layer=layer, window=window)
        return out[batch["token_seq_ids"]], k_cache, v_cache

    k_cache, v_cache = write_kv(
        k_cache, v_cache, k_new, v_new, batch["slot_mapping"], layer=layer)
    if kernel_ok and qtok_idx.shape[1] > 1:
        # Prefill / mixed batches: flash kernel streaming KV pages through
        # VMEM (scatter-then-read; no aliasing needed), over the step's
        # query tiles.  Same geometry gate as the decode kernel.
        from llm_d_tpu.ops.pallas.flash_prefill import flash_prefill_paged
        q_tiles, batch = gather_query_tiles(q, batch, F)
        out_t = flash_prefill_paged(
            q_tiles, batch["tile_pos"], k_cache, v_cache,
            batch["block_tables"], batch["seq_lens"],
            block_size=block_size, num_kv_heads=F // D,
            scale=scale, soft_cap=soft_cap, layer=layer, window=window,
            tile_seq=batch["tile_seq"])
        return out_t[batch["tok_tile"], batch["tok_slot"]], k_cache, v_cache
    limits = batch.get("vis_limit")
    if backend in ("pallas", "chunked") and qtok_idx is not None:
        out = ragged_paged_attention_chunked(
            q, k_cache, v_cache, batch["token_seq_ids"], batch["positions"],
            batch["block_tables"], batch["seq_lens"], qtok_idx,
            batch["token_qpos"], block_size=block_size,
            scale=scale, soft_cap=soft_cap, layer=layer, window=window,
            limits=limits)
    else:
        out = ragged_paged_attention_reference(
            q, k_cache, v_cache, batch["token_seq_ids"], batch["positions"],
            batch["block_tables"], batch["seq_lens"],
            block_size=block_size, scale=scale, soft_cap=soft_cap,
            layer=layer, window=window, limits=limits)
    return out, k_cache, v_cache


def attention_one_query(
    q: jax.Array,            # [S, H, D]: one query a row
    k_cache: jax.Array,      # stacked [L, slots, KVH*D]
    v_cache: jax.Array,
    batch,                   # block_tables [S, B], seq_lens [S]
    block_size: int,
    scale=None,
    backend: str = "auto",
    layer: Optional[jax.Array] = None,
):
    """One query a row over plane ``layer`` of a cache that ALREADY holds
    the row's keys and values up to the query's own (position ``seq_lens -
    1``): cross-layer attention, which reads another layer's plane and
    writes none.  Nothing is written.  The Pallas backend takes the decode
    kernel's page walk (``paged_attention_read``), the others the chunked
    XLA recurrence.  Returns [S, H, D]; a padded row (``seq_lens`` 0)
    zeros."""
    S, H, D = q.shape
    F = k_cache.shape[-1]
    seq_lens, tables = batch["seq_lens"], batch["block_tables"]
    if (resolve_backend(backend) == "pallas"
            and pallas_ineligible_reason(block_size, F) is None):
        from llm_d_tpu.ops.pallas.paged_attention import paged_attention_read
        return paged_attention_read(
            q, k_cache, v_cache, tables, seq_lens, block_size=block_size,
            num_kv_heads=F // D, scale=scale, layer=layer)
    C = tables.shape[1] * block_size
    slot_ids = (tables[:, :, None] * block_size
                + jnp.arange(block_size)[None, None, :]).reshape(S, C)
    return _flash_over_kv_chunks(
        q[:, None], (seq_lens - 1)[:, None], slot_ids, seq_lens, k_cache,
        v_cache, _chunk_size_for(C), scale if scale is not None
        else D ** -0.5, None, layer=layer)[:, 0]


def diff_pair_queries(q: jax.Array) -> jax.Array:
    """Differential attention through kernels that know GQA only: a PAIR of
    neighbouring heads is one head of twice the size.  ``q`` [T, H, D] ->
    [T, H, 2D]: query s of a pair in half s, zero in the other, so that
    against the pair's keys side by side ``[k1, k2]`` the score is ``q_s .
    k_s``, and over the pair's values side by side the output is ``P_s [v1,
    v2]``.  The keys and values need no copy: KVH heads of D folded in a
    cache row ARE KVH / 2 heads of 2D."""
    T, H, D = q.shape
    half = jnp.eye(2, dtype=q.dtype).reshape(1, 1, 2, 2, 1)
    return (q.reshape(T, H // 2, 2, 1, D) * half).reshape(T, H, 2 * D)


def diff_combine(out: jax.Array, lam: jax.Array) -> jax.Array:
    """``out`` [T, H, 2D], the attention of ``diff_pair_queries``' heads ->
    float32 [T, H / 2, 2D]: P_1 V - lambda P_2 V of each pair."""
    T, H, D2 = out.shape
    o = out.reshape(T, H // 2, 2, D2).astype(jnp.float32)
    return o[:, :, 0] - lam * o[:, :, 1]
