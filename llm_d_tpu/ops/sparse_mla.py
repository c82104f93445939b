"""Learned key selection over the paged latent cache (DeepSeek sparse
attention): the indexer's scores, the exact top-k a query, and attention
over the selected keys.

A FULL layer with ``index_topk`` > 0 caches, beside each token's latent row,
one small index key.  For every query of the step

    I(t, s) = sum_j w[t, j] * relu(qI[t, j] . kI[s])        s <= t

is computed against the cached index keys of the query's own sequence, the
``index_topk`` keys of largest I are kept (all of them while fewer are
visible), and the layer's attention weighs those keys only:

  - scores by query TILE (``ops.attention.query_tiles``: slots of one row
    each, no padded [S, Q] rectangle) against key chunks gathered a PAGE an
    index, once a row of the batch, only the chunks below the step's longest
    context (XLA's gather costs by the index, 17 ns, not by the byte);
  - the exact top-k as a THRESHOLD, not a sort: the k-th largest score of a
    query is found two bits a pass over the scores' order-preserving
    integer image (16 passes of a compare and a count, where a stable sort
    of [2304, 16384] with payload was 33 ms a layer and 20 s to compile),
    equal scores at the threshold go to the lower positions by the same
    search over positions: the set ``lax.top_k`` gives, as a [query, key]
    mask (``lax.approx_max_k`` is another result), over no more columns
    than the longest context needs, by halves of the table;
  - attention DENSE under that mask: on the TPU
    ``ops.pallas.mla_masked.mla_masked_attention`` walks all the sequence's
    pages with the mask as a bias (its docstring says why reading every key
    beats gathering the chosen ones while contexts stay within a few times
    the top-k); elsewhere a tile at a time over the row's gathered table.
    One path for prefill chunks, mixed steps and decode rows.

Two forms of the first two steps, chosen by what ``kernel_serves`` sees
(the Pallas backend and a geometry ``mla_masked`` takes: the choice
``attend_chosen(kernel=...)`` makes).  Where it is true, ``index_bias``:
the scores, the threshold and the bias the masked kernel reads in ONE
Pallas kernel over the step's tile list (``ops.pallas.dsa_index``), a
tile's work bounded by the tile's own last key, the scores in VMEM only;
XLA gathers each row's keys once (``row_index_keys``) and lays the queries
out.  Everywhere else (the CPU, a refused geometry, the tests' oracle, the
reference-side tools) the XLA form: ``index_scores`` + ``choose_topk`` =
``index_select``, a [tiles, slots, C] mask whose shapes are static, so
every tile pays for the batch's longest context.  Both give the same set.

A SLIDING layer's window on the MLA path is served here too
(``attend_window``): a tile of consecutive queries of one row sees the
``window - 1`` keys before its first query and the tile's own, one band
of Qt + window - 1 rows whatever the context.  On the TPU the same flash
kernel walks that band, from the key block that holds its first key, with
the window as the bias (``mla_masked``'s docstring: one body, two walks);
elsewhere a tile gathers the band's rows through the block table.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from llm_d_tpu.ops import attention as A

# Query slots a tile of a layer that selects holds (its index scores, its
# mask and its attention walk the same tile list), and keys a chunk of the
# score computation covers: they bound the temporaries ([tiles,
# SELECT_Q_TILE, heads, INDEX_KEY_CHUNK] f32), not the result.  A decode row
# of a mixed step fills one slot of its tile: 8 slots x 128 heads are 1,024
# fused rows, as many as feed the MXU well.
SELECT_Q_TILE = 8
INDEX_KEY_CHUNK = 512
# Keys a step of ``row_index_keys`` gathers a row: a step costs 15 us
# whatever it moves, a page gathered past the longest context 17 ns.
ROW_KEY_CHUNK = 2048
# Bits of the threshold a pass of ``choose_topk`` settles (2**bits - 1
# counts in one read of the scores).
THRESHOLD_BITS = 2
# Query slots a tile of a windowed layer holds where XLA serves it, and
# the f32 score elements [tiles, WINDOW_Q_TILE, H, WINDOW_Q_TILE + window -
# 1] one pass of ``attend_window`` may hold (64 MiB).  Where the kernel
# serves it the height follows the geometry (``window_q_tile``).
WINDOW_Q_TILE = 128
WINDOW_SCORE_BUDGET = 1 << 24


def kernel_refusal(g, block_size: int, table_keys: int) -> str | None:
    """Why ``mla_masked_attention`` cannot serve the layers of MLA geometry
    ``g`` (``ModelConfig.mla_geometry``) whose rows' block tables hold
    ``table_keys`` positions; None: it can.  Geometry the code can see,
    asked by ``models/mla.py`` (which path), the models' ``forward`` (which
    tiles) and the engine (what it announces and counts)."""
    from llm_d_tpu.ops.pallas import mla_masked
    return (A.pallas_ineligible_reason(block_size, g.row_width)
            or mla_masked.ineligible_reason(g.num_heads, g.kv_lora_rank,
                                            table_keys))


def kernel_serves(g, backend: str, block_size: int, table_keys: int) -> bool:
    """Whether ``mla_masked_attention`` serves a layer kind that selects or
    sees a window: the backend and ``kernel_refusal``, nothing else."""
    return (A.resolve_backend(backend) == "pallas"
            and kernel_refusal(g, block_size, table_keys) is None)


def window_q_tile(g, kernel: bool) -> int:
    """Query slots a tile of a windowed layer of geometry ``g`` holds: the
    kernel's pick from the heads and the row (16 at 64 heads: a decode row
    of a mixed step costs a tile of 16 slots, a band 3-4 key blocks), the
    XLA form's WINDOW_Q_TILE where it serves."""
    if not kernel:
        return WINDOW_Q_TILE
    from llm_d_tpu.ops.pallas import mla_masked
    return mla_masked.pick_q_tile(g.num_heads, g.row_width, g.kv_lora_rank)


def with_tiles(batch: Dict[str, jax.Array], q_tile: int
               ) -> Dict[str, jax.Array]:
    """``batch`` plus the query tile list (``ops.attention.query_tiles``) of
    ``q_tile`` slots that a layer that selects (SELECT_Q_TILE) or
    ``attend_window`` (``window_q_tile``) walks, no wider than the step's
    query bucket: derived once a step program by the models' ``forward``."""
    return dict(batch, **A.query_tiles(
        batch, min(q_tile, batch["qtok_idx"].shape[1])))


def _tile_positions(batch, tiles) -> jax.Array:
    """[NT, Qt] position of each slot's query, -1 for a pad slot."""
    return jnp.concatenate(
        [batch["positions"], jnp.full((1,), -1, jnp.int32)])[tiles["tile_tok"]]


def _tile_live(batch, tiles) -> jax.Array:
    """[NT] the key each tile's walk ends before: its last query's position
    + 1, capped by its row's length (0: a tile of pad slots)."""
    return jnp.minimum(jnp.max(_tile_positions(batch, tiles), axis=1) + 1,
                       batch["seq_lens"][tiles["tile_seq"]])


def index_scores(
    q_idx: jax.Array,         # [T, Hi, Di] the indexer's queries
    w: jax.Array,             # [T, Hi] f32 head weights (scales folded in)
    idx_cache: jax.Array,     # [L, slots, Di] index keys, this step's written
    batch: Dict[str, jax.Array],
    block_size: int,
    layer: jax.Array,
):
    """(I [NT, Qt, C] f32 of every query slot of the step's tiles against
    the C positions of its sequence's block table, -inf where the slot does
    not see the key; the number of leading columns that hold anything)."""
    T, Hi, Di = q_idx.shape
    S, B = batch["block_tables"].shape
    C = B * block_size
    tiles = batch if "tile_tok" in batch else with_tiles(batch, SELECT_Q_TILE)
    tile_tok, tile_seq = tiles["tile_tok"], tiles["tile_seq"]
    NT, qt = tile_tok.shape
    q_t = jnp.concatenate([q_idx, jnp.zeros((1, Hi, Di), q_idx.dtype)])[
        tile_tok]                                         # [NT, Qt, Hi, Di]
    w_t = jnp.concatenate([w, jnp.zeros((1, Hi), w.dtype)])[tile_tok]
    pos_t = _tile_positions(batch, tiles)
    len_t = batch["seq_lens"][tile_seq]                   # [NT]
    # A chunk's keys are gathered once a ROW of the batch, a page an index,
    # and handed to the row's tiles by a one-hot dot that copies them
    # exactly (a 2,048-token chunk is 256 tiles of one row).
    pages = idx_cache.reshape(idx_cache.shape[0], -1, block_size, Di)
    row_of = (tile_seq[:, None] == jnp.arange(S)[None, :]).astype(
        idx_cache.dtype)                                  # [NT, S]
    pc = A._chunk_size_for(B, max(INDEX_KEY_CHUNK // block_size, 1))
    kc = pc * block_size
    n_live = jnp.minimum(-(-jnp.max(batch["seq_lens"]) // kc), C // kc)

    def chunk(carry):
        i, scores = carry
        keys = pages[layer, jax.lax.dynamic_slice_in_dim(
            batch["block_tables"], i * pc, pc, 1)].reshape(S, kc, Di)
        keys = jnp.einsum("ns,skd->nkd", row_of, keys,
                          preferred_element_type=jnp.float32
                          ).astype(keys.dtype)            # [NT, kc, Di]
        s = jnp.einsum("nqhd,nkd->nqhk", q_t, keys,
                       preferred_element_type=jnp.float32)
        s = jnp.einsum("nqhk,nqh->nqk", jax.nn.relu(s), w_t)
        key_pos = i * kc + jnp.arange(kc)
        seen = (key_pos[None, None, :] <= pos_t[:, :, None]) & (
            key_pos[None, None, :] < len_t[:, None, None])
        return i + 1, jax.lax.dynamic_update_slice_in_dim(
            scores, jnp.where(seen, s, -jnp.inf), i * kc, 2)

    _, scores = jax.lax.while_loop(
        lambda c: c[0] < n_live, chunk,
        (jnp.int32(0), jnp.full((NT, qt, C), -jnp.inf, jnp.float32)))
    return scores, n_live * kc


def _largest(holds, bits: int, shape) -> jax.Array:
    """The largest t < 2**bits (u32, of ``shape``) of which ``holds`` is
    true, for a ``holds`` (candidates [..., J] u32 -> bool [..., J]) that is
    true of 0 and, once false, false of everything larger: THRESHOLD_BITS
    bits a pass, from the top."""
    r = THRESHOLD_BITS
    passes = -(-bits // r)
    digits = jnp.arange(1, 1 << r, dtype=jnp.uint32)

    def settle(i, t):
        shift = ((passes - 1 - i) * r).astype(jnp.uint32)
        # The candidates rise with the digit, so those that hold are the
        # first ones: their number is the digit.
        digit = jnp.sum(holds(t[..., None] | (digits << shift)), axis=-1,
                        dtype=jnp.uint32)
        return t | (digit << shift)

    return jax.lax.fori_loop(0, passes, settle, jnp.zeros(shape, jnp.uint32))


def choose_topk(scores: jax.Array, k: int) -> jax.Array:
    """[..., W] f32 scores (-inf: not visible) -> [..., W] bool, the ``k``
    largest of each row, of equal scores the lower columns first (the set
    ``lax.top_k`` returns), every visible column where fewer than ``k``
    are.  No sort: the k-th largest value by a search over the bits of the
    scores' order-preserving integer image, then the last tied column
    kept by the same search over columns."""
    W = scores.shape[-1]
    seen = scores > -jnp.inf
    if W <= k:
        return seen
    u = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores), jnp.uint32)    # -0.0 is 0.0
    key = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))
    # The k-th largest: the largest t that k keys reach.
    kth = _largest(
        lambda t: jnp.sum(key[..., None, :] >= t[..., None], axis=-1,
                          dtype=jnp.int32) >= k,
        32, key.shape[:-1])[..., None]
    above, tied = key > kth, key == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    col = jnp.arange(W, dtype=jnp.uint32)
    # The column of the ``room``-th tied key: the largest c that fewer
    # than ``room`` of them lie under.
    last = _largest(
        lambda c: jnp.sum(tied[..., None, :] & (col < c[..., None]), axis=-1,
                          dtype=jnp.int32) < room,
        max(W - 1, 1).bit_length(), key.shape[:-1])[..., None]
    return seen & (above | (tied & (col <= last)))


def index_select(
    q_idx: jax.Array,         # [T, Hi, Di] the indexer's queries
    w: jax.Array,             # [T, Hi] f32 head weights (scales folded in)
    idx_cache: jax.Array,     # [L, slots, Di] index keys, this step's written
    batch: Dict[str, jax.Array],
    block_size: int,
    layer: jax.Array,
    topk: int,
) -> jax.Array:
    """For every query slot of the step's tiles, the keys of its sequence
    it attends to: [NT, Qt, C] bool over the positions of the block table,
    the ``topk`` visible keys with the largest index score (equal scores:
    the lower position first), all visible ones while there are fewer."""
    scores, live = index_scores(q_idx, w, idx_cache, batch, block_size, layer)
    C = scores.shape[-1]
    # The search reads the columns up to the longest context only, in one
    # of three widths: the whole table, a half, a quarter.
    widths = [C]
    while (len(widths) < 3 and widths[0] % 2 == 0
           and widths[0] // 2 >= 2 * topk):
        widths.insert(0, widths[0] // 2)

    def within(wd):
        return lambda sc: jnp.pad(choose_topk(sc[..., :wd], topk),
                                  ((0, 0), (0, 0), (0, C - wd)))

    if len(widths) == 1:
        return within(C)(scores)
    return jax.lax.switch(
        sum((live > wd).astype(jnp.int32) for wd in widths[:-1]),
        [within(wd) for wd in widths], scores)


def row_index_keys(idx_cache: jax.Array, batch: Dict[str, jax.Array],
                   block_size: int, layer: jax.Array) -> jax.Array:
    """[S, C, Di] each row's index keys by position, gathered a PAGE an
    index once a row (a 2,048-token chunk is 256 tiles of one row), the
    chunks below the step's longest context only (past them nothing is
    written: the kernel masks what it reads past a tile's last key)."""
    from llm_d_tpu.ops.pallas import dsa_index
    S, B = batch["block_tables"].shape
    Di = idx_cache.shape[-1]
    pages = idx_cache.reshape(idx_cache.shape[0], -1, block_size, Di)
    pc = A._chunk_size_for(B, max(ROW_KEY_CHUNK // block_size, 1))
    kc = pc * block_size
    n_live = jnp.minimum(-(-jnp.max(batch["seq_lens"]) // kc), B // pc)

    def chunk(i, keys):
        return jax.lax.dynamic_update_slice_in_dim(
            keys, pages[layer, jax.lax.dynamic_slice_in_dim(
                batch["block_tables"], i * pc, pc, 1)].reshape(S, kc, Di),
            i * kc, 1)

    return jax.lax.fori_loop(0, n_live, chunk, dsa_index.unwritten(
        (S, B * block_size, Di), idx_cache.dtype))


def index_bias(
    q_idx: jax.Array,         # [T, Hi, Di] the indexer's queries
    w: jax.Array,             # [T, Hi] f32 head weights (scales folded in)
    idx_cache: jax.Array,     # [L, slots, Di] index keys, this step's written
    batch: Dict[str, jax.Array],
    block_size: int,
    layer: jax.Array,
    topk: int,
) -> jax.Array:
    """``index_select`` where the Pallas kernels serve the layer
    (``kernel_serves``), as the bias ``mla_masked_attention`` reads: [NT,
    C / KEY_BLOCK, Qt, KEY_BLOCK] f32, 0 where the slot's query attends to
    the key and ``mla_masked.NEG_INF`` elsewhere, written for the key
    blocks under each tile's own last key only (``ops.pallas.dsa_index``:
    a tile's scores, threshold and bias in one kernel that reads the row's
    keys a block a copy; nothing of them in HBM but the bias)."""
    from llm_d_tpu.ops.pallas import dsa_index, mla_masked
    tiles = batch if "tile_tok" in batch else with_tiles(batch, SELECT_Q_TILE)
    keys = row_index_keys(idx_cache, batch, block_size, layer)
    # A pad slot rides the last token's queries: its position of -1 sees
    # no key.
    at = jnp.minimum(tiles["tile_tok"], q_idx.shape[0] - 1)
    return dsa_index.index_bias(
        q_idx[at], w[at], _tile_positions(batch, tiles), tiles["tile_seq"],
        _tile_live(batch, tiles), keys, topk=topk,
        key_block=mla_masked.KEY_BLOCK, index_block=dsa_index.index_block(
            keys.shape[1], mla_masked.KEY_BLOCK))


def attend_chosen(
    q_eff: jax.Array,         # [T, H, F] absorbed queries
    kv_cache: jax.Array,      # [L, slots, F] latent rows, this step's written
    chosen: jax.Array,        # [NT, Qt, C] bool (``index_select``), or the
                              # bias ``index_bias`` wrote (f32; ``kernel``)
    batch: Dict[str, jax.Array],
    block_size: int,
    layer: jax.Array,
    scale: float,
    R: int,
    kernel: bool,             # the Pallas kernel serves this geometry
) -> jax.Array:               # [T, H, R] f32 attended latents
    """Softmax attention of every query over ITS chosen keys of the latent
    cache, dense under the choice as a mask."""
    T, H, F = q_eff.shape
    tiles = batch if "tile_tok" in batch else with_tiles(batch, SELECT_Q_TILE)
    tile_seq = tiles["tile_seq"]
    q_t = jnp.concatenate([q_eff, jnp.zeros((1, H, F), q_eff.dtype)])[
        tiles["tile_tok"]]                                # [NT, Qt, H, F]
    if kernel:
        from llm_d_tpu.ops.pallas import mla_masked
        bias = chosen
        if bias.dtype == jnp.bool_:
            NT, qt, C = chosen.shape
            KB = mla_masked.KEY_BLOCK
            bias = jnp.where(chosen, 0.0, mla_masked.NEG_INF).astype(
                jnp.float32).reshape(NT, qt, C // KB, KB).transpose(0, 2, 1, 3)
        live = _tile_live(batch, tiles)
        out = mla_masked.mla_masked_attention(
            q_t, bias, tile_seq, live, jnp.zeros_like(live), kv_cache,
            batch["block_tables"], layer, block_size=block_size, scale=scale,
            value_width=R)
    else:
        C = chosen.shape[-1]
        slot_of = (batch["block_tables"][:, :, None] * block_size
                   + jnp.arange(block_size, dtype=jnp.int32)[None, None, :]
                   ).reshape(-1, C)

        def tile(args):
            q, sl, ok = args
            rows = kv_cache[layer, sl]                    # [C, F]
            s = jnp.einsum("qhf,kf->qhk", q, rows,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(ok[:, None, :], s, A.NEG_INF)
            p = jax.nn.softmax(s, axis=-1).astype(rows.dtype)
            return jnp.einsum("qhk,kr->qhr", p, rows[:, :R],
                              preferred_element_type=jnp.float32)

        out = jax.lax.map(tile, (q_t, slot_of[tile_seq], chosen))
    return out[tiles["tok_tile"], tiles["tok_slot"]]


def attend_window(
    q_eff: jax.Array,         # [T, H, F] absorbed queries
    kv_cache: jax.Array,      # [L, slots, F] latent rows, this step's written
    batch: Dict[str, jax.Array],
    window: int,              # keys a query sees, itself included
    block_size: int,
    layer: jax.Array,
    scale: float,
    R: int,
    kernel: bool,             # the Pallas kernel serves this geometry
) -> jax.Array:               # [T, H, R] f32 attended latents
    """Softmax attention of every query over the last ``window`` keys of its
    sequence, a tile of queries at a time over the one band of rows the
    tile sees."""
    if kernel:
        return _attend_window_blocks(
            q_eff, kv_cache, batch, window, block_size, layer, scale, R)
    T, H, F = q_eff.shape
    tiles = batch if "tile_tok" in batch else with_tiles(batch, WINDOW_Q_TILE)
    tile_tok, tile_seq = tiles["tile_tok"], tiles["tile_seq"]
    NT, qt = tile_tok.shape
    K = qt + window - 1
    pos_t = jnp.concatenate(
        [batch["positions"], jnp.full((1,), -1, jnp.int32)])[tile_tok]
    # A tile's slots are consecutive queries of one row: its band starts
    # window - 1 keys before its first query.
    key_pos = (pos_t[:, :1] - (window - 1)
               + jnp.arange(K, dtype=jnp.int32)[None, :])       # [NT, K]
    there = (key_pos >= 0) & (key_pos < batch["seq_lens"][tile_seq][:, None])
    at = jnp.clip(key_pos, 0, batch["block_tables"].shape[1] * block_size - 1)
    slots = (jnp.take_along_axis(batch["block_tables"][tile_seq],
                                 at // block_size, axis=1)
             * block_size + at % block_size)                    # [NT, K]
    seen = (there[:, None, :]
            & (key_pos[:, None, :] <= pos_t[:, :, None])
            & (key_pos[:, None, :] > pos_t[:, :, None] - window))
    q_t = jnp.concatenate([q_eff, jnp.zeros((1, H, F), q_eff.dtype)])[
        tile_tok]                                         # [NT, Qt, H, F]

    def tile(args):
        q, sl, ok = args
        rows = kv_cache[layer, sl]                        # [K, F]
        s = jnp.einsum("qhf,kf->qhk", q, rows,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok[:, None, :], s, A.NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(rows.dtype)
        return jnp.einsum("qhk,kr->qhr", p, rows[:, :R],
                          preferred_element_type=jnp.float32)

    out = jax.lax.map(tile, (q_t, slots, seen), batch_size=max(
        min(WINDOW_SCORE_BUDGET // (qt * H * K), NT), 1))  # [NT, Qt, H, R]
    return out[tiles["tok_tile"], tiles["tok_slot"]]


def _attend_window_blocks(q_eff, kv_cache, batch, window, block_size, layer,
                          scale, R) -> jax.Array:
    """``attend_window`` through ``mla_masked_attention``: the band by key
    BLOCK, from the block that holds the first key the tile's first query
    sees to its last query's own, the window as the bias over those
    blocks."""
    from llm_d_tpu.ops.pallas import mla_masked
    T, H, F = q_eff.shape
    tiles = batch if "tile_tok" in batch else with_tiles(
        batch, mla_masked.pick_q_tile(H, F, R))
    tile_tok, tile_seq = tiles["tile_tok"], tiles["tile_seq"]
    NT, qt = tile_tok.shape
    KB = mla_masked.KEY_BLOCK
    nb = min(mla_masked.window_bias_blocks(qt, window),
             batch["block_tables"].shape[1] * block_size // KB)
    pos_t = _tile_positions(batch, tiles)[:, None, :, None]  # [NT, 1, Qt, 1]
    len_t = batch["seq_lens"][tile_seq]                     # [NT]
    first = jnp.maximum(pos_t[:, 0, 0, 0] - (window - 1), 0) // KB
    key_pos = (first[:, None, None, None] * KB + jnp.arange(
        nb * KB, dtype=jnp.int32).reshape(1, nb, 1, KB))    # [NT, nb, 1, KB]
    seen = ((key_pos < len_t[:, None, None, None]) & (key_pos <= pos_t)
            & (key_pos > pos_t - window))                   # [NT, nb, Qt, KB]
    # A pad slot rides the last token's queries: its bias masks every key,
    # so no zero row is appended (a copy of all the step's queries).
    q_t = q_eff[jnp.minimum(tile_tok, T - 1)]               # [NT, Qt, H, F]
    out = mla_masked.mla_masked_attention(
        q_t, jnp.where(seen, 0.0, mla_masked.NEG_INF).astype(jnp.float32),
        tile_seq, _tile_live(batch, tiles), first, kv_cache,
        batch["block_tables"], layer,
        block_size=block_size, scale=scale, value_width=R)
    return out[tiles["tok_tile"], tiles["tok_slot"]]
