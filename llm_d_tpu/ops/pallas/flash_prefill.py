"""Pallas TPU flash-attention kernel for prefill / mixed batches.

The chunked XLA prefill path materializes [S, Q, KVH, G, kv_chunk] f32
score tensors in HBM (~134 MB per (layer, q-chunk) at the 64x128 bench
shape) and pays several elementwise passes over them — measured ~48% of the
prefill step on v5e.  This kernel runs the flash recurrence entirely in
VMEM: each grid program owns one query tile (Qt query slots of ONE
sequence), streams that sequence's KV pages through a double buffer (same
DMA pattern as the decode kernel), and leaves only the tile's outputs in
HBM.

The grid walks a compact LIST of query tiles, not the padded [S bucket x
Q bucket] rectangle: ``tile_seq[n]`` (scalar prefetch) names the sequence
row of tile n, so a mixed step of 63 one-query decode rows and one prompt
costs 63 + ceil(prompt / Qt) tiles and a few dead ones
(``ops.attention.query_tiles``), not S * Q / Qt.  The rectangle call
``flash_prefill_paged(qs [S, Q, H, D], q_pos [S, Q], ...)`` is the special
case ``tile_seq = repeat(arange(S), Q / Qt)`` of the same body.

Everything inside the kernel lives in the FUSED row space [Qt*H, *] (row
r = query-slot r//H, head r%H), so there are no vector reshapes for Mosaic
to reject: the wrapper pre-shapes queries to [NT, Qt*H, D] and un-fuses the
[NT, Qt*H, D] output outside the kernel; the slots' positions arrive as
NT*Qt scalars and are spread to the fused rows in VMEM.  GQA
uses the zero-expansion trick (see paged_attention.py): queries fold to
[Qt*H, KVH*D] with one nonzero D-block per head, scores for the whole tile
come from ONE MXU dot per page, and values accumulate in folded space,
unfolded once at the end.

Causality bounds the page loop per tile: pages past min(seq_len,
max q-position + 1) are never streamed.  KV rows for the tokens being
computed are scattered into the cache by the caller BEFORE the kernel runs
(write_kv) — this kernel only reads, so no aliasing contract is needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _prefill_kernel(
    # scalar prefetch
    block_tables_ref,   # [S, B] SMEM
    seq_lens_ref,       # [S]    SMEM
    layer_ref,          # [1]    SMEM; [2] when ``windowed``: (layer, window)
    tile_seq_ref,       # [NT]   SMEM: the sequence row of each query tile
    tile_pos_ref,       # [NT*Qt] SMEM: position of each query slot (pad -1)
    # inputs (this kernel only READS the cache)
    q_ref, k_hbm, v_hbm,
    # outputs
    o_ref,
    # scratch
    k_buf, v_buf, sems, qpos_buf,
    *,
    block_size: int,
    num_heads: int,
    num_kv_heads: int,
    scale: float,
    soft_cap: float | None,
    windowed: bool,
):
    s = tile_seq_ref[pl.program_id(0)]
    R, D = q_ref.shape[1], q_ref.shape[2]     # R = Qt * H
    H = num_heads
    KVH = num_kv_heads
    G = H // KVH
    F = KVH * D
    bs = block_size
    li = layer_ref[0]
    seq_len = seq_lens_ref[s]

    q_pos = slot_positions(tile_pos_ref, qpos_buf, H)         # [R, 1] i32
    qmax = jnp.max(q_pos)
    # Causal bound: keys at positions > qmax never score for this tile.
    live = jnp.minimum(seq_len, qmax + 1)
    n_pages = pl.cdiv(jnp.maximum(live, 0), bs)
    if windowed:
        # Window: query i sees keys j > i - window, so the tile's walk
        # starts at the page of its first real query's oldest key (pad
        # slots carry position -1).
        window = layer_ref[1]
        qmin = jnp.min(jnp.where(q_pos >= 0, q_pos, jnp.iinfo(jnp.int32).max))
        first = jnp.minimum(jnp.maximum(qmin - window + 1, 0) // bs, n_pages)
    else:
        first = 0

    def page_dma(slot, j):
        b = block_tables_ref[s, j]
        start = pl.multiple_of(b * bs, bs)
        return [
            pltpu.make_async_copy(
                k_hbm.at[li, pl.ds(start, bs)], k_buf.at[slot],
                sems.at[slot, 0]),
            pltpu.make_async_copy(
                v_hbm.at[li, pl.ds(start, bs)], v_buf.at[slot],
                sems.at[slot, 1]),
        ]

    @pl.when(n_pages > first)
    def _():
        for dma in page_dma(first % 2, first):
            dma.start()

    # Zero-expanded queries in fused row space: row r belongs to head r % H,
    # nonzero only in that head's KV D-block.
    q = q_ref[0].astype(jnp.float32) * scale                  # [R, D]
    q_rep = jnp.concatenate([q] * KVH, axis=1)                # [R, F]
    col_kv = jax.lax.broadcasted_iota(jnp.int32, (R, F), 1) // D
    row_kv = (jax.lax.broadcasted_iota(jnp.int32, (R, F), 0) % H) // G
    block_mask = (col_kv == row_kv).astype(jnp.float32)       # [R, F]
    q2 = q_rep * block_mask

    def body(j, carry):
        m, l, acc = carry
        slot = j % 2

        @pl.when(j + 1 < n_pages)
        def _():
            for dma in page_dma((j + 1) % 2, j + 1):
                dma.start()

        for dma in page_dma(slot, j):
            dma.wait()

        # bf16 operands, f32 accumulation: 2x MXU rate and no VPU convert
        # of the page (the flash statistics stay f32).
        k = k_buf[slot]                                       # [bs, F] bf16
        v = v_buf[slot]
        s_hb = jax.lax.dot_general(
            q2.astype(jnp.bfloat16), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [R, bs]
        if soft_cap is not None:
            s_hb = soft_cap * jnp.tanh(s_hb / soft_cap)
        key_pos = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (1, bs), 1)                            # [1, bs]
        valid = (key_pos <= q_pos) & (key_pos < seq_len)      # [R, bs]
        if windowed:
            valid &= key_pos > q_pos - window
        s_hb = jnp.where(valid, s_hb, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_hb, axis=-1, keepdims=True))
        p = jnp.exp(s_hb - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [R, F]
        acc_new = acc * corr + pv
        return m_new, l_new, acc_new

    init = (
        jnp.full((R, 1), -1e29, jnp.float32),
        jnp.zeros((R, 1), jnp.float32),
        jnp.zeros((R, F), jnp.float32),
    )
    m, l, acc = jax.lax.fori_loop(first, n_pages, body, init)
    masked = acc * block_mask                                 # [R, F]
    out = masked[:, 0:D]
    for kk in range(1, KVH):
        out = out + masked[:, kk * D:(kk + 1) * D]
    out = out / jnp.maximum(l, 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)


def slot_positions(tile_pos_ref, qpos_buf, num_heads: int):
    """This tile's query positions as the [Qt*H, 1] column of the fused row
    space, spread from its Qt scalars in SMEM ([NT*Qt], flat: a second
    dimension would lane-pad there) through a VMEM scratch.  (As an input
    block the column lane-pads 128-fold: [NT, Qt*H, 1] i32 was 64 MB a
    layer at 128 tiles of 32 slots x 32 heads.)"""
    q_tile = qpos_buf.shape[0] // num_heads
    first = pl.program_id(0) * q_tile
    for j in range(q_tile):
        qpos_buf[j * num_heads:(j + 1) * num_heads, :] = jnp.full(
            (num_heads, 1), tile_pos_ref[first + j], jnp.int32)
    return qpos_buf[...]


def rectangle_as_tiles(qs, q_pos, q_tile: int):
    """The padded rectangle (``qs`` [S, Q, H, D], ``q_pos`` [S, Q]) as the
    tile list that holds all of it: every row cut into ceil(Q / q_tile)
    tiles (its last padded up), ``tile_seq = repeat(arange(S), ...)``."""
    S, Q = q_pos.shape
    per_row = -(-Q // q_tile)
    pad = per_row * q_tile - Q
    tiles = jnp.pad(qs, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        S * per_row, q_tile, *qs.shape[2:])
    tile_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1).reshape(
        S * per_row, q_tile)
    return tiles, tile_pos, jnp.repeat(
        jnp.arange(S, dtype=jnp.int32), per_row)


def pick_q_tile(Q: int, H: int, row_bytes: int, budget: int) -> int:
    """Query slots a tile holds, a function of the step's query bucket Q
    and of what one shard's kernel sees (heads, VMEM bytes a fused row):

      - at most the largest power of two whose Qt*H fused rows fit the VMEM
        ``budget``, and at most Q (a step whose query bucket is small,
        ``--spec-k``'s k + 1 slots a row, gets no larger tile than its rows
        can fill);
      - under that bound, the longest row's Q slots in 64 tiles, but no
        fewer than 256 fused rows a tile.  A page costs a tile about
        0.27 us + 3.3 ns a fused row (v5e, PERF.md PR 28), whether the rows
        hold queries or padding: a one-query decode row of a mixed step
        pays for the whole tile, a prompt cut into twice the tiles pays
        7-11 % more.  With 63 decode rows beside a 400-token prompt 8 slots
        took 2.3 ms where 16 took 3.8 (H = 32, MLA) and 1.9 where 32 took
        5.7 (GQA); a 2,048-token chunk alone wants the largest tile.

    The tile list needs no divisor of Q."""
    qt = 1
    while 2 * qt * H * row_bytes <= budget:
        qt *= 2
    return max(1, min(qt, max(Q // 64, 256 // H), Q))


def _pick_q_tile(Q: int, H: int, F: int, budget: int = 8 << 20) -> int:
    """``pick_q_tile`` with this kernel's VMEM bytes per fused row (Qt*H
    rows): the f32 accumulator + zero-expanded query pair (8*F bytes) PLUS
    the blocks whose minor dim lane-pads to 128 — the [rows, 1] i32
    position column, the [rows, D] q/out blocks (double buffered) and the
    [rows, block_size] f32 score/probability pair.  The padded terms
    dominate when F is small (a tp shard's F = KVH*D/tp): leaving them out
    let a 4096-row tile through at F=128, which the v5e compiler refused
    (16.17 MB of scoped VMEM)."""
    return pick_q_tile(Q, H, 8 * F + 3072, budget)


@functools.partial(
    jax.jit, static_argnames=("block_size", "num_kv_heads", "scale",
                              "soft_cap", "interpret", "q_tile"))
def flash_prefill_paged(
    qs: jax.Array,            # [S, Q, H, D] per-seq padded queries, or
                              # [NT, Qt, H, D] query tiles with ``tile_seq``
    q_pos: jax.Array,         # [S, Q] / [NT, Qt] i32 absolute positions
                              # (pad -> -1)
    k_cache: jax.Array,       # [L, num_slots, KVH*D] (or [num_slots, KVH*D])
    v_cache: jax.Array,
    block_tables: jax.Array,  # [S, B]
    seq_lens: jax.Array,      # [S]
    block_size: int,
    num_kv_heads: int,
    scale: float | None = None,
    soft_cap: float | None = None,
    layer: jax.Array | None = None,
    interpret: bool = False,
    q_tile: int | None = None,
    window: jax.Array | None = None,    # i32 scalar: keys a query sees
                                        # (itself included); None = all
    tile_seq: jax.Array | None = None,  # [NT] i32: the row of block_tables /
                                        # seq_lens each query tile belongs to
):
    """Attention outputs in the layout of ``qs`` (caches already written).

    With ``tile_seq`` the queries are the step's compact tile list
    (``ops.attention.gather_query_tiles``): all real slots of a tile belong
    to row ``tile_seq[n]``.  Without it they are the [S, Q] rectangle, cut
    here into ``q_tile`` slots a tile (rows padded up to a multiple)."""
    F = k_cache.shape[-1]
    if tile_seq is None:
        S, Q, H, D = qs.shape
        tiles, tile_pos, tile_seq = rectangle_as_tiles(
            qs, q_pos, q_tile if q_tile is not None
            else _pick_q_tile(Q, H, F))
        out = flash_prefill_paged(
            tiles, tile_pos, k_cache, v_cache, block_tables, seq_lens,
            block_size=block_size, num_kv_heads=num_kv_heads, scale=scale,
            soft_cap=soft_cap, layer=layer, interpret=interpret,
            window=window, tile_seq=tile_seq)
        return out.reshape(S, -1, H, D)[:, :Q]
    NT, Qt, H, D = qs.shape
    scale = scale if scale is not None else D ** -0.5
    if k_cache.ndim == 2:
        k_cache = k_cache[None]
        v_cache = v_cache[None]
    layer_arr = jnp.asarray([0 if layer is None else layer]
                            + ([] if window is None else [window]), jnp.int32)

    # Fused row space (slot-major, head-minor), shaped OUTSIDE the kernel so
    # Mosaic never sees a vector reshape.
    q_fused = qs.reshape(NT, Qt * H, D)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(NT,),
        in_specs=[
            pl.BlockSpec((1, Qt * H, D), lambda n, *_: (n, 0, 0),
                         memory_space=pltpu.VMEM),
            any_spec, any_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, Qt * H, D), lambda n, *_: (n, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, block_size, F), k_cache.dtype),
            pltpu.VMEM((2, block_size, F), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((Qt * H, 1), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel, block_size=block_size, num_heads=H,
        num_kv_heads=num_kv_heads, scale=scale, soft_cap=soft_cap,
        windowed=window is not None)
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((NT, Qt * H, D), qs.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables, seq_lens, layer_arr, tile_seq,
      q_pos.reshape(-1), q_fused, k_cache, v_cache)
    return out.reshape(NT, Qt, H, D)
