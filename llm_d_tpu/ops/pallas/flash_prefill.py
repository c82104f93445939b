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

The unit of work of the inner loop is (one KV-head group, one KEY BLOCK of
several pages), so that both dots have the shape of the MXU:

  - a tile's keys are walked a block of KB keys = KB / block_size pages at
    a time (``pick_key_block``: 512 keys for the cells' geometry).  The
    pages of a block are not contiguous in HBM: one DMA a page (K and V)
    lands them in consecutive row ranges of ONE [KB, F] VMEM buffer, double
    buffered by block and waited on together.  Scores are [rows, KB]
    (lane-dense), and the running max / sum / accumulator are corrected
    once a block.  The walk starts at the page of the first key the tile's
    first query sees; its last block is filled up with the row's last page
    again (masked by position), so every row of a walked block holds real
    cache rows of the sequence.
  - where the head size D is whole 128-lane tiles the K and V of KV head g
    are the static lane-aligned slice [:, g*D:(g+1)*D] of the block buffer:
    the wrapper lays the tile's queries out by KV head group ([NT, KVH,
    Qt*G, D], slot-major, head of the group minor) and the kernel runs, for
    each g, [Qt*G, D] x [D, KB] and [Qt*G, KB] x [KB, D] into a [Qt*G, D]
    accumulator.  Where it is not (``llama3-1b``, D = 64) the queries stay
    ONE unit of fused rows [Qt*H, D] (slot-major, head-minor) that the
    kernel zero-expands to [Qt*H, KVH*D] with one nonzero D-block per head
    (see paged_attention.py): one dot a block serves every head, values
    accumulate in folded space and are unfolded once at the end.  The
    choice is made from D (``dot_rows``).

Either way the rows are shaped OUTSIDE the kernel, so there are no vector
reshapes for Mosaic to reject, and the slots' positions arrive as NT*Qt
scalars that are spread to the rows in VMEM.

Causality bounds the walk per tile: pages past min(seq_len, max q-position
+ 1) are never streamed.  KV rows for the tokens being computed are
scattered into the cache by the caller BEFORE the kernel runs (write_kv):
this kernel only reads, so no aliasing contract is needed.
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
    q_ref,              # [1, KVH, Qt*G, D] grouped / [1, 1, Qt*H, D] expanded
    k_hbm, v_hbm,
    # outputs
    o_ref,
    # scratch
    k_buf, v_buf,       # [2, KB, F]: a key block each, double buffered
    sems, qpos_buf,
    *,
    block_size: int,
    num_heads: int,
    num_kv_heads: int,
    scale: float,
    soft_cap: float | None,
    windowed: bool,
):
    s = tile_seq_ref[pl.program_id(0)]
    U, R, D = q_ref.shape[1:]                 # U dots a block, of R rows
    H = num_heads
    KVH = num_kv_heads
    F = KVH * D
    grouped = U == KVH
    bs = block_size
    KB = k_buf.shape[1]
    P = KB // bs                              # pages a key block
    li = layer_ref[0]
    seq_len = seq_lens_ref[s]

    # Rows of a slot: its G heads of one KV group, or all H of them.
    q_pos = slot_positions(
        tile_pos_ref, qpos_buf, H // KVH if grouped else H)   # [R, 1] i32
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
    # The walk: key blocks of P pages from page ``first`` on.  The last is
    # filled up with the row's last page again (a dead read of real rows,
    # masked by position): no row of a walked block is left as the buffer
    # held it, so p = 0 never meets a NaN in the p v dot, and the table is
    # never read past the row's pages.
    n_blocks = pl.cdiv(n_pages - first, P)

    def block_dma(slot, i, act):
        """``act`` ("start" / "wait") the 2 P page copies of block ``i``."""
        def page(p, _):
            j = jnp.minimum(first + i * P + p, n_pages - 1)
            src = pl.ds(pl.multiple_of(block_tables_ref[s, j] * bs, bs), bs)
            dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
            for side, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                               (v_hbm, v_buf))):
                getattr(pltpu.make_async_copy(
                    hbm.at[li, src], buf.at[slot, dst],
                    sems.at[slot, side]), act)()
            return _
        jax.lax.fori_loop(0, P, page, 0)

    @pl.when(n_blocks > 0)
    def _():
        block_dma(0, 0, "start")

    if grouped:
        # One KV-head group a dot: its K and V are a lane-aligned D-slice
        # of the block, its queries the [R, D] rows the wrapper laid out.
        lanes = [pl.ds(g * D, D) for g in range(KVH)]
        qs = [(q_ref[0, g].astype(jnp.float32) * scale).astype(jnp.bfloat16)
              for g in range(KVH)]
    else:
        # Zero-expanded queries in fused row space: row r belongs to head
        # r % H, nonzero only in that head's KV D-block; one dot over the
        # whole row width serves every head.
        q = q_ref[0, 0].astype(jnp.float32) * scale           # [R, D]
        q_rep = jnp.concatenate([q] * KVH, axis=1)            # [R, F]
        col_kv = jax.lax.broadcasted_iota(jnp.int32, (R, F), 1) // D
        row_kv = (jax.lax.broadcasted_iota(jnp.int32, (R, F), 0) % H) // (
            H // KVH)
        block_mask = (col_kv == row_kv).astype(jnp.float32)   # [R, F]
        lanes = [slice(None)]
        qs = [(q_rep * block_mask).astype(jnp.bfloat16)]

    def body(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _():
            block_dma((i + 1) % 2, i + 1, "start")

        block_dma(slot, i, "wait")

        key_pos = (first + i * P) * bs + jax.lax.broadcasted_iota(
            jnp.int32, (1, KB), 1)                            # [1, KB]
        valid = (key_pos <= q_pos) & (key_pos < seq_len)      # [R, KB]
        if windowed:
            valid &= key_pos > q_pos - window
        out = []
        for q_u, cols, (m, l, acc) in zip(qs, lanes, carry):
            # bf16 operands, f32 accumulation: 2x MXU rate and no VPU
            # convert of the block (the flash statistics stay f32).
            k = k_buf[slot, :, cols]                          # [KB, W] bf16
            v = v_buf[slot, :, cols]
            s_hb = jax.lax.dot_general(
                q_u, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # [R, KB]
            if soft_cap is not None:
                s_hb = soft_cap * jnp.tanh(s_hb / soft_cap)
            s_hb = jnp.where(valid, s_hb, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s_hb, axis=-1, keepdims=True))
            p = jnp.exp(s_hb - m_new)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [R, W]
            out.append((m_new, l_new, acc * corr + pv))
        return tuple(out)

    W = D if grouped else F
    init = tuple((
        jnp.full((R, 1), -1e29, jnp.float32),
        jnp.zeros((R, 1), jnp.float32),
        jnp.zeros((R, W), jnp.float32),
    ) for _ in qs)
    stats = jax.lax.fori_loop(0, n_blocks, body, init)
    if grouped:
        for g, (m, l, acc) in enumerate(stats):
            o_ref[0, g] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        return
    (m, l, acc), = stats
    masked = acc * block_mask                                 # [R, F]
    out = masked[:, 0:D]
    for kk in range(1, KVH):
        out = out + masked[:, kk * D:(kk + 1) * D]
    out = out / jnp.maximum(l, 1e-30)
    o_ref[0, 0] = out.astype(o_ref.dtype)


def slot_positions(tile_pos_ref, qpos_buf, num_heads: int):
    """This tile's query positions as a [Qt*num_heads, 1] column (a slot's
    ``num_heads`` rows one after the other: the fused row space, or one KV
    head group's rows), spread from its Qt scalars in SMEM ([NT*Qt], flat: a
    second dimension would lane-pad there) through a VMEM scratch.  (As an input
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
        fewer than 256 fused rows a tile.  A step of the inner loop costs a
        tile the same whether its rows hold queries or padding (the MLA
        kernel's page about 0.27 us + 3.3 ns a fused row, v5e, PERF.md
        PR 28): a one-query decode row of a mixed step pays for the whole
        tile, a prompt cut into twice the tiles pays more for the same
        keys.  With 63 decode rows beside a 400-token prompt 8 slots took
        2.3 ms where 16 took 3.8 (H = 32, MLA) and, over key blocks, 0.60
        where 16 took 0.67 and 4 took 0.78 (GQA, PERF.md PR 30); a
        2,048-token chunk alone wants the largest tile.

    The tile list needs no divisor of Q."""
    qt = 1
    while 2 * qt * H * row_bytes <= budget:
        qt *= 2
    return max(1, min(qt, max(Q // 64, 256 // H), Q))


def _pick_q_tile(Q: int, H: int, F: int, budget: int = 8 << 20) -> int:
    """``pick_q_tile`` with the VMEM bytes per fused row (Qt*H rows) that do
    not depend on the key block, counted for the zero-expanded body, the
    larger of the two: the f32 accumulator, the p v product and the
    zero-expanded bf16 queries (8*F bytes and change), PLUS the blocks whose
    minor dim lane-pads to 128: the [rows, 1] i32 position column, the f32
    running max / sum columns and the [rows, D] q / out blocks (double
    buffered).  The grouped body (D a multiple of 128) holds [rows, D]
    accumulators, a KVH-th of that.  The padded terms dominate when F is
    small (a tp shard's F = KVH*D/tp): leaving them out let a 4096-row tile
    through at F=128, which the v5e compiler refused (16.17 MB of scoped
    VMEM).  What grows with the key block ([rows, KB] score / probability
    tiles, the [2, KB, F] K and V buffers) is ``pick_key_block``'s to fit."""
    return pick_q_tile(Q, H, 8 * F + 3072, budget)


def pick_key_block(block_size: int, F: int, rows: int,
                   budget: int = 4 << 20, most: int = 512) -> int:
    """Keys one step of the kernel's inner loop covers, a whole number of
    pages: ``block_size`` doubled while it stays within ``most`` keys and
    what a key costs in VMEM fits ``budget``: a row of the double-buffered
    K and V blocks (8*F bytes) and a column of one dot's score tiles (14
    bytes a row of the dot, Qt*G rows grouped, Qt*H zero-expanded: f32
    scores, f32 probabilities, their bf16 copy, the mask).  512 keys for
    the cells' 32 / 4 x 128 heads at 8 to 32 slots a tile, 128 for
    ``llama3-1b``'s 1,024 zero-expanded rows.  ``most``: on the v5e a
    2,045-token chunk's call over 12,288 keys took 9.70 / 6.71 / 4.78 ms at
    128 / 256 / 512 keys a block (the flash statistics are corrected once a
    block), while a mixed step's short rows gain nothing past 256 and a
    longer block wastes more keys past the causal diagonal and before the
    window (PERF.md PR 30)."""
    kb = block_size
    while 2 * kb <= most and 2 * kb * (8 * F + 14 * rows) <= budget:
        kb *= 2
    return kb


def dot_rows(q_tile: int, H: int, KVH: int, D: int) -> int:
    """Rows of one dot of the kernel for a tile of ``q_tile`` slots: one KV
    head's group of G = H / KVH heads where the head size is whole lane
    tiles (the K and V of a group are then a lane-aligned slice of the
    cache row), all H heads zero-expanded over the row otherwise."""
    return q_tile * (H // KVH if D % 128 == 0 or KVH == 1 else H)


@functools.partial(
    jax.jit, static_argnames=("block_size", "num_kv_heads", "scale",
                              "soft_cap", "interpret", "q_tile", "key_block"))
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
    key_block: int | None = None,       # keys a step of the inner loop, a
                                        # multiple of block_size; None: by
                                        # the shapes (``pick_key_block``)
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
            window=window, tile_seq=tile_seq, key_block=key_block)
        return out.reshape(S, -1, H, D)[:, :Q]
    NT, Qt, H, D = qs.shape
    KVH = num_kv_heads
    scale = scale if scale is not None else D ** -0.5
    if k_cache.ndim == 2:
        k_cache = k_cache[None]
        v_cache = v_cache[None]
    layer_arr = jnp.asarray([0 if layer is None else layer]
                            + ([] if window is None else [window]), jnp.int32)

    # The rows of each dot, laid out OUTSIDE the kernel so Mosaic never sees
    # a vector reshape: by KV head group [KVH, Qt*G, D] (slot-major, head of
    # the group minor), or ONE unit of the fused rows [Qt*H, D] (slot-major,
    # head-minor) that the kernel zero-expands.
    R = dot_rows(Qt, H, KVH, D)
    U = Qt * H // R
    q_units = qs.reshape(NT, Qt, U, H // U, D).swapaxes(1, 2).reshape(
        NT, U, R, D)
    KB = key_block if key_block is not None else pick_key_block(
        block_size, F, R)
    if KB % block_size:
        raise ValueError(f"key_block={KB} must be whole pages of "
                         f"{block_size} keys")

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(NT,),
        in_specs=[
            pl.BlockSpec((1, U, R, D), lambda n, *_: (n, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            any_spec, any_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, U, R, D), lambda n, *_: (n, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, KB, F), k_cache.dtype),
            pltpu.VMEM((2, KB, F), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((R, 1), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel, block_size=block_size, num_heads=H,
        num_kv_heads=KVH, scale=scale, soft_cap=soft_cap,
        windowed=window is not None)
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((NT, U, R, D), qs.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables, seq_lens, layer_arr, tile_seq,
      q_pos.reshape(-1), q_units, k_cache, v_cache)
    return out.reshape(NT, U, Qt, H // U, D).swapaxes(1, 2).reshape(
        NT, Qt, H, D)
