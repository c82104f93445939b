"""Pallas TPU flash-prefill kernel for MLA's single latent buffer.

The MLA prefill previously attended via the chunked XLA path
(``ragged_paged_attention_chunked``), materializing [S, Q, H, kv_chunk]
f32 score tensors in HBM — measured 5-10% MFU on the MoE bench while the
dense Pallas prefill reached ~30% (round-4 chip record, pre-PR-1; round-4 verdict Weak #4).
This kernel runs the flash recurrence in VMEM like
``ops.pallas.flash_prefill``, specialized to weight-absorbed MLA
(reference role: FlashInfer's prefill kernels behind vLLM MLA,
/root/reference/docker/Dockerfile.cuda:57-58):

  - MQA, not GQA: every head scores against the SAME latent row
    (KVH = 1), so there is no zero-expansion trick — the fused-row query
    tile [Qt*H, F] hits the key block in one MXU dot.
  - ONE block buffer: the latent rows serve BOTH the score dot and the
    value dot (values are the row's first kv_lora_rank columns; we
    accumulate over the full padded F and let the caller slice), exactly
    the single-DMA pattern of ``mla_attention.py``'s decode kernel —
    half the DMA traffic of reusing the dense prefill kernel with
    v_cache aliased to k_cache.

The grid walks the step's compact LIST of query tiles (Qt query slots of
one sequence each; ``tile_seq[n]`` names tile n's sequence row), not the
padded [S bucket x Q bucket] rectangle: see ``flash_prefill.py``.  The
rectangle call ``mla_flash_prefill(qs [S, Q, H, F], q_pos [S, Q], ...)`` is
the special case ``tile_seq = repeat(arange(S), Q / Qt)`` of the same body.

The unit of work of the inner loop is one KEY BLOCK of several pages, so
that both dots have the shape of the MXU (as ``flash_prefill.py``'s, PR 30):
a tile's keys are walked KB keys = KB / block_size pages at a time
(``_pick_key_block``: 256 keys for ``kanana-2-30b-a3b``'s 8 x 32 fused rows).
The pages of a block are not contiguous in HBM: one DMA a page lands them
in consecutive row ranges of ONE [KB, F] VMEM buffer, double buffered by
block and waited on together.  Scores are [Qt*H, KB] (lane-dense), and the
running max / sum / [Qt*H, F] accumulator are corrected once a block.  The
last block of a walk is filled up with the row's last page again (masked by
position), so every row of a walked block holds real cache rows of the
sequence.

The block changes the SHAPE of the work, not its rounding.  A flash loop
that weighs a whole block against one max rounds the probabilities to bf16
at another scale than a loop a page a step: the same answer to 1e-3, but
a fifth of the result's elements an ulp apart, where this kernel and the
decode kernel (``mla_attention.py``, a page a step) had been bit-equal; in
``kanana-2-30b-a3b``, whose sigmoid routing flips on an ulp, that put
decode-against-prefill |d logprob| at 0.045 of the benchmark's 0.05 (PERF.md
PR 35).  So every key is weighed against the running max at the END OF ITS
OWN PAGE (``_max_to_page_end``: the bf16 probabilities are the page loop's,
bit for bit), and the factor that carries a page to the block's max, which
the page loop applied to its f32 accumulator, multiplies the rounded
probability in f32 and goes through the MXU as THREE bf16 terms (24 bits
of the product; one dot over all three, the block loaded once).  One of
2,800 elements then differs from the decode kernel's result (what is left
is the order of the f32 sums) and the check reads 0.004-0.011 again; two
terms left one of 1,100 and the check's rows chose another token than
decode in 2-5 of 12 positions where the page loop's chose it in 0-2.

Causality bounds the walk per tile; pad query slots carry position -1 and
produce zeros.  KV rows for the tokens being computed are scattered by the
caller (write_kv) BEFORE the kernel runs — read-only, no aliasing contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_d_tpu.ops.pallas.flash_prefill import (
    rectangle_as_tiles, slot_positions)

NEG_INF = -1e30


def _mla_prefill_kernel(
    # scalar prefetch
    block_tables_ref,   # [S, B] SMEM
    seq_lens_ref,       # [S]    SMEM
    layer_ref,          # [1]    SMEM
    tile_seq_ref,       # [NT]   SMEM: the sequence row of each query tile
    tile_pos_ref,       # [NT*Qt] SMEM: position of each query slot (pad -1)
    # inputs
    q_ref, kv_hbm,
    # outputs
    o_ref,
    # scratch
    kv_buf,             # [2, KB, F]: a key block, double buffered
    sems, qpos_buf,
    *,
    block_size: int,
    num_heads: int,
    scale: float,
):
    s = tile_seq_ref[pl.program_id(0)]
    bs = block_size
    KB = kv_buf.shape[1]
    P = KB // bs                              # pages a key block
    li = layer_ref[0]
    seq_len = seq_lens_ref[s]

    q_pos = slot_positions(tile_pos_ref, qpos_buf, num_heads)  # [R, 1] i32
    qmax = jnp.max(q_pos)
    # Causal bound: keys at positions > qmax never score for this tile.
    live = jnp.minimum(seq_len, qmax + 1)
    n_pages = pl.cdiv(jnp.maximum(live, 0), bs)
    # The walk: key blocks of P pages.  The last is filled up with the
    # row's last page again (a dead read of real rows, masked by position):
    # no row of a walked block is left as the buffer held it, so p = 0 never
    # meets a NaN in the p v dot, and the table is never read past the
    # row's pages.
    n_blocks = pl.cdiv(n_pages, P)

    def block_dma(slot, i, act):
        """``act`` ("start" / "wait") the P page copies of block ``i``."""
        def page(p, _):
            j = jnp.minimum(i * P + p, n_pages - 1)
            src = pl.ds(pl.multiple_of(block_tables_ref[s, j] * bs, bs), bs)
            dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
            getattr(pltpu.make_async_copy(
                kv_hbm.at[li, src], kv_buf.at[slot, dst],
                sems.at[slot]), act)()
            return _
        jax.lax.fori_loop(0, P, page, 0)

    @pl.when(n_blocks > 0)
    def _():
        block_dma(0, 0, "start")

    # bf16 operands, f32 accumulation (flash statistics stay f32).
    q2 = (q_ref[0].astype(jnp.float32) * scale).astype(jnp.bfloat16)
    R, F = q_ref.shape[1], q_ref.shape[2]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, KB), 1)

    def body(i, carry):
        m, l, acc = carry
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _():
            block_dma((i + 1) % 2, i + 1, "start")

        block_dma(slot, i, "wait")
        kv = kv_buf[slot]                                     # [KB, F] bf16
        s_hb = jax.lax.dot_general(
            q2, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [R, KB]
        key_pos = i * KB + col                                # [1, KB]
        valid = (key_pos <= q_pos) & (key_pos < seq_len)      # [R, KB]
        s_hb = jnp.where(valid, s_hb, NEG_INF)
        return weigh_key_block(s_hb, kv, col, bs, m, l, acc)

    init = (
        jnp.full((R, 1), -1e29, jnp.float32),
        jnp.zeros((R, 1), jnp.float32),
        jnp.zeros((R, F), jnp.float32),
    )
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def weigh_key_block(s_hb, kv, col, page: int, m, l, acc):
    """One key block of the flash recurrence, rounded as a loop a page a
    step rounds: ``s_hb`` [R, KB] the block's masked f32 scores, ``kv``
    [KB, F] its bf16 rows (keys and values at once), ``col`` [1, KB] the
    column index, ``m`` / ``l`` [R, 1] and ``acc`` [R, F] the f32
    statistics before the block; returns them after it.  The ONE body of
    both MLA kernels (this file's and ``mla_attention.py``'s decode kernel),
    so that the two round alike whatever block either picks."""
    R = s_hb.shape[0]
    # Every key is weighed against the running max at the END OF ITS
    # PAGE, as a loop a page a step does: the probabilities round to the
    # same bf16 there and here.
    ref, m_new = _max_to_page_end(s_hb, col, page, m)         # [R, KB]
    p = jnp.exp(s_hb - ref)
    # ... and carried to the block's max in f32 afterwards, as that
    # loop's corrections of the accumulator did: c p in three bf16
    # terms, f32's 24 bits.
    c = jnp.exp(ref - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p * c, axis=-1, keepdims=True)
    w = p.astype(jnp.bfloat16).astype(jnp.float32) * c
    terms = []
    for _ in range(3):
        terms.append(w.astype(jnp.bfloat16))
        w = w - terms[-1].astype(jnp.float32)
    # Value dot on the SAME block buffer — no second DMA; the terms
    # stream through one load of the block.
    pv = jax.lax.dot_general(
        jnp.concatenate(terms, axis=0), kv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                   # [3 R, F]
    acc_new = acc * corr + (pv[:R] + pv[R:2 * R] + pv[2 * R:])
    return m_new, l_new, acc_new


def _max_to_page_end(x, col, page: int, before):
    """``x`` [R, KB] -> (in every column the max of ``before`` [R, 1] and of
    ``x`` over the columns up to the end of that column's page of ``page``
    keys, that max at the block's end [R, 1]); ``col`` [1, KB] is the
    column index.  It is what a running max taken a page at a time holds
    while it weighs that page.  One masked lane reduction a page, on the
    page's own 128-lane tile where pages tile the lanes."""
    KB = x.shape[1]
    wide = 128 if KB % 128 == 0 and 128 % page == 0 else KB
    out = []
    for t in range(0, KB, wide):
        xt, ct = x[:, t:t + wide], col[:, t:t + wide]
        ends = range(t + page, t + wide, page)      # of all pages but the last
        upto = [ct < e for e in ends]
        runs = [jnp.maximum(before, jnp.max(
            jnp.where(u, xt, NEG_INF), axis=-1, keepdims=True)) for u in upto]
        before = jnp.maximum(before, jnp.max(xt, axis=-1, keepdims=True))
        ref = jnp.broadcast_to(before, xt.shape)
        for u, run in zip(reversed(upto), reversed(runs)):
            ref = jnp.where(u, run, ref)
        out.append(ref)
    return (out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)), before


def _pick_q_tile(Q: int, H: int, F: int, budget: int = 3 << 20) -> int:
    """Query slots a tile holds, by the rule of ``flash_prefill.pick_q_tile``
    with this kernel's own figures:

      - at most the largest power of two whose Qt*H fused rows fit the VMEM
        ``budget`` at 8*F bytes a row, the f32 accumulator + query pair
        (~3 MB, tighter than the dense prefill's: the MLA row F is wide, 640
        for V3, and at the bench shape H=16/F=640 a 6 MB tile put the scoped
        stack 0.4 MB over the 16 MB limit; what grows with the key block is
        ``_pick_key_block``'s to fit), and at most Q;
      - under that bound, the longest row's Q slots in 128 tiles, but no
        fewer than 128 fused rows a tile, one pass of the MXU's height.
        Over key blocks a step of the inner loop costs a tile by its rows,
        queries or padding: a one-query decode row of a mixed step pays for
        the whole tile in every block it walks, a prompt cut into twice the
        tiles pays more for the same keys.  On the v5e (one layer's call, H
        = 32, F = 640, 256 keys a block, 63 decode rows of a mean 670-token
        context beside a 128- / 320- / 512-token prompt; PERF.md PR 35): 4
        slots a tile 0.53 / 0.72 / 1.02 ms, 8 slots 0.75 / 0.90 / 1.14; and
        with two carry terms, a tenth cheaper, 2 slots 0.41 / 0.66 / 1.02,
        4 slots 0.47 / 0.64 / 0.91, 8 slots 0.64 / 0.78 / 0.99, 16 slots
        1.07 / 1.19 / 1.38.  (A page a step, the loop before key blocks,
        paid 0.27 us + 3.3 ns a fused row a page, and 8 slots were its
        best: 0.74 / 1.16 / 1.91 at 2, 0.94 / 1.24 / 1.78 at 4, 1.35 / 1.58
        / 2.00 at 8, 2.35 / 2.56 / 2.94 at 16.)"""
    qt = 1
    while 2 * qt * H * 8 * F <= budget:
        qt *= 2
    return max(1, min(qt, max(Q // 128, 128 // H), Q))


def _pick_key_block(block_size: int, F: int, rows: int,
                    budget: int = 5 << 20, most: int = 256) -> int:
    """Keys one step of the kernel's inner loop covers, a whole number of
    pages, by the rule of ``flash_prefill.pick_key_block``: ``block_size``
    doubled while it stays within ``most`` keys and what a key costs in
    VMEM fits ``budget``: a row of the ONE double-buffered [KB, F] bf16
    block (4*F bytes) and a column of the score tiles (32 bytes a fused
    row: f32 scores, page maxima, probabilities, carry factors and their
    product, its three bf16 terms twice over).  256 keys for
    ``kanana-2-30b-a3b``'s 4 x 32 fused rows, for a 2,048-token chunk's 16
    x 32 and for a tp-4 shard's 8 heads; every tile ``_pick_q_tile`` hands
    out (to 512 fused rows at F = 640, H = 8 / 16 / 32 / 128) compiles for
    the v5e at 128 to 512 keys a block.

    ``most``, on the v5e (one layer's call, H = 32, F = 640, pages of 32;
    ms at 32 = a page a step, the loop before key blocks / 128 / 256 / 512
    keys, with two carry terms, a tenth cheaper than the three the kernel
    has; PERF.md PR 35): 63 decode rows of a mean 670-token context beside
    a 128-token prompt 1.35 / 0.78 / 0.64 / 0.65, beside 320 tokens 1.58 /
    0.94 / 0.78 / 0.81, beside 512 tokens 2.00 / 1.19 / 0.99 / 1.00 (8
    slots a tile); a 2,048-token chunk alone 8.05 / 4.22 / 3.16 / 2.86 and
    one ending at context 8,192 53.1 / 25.7 / 17.9 / 14.7 (16 slots a tile).
    A mixed step's short rows gain nothing past 256 and a longer block
    walks more keys past the context and the causal diagonal; a long chunk
    would take 512 (9 to 18 %), which no cell measures yet."""
    kb = block_size
    while 2 * kb <= most and 2 * kb * (4 * F + 32 * rows) <= budget:
        kb *= 2
    return kb


@functools.partial(
    jax.jit, static_argnames=("block_size", "scale", "interpret", "q_tile",
                              "key_block"))
def mla_flash_prefill(
    qs: jax.Array,            # [S, Q, H, F] per-seq padded absorbed queries,
                              # or [NT, Qt, H, F] query tiles with ``tile_seq``
    q_pos: jax.Array,         # [S, Q] / [NT, Qt] i32 absolute positions
                              # (pad -> -1)
    kv_cache: jax.Array,      # [L, num_slots, F] (or [num_slots, F])
    block_tables: jax.Array,  # [S, B]
    seq_lens: jax.Array,      # [S]
    block_size: int,
    scale: float,
    layer: jax.Array | None = None,
    interpret: bool = False,
    q_tile: int | None = None,
    tile_seq: jax.Array | None = None,   # [NT] i32: the row of block_tables /
                                         # seq_lens each query tile belongs to
    key_block: int | None = None,        # keys a step of the inner loop, a
                                         # multiple of block_size; None: by
                                         # the shapes (``_pick_key_block``)
):
    """Attended latent rows in the layout of ``qs`` (cache already
    written).

    With ``tile_seq`` the queries are the step's compact tile list
    (``ops.attention.gather_query_tiles``): all real slots of a tile belong
    to row ``tile_seq[n]``.  Without it they are the [S, Q] rectangle, cut
    here into ``q_tile`` slots a tile (rows padded up to a multiple).

    The caller slices the first ``kv_lora_rank`` columns (attended values)
    and absorbs W_uv, exactly as with the chunked path."""
    if tile_seq is None:
        S, Q, H, F = qs.shape
        tiles, tile_pos, tile_seq = rectangle_as_tiles(
            qs, q_pos, q_tile if q_tile is not None
            else _pick_q_tile(Q, H, F))
        out = mla_flash_prefill(
            tiles, tile_pos, kv_cache, block_tables, seq_lens,
            block_size=block_size, scale=scale, layer=layer,
            interpret=interpret, tile_seq=tile_seq, key_block=key_block)
        return out.reshape(S, -1, H, F)[:, :Q]
    NT, Qt, H, F = qs.shape
    if kv_cache.ndim == 2:
        kv_cache = kv_cache[None]
    assert kv_cache.shape[2] == F, (kv_cache.shape, F)
    layer_arr = jnp.asarray([0 if layer is None else layer], jnp.int32)
    KB = key_block if key_block is not None else _pick_key_block(
        block_size, F, Qt * H)
    if KB % block_size:
        raise ValueError(f"key_block={KB} must be whole pages of "
                         f"{block_size} keys")

    # Fused row space (slot-major, head-minor), shaped OUTSIDE the kernel so
    # Mosaic never sees a vector reshape.
    q_fused = qs.reshape(NT, Qt * H, F)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(NT,),
        in_specs=[
            pl.BlockSpec((1, Qt * H, F), lambda n, *_: (n, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, Qt * H, F), lambda n, *_: (n, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, KB, F), kv_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((Qt * H, 1), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _mla_prefill_kernel, block_size=block_size, num_heads=H, scale=scale)
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((NT, Qt * H, F), qs.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables, seq_lens, layer_arr, tile_seq,
      q_pos.reshape(-1), q_fused, kv_cache)
    return out.reshape(NT, Qt, H, F)
