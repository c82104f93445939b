"""Pallas TPU decode kernel for MLA (single latent cache buffer).

The generic decode kernel (paged_attention.py) carries separate K and V
buffers; MLA attends queries against ONE [slots, F] latent row per token
(F = kv_lora_rank + rope, lane-padded) where the attended "values" are the
same rows — so this kernel streams each page once, uses it for both the
score dot and the value dot, and writes the new token's row back into its
(already resident) page.  All H heads share the row (MQA): scores come
from one [H, F] x [F, bs] MXU dot per page, no GQA zero-expansion needed.

Sequence grouping mirrors paged_attention.py: each grid program owns G
sequences (launch overhead inside the fused decode scan is ~45 us + ~3 us
per program; one-sequence programs made that ~70% of dense decode step time
before grouping).  The auto pick budgets VMEM for both the page double
buffer (2*bs*F per sequence) and the f32 accumulator+query pair
(8*H*F per sequence — DeepSeek's H=128 makes this the binding term).

``kv_cache_dtype=int8`` (the latent-row cache): the page payload is int8
and each page's per-row f32 scales ([bs, SW], SW = 1 for the latent — one
symmetric scale per 576-wide ``c_kv | k_pe`` row) ride a parallel DMA
chain from the sibling scale plane; the page is dequantized in VMEM right
after the DMA and BOTH dots (score and value — the two weight-absorption
consumers) read the dequantized bf16 page, so the flash recurrence itself
is unchanged.  The new token's pre-quantized row + scale splice into the
resident pages and ride the same whole-page write-back.  This halves the
dominant MoE-decode byte term: the latent stream is the only per-step
byte cost that grows with batch and context.

This is the DeepSeek-decode hot op the reference gets from vLLM's MLA CUDA
kernels; the chunked XLA path remains the CPU/odd-shape fallback.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from llm_d_tpu.ops.pallas.paged_attention import pick_seq_group
from llm_d_tpu.ops.pallas.quant_util import make_page_dequant

NEG_INF = -1e30

_GROUP_VMEM_BUDGET = 6 << 20


def _mla_decode_kernel(
    # scalar prefetch
    block_tables_ref,   # [S, B] SMEM
    seq_lens_ref,       # [S]    SMEM (context length INCLUDING the new token)
    layer_ref,          # [1]    SMEM (layer plane of the stacked cache)
    # inputs / outputs / scratch — layout depends on ``quantized``:
    #   bf16: q, rn, kv_hbm | o, kv_out | kv_buf, sems, wsems
    #   int8: q, rn, rsn, kv_hbm, ks_hbm | o, kv_out, ks_out
    #         | kv_buf, ks_buf, sems, wsems
    # (rsn is the new rows' [G, 1, SW] f32 scales; ks the [L, slots, SW]
    #  scale plane riding next to the int8 latent payload.)
    *refs,
    block_size: int,
    scale: float,
    group: int,
    quantized: bool,
):
    if quantized:
        (q_ref, rn_ref, rsn_ref, kv_hbm, ks_hbm,
         o_ref, kv_out, ks_out,
         kv_buf, ks_buf, sems, wsems) = refs
    else:
        (q_ref, rn_ref, kv_hbm,
         o_ref, kv_out, kv_buf, sems, wsems) = refs
    i = pl.program_id(0)
    G = group
    H, F = q_ref.shape[1], q_ref.shape[2]
    bs = block_size
    li = layer_ref[0]
    base = i * G

    seq_len_g = [seq_lens_ref[base + g] for g in range(G)]
    n_pages_g = [pl.cdiv(sl, bs) for sl in seq_len_g]
    n_max = n_pages_g[0]
    for g in range(1, G):
        n_max = jnp.maximum(n_max, n_pages_g[g])
    write_page_g = [(sl - 1) // bs for sl in seq_len_g]
    w_row_g = [(sl - 1) % bs for sl in seq_len_g]

    def page_dma(slot, j):
        copies = []
        for g in range(G):
            # Clamped dead re-read for sequences out of pages (and pad rows).
            jj = jnp.clip(j, 0, jnp.maximum(n_pages_g[g] - 1, 0))
            b = block_tables_ref[base + g, jj]
            start = pl.multiple_of(b * bs, bs)
            copies.append(pltpu.make_async_copy(
                kv_hbm.at[li, pl.ds(start, bs)], kv_buf.at[slot, g],
                sems.at[slot, g, 0]))
            if quantized:
                copies.append(pltpu.make_async_copy(
                    ks_hbm.at[li, pl.ds(start, bs)], ks_buf.at[slot, g],
                    sems.at[slot, g, 1]))
        return copies

    @pl.when(n_max > 0)
    def _():
        for dma in page_dma(0, 0):
            dma.start()

    q = q_ref[...].astype(jnp.float32) * scale                # [G, H, F]
    row_ids2 = jax.lax.broadcasted_iota(jnp.int32, (bs, F), 0)
    # Per-group seq_len plane for score masking (iota/select chain — Mosaic
    # has no scalar-vector stack/reshape).
    g_ids = jax.lax.broadcasted_iota(jnp.int32, (G, 1, bs), 0)
    sl_arr = jnp.zeros((G, 1, bs), jnp.int32)
    for g in range(G):
        sl_arr = jnp.where(g_ids == g, seq_len_g[g], sl_arr)

    if quantized:
        SW = rsn_ref.shape[2]
        row_ids_sw = jax.lax.broadcasted_iota(jnp.int32, (bs, SW), 0)
        dequant = make_page_dequant(SW, F)

    def wb_copies(g):
        """The (re-constructible) write-back descriptors for group g."""
        wp = write_page_g[g]
        b = block_tables_ref[base + g, jnp.maximum(wp, 0)]
        start = pl.multiple_of(b * bs, bs)
        copies = [pltpu.make_async_copy(
            kv_buf.at[wp % 2, g], kv_out.at[li, pl.ds(start, bs)],
            wsems.at[g, 0])]
        if quantized:
            copies.append(pltpu.make_async_copy(
                ks_buf.at[wp % 2, g], ks_out.at[li, pl.ds(start, bs)],
                wsems.at[g, 1]))
        return copies

    def body(j, carry):
        m, l, acc = carry
        slot = j % 2

        @pl.when(j + 1 < n_max)
        def _():
            # Before an inbound page DMA reuses (slot, g), consume any
            # still-flying write-back FROM that buffer (started at
            # j == wp_g, reused for page wp_g + 2).  Pad rows (seq_len 0
            # -> wp_g = -1) never STARTED a write: waiting their
            # never-signaled semaphore would deadlock the kernel.
            for g in range(G):
                @pl.when((write_page_g[g] >= 0)
                         & (j == write_page_g[g] + 1))
                def _(g=g):
                    for w in wb_copies(g):
                        w.wait()
            for dma in page_dma((j + 1) % 2, j + 1):
                dma.start()

        for dma in page_dma(slot, j):
            dma.wait()

        # On each sequence's write page (exactly once per call): splice the
        # new latent row (and, quantized, its scale) into the resident
        # page(s) and START the page write-back — the wait happens at slot
        # reuse (above) or after the loop, so the write flies UNDER the
        # score/value dots instead of stalling every group serially (decode
        # writes land on the LAST page, so in the common case all waits
        # coalesce after the loop).
        for g in range(G):
            @pl.when(j == write_page_g[g])
            def _(g=g):
                is_wr = row_ids2 == w_row_g[g]
                kv_buf[slot, g] = jnp.where(is_wr, rn_ref[g], kv_buf[slot, g])
                if quantized:
                    is_wr_s = row_ids_sw == w_row_g[g]
                    ks_buf[slot, g] = jnp.where(
                        is_wr_s, rsn_ref[g], ks_buf[slot, g])
                for w in wb_copies(g):
                    w.start()

        # bf16 operands, f32 accumulation: 2x MXU rate, no VPU convert of
        # the page (see paged_attention.py's decode kernel).  Int8 pages
        # pay one VPU dequant pass right here — half the page DMA bytes
        # dominate in the byte-bound decode regime.
        if quantized:
            page = dequant(kv_buf[slot], ks_buf[slot])        # [G, bs, F]
        else:
            page = kv_buf[slot]                               # [G, bs, F] bf16
        s_hb = jax.lax.dot_general(
            q.astype(jnp.bfloat16), page, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)               # [G, H, bs]
        key_pos = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (G, 1, bs), 2)
        s_hb = jnp.where(key_pos < sl_arr, s_hb, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_hb, axis=-1, keepdims=True))
        p = jnp.exp(s_hb - m_new)                             # [G, H, bs]
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(jnp.bfloat16), page, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)               # [G, H, F]
        acc_new = acc * corr + pv
        return m_new, l_new, acc_new

    init = (jnp.full((G, H, 1), -1e29, jnp.float32),
            jnp.zeros((G, H, 1), jnp.float32),
            jnp.zeros((G, H, F), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, n_max, body, init)
    # Consume write-backs whose slot was never reused in-loop (every
    # started DMA must be waited before the kernel ends): started at
    # wp_g >= 0, in-loop wait only ran when wp_g + 2 < n_max.
    for g in range(G):
        @pl.when((write_page_g[g] >= 0)
                 & (write_page_g[g] + 2 >= n_max))
        def _(g=g):
            for w in wb_copies(g):
                w.wait()
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_size", "scale", "interpret", "seq_group"))
def mla_paged_decode_update(
    q_eff: jax.Array,         # [S, H, F] absorbed queries
    row_new: jax.Array,       # [S, F] new latent rows (one per sequence;
                              #        PRE-QUANTIZED int8 when kv_scale given)
    kv_cache: jax.Array,      # [L, num_slots, F] (or [num_slots, F])
    block_tables: jax.Array,  # [S, B]
    seq_lens: jax.Array,      # [S] incl. the new token
    block_size: int,
    scale: float,
    layer: jax.Array | None = None,
    interpret: bool = False,
    seq_group: int | None = None,   # sequences per grid program (None = auto)
    kv_scale: jax.Array | None = None,   # int8 latent: [L, slots, SW] f32
    row_scale_new: jax.Array | None = None,  # [S, SW] new rows' scales
):
    """Returns (attn_out [S, H, F] f32-accurate in q dtype, kv_cache') —
    plus kv_scale' appended when the latent cache is int8-quantized
    (``kv_scale`` given; payload cache int8, new rows pre-quantized by the
    caller alongside ``row_scale_new``)."""
    S, H, F = q_eff.shape
    quantized = kv_scale is not None
    if quantized and block_size % 32:
        # int8 latent pages pack (32, 128)-tiled; an unaligned page would
        # tear the deferred whole-page byte splice off-device, where no
        # exception ever surfaces.  The dispatch (models/mla.py) already
        # routes such configs to the XLA fallback — this guards direct
        # callers of the kernel.
        raise ValueError(
            f"int8 latent cache requires block_size % 32 == 0, "
            f"got {block_size}")
    squeeze = kv_cache.ndim == 2
    if squeeze:
        kv_cache = kv_cache[None]
        if quantized:
            kv_scale = kv_scale[None]
    SW = kv_scale.shape[2] if quantized else 0
    # Per-sequence VMEM: single latent page double-buffer (+ scale pages)
    # + f32 q/acc pair.
    G = pick_seq_group(
        S, seq_group,
        2 * block_size * F * kv_cache.dtype.itemsize
        + 8 * block_size * SW + 8 * H * F,
        budget=_GROUP_VMEM_BUDGET)
    layer_arr = jnp.asarray([0 if layer is None else layer], jnp.int32)

    def vspec(shape):
        return pl.BlockSpec(shape, lambda i, *_: (i,) + (0,) * (len(shape) - 1),
                            memory_space=pltpu.VMEM)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [vspec((G, H, F)), vspec((G, 1, F))]
    if quantized:
        in_specs.append(vspec((G, 1, SW)))
    in_specs.append(any_spec)
    if quantized:
        in_specs.append(any_spec)
    out_specs = [vspec((G, H, F)), any_spec] \
        + ([any_spec] if quantized else [])
    n_chan = 2 if quantized else 1
    scratch = [pltpu.VMEM((2, G, block_size, F), kv_cache.dtype)]
    if quantized:
        scratch.append(pltpu.VMEM((2, G, block_size, SW), jnp.float32))
    scratch += [pltpu.SemaphoreType.DMA((2, G, n_chan)),
                pltpu.SemaphoreType.DMA((G, n_chan))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S // G,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _mla_decode_kernel, block_size=block_size, scale=scale, group=G,
        quantized=quantized)
    out_shape = [jax.ShapeDtypeStruct((S, H, F), q_eff.dtype),
                 jax.ShapeDtypeStruct(kv_cache.shape, kv_cache.dtype)]
    operands = [block_tables, seq_lens, layer_arr, q_eff,
                row_new.reshape(S, 1, F).astype(kv_cache.dtype)]
    if quantized:
        operands.append(row_scale_new.reshape(S, 1, SW).astype(jnp.float32))
    operands.append(kv_cache)
    if quantized:
        operands.append(kv_scale)
        out_shape.append(jax.ShapeDtypeStruct(kv_scale.shape, kv_scale.dtype))
        # Operand indices in input_output_aliases include scalar prefetch.
        aliases = {6: 1, 7: 2}
    else:
        aliases = {5: 1}
    results = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), has_side_effects=True),
        interpret=interpret,
    )(*operands)
    if quantized:
        out, kv_cache, kv_scale = results
        if squeeze:
            return out, kv_cache[0], kv_scale[0]
        return out, kv_cache, kv_scale
    out, kv_cache = results
    if squeeze:
        kv_cache = kv_cache[0]
    return out, kv_cache
