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

NEG_INF = -1e30

_GROUP_VMEM_BUDGET = 6 << 20


def _mla_decode_kernel(
    # scalar prefetch
    block_tables_ref,   # [S, B] SMEM
    seq_lens_ref,       # [S]    SMEM (context length INCLUDING the new token)
    layer_ref,          # [1]    SMEM (layer plane of the stacked cache)
    # inputs
    q_ref, rn_ref, kv_hbm,
    # outputs
    o_ref, kv_out,
    # scratch
    kv_buf, sems, wsems,
    *,
    block_size: int,
    scale: float,
    group: int,
):
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

    def wb_copy(g):
        """The (re-constructible) write-back descriptor for group g."""
        wp = write_page_g[g]
        b = block_tables_ref[base + g, jnp.maximum(wp, 0)]
        start = pl.multiple_of(b * bs, bs)
        return pltpu.make_async_copy(
            kv_buf.at[wp % 2, g], kv_out.at[li, pl.ds(start, bs)],
            wsems.at[g, 0])

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
                    wb_copy(g).wait()
            for dma in page_dma((j + 1) % 2, j + 1):
                dma.start()

        for dma in page_dma(slot, j):
            dma.wait()

        # On each sequence's write page (exactly once per call): splice the
        # new latent row into the resident
        # page and START the page write-back — the wait happens at slot
        # reuse (above) or after the loop, so the write flies UNDER the
        # score/value dots instead of stalling every group serially (decode
        # writes land on the LAST page, so in the common case all waits
        # coalesce after the loop).
        for g in range(G):
            @pl.when(j == write_page_g[g])
            def _(g=g):
                is_wr = row_ids2 == w_row_g[g]
                kv_buf[slot, g] = jnp.where(is_wr, rn_ref[g], kv_buf[slot, g])
                wb_copy(g).start()

        # bf16 operands, f32 accumulation: 2x MXU rate, no VPU convert of
        # the page (see paged_attention.py's decode kernel).
        page = kv_buf[slot]                                   # [G, bs, F] bf16
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
            wb_copy(g).wait()
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_size", "scale", "interpret", "seq_group"))
def mla_paged_decode_update(
    q_eff: jax.Array,         # [S, H, F] absorbed queries
    row_new: jax.Array,       # [S, F] new latent rows (one per sequence)
    kv_cache: jax.Array,      # [L, num_slots, F] (or [num_slots, F])
    block_tables: jax.Array,  # [S, B]
    seq_lens: jax.Array,      # [S] incl. the new token
    block_size: int,
    scale: float,
    layer: jax.Array | None = None,
    interpret: bool = False,
    seq_group: int | None = None,   # sequences per grid program (None = auto)
):
    """Returns (attn_out [S, H, F] f32-accurate in q dtype, kv_cache')."""
    S, H, F = q_eff.shape
    squeeze = kv_cache.ndim == 2
    if squeeze:
        kv_cache = kv_cache[None]
    # Per-sequence VMEM: single latent page double-buffer + f32 q/acc pair.
    G = pick_seq_group(
        S, seq_group,
        2 * block_size * F * kv_cache.dtype.itemsize + 8 * H * F,
        budget=_GROUP_VMEM_BUDGET)
    layer_arr = jnp.asarray([0 if layer is None else layer], jnp.int32)

    def vspec(shape):
        return pl.BlockSpec(shape, lambda i, *_: (i,) + (0,) * (len(shape) - 1),
                            memory_space=pltpu.VMEM)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S // G,),
        in_specs=[vspec((G, H, F)), vspec((G, 1, F)), any_spec],
        out_specs=[vspec((G, H, F)), any_spec],
        scratch_shapes=[
            pltpu.VMEM((2, G, block_size, F), kv_cache.dtype),
            pltpu.SemaphoreType.DMA((2, G, 1)),
            pltpu.SemaphoreType.DMA((G, 1)),
        ],
    )
    kernel = functools.partial(
        _mla_decode_kernel, block_size=block_size, scale=scale, group=G)
    out, kv_cache = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, H, F), q_eff.dtype),
                   jax.ShapeDtypeStruct(kv_cache.shape, kv_cache.dtype)],
        # Operand indices in input_output_aliases include scalar prefetch.
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), has_side_effects=True),
        interpret=interpret,
    )(block_tables, seq_lens, layer_arr, q_eff,
      row_new.reshape(S, 1, F).astype(kv_cache.dtype), kv_cache)
    if squeeze:
        kv_cache = kv_cache[0]
    return out, kv_cache
