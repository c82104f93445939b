"""Pallas TPU paged-attention kernels.

The FlashInfer-equivalent hot op (reference: docker/Dockerfile.cuda:57-58).
XLA's generic row-gather reads the paged KV cache at ~5 GB/s on TPU (32k
random 1 KB rows per step); these kernels instead DMA whole pages
(contiguous [block_size, KVH*D] slabs in the folded cache layout) into VMEM
double buffers and run the flash recurrence on-chip.

GQA without batched matmuls: queries are zero-expanded into the folded
[H, KVH*D] space (each head's row is nonzero only in its KV head's D-block),
so scores for all heads come from ONE MXU dot per page:
    scores = q_full [H, KVH*D] @ k_page.T [KVH*D, bs]  -> [H, bs]
and the weighted values accumulate in folded space, unfolded once per
sequence after the page loop.  This keeps every DMA 128-lane aligned even
for head_dim 64 models and keeps the MXU fed with one large dot.

Sequence grouping: each grid program handles a GROUP of ``G`` sequences
(auto-picked: largest of 16/8/4/2 dividing S within the VMEM budget).  A Mosaic kernel invocation embedded in the engine's fused
decode scan costs ~45 us of launch overhead plus ~3 us per grid program
(measured on v5e; standalone back-to-back dispatches hide this, loop-carried
ones cannot) — at S=64 with one sequence per program that overhead was ~70%
of decode step time.  Grouping cuts program count G-fold and runs the G
page streams as concurrent DMA chains, which also keeps the HBM pipe full
across short sequences.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    # scalar prefetch
    block_tables_ref,   # [S, B] SMEM
    seq_lens_ref,       # [S]    SMEM (context length INCLUDING the new token)
    layer_ref,          # [1]    SMEM (layer plane of the stacked cache);
                        # [2] when ``windowed``: (layer, window)
    # inputs
    q_ref, kn_ref, vn_ref, k_hbm, v_hbm,
    # outputs
    o_ref, k_out, v_out,
    # scratch
    k_buf, v_buf, sems, wsems,
    *,
    block_size: int,
    num_kv_heads: int,
    scale: float,
    group: int,
    windowed: bool,
    write: bool = True,
):
    """Fused decode attention + KV update on the STACKED cache.

    The kernel addresses one layer plane of the whole [L, slots, F] cache
    (``layer_ref``), so the engine's layer loop never slices the cache —
    that slicing cost ~10 ms/step of pure HBM copies at 1B-model scale
    (2×2.1 GB of dynamic-slice + dynamic-update-slice per decode step).

    Each program walks the pages of its G sequences in lockstep (loop bound
    = the group's max page count; shorter sequences re-read a clamped page
    and mask it out — dead reads, never dead locks).  The new token's KV
    row lives in each sequence's LAST page (decode invariant: slot ==
    seq_len - 1 position).  That page is already pulled to VMEM for
    attention; the row is spliced in with a sublane mask, used for
    attention, and the whole (DMA-aligned) page is written back —
    single-row HBM scatters are not expressible as aligned TPU DMAs.

    ``windowed``: the query (position seq_len - 1) sees only the last
    ``window`` keys, so each sequence's walk STARTS at the page that holds
    key seq_len - window; step j of the lockstep loop is that sequence's
    page start + j, and the pages before it are neither fetched nor
    multiplied.  A window that never binds starts every walk at page 0.
    """
    i = pl.program_id(0)
    G = group
    H, D = q_ref.shape[1], q_ref.shape[2]
    KVH = num_kv_heads
    Gq = H // KVH
    F = KVH * D
    bs = block_size
    li = layer_ref[0]
    base = i * G

    seq_len_g = [seq_lens_ref[base + g] for g in range(G)]
    n_pages_g = [pl.cdiv(sl, bs) for sl in seq_len_g]
    if windowed:
        window = layer_ref[1]
        start_g = [jnp.maximum(sl - window, 0) // bs for sl in seq_len_g]
        steps_g = [n - s for n, s in zip(n_pages_g, start_g)]
    else:
        steps_g = n_pages_g
    n_max = steps_g[0]
    for g in range(1, G):
        n_max = jnp.maximum(n_max, steps_g[g])

    def page_of(g, j):
        """Logical page of sequence ``g`` at step ``j`` of the loop."""
        return start_g[g] + j if windowed else j

    # Decode invariant: the new token sits at position seq_len - 1, i.e. in
    # LOGICAL page n_pages - 1, row (seq_len - 1) % bs.
    write_page_g = [(sl - 1) // bs for sl in seq_len_g]
    w_row_g = [(sl - 1) % bs for sl in seq_len_g]

    def page_dma(slot, j):
        copies = []
        for g in range(G):
            # Clamp for sequences whose pages ran out (and 0-length pad
            # rows): a dead re-read of a valid page, masked at compute.
            jj = jnp.clip(page_of(g, j), 0,
                          jnp.maximum(n_pages_g[g] - 1, 0))
            b = block_tables_ref[base + g, jj]
            start = pl.multiple_of(b * bs, bs)
            copies.append(pltpu.make_async_copy(
                k_hbm.at[li, pl.ds(start, bs)], k_buf.at[slot, g],
                sems.at[slot, g, 0]))
            copies.append(pltpu.make_async_copy(
                v_hbm.at[li, pl.ds(start, bs)], v_buf.at[slot, g],
                sems.at[slot, g, 1]))
        return copies

    @pl.when(n_max > 0)
    def _():
        for dma in page_dma(0, 0):
            dma.start()

    # Zero-expanded queries: q_full[g, h, k*D+d] = q[g, h, d] iff k == h // Gq.
    q = q_ref[...].astype(jnp.float32) * scale                # [G, H, D]
    q_rep = jnp.concatenate([q] * KVH, axis=2)                # [G, H, F]
    col_kv = jax.lax.broadcasted_iota(jnp.int32, (H, F), 1) // D
    row_kv = jax.lax.broadcasted_iota(jnp.int32, (H, F), 0) // Gq
    block_mask = (col_kv == row_kv).astype(jnp.float32)       # [H, F]
    q_full = q_rep * block_mask[None]                         # [G, H, F]

    row_ids2 = jax.lax.broadcasted_iota(jnp.int32, (bs, F), 0)
    # Per-group seq_len plane for score masking, built with an iota/select
    # chain (Mosaic has no scalar-vector stack/reshape).
    g_ids = jax.lax.broadcasted_iota(jnp.int32, (G, 1, bs), 0)
    sl_arr = jnp.zeros((G, 1, bs), jnp.int32)
    for g in range(G):
        sl_arr = jnp.where(g_ids == g, seq_len_g[g], sl_arr)
    if windowed:
        start_arr = jnp.zeros((G, 1, bs), jnp.int32)
        for g in range(G):
            start_arr = jnp.where(g_ids == g, start_g[g], start_arr)

    def body(j, carry):
        m, l, acc = carry
        slot = j % 2

        @pl.when(j + 1 < n_max)
        def _():
            for dma in page_dma((j + 1) % 2, j + 1):
                dma.start()

        for dma in page_dma(slot, j):
            dma.wait()

        # On each sequence's write page (exactly once per call): splice the
        # new-token row into the resident page and write the page back.
        for g in range(G if write else 0):
            @pl.when(page_of(g, j) == write_page_g[g])
            def _(g=g):
                is_wr = row_ids2 == w_row_g[g]
                k_buf[slot, g] = jnp.where(is_wr, kn_ref[g], k_buf[slot, g])
                v_buf[slot, g] = jnp.where(is_wr, vn_ref[g], v_buf[slot, g])
                b = block_tables_ref[base + g, page_of(g, j)]
                start = pl.multiple_of(b * bs, bs)
                writes = [
                    pltpu.make_async_copy(
                        k_buf.at[slot, g], k_out.at[li, pl.ds(start, bs)],
                        wsems.at[g, 0]),
                    pltpu.make_async_copy(
                        v_buf.at[slot, g], v_out.at[li, pl.ds(start, bs)],
                        wsems.at[g, 1]),
                ]
                for w in writes:
                    w.start()
                for w in writes:
                    w.wait()

        # bf16 operands, f32 accumulation: the MXU runs bf16 at 2x the
        # f32 rate and the page buffers skip a VPU convert pass; the f32
        # flash statistics (m, l, acc) keep the recurrence numerics.
        k = k_buf[slot]                                       # [G, bs, F] bf16
        v = v_buf[slot]
        s_hb = jax.lax.dot_general(
            q_full.astype(jnp.bfloat16), k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)               # [G, H, bs]
        key_pos = (start_arr + j if windowed else j) * bs \
            + jax.lax.broadcasted_iota(jnp.int32, (G, 1, bs), 2)
        valid = key_pos < sl_arr
        if windowed:
            valid &= key_pos >= sl_arr - window
        s_hb = jnp.where(valid, s_hb, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_hb, axis=-1, keepdims=True))
        p = jnp.exp(s_hb - m_new)                             # [G, H, bs]
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(jnp.bfloat16), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)               # [G, H, F]
        acc_new = acc * corr + pv
        return m_new, l_new, acc_new

    init = (
        jnp.full((G, H, 1), -1e29, jnp.float32),
        jnp.zeros((G, H, 1), jnp.float32),
        jnp.zeros((G, H, F), jnp.float32),
    )
    m, l, acc = jax.lax.fori_loop(0, n_max, body, init)
    # Unfold: each head's output lives in its KV head's D-block.
    masked = acc * block_mask[None]                           # [G, H, F]
    out = masked[:, :, 0:D]
    for kk in range(1, KVH):
        out = out + masked[:, :, kk * D:(kk + 1) * D]
    out = out / jnp.maximum(l, 1e-30)
    o_ref[...] = out.astype(o_ref.dtype)


# VMEM budget for the per-sequence kernel state: the page double-buffers
# PLUS the f32 query/accumulator intermediates (q_full and acc are [H, F]
# f32 each -> 8 * H * F bytes per sequence; wide-GQA and many-head MLA
# configs make this the binding term).  Keeps the auto-picked group well
# under the ~16 MiB/core VMEM on v5e.
_GROUP_VMEM_BUDGET = 4 << 20


def pick_seq_group(S: int, group, per_seq_bytes: int,
                   budget: int = _GROUP_VMEM_BUDGET) -> int:
    """Sequences per grid program: explicit (validated) or the largest of
    16/8/4/2 dividing S whose per-program state fits ``budget``.  Shared by
    the dense and MLA decode kernels."""
    if group is not None:
        if group < 1 or S % group:
            raise ValueError(
                f"seq_group={group} must divide the sequence count S={S} "
                "(grid programs each own exactly G sequences)")
        return group
    for g in (16, 8, 4, 2):
        if S % g == 0 and g * per_seq_bytes <= budget:
            return g
    return 1


@functools.partial(
    jax.jit, static_argnames=("block_size", "num_kv_heads", "scale", "soft_cap",
                              "interpret", "seq_group"))
def paged_attention_decode_update(
    q: jax.Array,             # [S, H, D]
    k_new: jax.Array,         # [S, F] new K rows (one per sequence)
    v_new: jax.Array,         # [S, F]
    k_cache: jax.Array,       # [L, num_slots, KVH*D] (or [num_slots, KVH*D])
    v_cache: jax.Array,
    block_tables: jax.Array,  # [S, B]
    seq_lens: jax.Array,      # [S] incl. the new token
    block_size: int,
    num_kv_heads: int,
    scale: float | None = None,
    soft_cap: float | None = None,
    layer: jax.Array | None = None,   # i32 scalar; None -> 2D caches
    interpret: bool = False,  # CPU emulation for kernel parity tests
    seq_group: int | None = None,   # sequences per grid program (None = auto)
    window: jax.Array | None = None,   # i32 scalar: keys the query sees
                                       # (itself included); None = all
):
    """Returns (attn_out [S, H, D], k_cache', v_cache').

    Caches may be per-layer 2D ([slots, F], ``layer=None``) or the engine's
    full stacked 3D buffer with a traced ``layer`` index — the stacked form
    lets the model's layer loop carry the whole cache through ``lax.scan``
    with zero slice/copy traffic (the kernel addresses the plane directly).
    """
    S, H, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    del soft_cap  # not yet supported in the kernel (no current model needs it)
    squeeze = k_cache.ndim == 2
    if squeeze:
        k_cache = k_cache[None]
        v_cache = v_cache[None]
    F = k_cache.shape[2]
    # Per-sequence VMEM: K+V page double-buffers + f32 q_full/acc pair.
    G = pick_seq_group(
        S, seq_group,
        4 * block_size * F * k_cache.dtype.itemsize + 8 * H * F)
    layer_arr = jnp.asarray(
        [0 if layer is None else layer]
        + ([] if window is None else [window]), jnp.int32)

    def vspec(shape):
        return pl.BlockSpec(shape, lambda i, *_: (i,) + (0,) * (len(shape) - 1),
                            memory_space=pltpu.VMEM)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S // G,),
        in_specs=[vspec((G, H, D)), vspec((G, 1, F)), vspec((G, 1, F)),
                  any_spec, any_spec],
        out_specs=[vspec((G, H, D)), any_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((2, G, block_size, F), k_cache.dtype),
            pltpu.VMEM((2, G, block_size, F), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, G, 2)),
            pltpu.SemaphoreType.DMA((G, 2)),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, block_size=block_size, num_kv_heads=num_kv_heads,
        scale=scale, group=G, windowed=window is not None)
    out, k_cache, v_cache = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, H, D), q.dtype),
                   jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)],
        # Operand indices in input_output_aliases include scalar prefetch.
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), has_side_effects=True),
        interpret=interpret,
    )(block_tables, seq_lens, layer_arr, q,
      k_new.reshape(S, 1, F), v_new.reshape(S, 1, F), k_cache, v_cache)
    if squeeze:
        k_cache = k_cache[0]
        v_cache = v_cache[0]
    return out, k_cache, v_cache


def _read_kernel(block_tables_ref, seq_lens_ref, layer_ref, q_ref, k_hbm,
                 v_hbm, o_ref, k_buf, v_buf, sems, **kw):
    _decode_kernel(block_tables_ref, seq_lens_ref, layer_ref, q_ref, None,
                   None, k_hbm, v_hbm, o_ref, None, None, k_buf, v_buf, sems,
                   None, write=False, **kw)


@functools.partial(
    jax.jit, static_argnames=("block_size", "num_kv_heads", "scale",
                              "interpret", "seq_group"))
def paged_attention_read(
    q: jax.Array,             # [S, H, D]
    k_cache: jax.Array,       # [L, num_slots, KVH*D]
    v_cache: jax.Array,
    block_tables: jax.Array,  # [S, B]
    seq_lens: jax.Array,      # [S]: the query of row s sees keys < seq_lens[s]
    block_size: int,
    num_kv_heads: int,
    scale: float | None = None,
    layer: jax.Array | None = None,
    interpret: bool = False,
    seq_group: int | None = None,
):
    """One query a row over a plane of the stacked cache that holds the
    query's own keys and values already (another layer's plane, or this
    layer's after the step's rows were scattered): the decode kernel's
    walk with no row to splice in and no page to write back.  Returns
    attn_out [S, H, D]."""
    S, H, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    F = k_cache.shape[2]
    G = pick_seq_group(
        S, seq_group,
        4 * block_size * F * k_cache.dtype.itemsize + 8 * H * F)
    layer_arr = jnp.asarray([0 if layer is None else layer], jnp.int32)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    q_spec = pl.BlockSpec((G, H, D), lambda i, *_: (i, 0, 0),
                          memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(
            _read_kernel, block_size=block_size, num_kv_heads=num_kv_heads,
            scale=scale, group=G, windowed=False),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S // G,),
            in_specs=[q_spec, any_spec, any_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((2, G, block_size, F), k_cache.dtype),
                pltpu.VMEM((2, G, block_size, F), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, G, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_attention_read",
        interpret=interpret,
    )(block_tables, seq_lens, layer_arr, q, k_cache, v_cache)
