"""Pallas TPU kernel: fused-routing grouped int8 MoE FFN (decode regime).

One of the three int8 MoE kernels, built for the regime that both of
these lose in:

  - ``dense_moe_int8`` computes every expert against every token — right
    for tiny batches (weight-bound), an 8x routed-FLOPs overspend once
    ``T x E`` work turns MXU-bound (measured r5: decode bs256 spends
    9.47 of 16.8 ms/step there, 12% MFU / 36.9% HBM roofline).
  - a kernel over rows that XLA sorts and pads by expert computes only
    routed rows, but that glue (padded-row gather/scatter + unsort
    combine) moves every activation row through HBM twice more: it
    was no faster than dense at decode sizes and is gone (PR 44).

This kernel keeps that FLOP discipline (routed rows only) and moves ALL
row-data movement onto the MXU, inside the kernel:

  - ``x`` stays in TOKEN order and is resident in VMEM for the whole
    grid (decode batches are small: T <= ~512 is ~2 MB bf16) — one DMA,
    no gathered/padded [S_pad, H] copy in HBM at all;
  - per row tile, the sorted-by-expert row set is materialized by a
    ONE-HOT GATHER MATMUL: ``onehot[rt, T] @ x[T, H]`` selects the
    tile's tokens on the MXU (exact — selection of bf16 rows);
  - the combine (un-sort + k-way sum + duplicate-route accumulation) is
    the TRANSPOSED one-hot matmul ``onehot_T[T, rt] @ y[rt, H]``,
    accumulated in f32 across the whole grid into the resident [T, H]
    output block — no scatter, no unsort gather, no [S_pad, H] f32
    round trip;
  - routing metadata (counting-sort outputs: token id, combine weight
    and expert id per sorted-padded slot) rides in as scalar-prefetch /
    tiny 1-D blocks — the only per-layer XLA work left is the counting
    sort itself plus O(S) int32 index arithmetic;
  - experts with ZERO routed tokens get no tiles, and an expert spanning
    several tiles streams its weights ONCE.

**Expert-weight streaming is a manual double-buffered DMA chain** (round
9; previously a weight BlockSpec).  The weight tensors stay in HBM
(``ANY``) and each distinct expert's six slabs (w_gate/w_up/w_down int8 +
their f32 scales) are DMA'd into one of two VMEM slot sets; tile ``t``
STARTS the DMA for the next tile's expert before its own MXU work, so
the next expert's ~3 MB weight stream flies UNDER the current tile's
three GEMMs instead of serializing in the pipeline prologue.  Slot and
load schedules are computed OUTSIDE the kernel from the tile->expert
table (scalar prefetch): consecutive tiles of one expert share the
resident slot with no re-fetch (``load[t] = 0``), distinct experts
alternate slots — at decode sizes the weight stream is the roofline
term, so every skipped refetch is direct HBM headroom.

The extra MXU work for the fused gather/scatter is 2*rt*T*H MACs per
tile vs 3*rt*H*I for the FFN itself — ~T/I of the tile's FLOPs, a
fraction of the 8x all-experts overspend it removes.  Weight traffic is
identical to the dense kernel's one-pass stream (minus never-visited
experts), so once the MXU term collapses the kernel runs at the weight
roofline — the decode target.

Reference role: DeepGEMM's ``m_grouped_gemm_fp8_fp8_bf16_nt_masked``
(the low-latency-decode grouped GEMM; docker/Dockerfile.cuda:53-54,
wide-ep decode.yaml:129-132).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _routed_kernel(
    # scalar prefetch
    meta_ref,     # [2]  SMEM ([layer plane, num_tiles])
    te_ref,       # [NT] SMEM expert id per row tile
    slot_ref,     # [NT] SMEM VMEM weight slot per tile (alternates per
                  #      DISTINCT expert; tiles of one expert share a slot)
    load_ref,     # [NT] SMEM 1 where the tile's expert differs from its
                  #      predecessor's (a weight DMA is needed), else 0
    # inputs
    x_ref,        # [Tp, H] bf16 (whole token batch; same block every step)
    tokc_ref,     # [RT, 1] i32  token id per sorted-padded slot (column)
    tokr_ref,     # [1, RT] i32  same metadata, row layout (for onehot_T)
    wslot_ref,    # [RT, 1] f32  combine weight per slot (0 = pad)
    wg_hbm,       # [Lm, E, H, I] int8 (ANY — streamed per expert)
    wu_hbm,       # [Lm, E, H, I] int8 (ANY)
    wd_hbm,       # [Lm, E, I, H] int8 (ANY)
    gs_hbm,       # [Lm, E, 1, I] f32  (ANY)
    us_hbm,       # [Lm, E, 1, I] f32  (ANY)
    ds_hbm,       # [Lm, E, 1, H] f32  (ANY)
    # outputs
    o_ref,        # [Tp, H] f32 (accumulated across the whole grid)
    # scratch
    wg_buf,       # [2, H, I] int8 double-buffered expert weight slots
    wu_buf,       # [2, H, I] int8
    wd_buf,       # [2, I, H] int8
    gs_buf,       # [2, 1, I] f32
    us_buf,       # [2, 1, I] f32
    ds_buf,       # [2, 1, H] f32
    sems,         # [2, 6] DMA semaphores (slot x weight channel)
):
    t = pl.program_id(0)
    NT = pl.num_programs(0)
    Tp = x_ref.shape[0]
    RT = tokc_ref.shape[0]
    li = meta_ref[0]

    def weight_dma(s, e):
        """The six HBM->VMEM copies for expert ``e`` into slot ``s``."""
        return [
            pltpu.make_async_copy(wg_hbm.at[li, e], wg_buf.at[s],
                                  sems.at[s, 0]),
            pltpu.make_async_copy(wu_hbm.at[li, e], wu_buf.at[s],
                                  sems.at[s, 1]),
            pltpu.make_async_copy(wd_hbm.at[li, e], wd_buf.at[s],
                                  sems.at[s, 2]),
            pltpu.make_async_copy(gs_hbm.at[li, e], gs_buf.at[s],
                                  sems.at[s, 3]),
            pltpu.make_async_copy(us_hbm.at[li, e], us_buf.at[s],
                                  sems.at[s, 4]),
            pltpu.make_async_copy(ds_hbm.at[li, e], ds_buf.at[s],
                                  sems.at[s, 5]),
        ]

    @pl.when(t == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        for dma in weight_dma(slot_ref[0], te_ref[0]):
            dma.start()

    # Prefetch the NEXT tile's expert weights before this tile's compute:
    # distinct experts alternate slots, so the inbound stream never lands
    # on the slot this tile reads, and the grid's sequential semantics
    # guarantee the slot's previous reader already finished.  Same-expert
    # successors (load == 0) skip the DMA entirely and reuse the slot.
    @pl.when((t + 1 < NT) & (load_ref[jnp.minimum(t + 1, NT - 1)] == 1))
    def _():
        tn = jnp.minimum(t + 1, NT - 1)
        for dma in weight_dma(slot_ref[tn], te_ref[tn]):
            dma.start()

    # Consume this tile's own load (started at t-1, or above at t == 0).
    # Tiles with load == 0 read weights a predecessor already waited for.
    @pl.when(load_ref[t] == 1)
    def _():
        for dma in weight_dma(slot_ref[t], te_ref[t]):
            dma.wait()

    # Inactive trailing tiles (static grid, dynamic tile count): their
    # metadata is zeroed and their expert id repeats (load == 0, no DMA),
    # so skipping compute is purely an optimization — the contribution
    # would be 0.
    @pl.when(t < meta_ref[1])
    def _():
        s = slot_ref[t]
        tok_c = tokc_ref[...]                              # [RT, 1]
        tok_r = tokr_ref[...]                              # [1, RT]
        # Gather matmul: one-hot row selector (exact for bf16 payloads).
        sel = (tok_c == jax.lax.broadcasted_iota(
            jnp.int32, (RT, Tp), 1)).astype(jnp.bfloat16)  # [RT, Tp]
        # f32 accumulator (Mosaic's matmul takes no 16-bit one); the
        # cast back is exact — each output is one selected bf16 value.
        xg = jax.lax.dot(sel, x_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(jnp.bfloat16)                 # [RT, H]
        wg = wg_buf[s].astype(jnp.bfloat16)                # exact |q|<=127
        wu = wu_buf[s].astype(jnp.bfloat16)
        h = jax.lax.dot(xg, wg,
                        preferred_element_type=jnp.float32) * gs_buf[s]
        u = jax.lax.dot(xg, wu,
                        preferred_element_type=jnp.float32) * us_buf[s]
        a = jax.nn.silu(h) * u * wslot_ref[...]            # [RT, I] f32
        wd = wd_buf[s].astype(jnp.bfloat16)
        y = jax.lax.dot(a.astype(jnp.bfloat16), wd,
                        preferred_element_type=jnp.float32) * ds_buf[s]
        # Combine matmul: transposed one-hot un-sorts, k-sums and merges
        # duplicate routes in one accumulating MXU pass.
        sel_t = (tok_r == jax.lax.broadcasted_iota(
            jnp.int32, (Tp, RT), 0)).astype(jnp.bfloat16)  # [Tp, RT]
        o_ref[...] += jax.lax.dot(sel_t, y.astype(jnp.bfloat16),
                                  preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def routed_moe_int8(
    x: jax.Array,           # [Tp, H] bf16 — token order (Tp: T padded to 16)
    tok_pad: jax.Array,     # [S_pad, 1] i32 token id per sorted-padded slot
    tok_row: jax.Array,     # [NT, RT] i32 same metadata, one row per tile
    wslot_pad: jax.Array,   # [S_pad, 1] f32 combine weights (0 = pad slot)
    tile_expert: jax.Array, # [NT] i32 expert id per tile (repeats when idle)
    num_tiles,              # scalar int32: tiles actually populated
    layer,                  # scalar int32: plane of the stacked weights
    w_gate_q: jax.Array,    # [Lm, E, H, I] int8
    w_gate_s: jax.Array,    # [Lm, E, 1, I] f32
    w_up_q: jax.Array,
    w_up_s: jax.Array,
    w_down_q: jax.Array,    # [Lm, E, I, H] int8
    w_down_s: jax.Array,    # [Lm, E, 1, H] f32
    row_tile: int = 32,
    interpret: bool = False,
) -> jax.Array:             # [Tp, H] f32 — routed MoE output, token order
    """Fused-routing grouped int8 MoE FFN over stacked weights.

    The caller owns ONLY the counting sort and int32 slot arithmetic
    (``ops.moe._routed_int8_kernel_path``); every activation row moves
    inside the kernel, and expert weights stream through a manual
    double-buffered DMA chain (next expert's slabs overlap this tile's
    GEMMs; consecutive tiles of one expert re-use the resident slot).
    Output is already combined per token — no unsort, no scatter, no
    [T, k, H] reduction outside.
    """
    Tp, H = x.shape
    S_pad = tok_pad.shape[0]
    Lm, E, _, I = w_gate_q.shape
    assert S_pad % row_tile == 0
    NT = S_pad // row_tile
    assert tok_row.shape == (NT, row_tile)
    assert tile_expert.shape == (NT,)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(num_tiles, jnp.int32)])
    # Weight-DMA schedule: a tile loads iff its expert differs from its
    # predecessor's; distinct experts alternate VMEM slots.  Trailing
    # inactive tiles repeat the last expert id -> load 0, no DMA at all.
    te = tile_expert.astype(jnp.int32)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), te[:-1]])
    load = (te != prev).astype(jnp.int32)              # load[0] == 1 always
    slot = ((jnp.cumsum(load) - 1) % 2).astype(jnp.int32)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(NT,),
        in_specs=[
            pl.BlockSpec((Tp, H), lambda t, *_: (0, 0)),        # x resident
            pl.BlockSpec((row_tile, 1), lambda t, *_: (t, 0)),  # tok col
            # One [1, RT] row per tile.  Blocked over a leading tile dim
            # so the block's last two dims EQUAL the array's: a (1, RT)
            # block of [NT, RT] breaks Mosaic's (8, 128) divisibility rule.
            pl.BlockSpec((None, 1, row_tile), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((row_tile, 1), lambda t, *_: (t, 0)),  # wslot
            any_spec, any_spec, any_spec,                       # w_{g,u,d}_q
            any_spec, any_spec, any_spec,                       # scales
        ],
        out_specs=pl.BlockSpec((Tp, H), lambda t, *_: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, H, I), jnp.int8),
            pltpu.VMEM((2, H, I), jnp.int8),
            pltpu.VMEM((2, I, H), jnp.int8),
            pltpu.VMEM((2, 1, I), jnp.float32),
            pltpu.VMEM((2, 1, I), jnp.float32),
            pltpu.VMEM((2, 1, H), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 6)),
        ],
    )
    return pl.pallas_call(
        _routed_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),   # sequential accumulation
        interpret=interpret,
    )(meta, te, slot, load, x, tok_pad, tok_row[:, None, :], wslot_pad,
      w_gate_q, w_up_q, w_down_q, w_gate_s, w_up_s, w_down_s)
