"""Pallas TPU kernel: chunk-streamed fused-routing int8 MoE FFN (the a2a
exchange's arrival chunks).

**What this kernel serves since PR 48.**  Its one caller is
``ops.moe._a2a_moe_chunk``: on an expert-parallel mesh each shard's received
rows (arrival order, k = 1, validity as the combine weight) go through it a
chunk at a time, and the result lands in arrival order with no un-sort.  No
benchmark cell runs that path.  The single-device steps above 512 rows,
which it served until then, left it for ``moe_one_pass.py``: the cost model
below was written at H = 2048, I = 512, E = 64 (201 MB of int8 experts a
layer), which no cell runs.  At the published widths a 2,048-token step's
four chunks streamed 604-805 MB of experts four times (3.0-3.9 ms a layer at
the HBM rate against 0.7-1.0 for one pass), in tiles of the 32 rows a CHUNK
gives an expert, with 33-44 % more FLOPs for the one-hot gather and combine:
one layer's call read 7.05 ms (``trinity-mini``), 5.78 (``qwen3-30b-a3b``),
4.52 (``kanana-2-30b-a3b``), 5.66 (``mellum2-12b-a2.5b``) against 3.23, 2.74,
2.06, 2.65 through the one-pass path (my chip runs, PR 48; the table at
``ops.moe.DENSE_INT8_MAX_T``).  The text below is the design as it was
written for the prefill regime; it still describes the kernel.

``moe_routed.py`` proved the fused-routing idea for decode: keep ``x``
token-ordered and VMEM-resident, and turn the gather / un-sort / combine
into one-hot matmuls inside the kernel — zero XLA row glue.  Its limit
is residency: the whole batch plus the f32 output block must sit in VMEM
(~6 MB at T=512, H=2048), so the prefill regime (T up to 8192 — 32 MB
bf16 for ``x`` alone) would need rows sorted and padded by expert in HBM,
whose XLA glue moves every activation row across HBM four extra times
per layer with up to 5x ``S_pad`` padding inflation (the very
HBM-row-movement tax P/D-Serve, arXiv:2408.08147, charges to the
prefill side of disaggregated serving).

This kernel removes the residency requirement instead of the fusion:

  - ``x`` is split into TOKEN-ORDER chunks of ``chunk_t`` rows
    (``ops.moe.PREFILL_CHUNK_T``).  The chunk is the resident unit:
    grid = (C, NT_c) with the chunk index OUTER, so Pallas streams
    chunk c+1's block (double-buffered, one DMA per chunk) while chunk
    c's expert tiles compute;
  - routing metadata is per chunk: a counting sort of the chunk's
    ``S_c = chunk_t * k`` routed slots (token id, combine weight,
    expert id per sorted-padded slot) rides in as scalar prefetch and
    tiny 1-D blocks — the metadata is O(S) int32, never ``[_, H]``
    rows, and the per-chunk padding bound is ``E * rt`` slots instead
    of the global layout's multiplicative tax;
  - per (chunk, expert-tile) grid cell the gather is the one-hot
    matmul ``onehot[rt, chunk_t] @ x_chunk[chunk_t, H]`` (exact for
    bf16 payloads) and the combine is the transposed one-hot
    accumulated in f32 into the chunk's RESIDENT output block — the
    un-sort, k-way sum and duplicate-route merge never leave VMEM;
  - a chunk's inactive trailing tiles repeat the last active tile's
    expert id (same weight index map -> Pallas skips the DMA) and are
    compute-skipped via the per-chunk ``num_tiles`` guard; experts
    with zero routed tokens in a chunk get no tiles at all.

Cost model vs such a layout (bench shapes H=2048, I=512, E=64, k=8):
activation HBM traffic collapses to the minimum — ``x`` read once,
output written once, NO ``[S_pad, H]`` intermediate in HBM at all.  The
price is (a) the one-hot tax, ``2*chunk_t/(3*I)`` of the FFN FLOPs
(33% at chunk_t=256, 67% at 512), and (b) weight re-streaming: each
chunk re-streams the weights of every expert it touches, so weight
traffic is up to ``C`` passes/layer instead of one.  Both are paid
INSIDE one kernel where Pallas overlaps them with compute, versus the
sorted layout's glue which serializes between kernel launches; the
chunk size trades the two taxes (small chunks -> more weight passes,
large chunks -> more one-hot FLOPs + VMEM); the comment at
``ops.moe.PREFILL_CHUNK_T`` says where the two meet on the v5e.

Reference role: DeepGEMM's contiguous grouped GEMM for prefill
(m_grouped_gemm_fp8_fp8_bf16_nt_contiguous; docker/Dockerfile.cuda:
53-54, wide-ep prefill.yaml:100-101), fused with DeepEP's
dispatch/combine row movement instead of delegating it to glue.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _streamed_kernel(
    meta_ref,     # [1]  SMEM (scalar prefetch: layer plane)
    nt_ref,       # [C]  SMEM (scalar prefetch: populated tiles per chunk)
    te_ref,       # [C*NT_c] SMEM (scalar prefetch: expert id per tile)
    x_ref,        # [chunk_t, H] bf16 (this CHUNK of the token batch)
    tokc_ref,     # [RT, 1] i32  chunk-local token id per sorted slot (col)
    tokr_ref,     # [1, RT] i32  same metadata, row layout (for onehot_T)
    wslot_ref,    # [RT, 1] f32  combine weight per slot (0 = pad)
    wg_ref,       # [1, 1, H, I] int8 (this tile's expert)
    wu_ref,       # [1, 1, H, I] int8
    wd_ref,       # [1, 1, I, H] int8
    gs_ref,       # [1, 1, 1, I] f32
    us_ref,       # [1, 1, 1, I] f32
    ds_ref,       # [1, 1, 1, H] f32
    o_ref,        # [chunk_t, H] f32 (accumulated across the chunk's tiles)
):
    t = pl.program_id(1)
    Tc = x_ref.shape[0]
    RT = tokc_ref.shape[0]

    @pl.when(t == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    # Tiles beyond this chunk's populated count carry zeroed metadata and
    # a repeated weight index; skipping them is purely an optimization.
    @pl.when(t < nt_ref[pl.program_id(0)])
    def _():
        tok_c = tokc_ref[...]                              # [RT, 1]
        tok_r = tokr_ref[...]                              # [1, RT]
        # Gather matmul: one-hot row selector over the CHUNK (exact for
        # bf16 payloads) — the rows never take a detour through HBM.
        sel = (tok_c == jax.lax.broadcasted_iota(
            jnp.int32, (RT, Tc), 1)).astype(jnp.bfloat16)  # [RT, Tc]
        # f32 accumulator (Mosaic's matmul takes no 16-bit one); the
        # cast back is exact — each output is one selected bf16 value.
        xg = jax.lax.dot(sel, x_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(jnp.bfloat16)                 # [RT, H]
        wg = wg_ref[0, 0].astype(jnp.bfloat16)             # exact |q|<=127
        wu = wu_ref[0, 0].astype(jnp.bfloat16)
        h = jax.lax.dot(xg, wg,
                        preferred_element_type=jnp.float32) * gs_ref[0, 0]
        u = jax.lax.dot(xg, wu,
                        preferred_element_type=jnp.float32) * us_ref[0, 0]
        a = jax.nn.silu(h) * u * wslot_ref[...]            # [RT, I] f32
        wd = wd_ref[0, 0].astype(jnp.bfloat16)
        y = jax.lax.dot(a.astype(jnp.bfloat16), wd,
                        preferred_element_type=jnp.float32) * ds_ref[0, 0]
        # Combine matmul: transposed one-hot un-sorts, k-sums and merges
        # duplicate routes into the chunk-resident f32 accumulator.
        sel_t = (tok_r == jax.lax.broadcasted_iota(
            jnp.int32, (Tc, RT), 0)).astype(jnp.bfloat16)  # [Tc, RT]
        o_ref[...] += jax.lax.dot(sel_t, y.astype(jnp.bfloat16),
                                  preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("chunk_t", "row_tile", "interpret"))
def streamed_moe_int8(
    x: jax.Array,           # [Tp, H] bf16 — token order, Tp = C * chunk_t
    tok_pad: jax.Array,     # [C*S_pad_c, 1] i32 chunk-LOCAL token id/slot
    tok_row: jax.Array,     # [C*NT_c, RT] i32 same metadata, row per tile
    wslot_pad: jax.Array,   # [C*S_pad_c, 1] f32 combine weights (0 = pad)
    tile_expert: jax.Array, # [C*NT_c] i32 expert id per tile (repeats idle)
    num_tiles: jax.Array,   # [C] i32: populated tiles per chunk
    layer,                  # scalar int32: plane of the stacked weights
    w_gate_q: jax.Array,    # [Lm, E, H, I] int8
    w_gate_s: jax.Array,    # [Lm, E, 1, I] f32
    w_up_q: jax.Array,
    w_up_s: jax.Array,
    w_down_q: jax.Array,    # [Lm, E, I, H] int8
    w_down_s: jax.Array,    # [Lm, E, 1, H] f32
    chunk_t: int = 512,
    row_tile: int = 32,
    interpret: bool = False,
) -> jax.Array:             # [Tp, H] f32 — routed MoE output, token order
    """Chunk-streamed fused-routing grouped int8 MoE FFN.

    The caller owns ONLY the per-chunk counting sorts and int32 slot
    arithmetic (``ops.moe._streamed_int8_kernel_path``); every
    activation row moves inside the kernel.  Output is already combined
    per token — no unsort, no scatter, no ``[S_pad, H]`` round trip.
    """
    Tp, H = x.shape
    assert Tp % chunk_t == 0
    C = Tp // chunk_t
    Lm, E, _, I = w_gate_q.shape
    NT_total = tile_expert.shape[0]
    assert NT_total % C == 0
    NT_c = NT_total // C
    assert tok_row.shape == (NT_total, row_tile)
    assert tok_pad.shape == (NT_total * row_tile, 1)
    assert num_tiles.shape == (C,)
    meta = jnp.asarray([layer], jnp.int32)

    def tmap(c, t, *_):
        return (c * NT_c + t, 0)

    def wmap(c, t, meta_ref, nt_ref, te_ref):
        return (meta_ref[0], te_ref[c * NT_c + t], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(C, NT_c),
        in_specs=[
            pl.BlockSpec((chunk_t, H), lambda c, t, *_: (c, 0)),  # x chunk
            pl.BlockSpec((row_tile, 1), tmap),                    # tok col
            # tok row: blocked over a leading tile dim so the block's
            # last two dims equal the array's (Mosaic's (8, 128) rule
            # refuses a (1, RT) block of [NT, RT]).
            pl.BlockSpec((None, 1, row_tile),
                         lambda c, t, *_: (c * NT_c + t, 0, 0)),
            pl.BlockSpec((row_tile, 1), tmap),                    # wslot
            pl.BlockSpec((1, 1, H, I), wmap),
            pl.BlockSpec((1, 1, H, I), wmap),
            pl.BlockSpec((1, 1, I, H), wmap),
            pl.BlockSpec((1, 1, 1, I), wmap),
            pl.BlockSpec((1, 1, 1, I), wmap),
            pl.BlockSpec((1, 1, 1, H), wmap),
        ],
        out_specs=pl.BlockSpec((chunk_t, H), lambda c, t, *_: (c, 0)),
    )
    return pl.pallas_call(
        _streamed_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # Sequential accumulation within a chunk; chunks advance the
            # resident x/output blocks (streamed, double-buffered).
            dimension_semantics=("arbitrary", "arbitrary"),
            # The double-buffered blocks alone are 18 MB at the default
            # chunk (x 2x2, f32 out 2x4, three int8 weight slabs 2x1 each
            # at H=2048, I=512): over the compiler's 16 MB default scope,
            # which it enforces from T = 32768 rows (the a2a path's
            # arrival buffer at EP=4) and not below.  v5e has 128 MiB.
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
    )(meta, num_tiles, tile_expert, x, tok_pad, tok_row[:, None, :],
      wslot_pad,
      w_gate_q, w_up_q, w_down_q, w_gate_s, w_up_s, w_down_s)
