"""Pallas TPU kernel: the int8 experts of a whole prefill step in ONE pass
over the weights (single device, above ``ops.moe.ROUTED_INT8_MAX_T`` rows).

``moe_routed_stream.py`` serves such a step a 512-row token-order chunk at
a time, and every chunk streams every expert it touches again: four passes
over 604-805 MB of int8 matrices a layer at the widths the benchmark's
cells run, in tiles of the 32 rows a CHUNK gives an expert.  Here the rows
are grouped by expert over the whole step (the form ``moe_held.py`` proved
for the held bf16 experts):

  - **One layout over all S = T x k slots** (``ops.moe._one_pass_layout``):
    each expert's slots a run padded to the row tile, one expert a tile, in
    expert order; an expert that no row selects has no tile and is not
    read.  The grid is the static worst case, ``ceil(S / rt) + E`` tiles; a
    tile past ``num_tiles`` maps to the last tile's blocks (nothing is
    fetched, nothing written back) and does nothing.
  - **An expert's three int8 matrices are fetched once** (a block a
    matrix, chosen by the tile's expert: the pipeline fetches the next
    tile's while this tile's dots run and skips a block whose index
    repeats) **and cast to bf16 once**, at the expert's first tile, into a
    VMEM scratch the later tiles of the expert multiply from.
  - **Rows travel sorted.**  XLA gathers the step's rows into the padded
    layout ``[S_pad, H]`` bf16 by their token ids and gathers the results
    back by each (token, choice)'s padded slot for the k-sum: 0.2-0.35 GB a
    layer out and back against the 1.8-2.4 GB of streaming the expert
    matrices again.  A one-hot product over 2,048 tokens would cost 1.3 x
    the experts' FLOPs, so the kernel has none.

Same mathematics as ``_streamed_kernel``: bf16 rows, int8 matrices cast
exactly to bf16, f32 accumulation, the scales applied to the f32 products,
``silu(h) * u`` times the combine weight rounded to bf16 before the down
projection, the result rounded to bf16 before the k-sum in f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The scoped VMEM a call may take.  At 128 experts of [2048, 1024] a tile of
# 128 rows keeps 12.6 MB of int8 blocks (double buffered), 12.6 MB of bf16
# matrices and 2 MB of rows and results: 27 MB before temporaries.
VMEM_LIMIT = 64 << 20


def _one_pass_kernel(
    meta_ref,     # [2]  SMEM (scalar prefetch: the stacks' plane, num_tiles)
    te_ref,       # [NT] SMEM (scalar prefetch: expert of each tile)
    x_ref,        # [RT, H] bf16: the tile's rows, sorted layout
    wslot_ref,    # [RT, 1] f32 combine weight of each row (0 = pad)
    wg_ref,       # [1, 1, H, I] int8 (the tile's expert)
    wu_ref,       # [1, 1, H, I] int8
    wd_ref,       # [1, 1, I, H] int8
    gs_ref,       # [1, 1, 1, I] f32
    us_ref,       # [1, 1, 1, I] f32
    ds_ref,       # [1, 1, 1, H] f32
    o_ref,        # [RT, H] bf16
    wg_bf,        # [H, I] bf16 scratch: the expert's matrices as the dots
    wu_bf,        # [H, I]      take them
    wd_bf,        # [I, H]
):
    t = pl.program_id(0)

    @pl.when(t < meta_ref[1])
    def _():
        @pl.when((t == 0) | (te_ref[t] != te_ref[jnp.maximum(t - 1, 0)]))
        def _():
            wg_bf[...] = wg_ref[0, 0].astype(jnp.bfloat16)    # exact |q|<=127
            wu_bf[...] = wu_ref[0, 0].astype(jnp.bfloat16)
            wd_bf[...] = wd_ref[0, 0].astype(jnp.bfloat16)

        x = x_ref[...]
        h = jax.lax.dot(x, wg_bf[...],
                        preferred_element_type=jnp.float32) * gs_ref[0, 0]
        u = jax.lax.dot(x, wu_bf[...],
                        preferred_element_type=jnp.float32) * us_ref[0, 0]
        a = jax.nn.silu(h) * u * wslot_ref[...]               # [RT, I] f32
        y = jax.lax.dot(a.astype(jnp.bfloat16), wd_bf[...],
                        preferred_element_type=jnp.float32) * ds_ref[0, 0]
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def one_pass_moe_int8(
    x_sorted: jax.Array,    # [S_pad, H] bf16: rows in the padded layout
    wslot_pad: jax.Array,   # [S_pad, 1] f32 combine weights (0 = pad)
    tile_expert: jax.Array, # [NT] i32 expert of each tile (idle ones repeat)
    num_tiles: jax.Array,   # scalar i32: the tiles that hold a row
    layer,                  # scalar int32: plane of the stacked weights
    w_gate_q: jax.Array,    # [Lm, E, H, I] int8
    w_gate_s: jax.Array,    # [Lm, E, 1, I] f32
    w_up_q: jax.Array,
    w_up_s: jax.Array,
    w_down_q: jax.Array,    # [Lm, E, I, H] int8
    w_down_s: jax.Array,    # [Lm, E, 1, H] f32
    row_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:             # [S_pad, H] bf16: each row's weighted result
    """The grouped int8 expert FFN over rows already sorted by expert.

    The caller owns the layout and both row gathers
    (``ops.moe._one_pass_int8_kernel_path``).  Rows of a tile past
    ``num_tiles`` and pad rows hold nothing a caller may read."""
    S_pad, H = x_sorted.shape
    I = w_gate_q.shape[3]
    rt = row_tile
    NT = tile_expert.shape[0]
    # Rows are bf16: a tile is whole sublane pairs of 8.
    assert S_pad == NT * rt and rt % 16 == 0, (S_pad, NT, rt)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(num_tiles, jnp.int32)])

    def tile(t, meta_ref, te_ref):
        return (jnp.minimum(t, jnp.maximum(meta_ref[1] - 1, 0)), 0)

    def wmap(t, meta_ref, te_ref):
        return (meta_ref[0], te_ref[t], 0, 0)

    return pl.pallas_call(
        _one_pass_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(NT,),
            in_specs=[
                pl.BlockSpec((rt, H), tile),
                pl.BlockSpec((rt, 1), tile),
                pl.BlockSpec((1, 1, H, I), wmap),
                pl.BlockSpec((1, 1, H, I), wmap),
                pl.BlockSpec((1, 1, I, H), wmap),
                pl.BlockSpec((1, 1, 1, I), wmap),
                pl.BlockSpec((1, 1, 1, I), wmap),
                pl.BlockSpec((1, 1, 1, H), wmap),
            ],
            out_specs=pl.BlockSpec((rt, H), tile),
            scratch_shapes=[
                pltpu.VMEM((H, I), jnp.bfloat16),
                pltpu.VMEM((H, I), jnp.bfloat16),
                pltpu.VMEM((I, H), jnp.bfloat16),
            ]),
        out_shape=jax.ShapeDtypeStruct((S_pad, H), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(meta, tile_expert, x_sorted, wslot_pad,
      w_gate_q, w_up_q, w_down_q, w_gate_s, w_up_s, w_down_s)
