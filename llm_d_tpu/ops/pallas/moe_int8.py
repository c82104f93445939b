# llmd: ignore[PAL002] every block is its array's whole trailing dims: no tiling
"""Pallas TPU kernel: all-experts MoE FFN over STACKED int8 expert weights.

The decode-regime MoE FFN (see ``ops.moe._dense_expert_ffn``) computes
every expert against the whole (small) token batch because the op is
HBM-bound on expert weights.  With int8 weights the XLA path hits a wall:
``convert(int8 -> bf16)`` cannot fuse into a dot operand, so every layer
XLA materializes the dequantized tensors — int8 read + bf16 write + bf16
read-back is ~2.5x the quantized byte count, forfeiting exactly the
bandwidth the quantization bought (measured: ~0.37 ms/layer of pure
convert traffic at deepseek-v3-bench scale).

This kernel streams the int8 weights HBM->VMEM once (Pallas auto
double-buffers the per-expert blocks across the sequential expert grid)
and dequantizes on the MXU's doorstep:

  - int8 -> bf16 is EXACT (|q| <= 127), so the dots run on the raw
    integer weights;
  - the per-output-column scale applies to the small [T, I] f32 OUTPUT —
    numerically identical to dequant-then-dot (the scale is constant
    through the contraction) at a fraction of the VPU work.

The kernel takes the WHOLE STACKED [Lm, E, ...] weights plus a layer
index (scalar prefetch drives the BlockSpec index maps), exactly like the
attention kernels address the stacked KV cache: a per-layer dynamic-slice
feeding ``pallas_call`` would materialize a copy of every layer's weights
per step, re-buying the traffic the kernel exists to avoid.

The combine weight (zero for unrouted (token, expert) pairs) scales the
activations before the down projection, so the output accumulated across
the expert grid equals the routed MoE output exactly — same math as the
XLA dense path, same weight-only-int8 numerics as
``ops.quant.dequantize``.

Reference role: DeepGEMM's quantized grouped GEMMs
(docker/Dockerfile.cuda:53-54; wide-ep decode.yaml:129-130).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    layer_ref,    # [1] SMEM (scalar prefetch: MoE-layer plane)
    x_ref,        # [T, H]  bf16 (same block every step)
    comb_ref,     # [E, T]  f32  (whole transposed combine matrix; tiny)
    wg_ref,       # [1, 1, H, I] int8 (this layer+expert's gate tile)
    wu_ref,       # [1, 1, H, I] int8
    wd_ref,       # [1, 1, I, H] int8
    gs_ref,       # [1, 1, 1, I] f32
    us_ref,       # [1, 1, 1, I] f32
    ds_ref,       # [1, 1, 1, H] f32
    o_ref,        # [T, H] f32 (accumulated across the expert grid)
):
    e = pl.program_id(0)
    x = x_ref[...]                                        # [T, H] bf16
    wg = wg_ref[0, 0].astype(jnp.bfloat16)                # [H, I] exact
    wu = wu_ref[0, 0].astype(jnp.bfloat16)
    h = jax.lax.dot(x, wg,
                    preferred_element_type=jnp.float32) * gs_ref[0, 0]
    u = jax.lax.dot(x, wu,
                    preferred_element_type=jnp.float32) * us_ref[0, 0]
    a = jax.nn.silu(h) * u * comb_ref[e, :][:, None]      # [T, I] f32
    wd = wd_ref[0, 0].astype(jnp.bfloat16)                # [I, H] exact
    y = jax.lax.dot(a.astype(jnp.bfloat16), wd,
                    preferred_element_type=jnp.float32) * ds_ref[0, 0]

    @pl.when(e == 0)
    def _():
        o_ref[...] = y

    @pl.when(e > 0)
    def _():
        o_ref[...] += y


@functools.partial(jax.jit, static_argnames=("interpret",))
def dense_moe_int8(
    x: jax.Array,          # [T, H] bf16
    comb: jax.Array,       # [T, E] f32 combine weights (0 = unrouted)
    layer,                 # scalar int32: plane of the stacked weights
    w_gate_q: jax.Array,   # [Lm, E, H, I] int8
    w_gate_s: jax.Array,   # [Lm, E, 1, I] f32
    w_up_q: jax.Array,
    w_up_s: jax.Array,
    w_down_q: jax.Array,   # [Lm, E, I, H] int8
    w_down_s: jax.Array,   # [Lm, E, 1, H] f32
    interpret: bool = False,
) -> jax.Array:            # [T, H] f32
    T, H = x.shape
    Lm, E, _, I = w_gate_q.shape
    layer_arr = jnp.asarray([layer], jnp.int32)

    def wmap(e, layer_ref):
        return (layer_ref[0], e, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(E,),
        in_specs=[
            pl.BlockSpec((T, H), lambda e, *_: (0, 0)),
            pl.BlockSpec((E, T), lambda e, *_: (0, 0)),
            pl.BlockSpec((1, 1, H, I), wmap),
            pl.BlockSpec((1, 1, H, I), wmap),
            pl.BlockSpec((1, 1, I, H), wmap),
            pl.BlockSpec((1, 1, 1, I), wmap),
            pl.BlockSpec((1, 1, 1, I), wmap),
            pl.BlockSpec((1, 1, 1, H), wmap),
        ],
        out_specs=pl.BlockSpec((T, H), lambda e, *_: (0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),   # sequential accumulation
        interpret=interpret,
    )(layer_arr, x, comb.T.astype(jnp.float32),
      w_gate_q, w_up_q, w_down_q, w_gate_s, w_up_s, w_down_s)
