"""Pallas TPU kernel: a linear-attention layer's one-token delta-rule update,
in place on the engine's state pool (ops/linear_attention.py has the
mathematics and the pool).

A decode row's whole state is read and written once a token and layer
(2 MiB at 32 heads x 128 x 128 float32), against a few KiB of inputs: the
computation is bound by HBM.  XLA's way (gather the rows' states, update,
scatter them back) moves them three times.  Here each grid program (one
row, eight heads) takes its block of the pool by the row's SLOT, named by
scalar prefetch in the block's index map, updates it in VMEM and writes it
back to the same place (``input_output_aliases``, as ``ssm_decode_update``
does): one read and one write, pipelined by Pallas across programs.

A head's state is held [K, V]: the key size on sublanes, the value size on
lanes.  Then v, the write u and the output o are lane-dense rows, and the
three vectors that run down the key axis (the decay exp g, k and q) come
from their rows by one aligned transpose each:

    S = S0 * exp(g) (down the sublanes)
    u = beta (v - sum_K S * k);   S1 = S + k (outer) u;   o = sum_K S1 * q
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEADS_PER_PROGRAM = 8


def _update_kernel(
    # scalar prefetch
    slot_ref,       # [S] SMEM: the row's slot of the pool
    fresh_ref,      # [S] SMEM: 1 = the row starts from a zero state
    layer_ref,      # [1] SMEM: the pool's layer plane
    # inputs
    q_ref,          # [1, hb, K]
    k_ref,          # [1, hb, K]
    g_ref,          # [1, hb, K]  the log-decay
    v_ref,          # [1, hb, V]
    beta_ref,       # [1, hb, V]  the same value along a row
    s_in_ref,       # [1, 1, hb, K, V]
    # outputs
    o_ref,          # [1, hb, V]
    s_out_ref,      # [1, 1, hb, K, V]
):
    del slot_ref, layer_ref         # used by the index maps
    hb, K, V = s_in_ref.shape[2:]
    keep = fresh_ref[pl.program_id(0)] == 0

    def column(row):
        """[1, K] -> [K, V]: the row's values down the sublanes, the same
        in every lane (an aligned transpose of the row laid V times)."""
        return jnp.broadcast_to(row, (V, K)).T

    for h in range(hb):
        s = jnp.where(keep, s_in_ref[0, 0, h], 0.0) \
            * column(jnp.exp(g_ref[0, h:h + 1, :]))             # [K, V]
        k_col = column(k_ref[0, h:h + 1, :])
        u = beta_ref[0, h:h + 1, :] * (
            v_ref[0, h:h + 1, :] - jnp.sum(s * k_col, axis=0, keepdims=True))
        s1 = s + k_col * u
        s_out_ref[0, 0, h] = s1
        o_ref[0, h:h + 1, :] = jnp.sum(
            s1 * column(q_ref[0, h:h + 1, :]), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_decode_update(
    q: jax.Array,         # [S, H, K] float32 (normed, scaled)
    k: jax.Array,         # [S, H, K] float32 (normed)
    v: jax.Array,         # [S, H, V] float32
    g: jax.Array,         # [S, H, K] float32: the log-decay, <= 0
    beta: jax.Array,      # [S, H] float32
    pool: jax.Array,      # [L, slots, H, K, V] float32
    layer: jax.Array,     # i32 scalar
    slot: jax.Array,      # [S] i32 (0: the trash slot, for rows to skip)
    fresh: jax.Array,     # [S] bool: start from zero, whatever the slot holds
    interpret: bool = False,
):
    """Returns (o [S, H, V] float32, the pool with the rows' slots of plane
    ``layer`` holding S_t).  Geometry: ``ops.linear_attention.
    pallas_ineligible_reason``."""
    S, H, K = q.shape
    V = v.shape[-1]
    hb = HEADS_PER_PROGRAM

    def rows(width):
        return pl.BlockSpec((1, hb, width), lambda s, j, *_: (s, j, 0))

    def state():
        return pl.BlockSpec(
            (1, 1, hb, K, V),
            lambda s, j, slot, fresh, layer: (layer[0], slot[s], j, 0, 0))

    o, pool = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, H // hb),
            in_specs=[rows(K), rows(K), rows(K), rows(V), rows(V), state()],
            out_specs=[rows(V), state()]),
        out_shape=[jax.ShapeDtypeStruct((S, H, V), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # Operand indices in input_output_aliases include scalar prefetch.
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            has_side_effects=True),
        name="delta_decode_update",
        interpret=interpret,
    )(slot.astype(jnp.int32), fresh.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      q.astype(jnp.float32), k.astype(jnp.float32), g.astype(jnp.float32),
      v.astype(jnp.float32),
      jnp.broadcast_to(beta.astype(jnp.float32)[:, :, None], (S, H, V)), pool)
    return o, pool
