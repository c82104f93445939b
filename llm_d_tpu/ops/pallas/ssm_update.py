"""Pallas TPU kernel: the state-space mixer's one-token update, in place on
the engine's state pool (ops/ssm.py has the mathematics and the pool).

A decode row's whole state is read and written once a token and layer
(4 MiB at 32 heads x 256 x 128 float32), against a few KiB of inputs: the
computation is bound by HBM.  XLA's way (gather the rows' states, update,
scatter them back) moves them three times and materialises two copies.
Here each grid program (one row, eight heads of one group) takes its block
of the pool by the row's SLOT, named by scalar prefetch in the block's
index map, updates it in VMEM and writes it back to the same place
(``input_output_aliases``, as ``paged_attention_decode_update`` does for
pages): one read and one write, pipelined by Pallas across programs.

A head's state is held [N, P]: the state size on sublanes, the head size on
lanes.  Then x and y are lane-dense rows, the outer product dt x (x) B is a
row times a column, and S C contracts over sublanes (adds across vregs and
one sublane reduce).  The columns B and C come from their rows by one
aligned transpose a program, shared by its eight heads.
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
    xdt_ref,        # [1, hb, P]  dt x
    da_ref,         # [1, hb, P]  exp(dt A), the same value along a row
    b_ref,          # [1, 1, 1, N]
    c_ref,          # [1, 1, 1, N]
    s_in_ref,       # [1, 1, hb, N, P]
    # outputs
    y_ref,          # [1, hb, P]
    s_out_ref,      # [1, 1, hb, N, P]
):
    del slot_ref, layer_ref         # used by the index maps
    hb, N, P = s_in_ref.shape[2:]
    keep = fresh_ref[pl.program_id(0)] == 0

    def column(ref):
        """[1, N] -> [N, P]: the row's values down the sublanes, the same
        in every lane (an aligned transpose of the row laid P times)."""
        return jnp.broadcast_to(ref[0, 0].astype(jnp.float32), (P, N)).T

    b_col, c_col = column(b_ref), column(c_ref)
    for h in range(hb):
        s0 = jnp.where(keep, s_in_ref[0, 0, h], 0.0)            # [N, P]
        s1 = s0 * da_ref[0, h:h + 1, :] + b_col * xdt_ref[0, h:h + 1, :]
        s_out_ref[0, 0, h] = s1
        y_ref[0, h:h + 1, :] = jnp.sum(s1 * c_col, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode_update(
    xdt: jax.Array,       # [S, H, P] float32: dt x of each row's one token
    dA: jax.Array,        # [S, H] float32: exp(dt A)
    B: jax.Array,         # [S, G, N]
    C: jax.Array,         # [S, G, N]
    pool: jax.Array,      # [L, slots, H, N, P] float32
    layer: jax.Array,     # i32 scalar
    slot: jax.Array,      # [S] i32 (0: the trash slot, for rows to skip)
    fresh: jax.Array,     # [S] bool: start from zero, whatever the slot holds
    interpret: bool = False,
):
    """Returns (S_t C_t [S, H, P] float32, the pool with the rows' slots of
    plane ``layer`` holding S_t).  Geometry: ``ops.ssm.
    pallas_ineligible_reason``."""
    S, H, P = xdt.shape
    G, N = B.shape[1], B.shape[2]
    hb = HEADS_PER_PROGRAM
    per_group = H // G // hb            # programs a group

    def rows(shape):
        return pl.BlockSpec(shape, lambda s, j, *_: (s, j, 0))

    def group_row():
        return pl.BlockSpec(
            (1, 1, 1, N), lambda s, j, *_: (s, j // per_group, 0, 0))

    def state():
        return pl.BlockSpec(
            (1, 1, hb, N, P),
            lambda s, j, slot, fresh, layer: (layer[0], slot[s], j, 0, 0))

    y, pool = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, H // hb),
            in_specs=[rows((1, hb, P)), rows((1, hb, P)), group_row(),
                      group_row(), state()],
            out_specs=[rows((1, hb, P)), state()]),
        out_shape=[jax.ShapeDtypeStruct((S, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # Operand indices in input_output_aliases include scalar prefetch.
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            has_side_effects=True),
        name="ssm_decode_update",
        interpret=interpret,
    )(slot.astype(jnp.int32), fresh.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      xdt.astype(jnp.float32),
      jnp.broadcast_to(dA.astype(jnp.float32)[:, :, None], (S, H, P)),
      B.reshape(S, G, 1, N), C.reshape(S, G, 1, N), pool)
    return y, pool
