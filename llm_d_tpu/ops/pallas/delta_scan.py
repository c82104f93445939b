"""Pallas TPU kernel: the walk over the PIECES of a linear-attention layer's
chunked form, the state carried through the engine's state pool in place
(ops/linear_attention.py has the mathematics, the pool and ``piece_terms``;
ops/ssm.py the list of pieces).

XLA computes, for every piece and head at once, what does not depend on the
state a piece starts from (``W``, ``U0``, ``P``, ``Q+``, ``Kend``, ``exp
G_C``).  What is left is sequential in the pieces of one row and four dots a
piece and head:

    U = U0 - W S0;   O = Q+ S0 + P U;   S1 = Diag(exp G_C) S0 + Kend^T U

A grid program is (eight heads, one piece), the pieces innermost: the eight
states [K, V] stay in VMEM from a row's first piece to its last, are read
from the row's slot of the pool before the first (or start from zero) and
written back to it after every piece, by the block index maps (the slot
comes by scalar prefetch; pool aliased in and out, as the one-token
update).  Every dot meets the float32 state: float32 operands, ``HIGHEST``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_d_tpu.ops.pallas.delta_update import HEADS_PER_PROGRAM

EXACT = jax.lax.Precision.HIGHEST


def _scan_kernel(
    # scalar prefetch
    slot_ref,       # [NT] SMEM: the piece's row's slot of the pool
    first_ref,      # [NT] SMEM: 1 = first piece of its row's chunk; 2 = a
                    # dead piece past the list's end: nothing to do
    fresh_ref,      # [NT] SMEM: 1 = that chunk starts from a zero state
    layer_ref,      # [1]  SMEM
    # inputs
    w_ref,          # [1, hb, c, K]
    u0_ref,         # [1, hb, c, V]
    p_ref,          # [1, hb, c, c]
    qp_ref,         # [1, hb, c, K]
    kend_ref,       # [1, hb, c, K]
    gam_ref,        # [1, hb, 1, K]   exp G_C
    s_in_ref,       # [1, 1, hb, K, V]
    # outputs
    o_ref,          # [1, hb, c, V]
    s_out_ref,      # [1, 1, hb, K, V]
    # scratch
    carry,          # [hb, K, V] float32: the states between pieces
):
    del slot_ref, layer_ref         # used by the index maps
    i = pl.program_id(1)

    @pl.when(first_ref[i] == 1)
    def _():
        carry[...] = jnp.where(fresh_ref[i] == 1, 0.0, s_in_ref[0, 0])

    @pl.when(first_ref[i] != 2)
    def _():
        hb, K, V = carry.shape
        for h in range(hb):
            s0 = carry[h]                                       # [K, V]
            u = u0_ref[0, h] - jnp.dot(
                w_ref[0, h], s0, precision=EXACT,
                preferred_element_type=jnp.float32)             # [c, V]
            o_ref[0, h] = jnp.dot(
                qp_ref[0, h], s0, precision=EXACT,
                preferred_element_type=jnp.float32) + jnp.dot(
                p_ref[0, h], u, precision=EXACT,
                preferred_element_type=jnp.float32)
            # exp G_C down the sublanes: an aligned transpose of its row.
            gam = jnp.broadcast_to(gam_ref[0, h], (V, K)).T
            s1 = gam * s0 + jax.lax.dot_general(
                kend_ref[0, h], u, (((0,), (0,)), ((), ())), precision=EXACT,
                preferred_element_type=jnp.float32)             # [K, V]
            carry[h] = s1
            s_out_ref[0, 0, h] = s1


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_chunk_scan(
    W: jax.Array,         # [NT, H, c, K] float32
    U0: jax.Array,        # [NT, H, c, V]
    P: jax.Array,         # [NT, H, c, c]
    Qp: jax.Array,        # [NT, H, c, K]
    Kend: jax.Array,      # [NT, H, c, K]
    gam: jax.Array,       # [NT, H, K]
    pool: jax.Array,      # [L, slots, H, K, V] float32
    layer: jax.Array,     # i32 scalar
    slot: jax.Array,      # [NT] i32: the piece's row's slot (0: a dead piece)
    first: jax.Array,     # [NT] bool: first piece of its row's chunk
    fresh: jax.Array,     # [NT] bool: that chunk starts from zero
    live: jax.Array,      # [NT] bool: a piece of the list (the dead ones
                          # behind it are skipped: their o is not written)
    interpret: bool = False,
):
    """Returns (o by piece [NT, H, c, V] float32, the pool with each row's
    slot of plane ``layer`` holding the state after its chunk)."""
    NT, H, c, K = W.shape
    V = U0.shape[-1]
    hb = HEADS_PER_PROGRAM

    def piece(*tail):
        return pl.BlockSpec((1, hb, *tail), lambda j, i, *_: (i, j, 0, 0))

    def state():
        return pl.BlockSpec(
            (1, 1, hb, K, V),
            lambda j, i, slot, first, fresh, layer:
            (layer[0], slot[i], j, 0, 0))

    o, pool = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(H // hb, NT),
            in_specs=[piece(c, K), piece(c, V), piece(c, c), piece(c, K),
                      piece(c, K), piece(1, K), state()],
            out_specs=[piece(c, V), state()],
            scratch_shapes=[pltpu.VMEM((hb, K, V), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((NT, H, c, V), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # Operand indices in input_output_aliases include scalar prefetch.
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            has_side_effects=True),
        name="delta_chunk_scan",
        interpret=interpret,
    )(slot.astype(jnp.int32),
      jnp.where(live, first.astype(jnp.int32), 2),
      fresh.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      W, U0, P, Qp, Kend, gam.reshape(NT, H, 1, K), pool)
    return o, pool
