"""Pallas TPU kernel: the state-space mixer's chunked scan (SSD) over a
ragged packed batch of prompt chunks, the state carried through the engine's
state pool in place (ops/ssm.py has the mathematics, the pool and the list
of pieces).

A row's chunk is walked in PIECES of ``c`` tokens (the model's scan chunk,
128), laid out by XLA as ``[pieces, c, ...]`` (``ops.ssm.scan_pieces``:
consecutive pieces of one row are consecutive in the list).  A grid program
is (eight heads of one group, one piece), the pieces innermost: the eight
states [N, P] stay in VMEM from a row's first piece to its last, are read
from the row's slot of the pool before the first (or start from zero) and
written back to it after the last, by the block index maps (the slot comes
by scalar prefetch; pool aliased in and out, as the one-token update).

Inside a piece the recurrence is three MXU dots a head, bf16 operands with
float32 accumulation, and the decay factors on the VPU in float32:

    y  = ((C B^T) * decay(i, j) * [j <= i]) (dt x)  +  exp(cum_i) C S_0
    S' = exp(cum_c) S_0 + B^T (exp(cum_c - cum_j) dt x)

with cum the running sum of dt A inside the piece (<= 0).  Tokens past a
piece's length come with dt = 0: they leave the state as it is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_d_tpu.ops.pallas.ssm_update import HEADS_PER_PROGRAM


def _scan_kernel(
    # scalar prefetch
    slot_ref,       # [NT] SMEM: the piece's row's slot of the pool
    first_ref,      # [NT] SMEM: 1 = first piece of its row's chunk; 2 = a
                    # dead piece past the list's end: nothing to do
    fresh_ref,      # [NT] SMEM: 1 = that chunk starts from a zero state
    layer_ref,      # [1]  SMEM
    # inputs
    xdt_ref,        # [1, c, hb * P]  dt x
    b_ref,          # [1, c, N]
    c_ref,          # [1, c, N]
    cum_col_ref,    # [1, 1, c, hb]   running sum of dt A, tokens on sublanes
    cum_row_ref,    # [1, 1, hb, c]   the same, tokens on lanes
    s_in_ref,       # [1, 1, hb, N, P]
    # outputs
    y_ref,          # [1, c, hb * P]
    s_out_ref,      # [1, 1, hb, N, P]
    # scratch
    carry,          # [hb, N, P] float32: the states between pieces
):
    del slot_ref, layer_ref         # used by the index maps
    i = pl.program_id(1)

    @pl.when(first_ref[i] == 1)
    def _():
        carry[...] = jnp.where(fresh_ref[i] == 1, 0.0, s_in_ref[0, 0])

    @pl.when(first_ref[i] != 2)
    def _():
        _piece(xdt_ref, b_ref, c_ref, cum_col_ref, cum_row_ref, y_ref,
               s_out_ref, carry)


def _piece(xdt_ref, b_ref, c_ref, cum_col_ref, cum_row_ref, y_ref, s_out_ref,
           carry):
    hb, N, P = carry.shape
    c = b_ref.shape[1]
    bs, cs = b_ref[0], c_ref[0]                                 # [c, N]
    scores = jax.lax.dot_general(
        cs, bs, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                     # [c, c]
    tri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    for h in range(hb):
        cum_c = cum_col_ref[0, 0, :, h:h + 1]                   # [c, 1]
        cum_r = cum_row_ref[0, 0, h:h + 1, :]                   # [1, c]
        last = cum_r[:, c - 1:c]                                # [1, 1]
        w = jnp.where(tri, jnp.exp(jnp.minimum(cum_c - cum_r, 0.0)), 0.0) \
            * scores
        xh = xdt_ref[0, :, h * P:(h + 1) * P]                   # [c, P]
        s0 = carry[h]                                           # [N, P]
        y = jnp.dot(w.astype(xh.dtype), xh,
                    preferred_element_type=jnp.float32)
        y = y + jnp.exp(cum_c) * jnp.dot(
            cs, s0.astype(cs.dtype), preferred_element_type=jnp.float32)
        xw = (xh.astype(jnp.float32) * jnp.exp(last - cum_c)).astype(xh.dtype)
        # ([1, 1] to [1, P] first: Mosaic broadcasts along one axis a time.)
        s1 = jnp.exp(jnp.broadcast_to(last, (1, P))) * s0 \
            + jax.lax.dot_general(
            bs, xw, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [N, P]
        carry[h] = s1
        s_out_ref[0, 0, h] = s1
        y_ref[0, :, h * P:(h + 1) * P] = y


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_chunk_scan(
    xdt: jax.Array,       # [NT, c, H, P]  dt x, by piece (0 past its length)
    B: jax.Array,         # [NT, c, G, N]
    C: jax.Array,         # [NT, c, G, N]
    cum: jax.Array,       # [NT, c, H] float32: running sum of dt A in a piece
    pool: jax.Array,      # [L, slots, H, N, P] float32
    layer: jax.Array,     # i32 scalar
    slot: jax.Array,      # [NT] i32: the piece's row's slot (0: a dead piece)
    first: jax.Array,     # [NT] bool: first piece of its row's chunk
    fresh: jax.Array,     # [NT] bool: that chunk starts from zero
    live: jax.Array,      # [NT] bool: a piece of the list (the dead ones
                          # behind it are skipped: their y is not written)
    interpret: bool = False,
):
    """Returns (S_t C_t by piece [NT, c, H, P] float32, the pool with each
    row's slot of plane ``layer`` holding the state after its chunk)."""
    NT, c, H, P = xdt.shape
    G, N = B.shape[2], B.shape[3]
    hb = HEADS_PER_PROGRAM
    HB = H // hb
    per_group = H // G // hb

    def piece_heads():
        return pl.BlockSpec((1, c, hb * P), lambda j, i, *_: (i, 0, j))

    def piece_group():
        return pl.BlockSpec((1, c, N),
                            lambda j, i, *_: (i, 0, j // per_group))

    def state():
        return pl.BlockSpec(
            (1, 1, hb, N, P),
            lambda j, i, slot, first, fresh, layer:
            (layer[0], slot[i], j, 0, 0))

    cum_col = cum.reshape(NT, c, HB, hb).transpose(0, 2, 1, 3)
    y, pool = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(HB, NT),
            in_specs=[
                piece_heads(), piece_group(), piece_group(),
                pl.BlockSpec((1, 1, c, hb), lambda j, i, *_: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, hb, c), lambda j, i, *_: (i, j, 0, 0)),
                state()],
            out_specs=[piece_heads(), state()],
            scratch_shapes=[pltpu.VMEM((hb, N, P), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((NT, c, H * P), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # Operand indices in input_output_aliases include scalar prefetch.
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            has_side_effects=True),
        name="ssm_chunk_scan",
        interpret=interpret,
    )(slot.astype(jnp.int32),
      jnp.where(live, first.astype(jnp.int32), 2),
      fresh.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      xdt.reshape(NT, c, H * P), B.reshape(NT, c, G * N),
      C.reshape(NT, c, G * N), cum_col, cum_col.transpose(0, 1, 3, 2), pool)
    return y.reshape(NT, c, H, P), pool
