"""Pallas TPU kernels: the Mamba-1 selective scan, in place on the engine's
state pool (ops/ssm.py has the mathematics, the pool and the list of
pieces).

Mamba-1's decay is A[N, inner] by channel and state, so the recurrence is
no product of matrices (the SSD kernels of ``ssm_scan.py`` need one scalar
decay a head): a token's update is elementwise over the [N, inner] state,

    S_t = exp(dt_t A) * S_{t-1} + B_t (outer) (dt_t x_t)      y_t = C_t . S_t

exp on the EUP, the rest on the VPU, all float32, token after token.  The
state is held [N, blk]: the states on sublanes, a block of channels on
lanes.  Then dt and dt x are lane-dense rows, A a resident tile, S C a
sublane reduction, and the columns B_t and C_t come as [N, 1] tiles of
arrays laid out [..., N, 1] (a lane broadcast each; the layout costs HBM
padding on a few KiB a token and no transpose in the kernel).

``ssm1_chunk_scan``: a row's chunk is walked in PIECES of ``c`` tokens
(``ops.ssm.scan_pieces``).  A grid program is (one piece, one block of
channels), the channel blocks innermost, so a piece's B and C are fetched
once; the whole [N, inner] state of the row being walked stays in a VMEM
scratch from the row's first piece to its last, is read from the row's slot
before the first (or starts from zero) and written to it after every piece
(slot by scalar prefetch; pool aliased in and out).  Tokens past a piece's
length come with dt = 0 and dt x = 0: they leave the state as it is.

``ssm1_decode_update``: rows of one token; a grid program is (one row, one
block of channels): the slot's block read, updated and written back in
place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TOKENS_PER_GROUP = 8        # a float32 sublane tile of dt / dt x rows


def channel_block(inner: int) -> int:
    """Channels a grid program holds: the widest of 512 / 256 / 128 lanes
    that divides ``inner`` (0: none does)."""
    return next((b for b in (512, 256, 128) if inner % b == 0), 0)


def _scan_kernel(
    # scalar prefetch
    slot_ref,       # [NT] SMEM: the piece's row's slot of the pool
    first_ref,      # [NT] SMEM: 1 = first piece of its row's chunk; 2 = a
                    # dead piece past the list's end: nothing to do
    fresh_ref,      # [NT] SMEM: 1 = that chunk starts from a zero state
    layer_ref,      # [1]  SMEM
    # inputs
    dt_ref,         # [1, c, blk]   dt (0 past the piece's length)
    xdt_ref,        # [1, c, blk]   dt x
    b_ref,          # [1, c, N, 1]
    c_ref,          # [1, c, N, 1]
    a_ref,          # [N, blk]
    s_in_ref,       # [1, 1, N, blk]
    # outputs
    y_ref,          # [1, c, blk]
    s_out_ref,      # [1, 1, N, blk]
    # scratch
    carry,          # [CB, N, blk] float32: the row's state between pieces
):
    del slot_ref, layer_ref         # used by the index maps
    i, j = pl.program_id(0), pl.program_id(1)
    c = dt_ref.shape[1]

    @pl.when(first_ref[i] == 1)
    def _():
        carry[j] = jnp.where(fresh_ref[i] == 1, 0.0, s_in_ref[0, 0])

    @pl.when(first_ref[i] != 2)
    def _():
        a = a_ref[...]

        def group(g, s):
            t0 = pl.multiple_of(g * TOKENS_PER_GROUP, TOKENS_PER_GROUP)
            dt8 = dt_ref[0, pl.ds(t0, TOKENS_PER_GROUP), :]
            x8 = xdt_ref[0, pl.ds(t0, TOKENS_PER_GROUP), :]
            for k in range(TOKENS_PER_GROUP):
                s = jnp.exp(dt8[k:k + 1, :] * a) * s \
                    + b_ref[0, t0 + k] * x8[k:k + 1, :]
                y_ref[0, pl.ds(t0 + k, 1), :] = jnp.sum(
                    s * c_ref[0, t0 + k], axis=0, keepdims=True)
            return s

        s = jax.lax.fori_loop(0, c // TOKENS_PER_GROUP, group, carry[j])
        carry[j] = s
        s_out_ref[0, 0] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm1_chunk_scan(
    dt: jax.Array,        # [NT, c, inner] float32, 0 past a piece's length
    xdt: jax.Array,       # [NT, c, inner] float32: dt x
    A: jax.Array,         # [N, inner] float32, < 0
    B: jax.Array,         # [NT, c, N]
    C: jax.Array,         # [NT, c, N]
    pool: jax.Array,      # [L, slots, N, inner] float32
    layer: jax.Array,     # i32 scalar
    slot: jax.Array,      # [NT] i32: the piece's row's slot (0: a dead piece)
    first: jax.Array,     # [NT] bool: first piece of its row's chunk
    fresh: jax.Array,     # [NT] bool: that chunk starts from zero
    live: jax.Array,      # [NT] bool: a piece of the list (the dead ones
                          # behind it are skipped: their y is not written)
    interpret: bool = False,
):
    """Returns (S_t C_t by piece [NT, c, inner] float32, the pool with each
    row's slot of plane ``layer`` holding the state after its chunk)."""
    NT, c, inner = dt.shape
    N = A.shape[0]
    blk = channel_block(inner)
    CB = inner // blk

    def piece_rows():
        return pl.BlockSpec((1, c, blk), lambda i, j, *_: (i, 0, j))

    def piece_cols():
        return pl.BlockSpec((1, c, N, 1), lambda i, j, *_: (i, 0, 0, 0))

    def state():
        return pl.BlockSpec(
            (1, 1, N, blk),
            lambda i, j, slot, first, fresh, layer:
            (layer[0], slot[i], 0, j))

    y, pool = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(NT, CB),
            in_specs=[piece_rows(), piece_rows(), piece_cols(), piece_cols(),
                      pl.BlockSpec((N, blk), lambda i, j, *_: (0, j)),
                      state()],
            out_specs=[piece_rows(), state()],
            scratch_shapes=[pltpu.VMEM((CB, N, blk), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((NT, c, inner), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # Operand indices in input_output_aliases include scalar prefetch.
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            has_side_effects=True),
        name="ssm1_chunk_scan",
        interpret=interpret,
    )(slot.astype(jnp.int32),
      jnp.where(live, first.astype(jnp.int32), 2),
      fresh.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      dt.astype(jnp.float32), xdt.astype(jnp.float32),
      B.astype(jnp.float32)[..., None], C.astype(jnp.float32)[..., None],
      A.astype(jnp.float32), pool)
    return y, pool


def _update_kernel(
    # scalar prefetch
    slot_ref,       # [S] SMEM: the row's slot of the pool
    fresh_ref,      # [S] SMEM: 1 = the row starts from a zero state
    layer_ref,      # [1] SMEM: the pool's layer plane
    # inputs
    dt_ref,         # [1, 1, blk]
    xdt_ref,        # [1, 1, blk]  dt x
    b_ref,          # [1, N, 1]
    c_ref,          # [1, N, 1]
    a_ref,          # [N, blk]
    s_in_ref,       # [1, 1, N, blk]
    # outputs
    y_ref,          # [1, 1, blk]
    s_out_ref,      # [1, 1, N, blk]
):
    del slot_ref, layer_ref         # used by the index maps
    keep = fresh_ref[pl.program_id(0)] == 0
    s0 = jnp.where(keep, s_in_ref[0, 0], 0.0)
    s1 = jnp.exp(dt_ref[0] * a_ref[...]) * s0 + b_ref[0] * xdt_ref[0]
    s_out_ref[0, 0] = s1
    y_ref[0] = jnp.sum(s1 * c_ref[0], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm1_decode_update(
    dt: jax.Array,        # [S, inner] float32
    xdt: jax.Array,       # [S, inner] float32: dt x of each row's one token
    A: jax.Array,         # [N, inner] float32
    B: jax.Array,         # [S, N]
    C: jax.Array,         # [S, N]
    pool: jax.Array,      # [L, slots, N, inner] float32
    layer: jax.Array,     # i32 scalar
    slot: jax.Array,      # [S] i32 (0: the trash slot, for rows to skip)
    fresh: jax.Array,     # [S] bool: start from zero, whatever the slot holds
    interpret: bool = False,
):
    """Returns (S_t C_t [S, inner] float32, the pool with the rows' slots of
    plane ``layer`` holding S_t)."""
    S, inner = dt.shape
    N = A.shape[0]
    blk = channel_block(inner)

    def rows():
        return pl.BlockSpec((1, 1, blk), lambda s, j, *_: (s, 0, j))

    def cols():
        return pl.BlockSpec((1, N, 1), lambda s, j, *_: (s, 0, 0))

    def state():
        return pl.BlockSpec(
            (1, 1, N, blk),
            lambda s, j, slot, fresh, layer: (layer[0], slot[s], 0, j))

    y, pool = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, inner // blk),
            in_specs=[rows(), rows(), cols(), cols(),
                      pl.BlockSpec((N, blk), lambda s, j, *_: (0, j)),
                      state()],
            out_specs=[rows(), state()]),
        out_shape=[jax.ShapeDtypeStruct((S, 1, inner), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # Operand indices in input_output_aliases include scalar prefetch.
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            has_side_effects=True),
        name="ssm1_decode_update",
        interpret=interpret,
    )(slot.astype(jnp.int32), fresh.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      dt.astype(jnp.float32)[:, None, :], xdt.astype(jnp.float32)[:, None, :],
      B.astype(jnp.float32)[..., None], C.astype(jnp.float32)[..., None],
      A.astype(jnp.float32), pool)
    return y[:, 0], pool
