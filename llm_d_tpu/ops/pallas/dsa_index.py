"""Pallas TPU kernel for the indexer of a layer that selects its keys: a
query tile's index scores, their exact top-k and the bias the masked flash
kernel reads, with the scores in VMEM from the dots to the bias.

The XLA form (``ops.sparse_mla.index_select``) writes f32 scores [tiles,
slots, C] to HBM for every tile against the batch's LONGEST context, reads
an integer image of them 23 times to find each query's threshold, and
hands a bool mask on to be rewritten as a bias: its shapes are static, so
it cannot bound a tile's work by the tile.  Here the grid walks the step's
query tiles (``ops.attention.query_tiles``, the list the masked kernel
walks) and a tile's work ends at its own ``live`` (its last query's
position + 1, capped by its row's length):

  1. the walk: key blocks of INDEX_BLOCK keys of the tile's row, from block
     0 to ``live``, out of the row's keys gathered once a row, layer and
     step by XLA ([S, C, Di], ``ops.sparse_mla.row_index_keys``: a page of
     index keys is 8 KiB, so a copy a page inside the walk would be bound
     by issuing copies).  The blocks a row's tiles have fetched STAY in
     VMEM ([C, Di], 8 MiB at 32,768 positions): a 2,048-token chunk is 256
     consecutive tiles of one row, each walks the blocks the one before it
     walked and fetches at most one more, under its other blocks' dots (a
     block a copy a TILE left the walk waiting for a 128 KiB copy's latency
     at every block, PERF.md PR 43).  A block's scores sum_j w[t, j]
     relu(qI[t, j] . kI[s]) are one MXU dot of the tile's fused rows
     against the block, the relu, the head weights and the sum over heads
     on the VPU, f32.  The fused rows lie HEAD-major (row j * SLOTS +
     slot), so a vreg of the dot's result holds the SLOTS queries of one
     head at 128 keys and the sum over heads is a chain of vreg adds with
     nothing crossing sublanes.  The scores' order-preserving integer image
     (``ops.sparse_mla.choose_topk``'s, as signed int32) lands in a VMEM
     scratch [SLOTS, C], -inf where the slot does not see the key, and
     never in HBM;
  2. the exact top-k on that scratch, over the walked columns only: the
     k-th largest image by a search THRESHOLD_BITS bits a pass (a compare
     and a count a candidate), then, only where equal scores straddle the
     threshold of some slot, the last tied column kept by the same search
     over columns: the set ``lax.top_k`` gives, every visible key while
     fewer than ``topk`` are;
  3. the bias, once, in the layout ``mla_masked_attention`` reads ([NT,
     C / KEY_BLOCK, Qt, KEY_BLOCK] f32, 0 / NEG_INF), for the walked blocks
     only: the masked kernel's walk ends at the same ``live``, so the rest
     of a tile's row is never read (and holds whatever the buffer held).

One body for every tile height: the kernel's tile always holds SLOTS = 8
slots (the f32 sublanes; a pure-decode step's tile of one slot is padded
with empty ones), the dot always has the same shape, and a slot's sum over
heads, its counts and its threshold are its own sublane's: a decode row
and its fresh prefill select the same set by construction.

Loops over key blocks, passes and candidates' counts are ``lax.fori_loop``s
with dynamic offsets, scalar arithmetic ``lax.div`` / ``rem``: Pallas
lowers the body again in every step program (PERF.md PR 42).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_d_tpu.ops.pallas.mla_masked import NEG_INF

# Query slots the kernel's tile holds: the sublanes of an f32 vreg.
SLOTS = 8
# Keys a step of the walk scores and a step of a search pass counts, where
# the table's width allows (else one KEY_BLOCK).  By measurement (PERF.md
# PR 43: 512 -> 2,048 keys took a quarter off a layer's call; what a step
# of a loop costs beside its work outweighs the half block walked for
# nothing).
INDEX_BLOCK = 2048
# Bits of a threshold a pass of the search settles (2**bits - 1 counts in
# one read of the scratch); a divisor of 32.
THRESHOLD_BITS = 2
# Copies of key blocks a tile keeps in flight ahead of its walk.
KEY_COPIES = 4
# The scoped VMEM a call may take: the row's keys (8 MiB at 32,768
# positions of 128), the image (1 MiB), the bias block double buffered
# (2 MiB), a block's scores of the fused rows and their weighted relu
# (4 MiB each at 512 rows x 2,048 keys).
VMEM_LIMIT = 48 << 20
_SIGN = -(1 << 31)


def index_block(table_keys: int, key_block: int) -> int:
    """Keys a step of the walk covers for a block table of ``table_keys``
    positions, whole ``key_block``s of the bias
    (``mla_masked.ineligible_reason``): INDEX_BLOCK where both divide."""
    whole = table_keys % INDEX_BLOCK == 0 and INDEX_BLOCK % key_block == 0
    return INDEX_BLOCK if whole else key_block


def unwritten(shape, dtype, interpret: bool = False) -> jax.Array:
    """An array nobody wrote: the buffer of a kernel that stores nothing,
    for a loop to fill as far as it is read (``jnp.zeros`` of the rows'
    keys, 128 MiB at 16 rows of 32,768, is 0.2 ms a layer)."""
    return pl.pallas_call(
        lambda o_ref: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY), interpret=interpret)()


def _index_kernel(
    # scalar prefetch
    tile_seq_ref,       # [NT] SMEM: the row of ``keys`` of each query tile
    tile_live_ref,      # [NT] SMEM: the key the tile's walk ends before
    # inputs
    q_ref,              # [1, Hi*SLOTS, Di] fused rows, head-major
    w_ref,              # [1, Hi*SLOTS, lanes] f32 head weights over lanes
    pos_ref,            # [1, SLOTS, lanes] i32 a slot's position, -1: pad
    keys_hbm,           # [S, C, Di] each row's index keys by position
    # outputs
    bias_ref,           # [1, C / KB, Qt, KB] f32
    # scratch
    key_buf,            # [C, Di]: the row's key blocks fetched so far
    sems,               # a copy in flight each
    img_buf,            # [SLOTS, C] i32: the scores' integer image
    held_ref,           # [2] SMEM: the row ``key_buf`` holds, its blocks
    *,
    topk: int,
    index_block: int,
):
    n = pl.program_id(0)
    row = tile_seq_ref[n]
    live = tile_live_ref[n]
    IB = index_block
    Qt, KB = bias_ref.shape[2:]
    lanes = w_ref.shape[2]
    C = img_buf.shape[1]
    n_copies = sems.shape[0]
    n_blocks = jax.lax.div(live + (IB - 1), IB)
    r = THRESHOLD_BITS

    def span(i):
        return pl.ds(pl.multiple_of(i * IB, IB), IB)

    def copy(i):
        at = jax.lax.rem(i, n_copies)
        return pltpu.make_async_copy(
            keys_hbm.at[row, span(i)], key_buf.at[span(i)], sems.at[at])

    # The row's key blocks this call has fetched stay in ``key_buf``: a
    # chunk's next tile walks them again and fetches at most one more.
    @pl.when((n == 0) | (held_ref[0] != row))
    def _():
        held_ref[0] = row
        held_ref[1] = 0

    have = held_ref[1]

    def first(i, carry):
        copy(i).start()
        return carry

    jax.lax.fori_loop(have, jnp.minimum(n_blocks, have + n_copies - 1),
                      first, 0)

    q, w = q_ref[0], w_ref[0]
    pos = jnp.concatenate([pos_ref[0]] * (IB // lanes), axis=1)  # [8, IB]
    lane = jax.lax.broadcasted_iota(jnp.int32, (SLOTS, IB), 1)

    def seen(i):
        col = lane + i * IB
        return col, (col <= pos) & (col < live)

    def score(i, carry):
        @pl.when(i >= have)
        def _():
            @pl.when(i + (n_copies - 1) < n_blocks)
            def _():
                copy(i + (n_copies - 1)).start()

            copy(i).wait()

        s = jax.lax.dot_general(
            q, key_buf[span(i), :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [Hi*8, IB]
        # A head's SLOTS rows are one f32 vreg a lane tile: the sum over
        # heads adds vregs.
        s = jnp.concatenate([jnp.sum(
            (jnp.maximum(s[:, c * lanes:(c + 1) * lanes], 0.0) * w).reshape(
                -1, SLOTS, lanes), axis=0)
            for c in range(IB // lanes)], axis=1)             # [SLOTS, IB]
        s = jnp.where(seen(i)[1], s, -jnp.inf)
        b = pltpu.bitcast(jnp.where(s == 0, 0.0, s), jnp.int32)  # -0.0 is 0.0
        img_buf[:, span(i)] = jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)
        return carry

    jax.lax.fori_loop(0, n_blocks, score, 0)
    held_ref[1] = jnp.maximum(have, n_blocks)

    def count(holds, m):
        """[SLOTS, 1] i32 a predicate: the walked columns of which each of
        the ``m`` predicates ``holds(image, column)`` is true."""
        def chunk(j, accs):
            found = holds(img_buf[:, span(j)], lane + j * IB)
            return tuple(
                a + functools.reduce(jnp.add, [
                    h[:, c * lanes:(c + 1) * lanes].astype(jnp.int32)
                    for c in range(IB // lanes)])
                for a, h in zip(accs, found))
        accs = jax.lax.fori_loop(
            0, n_blocks, chunk,
            (jnp.zeros((SLOTS, lanes), jnp.int32),) * m)
        return [jnp.sum(a, axis=1, keepdims=True) for a in accs]

    def largest(holds, bits, passes=None):
        """As ``ops.sparse_mla._largest``: the largest t < 2**bits a slot
        ([SLOTS, 1] i32 read as unsigned) of which ``holds`` (candidates ->
        [SLOTS, 1] bools) is true, ``r`` bits a pass from the top."""
        n_pass = -(-bits // r)

        def settle(p, t):
            shift = (n_pass - 1 - p) * r
            oks = holds([t | jax.lax.shift_left(jnp.int32(d), shift)
                         for d in range(1, 1 << r)])
            # The candidates rise with the digit: those that hold are the
            # first ones, their number is the digit.
            digit = functools.reduce(
                jnp.add, [ok.astype(jnp.int32) for ok in oks])
            return t | jax.lax.shift_left(
                digit, jnp.full(digit.shape, shift, jnp.int32))

        return jax.lax.fori_loop(
            0, n_pass if passes is None else passes, settle,
            jnp.zeros((SLOTS, 1), jnp.int32))

    @pl.when(n_blocks > 0)
    def _():
        # The k-th largest image: the largest t that ``topk`` images reach
        # (unsigned t against signed images: the sign bit turned).
        def reached(ts):
            ts = [jnp.broadcast_to(t ^ jnp.int32(_SIGN), (SLOTS, IB))
                  for t in ts]
            return [c >= topk for c in count(
                lambda x, col: [x >= t for t in ts], len(ts))]

        kth = largest(reached, 32) ^ jnp.int32(_SIGN)
        n_above, n_reach = count(lambda x, col: [x > kth, x >= kth], 2)
        room = topk - n_above
        # Equal scores straddle a slot's threshold where more than ``topk``
        # visible keys reach it: the column of the ``room``-th tied one is
        # the largest c that fewer than ``room`` of them lie under.
        visible = jnp.minimum(pos_ref[0][:, :1] + 1, live)
        tied = (visible > topk) & (n_reach > topk)
        bits = max(C - 1, 1).bit_length()
        def under(cs):
            return [m < room for m in count(
                lambda x, col: [(x == kth) & (col < c) for c in cs], len(cs))]

        last = largest(under, bits, passes=jnp.where(
            jnp.sum(tied.astype(jnp.int32)) > 0, -(-bits // r), 0))
        last = jnp.where(tied, last, jnp.int32(0x7FFFFFFF))

        def emit(j, _):
            x = img_buf[:, span(j)]
            col, ok = seen(j)
            chosen = ok & ((x > kth) | ((x == kth) & (col <= last)))
            b = jnp.where(chosen, 0.0, NEG_INF).astype(jnp.float32)
            for h in range(IB // KB):
                bias_ref[0, j * (IB // KB) + h] = b[:Qt, h * KB:(h + 1) * KB]
            return _

        jax.lax.fori_loop(0, n_blocks, emit, 0)


@functools.partial(jax.jit, static_argnames=(
    "topk", "key_block", "index_block", "interpret"))
def index_bias(
    q_tiles: jax.Array,       # [NT, Qt, Hi, Di] the indexer's queries by tile
    w_tiles: jax.Array,       # [NT, Qt, Hi] f32 head weights
    pos_tiles: jax.Array,     # [NT, Qt] i32 each slot's position, -1: pad
    tile_seq: jax.Array,      # [NT] i32 row of ``keys`` of each tile
    tile_live: jax.Array,     # [NT] i32 the key each tile's walk ends before
    keys: jax.Array,          # [S, C, Di] each row's index keys by position
    topk: int,
    key_block: int,           # ``mla_masked.KEY_BLOCK``: keys a bias block
    index_block: int,         # keys a step of the walk (``index_block``)
    interpret: bool = False,
) -> jax.Array:               # [NT, C / key_block, Qt, key_block] f32
    """The bias ``mla_masked_attention`` reads for a layer that selects: 0
    where the slot's query attends to the key (among its ``topk`` visible
    keys of largest index score, equal scores to the lower position; every
    visible key while fewer are), NEG_INF elsewhere, over the key blocks
    under each tile's ``tile_live`` (whole ``index_block``s); the rest of
    a tile's row is not written."""
    NT, Qt, Hi, Di = q_tiles.shape
    S, C, _ = keys.shape
    KB, IB = key_block, index_block
    assert Qt <= SLOTS and C % IB == 0 and IB % KB == 0, (Qt, C, IB, KB)
    lanes = math.gcd(IB, 128)
    pad = ((0, 0), (0, SLOTS - Qt))
    # Head-major fused rows: row j * SLOTS + slot.
    q = jnp.pad(q_tiles, pad + ((0, 0), (0, 0))).transpose(
        0, 2, 1, 3).reshape(NT, Hi * SLOTS, Di)
    w = jnp.broadcast_to(
        jnp.pad(w_tiles.astype(jnp.float32), pad + ((0, 0),)).transpose(
            0, 2, 1).reshape(NT, Hi * SLOTS, 1), (NT, Hi * SLOTS, lanes))
    pos = jnp.broadcast_to(
        jnp.pad(pos_tiles, pad, constant_values=-1)[:, :, None],
        (NT, SLOTS, lanes))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(NT,),
        in_specs=[
            pl.BlockSpec((1, Hi * SLOTS, Di), lambda n, *_: (n, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Hi * SLOTS, lanes), lambda n, *_: (n, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, SLOTS, lanes), lambda n, *_: (n, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, C // KB, Qt, KB),
                         lambda n, *_: (n, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((C, Di), keys.dtype),
            pltpu.SemaphoreType.DMA((KEY_COPIES,)),
            pltpu.VMEM((SLOTS, C), jnp.int32),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    (bias,) = pl.pallas_call(
        functools.partial(_index_kernel, topk=topk, index_block=IB),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(
            (NT, C // KB, Qt, KB), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(tile_seq, tile_live, q, w, pos, keys)
    return bias
