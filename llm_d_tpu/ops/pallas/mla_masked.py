"""Pallas TPU flash kernel for weight-absorbed MLA under a SELECTION of keys
a query: dense attention over the paged latent cache with the selection as
a mask.

A layer that selects its keys (``ops/sparse_mla.py``) attends, for every
query, to the ``index_topk`` keys its indexer chose.  Reading those rows one
by one is what the chip does worst: XLA's gather of a latent row through the
block table costs 17 ns an INDEX whatever the row's width (75 GB/s at 1,280
bytes a row, a tenth of the HBM rate; a third of ``dots3.longdoc``'s device
time, PERF.md PR 39), and a DMA a row is no cheaper to issue.  The MXU
reads every key of the context in less time than the gather reads a
selected one in three while contexts stay within a few times the top-k: at
128 heads a query's dense pass over 6,000 keys is 1.5 GFLOP, 15 us at half
the bf16 peak, where 2,048 gathered rows are 35 us.  So this kernel walks
ALL the sequence's pages, as ``mla_prefill.py`` does, and adds a bias of 0
or -1e30 a (query, key) that the selection wrote: the keys left out weigh
nothing, exactly.

Layout as ``mla_prefill.py``: the grid walks the step's compact list of
query tiles (Qt slots of one sequence each), a tile's Qt x H fused rows hit
a key block of KB keys = KB / block_size pages in one MXU dot, the pages
land by one DMA each in ONE double-buffered [KB, F] buffer that serves the
score dot and the value dot.  What differs:

  - the mask is data, not arithmetic on positions: ``bias`` [NT, C / KB,
    Qt, KB] f32 holds 0 where the slot's query attends to the key and
    -1e30 elsewhere (not chosen, after the query, past the context, a pad
    slot), laid out so that a key block's [Qt, KB] tile is a leading-axis
    index; it is spread over a slot's H rows through a VMEM scratch;
  - ``tile_live`` [NT] says where a tile's walk ends (its last query's
    position + 1) and ``tile_first`` [NT] at which key block it starts:
    computed outside, so the kernel holds no positions;
  - one running max a block and ONE bf16 term of the probabilities: the
    page-end maxima and the three-term carry of ``weigh_key_block`` keep
    two KERNELS rounding alike; here one kernel serves prefill chunks,
    mixed steps and pure-decode rows (a tile of one slot), and a row's
    arithmetic does not depend on the tile's other rows, so decode and a
    fresh prefill agree by construction;
  - scores are scaled in f32 after the dot and the attended values leave
    in f32, as the XLA path of ``ops/sparse_mla.py`` has them.

One body, one rounding, two walks.  A layer that SELECTS walks a tile's
keys from block 0 under the bias ``index_select`` wrote.  A layer with a
WINDOW (``ops.sparse_mla.attend_window``) walks from the block that holds
the first key its first query sees: ``tile_first`` = floor((first query's
position - (window - 1)) / KEY_BLOCK), at least 0, and the bias (0 where
t - window < s <= t, computed in XLA from the tile's positions) holds the
``window_bias_blocks`` blocks from there on only: a band of 16 + 512 keys
is 3 key blocks (4 where it straddles) whatever the context.  Both walks
start on ABSOLUTE multiples of KEY_BLOCK, so a query's visible keys fall
into the same blocks whatever tile carries it, and a block wholly masked
for a row leaves its ``m``, ``l`` and ``acc`` as they were: a decode row
and its fresh prefill agree by construction in a window layer too.

The cache rows of the step's own tokens are written by the caller BEFORE
the kernel runs (read-only, no aliasing contract).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Keys a step of the inner loop covers.  One value for every tile height, so
# that a decode row and a prefill tile walk the same blocks.
KEY_BLOCK = 256
# The scoped VMEM a call may take: 8 slots x 128 heads hold about 15 MB
# (query and output blocks double buffered, the f32 accumulator, the bias
# tiles of a whole row, four [Qt*H, KB] f32 temporaries), just over the
# compiler's default of 16.
VMEM_LIMIT = 48 << 20
# Fused rows (slots x heads) a tile aims at: as many as feed the MXU well
# (8 slots x 128 heads, ``ops.sparse_mla.SELECT_Q_TILE``'s reasoning).
TILE_ROWS = 1024


def _masked_kernel(
    # scalar prefetch
    block_tables_ref,   # [S, B] SMEM
    tile_seq_ref,       # [NT]   SMEM: the sequence row of each query tile
    tile_live_ref,      # [NT]   SMEM: the key the tile's walk ends before
    tile_first_ref,     # [NT]   SMEM: the key block the walk starts at
    layer_ref,          # [1]    SMEM
    # inputs
    q_ref,              # [1, Qt*H, F]
    bias_ref,           # [1, NB, Qt, KB] f32, from the walk's first block
    kv_hbm,
    # outputs
    o_ref,              # [1, Qt*H, Rv] f32
    # scratch
    kv_buf,             # [2, KB, F]: a key block, double buffered
    sems,
    bias_buf,           # [Qt*H, KB] f32: a block's bias, a row a head
    *,
    block_size: int,
    num_heads: int,
    scale: float,
):
    n = pl.program_id(0)
    s = tile_seq_ref[n]
    bs = block_size
    KB = kv_buf.shape[1]
    P = KB // bs                              # pages a key block
    li = layer_ref[0]
    n_pages = pl.cdiv(tile_live_ref[n], bs)
    # The last block is filled up with the walk's last page again (masked
    # by the bias): every row of a walked block holds real cache rows, so
    # p = 0 never meets a NaN in the p v dot.
    first = tile_first_ref[n]
    n_blocks = pl.cdiv(n_pages, P) - first
    H = num_heads
    Qt = bias_ref.shape[2]
    Rv = o_ref.shape[2]

    def block_dma(slot, i, act):
        """``act`` ("start" / "wait") the P page copies of the walk's
        block ``i``."""
        def page(p, _):
            j = jnp.minimum((first + i) * P + p, n_pages - 1)
            src = pl.ds(pl.multiple_of(block_tables_ref[s, j] * bs, bs), bs)
            dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
            getattr(pltpu.make_async_copy(
                kv_hbm.at[li, src], kv_buf.at[slot, dst],
                sems.at[slot]), act)()
            return _
        jax.lax.fori_loop(0, P, page, 0)

    @pl.when(n_blocks > 0)
    def _():
        block_dma(0, 0, "start")

    q = q_ref[0]                                              # [R, F]
    R = q.shape[0]

    def body(i, carry):
        m, l, acc = carry
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _():
            block_dma((i + 1) % 2, i + 1, "start")

        block_dma(slot, i, "wait")
        kv = kv_buf[slot]                                     # [KB, F]
        b = bias_ref[0, i]                                    # [Qt, KB]
        for j in range(Qt):
            bias_buf[j * H:(j + 1) * H, :] = jnp.broadcast_to(
                b[j:j + 1, :], (H, KB))
        sc = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale + bias_buf[...]
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :Rv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [R, Rv]
        return m_new, l_new, acc * corr + pv

    init = (
        jnp.full((R, 1), -1e29, jnp.float32),
        jnp.zeros((R, 1), jnp.float32),
        jnp.zeros((R, Rv), jnp.float32),
    )
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    o_ref[0] = acc / jnp.maximum(l, 1e-30)


def ineligible_reason(num_heads: int, value_width: int,
                      table_keys: int) -> str | None:
    """Why this kernel cannot serve a geometry the MLA kernels' own check
    (``ops.attention.pallas_ineligible_reason``) lets through;
    ``table_keys``: the positions a row's block table holds."""
    if num_heads % 8:
        return f"{num_heads} heads are not whole sublane tiles of 8"
    if value_width % 128:
        return f"kv_lora_rank {value_width} is not whole 128-lane tiles"
    if table_keys % KEY_BLOCK:
        return (f"a block table of {table_keys} keys is not whole key "
                f"blocks of {KEY_BLOCK}")
    return None


def pick_q_tile(num_heads: int, row_width: int, value_width: int) -> int:
    """Query slots a tile of a windowed layer holds: TILE_ROWS fused rows,
    halved while the call's VMEM (bf16 query block and f32 output block
    double buffered, the f32 accumulator, the key buffer, the bias spread
    and four [rows, KEY_BLOCK] f32 temporaries) passes VMEM_LIMIT: 16 at
    64 heads over rows of 1,152 with values of 1,024 (23 MB)."""
    qt = max(TILE_ROWS // num_heads, 1)

    def vmem(rows):
        return (2 * rows * row_width * 2 + 3 * rows * value_width * 4
                + 2 * KEY_BLOCK * row_width * 2 + 5 * rows * KEY_BLOCK * 4)

    while qt > 1 and vmem(qt * num_heads) > VMEM_LIMIT:
        qt //= 2
    return qt


def window_bias_blocks(q_tile: int, window: int) -> int:
    """Key blocks the band of a windowed tile can touch: its ``q_tile`` +
    ``window`` - 1 consecutive keys start anywhere in their first block."""
    return (q_tile + window - 1 + KEY_BLOCK - 2) // KEY_BLOCK + 1


@functools.partial(
    jax.jit, static_argnames=("block_size", "scale", "value_width",
                              "interpret"))
def mla_masked_attention(
    q_tiles: jax.Array,       # [NT, Qt, H, F] absorbed queries by tile
    bias: jax.Array,          # [NT, NB, Qt, KEY_BLOCK] f32 over the NB key
                              # blocks from each tile's first
    tile_seq: jax.Array,      # [NT] i32 row of block_tables of each tile
    tile_live: jax.Array,     # [NT] i32 the key each tile's walk ends before
    tile_first: jax.Array,    # [NT] i32 the key block each walk starts at
    kv_cache: jax.Array,      # [L, num_slots, F]
    block_tables: jax.Array,  # [S, B]
    layer: jax.Array,
    block_size: int,
    scale: float,
    value_width: int,         # the row's leading columns that are values
    interpret: bool = False,
) -> jax.Array:               # [NT, Qt, H, value_width] f32
    """Softmax attention of every query slot over the keys of its tile's
    sequence whose bias is 0 (cache already written).  A tile walks at most
    NB blocks: ``tile_live`` <= (``tile_first`` + NB) * KEY_BLOCK."""
    NT, Qt, H, F = q_tiles.shape
    KB = bias.shape[3]
    assert kv_cache.shape[2] == F, (kv_cache.shape, F)
    assert KB % block_size == 0 and bias.shape[2] == Qt, (bias.shape, Qt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(NT,),
        in_specs=[
            pl.BlockSpec((1, Qt * H, F), lambda n, *_: (n, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bias.shape[1], Qt, KB),
                         lambda n, *_: (n, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, Qt * H, value_width), lambda n, *_: (n, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, KB, F), kv_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((Qt * H, KB), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _masked_kernel, block_size=block_size, num_heads=H, scale=scale)
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((NT, Qt * H, value_width),
                                        jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(block_tables, tile_seq, tile_live, tile_first,
      jnp.asarray(layer, jnp.int32).reshape(1),
      q_tiles.reshape(NT, Qt * H, F), bias, kv_cache)
    return out.reshape(NT, Qt, H, value_width)
