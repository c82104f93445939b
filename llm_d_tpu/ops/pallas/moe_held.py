"""Pallas TPU kernels: the routed experts a device HOLDS, in bf16, as a
grouped product over tiles of the rows routed to them only.

One rank of a wide expert-parallel deployment holds a share of the experts
(``ModelConfig.num_local_experts`` of ``router_experts``): of the k slots a
token fills, most go to experts held elsewhere.  XLA's grouped product
(``ops.moe._swiglu_grouped``) costs by the experts' bytes at a third of the
HBM rate whatever rows it is handed, and its glue moves every slot's row
(``ops.moe._held_expert_ffn``).  Here the work is sized by what is held:

  - **Tiles of held rows only.**  The counting sort over ``E_held + 1`` keys
    gives each held expert's rows a run padded to the row tile, one expert a
    tile (``held_layout``).  The grid is the static worst case, ``ceil(S /
    rt) + E_held`` tiles (every slot may be routed here); a tile past
    ``num_tiles`` does nothing, no dot and no copy, and an expert that no
    row selects has no tile: its matrices are not read.
  - **Rows come by their token ids.**  A tile's REAL rows are copied from
    the token-order ``x`` in HBM one DMA a row (a row is 4 H bytes), the
    next tile's started before this tile's dots; pad rows keep whatever the
    buffer held, and nothing reads what they produce.
  - **An expert's matrices stream once, in blocks of the expert width.**
    ``h = x Wg[:, blk]``, ``u = x Wu[:, blk]``, ``acc += (silu(h) u)
    Wd[blk, :]`` are independent partial sums over blocks of ``bi``
    columns: three blocks of [5120, 512] are 15.7 MB, 31.5 MB double
    buffered.  The chain is a manual double buffer over the flat sequence of
    (tile, block) steps, reading the layers' stacks in place by plane: the
    next step's block is started before this step's dots unless it is
    RESIDENT.  Consecutive tiles of one expert walk the blocks forward and
    backward in turn, so a second tile of an expert finds two of three
    blocks where the first left them and reads one.
  - **The combine is a second small kernel** (``_combine_kernel``): the
    rows leave the first in the padded layout ``[S_pad, H]`` f32, one block
    a tile; a token's result is the f32 sum of its HELD slots' rows, each
    fetched by one DMA and weighted in f32, in slot order, 16 tokens a
    program.  It walks the list of held slots in token order that the
    layout compacts, so a slot held elsewhere costs nothing.  No pass over
    all S slots moves a row in either direction.
  - **A row is a slab.**  A DMA cannot address one row of a tiled [N, H]
    array (the compiler wants 8), so wherever a row travels alone it is
    ``[H / 128, 128]`` f32 behind an untiled leading axis: ``x`` is widened
    and reshaped once outside (exact), the grouped kernel turns slabs into
    the dots' [RT, H] and back by one strided read or write a lane tile,
    and the combine adds slabs as they are.

Same mathematics as the XLA form: bf16 operands, f32 accumulation,
``silu(h) * u`` rounded to bf16 before the down projection, the combine
weight applied in f32, the k-sum in f32 in slot order; only the order of the
f32 partial sums over the blocks of the expert width differs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows a tile holds: the MXU's height.  A tile of fewer rows costs the MXU
# as much (the weights' passes count, not the rows), a taller one pads the
# about 64 rows an expert gets at 2,048 tokens over 8 ranks fourfold.
ROW_TILE = 128
# Tokens a program of the combine serves: its buffer of 16 k slabs, double
# buffered, is 5.2 MB at 8 choices a token and hidden 5,120.
COMBINE_TOKENS = 16
# The scoped VMEM a call may take, as ``mla_masked.py`` asks.
VMEM_LIMIT = 48 << 20


def _vmem_bytes(hidden: int, block: int, row_tile: int = ROW_TILE) -> int:
    """What the grouped kernel keeps in VMEM: three bf16 weight blocks
    double buffered; of the tile's rows the f32 slabs double buffered, the
    bf16 matrix, the f32 accumulator, a partial product of its size and the
    f32 output block double buffered."""
    return (2 * 3 * hidden * block * 2
            + row_tile * hidden * (2 * 4 + 2 + 4 + 4 + 2 * 4))


def pick_block(hidden: int, width: int) -> int:
    """Columns of the expert width a step multiplies: the largest divisor of
    ``width`` in whole 128-lane tiles whose buffers fit ``VMEM_LIMIT`` (512
    of 1,536 at hidden 5,120: 48.5 MB); 0 where none does."""
    for n in range(1, width // 128 + 1):
        if width % n == 0 and (width // n) % 128 == 0 \
                and _vmem_bytes(hidden, width // n) <= VMEM_LIMIT:
            return width // n
    return 0


def ineligible_reason(x: jax.Array, w_gate: jax.Array) -> str | None:
    """Why these kernels cannot serve rows ``x`` [T, H] over experts
    ``w_gate`` [..., H, I]; None where they can."""
    hidden, width = w_gate.shape[-2:]
    if x.dtype != jnp.bfloat16 or w_gate.dtype != jnp.bfloat16:
        return f"rows {x.dtype} and experts {w_gate.dtype} are not bf16"
    if hidden % 1024 or width % 128:
        return (f"hidden {hidden} is not whole slabs of 8 x 128 or expert "
                f"width {width} not whole 128-lane tiles")
    if not pick_block(hidden, width):
        return (f"no block of the expert width fits {VMEM_LIMIT >> 20} MiB "
                f"of VMEM beside rows of {hidden}")
    return None


def _held_kernel(
    # scalar prefetch
    meta_ref,     # [2]     SMEM (the stacks' plane, num_tiles)
    te_ref,       # [NT]    SMEM expert of each tile
    rows_ref,     # [NT]    SMEM real rows of each tile
    back_ref,     # [NT]    SMEM 1 where the tile walks the blocks backward
    tok_ref,      # [S_pad] SMEM token id of each padded slot
    # inputs
    x_hbm,        # [T, H / 128, 128] f32 (ANY): token order, a row a slab
    wg_hbm,       # [L, E, H, I] bf16 (ANY)
    wu_hbm,       # [L, E, H, I]
    wd_hbm,       # [L, E, I, H]
    # outputs
    o_ref,        # [RT * H / 128, 128] f32: the tile's rows, as slabs
    # scratch
    x_buf,        # [2, RT * H / 128, 128] f32: the rows as they lie in HBM
    x_mat,        # [RT, H] bf16: the rows as the dots take them
    acc,          # [RT, H] f32
    wg_buf,       # [2, H, BI] bf16
    wu_buf,       # [2, H, BI]
    wd_buf,       # [2, BI, H]
    x_sems,       # [2]
    w_sems,       # [2, 3]
    state,        # [4] SMEM: the (expert, block) code each weight slot
                  # holds, and whether its copy is still to be waited for
):
    t = pl.program_id(0)
    NT = pl.num_programs(0)
    RT, H = x_mat.shape
    SLAB = H // 128                                   # sublanes of a slab
    BI = wg_buf.shape[2]
    NI = wg_hbm.shape[3] // BI
    li = meta_ref[0]
    nt = meta_ref[1]

    def x_rows(tile, slot, act):
        """``act`` the copies of ``tile``'s real rows into ``slot``."""
        def row(i, carry):
            tok = tok_ref[tile * RT + i]
            getattr(pltpu.make_async_copy(
                x_hbm.at[tok],
                x_buf.at[slot, pl.ds(pl.multiple_of(i * SLAB, SLAB), SLAB)],
                x_sems.at[slot]), act)()
            return carry
        jax.lax.fori_loop(0, rows_ref[tile], row, 0)

    def w_block(code, slot, act):
        """``act`` the three copies of block ``code`` = expert * NI + block
        into weight slot ``slot``."""
        e = jax.lax.div(code, NI)
        cols = pl.ds(pl.multiple_of(jax.lax.rem(code, NI) * BI, BI), BI)
        for c, (src, dst) in enumerate((
                (wg_hbm.at[li, e, :, cols], wg_buf.at[slot]),
                (wu_hbm.at[li, e, :, cols], wu_buf.at[slot]),
                (wd_hbm.at[li, e, cols, :], wd_buf.at[slot]))):
            getattr(pltpu.make_async_copy(src, dst, w_sems.at[slot, c]),
                    act)()

    def code_of(tile, j):
        # Block j of the walk: forward, or backward where back is 1.
        back = back_ref[tile]
        return te_ref[tile] * NI + j + back * (NI - 1 - 2 * j)

    @pl.when(t == 0)
    def _():
        state[0] = -1
        state[1] = -1
        state[2] = 0
        state[3] = 0

        @pl.when(nt > 0)
        def _():
            x_rows(0, 0, "start")
            w_block(code_of(0, 0), 0, "start")
            state[0] = code_of(0, 0)
            state[2] = 1

    @pl.when(t < nt)
    def _():
        xs = jax.lax.rem(t, 2)

        @pl.when(t + 1 < nt)
        def _():
            x_rows(t + 1, 1 - xs, "start")

        x_rows(t, xs, "wait")
        # A row's slab is H / 128 sublanes of 128 lanes: sublane g of every
        # row, one strided read, is lane tile g of the rows as a matrix
        # (the strided forms take a last axis of 128 only).  Exact: the
        # rows were bf16.
        def lanes(g):
            return pl.ds(pl.multiple_of(g * 128, 128), 128)

        def to_matrix(g, carry):
            x_mat[:, lanes(g)] = x_buf[
                xs, pl.ds(g, RT, stride=SLAB), :].astype(x_mat.dtype)
            return carry
        jax.lax.fori_loop(0, SLAB, to_matrix, 0)
        acc[...] = jnp.zeros_like(acc)

        def block(j, carry):
            xb = x_mat[...]                                   # [RT, H]
            code = code_of(t, j)
            slot = (state[0] != code).astype(jnp.int32)
            # The next step's block, started before this step's dots into
            # the slot this step does not read, unless a slot holds it.
            last = j + 1 == NI
            nxt = jax.lax.select(
                last, code_of(jax.lax.min(t + 1, NT - 1), 0),
                code_of(t, jax.lax.min(j + 1, NI - 1)))
            more = (j + 1 < NI) | (t + 1 < nt)

            @pl.when(more & (state[0] != nxt) & (state[1] != nxt))
            def _():
                w_block(nxt, 1 - slot, "start")
                state[1 - slot] = nxt
                state[3 - slot] = 1

            @pl.when(state[2 + slot] == 1)
            def _():
                w_block(code, slot, "wait")
                state[2 + slot] = 0

            h = jax.lax.dot(xb, wg_buf[slot],
                            preferred_element_type=jnp.float32)
            u = jax.lax.dot(xb, wu_buf[slot],
                            preferred_element_type=jnp.float32)
            a = (jax.nn.silu(h) * u).astype(xb.dtype)         # [RT, BI]
            acc[...] += jax.lax.dot(a, wd_buf[slot],
                                    preferred_element_type=jnp.float32)
            return carry
        jax.lax.fori_loop(0, NI, block, 0)

        def to_slabs(g, carry):
            o_ref[pl.ds(g, RT, stride=SLAB), :] = acc[:, lanes(g)]
            return carry
        jax.lax.fori_loop(0, SLAB, to_slabs, 0)


def _combine_kernel(
    # scalar prefetch
    start_ref,    # [NP + 1] SMEM: the held slots before each program's tokens
    held_ref,     # [S] SMEM: the held slots in token order, each its padded
                  # slot * TT k + its (token, choice) among the program's
    w_ref,        # [Tp * k] SMEM f32: the combine weights, token order
    # inputs
    y_hbm,        # [S_pad, H / 128, 128] f32 (ANY): the grouped kernel's rows
    # outputs
    o_ref,        # [TT, H / 128, 128] f32
    # scratch
    buf,          # [2, TT * k, H / 128, 128] f32
    sems,         # [2]
):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    TT, TTK = o_ref.shape[0], buf.shape[1]
    K = TTK // TT

    def rows(tile, slot, act):
        """``act`` the copies of the held slots of ``tile``'s tokens."""
        lo = start_ref[tile]

        def row(c, carry):
            getattr(pltpu.make_async_copy(
                y_hbm.at[jax.lax.div(held_ref[lo + c], TTK)], buf.at[slot, c],
                sems.at[slot]), act)()
            return carry
        jax.lax.fori_loop(0, start_ref[tile + 1] - lo, row, 0)

    @pl.when(i == 0)
    def _():
        rows(0, 0, "start")

    slot = jax.lax.rem(i, 2)

    @pl.when(i + 1 < n)
    def _():
        rows(i + 1, 1 - slot, "start")

    rows(i, slot, "wait")
    o_ref[...] = jnp.zeros_like(o_ref)
    lo = start_ref[i]

    def add(c, carry):
        # In token order, a token's choices in slot order: the f32 k-sum
        # of its weighted rows.
        at = jax.lax.rem(held_ref[lo + c], TTK)
        o_ref[jax.lax.div(at, K)] += w_ref[i * TTK + at] * buf[slot, c]
        return carry
    jax.lax.fori_loop(0, start_ref[i + 1] - lo, add, 0)


def held_layout(idx: jax.Array, e0, num_held: int, row_tile: int,
                combine_tokens: int = COMBINE_TOKENS):
    """The tables of the slots ``idx`` [T, k] routes to experts ``e0`` ..
    ``e0 + num_held - 1``: each held expert's slots, in token order, a run
    padded to ``row_tile``, one expert a tile.

    Returns ``(pos, tok_pad, tile_expert, tile_rows, tile_back, num_tiles,
    held, start)``.  For the grouped kernel: ``pos`` [T * k] the padded slot
    of each (token, choice), -1 where its expert is not held; ``tok_pad``
    [S_pad] the token of each padded slot (0 = pad); per tile of the static
    worst case its expert, its real rows and whether it is an odd tile of
    its expert (it walks the blocks backward); ``num_tiles`` the tiles that
    hold a row (a tile past them repeats the last one's expert and holds no
    row).  For the combine, whose programs serve ``combine_tokens`` tokens
    each: ``held`` [T * k] the held slots in token order, each ``pos`` *
    (combine_tokens * k) + its (token, choice) index among its program's;
    ``start`` [programs + 1] the held slots before each program's."""
    from llm_d_tpu.ops.moe import _excl_cumsum, _stable_argsort_bounded
    T, k = idx.shape
    S, rt, ttk = T * k, row_tile, combine_tokens * k
    NT = -(-S // rt) + num_held
    lid = idx.reshape(S) - e0
    is_held = (lid >= 0) & (lid < num_held)
    key = jnp.where(is_held, lid, num_held)
    _, dest, counts = _stable_argsort_bounded(key, num_held + 1)
    counts = counts[:num_held]
    tiles = jax.lax.div(counts + (rt - 1), rt)    # tiles of each expert
    first = _excl_cumsum(tiles)                   # its first tile

    def at(table, i):
        # Every index here is in range by construction: no wrap, no clamp.
        return table.at[i].get(mode="promise_in_bounds")

    e = jnp.minimum(key, num_held - 1)
    pos = jnp.where(
        is_held,
        at(first * rt - _excl_cumsum(counts), e) + dest, -1)
    flat = jnp.arange(S, dtype=jnp.int32)
    tok_pad = jnp.zeros((NT * rt,), jnp.int32).at[
        jnp.where(is_held, pos, NT * rt)].set(jax.lax.div(flat, k),
                                              mode="drop")
    num_tiles = tiles.sum().astype(jnp.int32)
    tile = jnp.minimum(jnp.arange(NT, dtype=jnp.int32),
                       jnp.maximum(num_tiles - 1, 0))
    # The expert whose run of tiles holds the tile: those that end at or
    # before it, counted (an expert without rows ends where it starts).
    tile_expert = jnp.minimum(
        jnp.sum(jnp.cumsum(tiles)[None, :] <= tile[:, None], axis=1),
        num_held - 1).astype(jnp.int32)
    rank = tile - at(first, tile_expert)
    tile_rows = jnp.where(
        jnp.arange(NT) < num_tiles,
        jnp.clip(at(counts, tile_expert) - rank * rt, 0, rt), 0)
    before = jnp.cumsum(is_held.astype(jnp.int32))
    held = jnp.zeros((S,), jnp.int32).at[
        jnp.where(is_held, before - 1, S)].set(
        pos * ttk + jax.lax.rem(flat, ttk), mode="drop")
    programs = -(-S // ttk)
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.pad(
        before, (0, programs * ttk - S), mode="edge")[ttk - 1::ttk]])
    return (pos.astype(jnp.int32), tok_pad, tile_expert,
            tile_rows.astype(jnp.int32), jax.lax.rem(rank, 2),
            num_tiles, held, start.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("e0", "row_tile", "block",
                                             "interpret"))
def held_expert_ffn(
    x: jax.Array,          # [T, H] bf16
    weights: jax.Array,    # [T, k] combine weights
    idx: jax.Array,        # [T, k] expert ids over the router's width
    w_gate: jax.Array,     # [E_held, H, I] bf16, or with ``plane`` the
    w_up: jax.Array,       # layers' stacks [L, E_held, ...]
    w_down: jax.Array,     # [E_held, I, H]
    e0: int,
    plane=None,
    row_tile: int = ROW_TILE,
    block: int | None = None,
    interpret: bool = False,
) -> jax.Array:            # [T, H] in x.dtype: the held experts' part
    """``ops.moe._held_expert_ffn`` through the two kernels."""
    T, H = x.shape
    k = idx.shape[1]
    if plane is None:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        plane = 0
    E_held, _, I = w_gate.shape[1:]
    rt, SLAB, W = row_tile, H // 128, 128
    bi = block or pick_block(H, I)
    assert bi and I % bi == 0 and SLAB % 8 == 0, (H, I, bi)
    TT = COMBINE_TOKENS
    _, tok_pad, tile_expert, tile_rows, tile_back, num_tiles, held, start = \
        held_layout(idx, e0, E_held, rt, TT)
    NT = tile_expert.shape[0]
    meta = jnp.stack([jnp.asarray(plane, jnp.int32), num_tiles])

    def tile_block(t, meta_ref, *_):
        # A tile past the last that holds rows maps to that one's blocks:
        # nothing is fetched for it and nothing written back.
        return (jnp.minimum(t, jnp.maximum(meta_ref[1] - 1, 0)), 0)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    y_pad = pl.pallas_call(
        _held_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(NT,),
            in_specs=[any_spec] * 4,
            out_specs=pl.BlockSpec((rt * SLAB, W), tile_block),
            scratch_shapes=[
                pltpu.VMEM((2, rt * SLAB, W), jnp.float32),
                pltpu.VMEM((rt, H), x.dtype),
                pltpu.VMEM((rt, H), jnp.float32),
                pltpu.VMEM((2, H, bi), w_gate.dtype),
                pltpu.VMEM((2, H, bi), w_up.dtype),
                pltpu.VMEM((2, bi, H), w_down.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2, 3)),
                pltpu.SMEM((4,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((NT * rt * SLAB, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(meta, tile_expert, tile_rows, tile_back, tok_pad,
      x.astype(jnp.float32).reshape(T, SLAB, W), w_gate, w_up, w_down)

    Tp = -(-T // TT) * TT
    out = pl.pallas_call(
        _combine_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Tp // TT,),
            in_specs=[any_spec],
            out_specs=pl.BlockSpec((TT, SLAB, W), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, TT * k, SLAB, W), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((Tp, SLAB, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(start, held,
      jnp.pad(weights.astype(jnp.float32).reshape(T * k), (0, (Tp - T) * k)),
      y_pad.reshape(NT * rt, SLAB, W))
    return out.reshape(Tp, H)[:T].astype(x.dtype)
