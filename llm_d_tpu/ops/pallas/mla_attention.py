"""Pallas TPU decode kernel for MLA (single latent cache buffer).

The generic decode kernel (paged_attention.py) carries separate K and V
buffers; MLA attends queries against ONE [slots, F] latent row per token
(F = kv_lora_rank + rope, lane-padded) where the attended "values" are the
same rows — so this kernel streams each page once, uses it for both the
score dot and the value dot, and writes the new token's row back into its
(already resident) page.  All H heads share the row (MQA): scores come
from one [H, F] x [F, KB] MXU dot per key block, no GQA zero-expansion.

The unit of work of the inner loop is one KEY BLOCK of several pages, as in
``mla_prefill.py`` (PR 35), which is the model for it: a sequence's keys
are walked KB keys = KB / block_size pages at a time (``decode_key_block``:
512 keys for ``kanana-2-30b-a3b``'s 32 heads).  The pages of a block are
not contiguous in HBM: one DMA a page lands them in consecutive row ranges
of ONE [KB, F] VMEM buffer a sequence, double buffered by block and waited
on together; the last block of a sequence is filled up with its last page
again (masked by position).  Scores are [H, KB] (lane-dense), and the
running max / sum / [H, F] accumulator are corrected once a block, by the
prefill kernel's own body (``mla_prefill.weigh_key_block``: every key
weighed against the running max at the end of its own page, the carry to
the block's max as three bf16 terms), so the two kernels round alike
whatever block either picks.

Each grid program owns G sequences (launch overhead inside the fused
decode scan is ~45 us + ~3 us per program; ``decode_seq_group`` budgets
VMEM for the block double buffer, 2*KB*F*2 bytes a sequence, and the f32
accumulator + query pair, 8*H*F — DeepSeek's H=128 makes that the binding
term), and its loop runs to the group's LONGEST sequence.  So the rows are
taken in the order of their context lengths (``order``, an argsort of
``seq_lens`` handed in by scalar prefetch: queries and outputs move by one
DMA a row, nothing is permuted in HBM): a group's rows end within a block
of one another, pad rows (``seq_len`` 0) share groups that run no step at
all, and a sequence past its last block starts no DMA.  Programs run one
after the other, so a program's last step also starts the NEXT program's
first blocks (into the buffer its own sequences no longer need) and the
attended rows of one program leave while the next one works.

This is the DeepSeek-decode hot op the reference gets from vLLM's MLA CUDA
kernels; the chunked XLA path remains the CPU/odd-shape fallback.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_d_tpu.ops.pallas.mla_prefill import (
    _pick_key_block, weigh_key_block)
from llm_d_tpu.ops.pallas.paged_attention import pick_seq_group

NEG_INF = -1e30

_GROUP_VMEM_BUDGET = 6 << 20


def _mla_decode_kernel(
    # scalar prefetch
    block_tables_ref,   # [S, B] SMEM
    seq_lens_ref,       # [S]    SMEM (context length INCLUDING the new token)
    layer_ref,          # [1]    SMEM (layer plane of the stacked cache)
    order_ref,          # [S]    SMEM: rows by context length, shortest first
    # inputs
    q_hbm,              # [S, H, F] in HBM: a row's queries move by one DMA
    rn_ref,             # [S, F] f32 VMEM: every row's new latent row
    kv_hbm,
    # outputs
    o_hbm, kv_out,
    # scratch
    kv_buf,             # [2, G, KB, F]: a key block a sequence, double buffered
    q_buf, o_buf, sems, wsems, io_sems,
    phase_ref,          # [1] SMEM: the buffer of the next program's block 0
    *,
    block_size: int,
    scale: float,
):
    G, H, F = q_buf.shape
    bs = block_size
    KB = kv_buf.shape[2]
    P = KB // bs                              # pages a key block
    li = layer_ref[0]
    pid = pl.program_id(0)
    first, last = pid == 0, pid == pl.num_programs(0) - 1
    # Block i of this program's sequences lies in buffer (i + phase) % 2:
    # the program before has already started block 0 into that buffer.
    phase = jnp.where(first, 0, phase_ref[0])

    def lanes(base):
        """(row, context, pages, key blocks) of the G sequences from
        ``order[base]`` on."""
        rows = [order_ref[base + g] for g in range(G)]
        lens = [seq_lens_ref[r] for r in rows]
        pages = [pl.cdiv(sl, bs) for sl in lens]
        return rows, lens, pages, [pl.cdiv(n, P) for n in pages]

    row_g, seq_len_g, n_pages_g, n_blocks_g = lanes(pid * G)
    n_max = functools.reduce(jnp.maximum, n_blocks_g)
    # The new row's page is the sequence's last: in its last block.
    write_page_g = [(sl - 1) // bs for sl in seq_len_g]

    def q_copy(g):
        return pltpu.make_async_copy(
            q_hbm.at[row_g[g]], q_buf.at[g], io_sems.at[0, g])

    def o_copy(g):
        return pltpu.make_async_copy(
            o_buf.at[g], o_hbm.at[row_g[g]], io_sems.at[1, g])

    def start_block(slot, g, i, row, n_pages, unroll=True):
        """Start the P page copies of block ``i`` of the sequence ``row``
        into lane g; the last block is filled up with the last page again,
        so every row of a walked block holds real cache rows.  Unrolled
        where a step runs it (a loop's branch a page cost the kernel a
        seventh of its time), a loop at the two places a CALL runs once or
        never: every unrolled copy is lowered anew in every process."""
        def page(p, _):
            j = jnp.minimum(i * P + p, n_pages - 1)
            src = pl.ds(pl.multiple_of(block_tables_ref[row, j] * bs, bs), bs)
            dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
            pltpu.make_async_copy(kv_hbm.at[li, src], kv_buf.at[slot, g, dst],
                                  sems.at[slot, g]).start()
            return _
        jax.lax.fori_loop(0, P, page, 0, unroll=unroll)

    def start_first_blocks(slot, base, unroll=True):
        """Block 0 of the G sequences from ``order[base]`` on."""
        rows, _, pages, blocks = lanes(base)
        for g in range(G):
            @pl.when(blocks[g] > 0)
            def _(g=g):
                start_block(slot, g, 0, rows[g], pages[g], unroll)

    def wait_block(slot, g):
        """Wait on a block's P page copies together: ONE wait for the
        bytes of the whole [KB, F] buffer they fill."""
        pltpu.make_async_copy(kv_hbm.at[li, pl.ds(0, KB)], kv_buf.at[slot, g],
                              sems.at[slot, g]).wait()

    def wb_copy(g):
        """The (re-constructible) write-back of sequence g's spliced page,
        from the buffer of its last block."""
        wp = jnp.maximum(write_page_g[g], 0)
        slot = (n_blocks_g[g] - 1 + phase) % 2
        src = pl.ds(pl.multiple_of(wp % P * bs, bs), bs)
        dst = pl.ds(pl.multiple_of(
            block_tables_ref[row_g[g], wp] * bs, bs), bs)
        return pltpu.make_async_copy(
            kv_buf.at[slot, g, src], kv_out.at[li, dst], wsems.at[g])

    for g in range(G):
        q_copy(g).start()

    @pl.when(first)
    def _():
        start_first_blocks(0, 0, unroll=False)

    for g in range(G):
        # A pad row (seq_len 0) beside live rows: its buffer is never
        # filled, and p = 0 must not meet a NaN in the p v dot.
        @pl.when((n_blocks_g[g] == 0) & (n_max > 0))
        def _(g=g):
            kv_buf[phase, g] = jnp.zeros((KB, F), kv_buf.dtype)
    for g in range(G):
        q_copy(g).wait()

    # bf16 operands, f32 accumulation (flash statistics stay f32).
    q2_g = [(q_buf[g].astype(jnp.float32) * scale).astype(jnp.bfloat16)
            for g in range(G)]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, KB), 1)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (bs, F), 0)

    def body(i, carry):
        slot, other = (i + phase) % 2, (i + 1 + phase) % 2
        for g in range(G):
            # A sequence past its last block starts no DMA.
            @pl.when(i + 1 < n_blocks_g[g])
            def _(g=g):
                start_block(other, g, i + 1, row_g[g], n_pages_g[g])

        # The program's last step: no sequence of its own needs the other
        # buffer again, so the NEXT program's first blocks start into it
        # and arrive under this step's dots (a program's first block is
        # otherwise the one copy nothing hides).  Sequences that ended
        # earlier may still be writing their page back from there: waited
        # on first.
        @pl.when((i == n_max - 1) & jnp.logical_not(last))
        def _():
            for g in range(G):
                @pl.when((n_blocks_g[g] > 0) & (n_blocks_g[g] < n_max))
                def _(g=g):
                    wb_copy(g).wait()
            start_first_blocks(other, (pid + 1) * G)

        for g in range(G):
            @pl.when(i < n_blocks_g[g])
            def _(g=g):
                wait_block(slot, g)

            # In the sequence's last block (exactly once per call): splice
            # the new latent row into its resident page and START that
            # page's write-back; it flies under the dots.  A pad row has no
            # last block: it starts no write and waits on none.
            @pl.when(i == n_blocks_g[g] - 1)
            def _(g=g):
                sl = seq_len_g[g]
                pg = pl.ds(pl.multiple_of(
                    write_page_g[g] % P * bs, bs), bs)
                new = rn_ref[pl.ds(row_g[g], 1), :].astype(kv_buf.dtype)
                kv_buf[slot, g, pg] = jnp.where(
                    row_ids == (sl - 1) % bs, new, kv_buf[slot, g, pg])
                wb_copy(g).start()

        key_pos = i * KB + col                                # [1, KB]
        out = []
        for g, (m, l, acc) in enumerate(carry):
            # Past its last block a sequence scores the buffer of its last
            # block again, every key masked: real rows (its own, or the
            # next program's arriving), p = 0, the statistics unmoved.
            own = (jnp.minimum(i, jnp.maximum(n_blocks_g[g] - 1, 0))
                   + phase) % 2
            kv = kv_buf[own, g]                               # [KB, F] bf16
            s_hb = jax.lax.dot_general(
                q2_g[g], kv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # [H, KB]
            s_hb = jnp.where(key_pos < seq_len_g[g], s_hb, NEG_INF)
            out.append(weigh_key_block(s_hb, kv, col, bs, m, l, acc))
        return out

    init = [(jnp.full((H, 1), -1e29, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, F), jnp.float32))] * G
    stats = jax.lax.fori_loop(0, n_max, body, init)

    # A program of pad rows alone ran no step: it hands the next program
    # its first blocks from here.
    @pl.when((n_max == 0) & jnp.logical_not(last))
    def _():
        start_first_blocks(phase, (pid + 1) * G, unroll=False)
    phase_ref[0] = (n_max + phase) % 2

    # The attended rows leave by one DMA a row, waited on by the NEXT
    # program before it fills ``o_buf`` again (the last program: at once).
    @pl.when(jnp.logical_not(first))
    def _():
        for g in range(G):
            o_copy(g).wait()
    for g, (m, l, acc) in enumerate(stats):
        o_buf[g] = (acc / jnp.maximum(l, 1e-30)).astype(o_buf.dtype)
        o_copy(g).start()
    # Every started DMA is waited on before the kernel ends.
    for g in range(G):
        @pl.when(last)
        def _(g=g):
            o_copy(g).wait()

        # (Sequences that ended before the last step were waited on there.)
        @pl.when((n_blocks_g[g] > 0) & ((n_blocks_g[g] == n_max) | last))
        def _(g=g):
            wb_copy(g).wait()


def decode_key_block(H: int, F: int, block_size: int) -> int:
    """Keys one step of the decode kernel's inner loop covers for a
    sequence: the prefill kernel's rule (``mla_prefill._pick_key_block``:
    ``block_size`` doubled while what a key costs in VMEM fits) with this
    kernel's rows of a dot, the ``H`` heads of one sequence, and at most
    512 keys: 512 for 8 to 128 heads at F = 640.

    On the v5e (one layer's call alone, H = 32, F = 640, pages of 32, 64
    rows of ``kanana2.batch``'s contexts, 150-1,350 with a mean of 570 /
    630 on two seeds; ms a call, the argsort of the lengths included;
    PERF.md PR 37), by keys a block and rows a program:

        keys    2 rows   4 rows   8 rows   16 rows
         128     0.220    0.200    0.196    0.205
         256     0.173    0.158    0.154    0.160
         512     0.158    0.145    0.148    -

    (the second seed reads 7-8 % more in every cell, in the same order);
    the page loop before key blocks 0.312 / 0.352 at its 16 rows a program
    and 0.296 / 0.321 at 8; this kernel a page a step 0.544; 256 keys and 4
    rows with the rows NOT taken in the order of their lengths 0.185.  A
    step's cost is a third fixed (twelve conditional regions and eight
    page copies a sequence to issue), so the longer block wins although
    it walks more keys past the context."""
    return _pick_key_block(block_size, F, H, most=512)


def decode_seq_group(S: int, H: int, F: int, key_block: int,
                     itemsize: int = 2, group: int | None = None) -> int:
    """Sequences a grid program owns (``paged_attention.pick_seq_group``:
    ``group`` if given and a divisor of ``S``, else the largest of 16 / 8 /
    4 / 2 that divides ``S`` and fits the budget)
    with this kernel's VMEM a sequence: the double-buffered key block and
    the f32 accumulator + query pair.  4 at 512 keys for 8 to 32 heads, 2
    for DeepSeek's 128; ``decode_key_block``'s table has what it is worth:
    within 3 % from 4 rows up, since the programs run one after the other
    and the next one's first blocks arrive under the last step."""
    return pick_seq_group(
        S, group, 2 * key_block * F * itemsize + 8 * H * F,
        budget=_GROUP_VMEM_BUDGET)


@functools.partial(
    jax.jit, static_argnames=("block_size", "scale", "interpret", "seq_group",
                              "key_block"))
def mla_paged_decode_update(
    q_eff: jax.Array,         # [S, H, F] absorbed queries
    row_new: jax.Array,       # [S, F] new latent rows (one per sequence)
    kv_cache: jax.Array,      # [L, num_slots, F] (or [num_slots, F])
    block_tables: jax.Array,  # [S, B]
    seq_lens: jax.Array,      # [S] incl. the new token
    block_size: int,
    scale: float,
    layer: jax.Array | None = None,
    interpret: bool = False,
    seq_group: int | None = None,   # sequences per grid program (None = auto)
    key_block: int | None = None,   # keys a step of the inner loop, a
                                    # multiple of block_size; None: by the
                                    # shapes (``decode_key_block``)
):
    """Returns (attn_out [S, H, F] f32-accurate in q dtype, kv_cache')."""
    S, H, F = q_eff.shape
    squeeze = kv_cache.ndim == 2
    if squeeze:
        kv_cache = kv_cache[None]
    KB = (key_block if key_block is not None
          else decode_key_block(H, F, block_size))
    if KB % block_size:
        raise ValueError(f"key_block={KB} must be whole pages of "
                         f"{block_size} keys")
    G = decode_seq_group(S, H, F, KB, kv_cache.dtype.itemsize, seq_group)
    layer_arr = jnp.asarray([0 if layer is None else layer], jnp.int32)
    # Rows by context length: a group's loop runs to its longest row.
    order = jnp.argsort(seq_lens).astype(jnp.int32)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S // G,),
        in_specs=[any_spec,
                  pl.BlockSpec((S, F), lambda i, *_: (0, 0),
                               memory_space=pltpu.VMEM),
                  any_spec],
        out_specs=[any_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((2, G, KB, F), kv_cache.dtype),
            pltpu.VMEM((G, H, F), q_eff.dtype),
            pltpu.VMEM((G, H, F), q_eff.dtype),
            pltpu.SemaphoreType.DMA((2, G)),
            pltpu.SemaphoreType.DMA((G,)),
            pltpu.SemaphoreType.DMA((2, G)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _mla_decode_kernel, block_size=block_size, scale=scale)
    out, kv_cache = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, H, F), q_eff.dtype),
                   jax.ShapeDtypeStruct(kv_cache.shape, kv_cache.dtype)],
        # Operand indices in input_output_aliases include scalar prefetch.
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), has_side_effects=True),
        interpret=interpret,
    )(block_tables, seq_lens, layer_arr, order, q_eff,
      # (The cache's dtype first: what the row rounds to; f32 in VMEM so
      # that one row loads at a dynamic sublane offset.)
      row_new.astype(kv_cache.dtype).astype(jnp.float32), kv_cache)
    if squeeze:
        kv_cache = kv_cache[0]
    return out, kv_cache
