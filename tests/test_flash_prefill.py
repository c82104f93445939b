"""Flash prefill kernel: interpret-mode parity vs the jnp reference.

Covers ragged lengths, chunked prefill (prior cached context), GQA ratios,
q-tiling, soft-cap, pad rows/slots, and stacked-cache layer addressing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.ops import attention as A
from llm_d_tpu.ops.pallas.flash_prefill import flash_prefill_paged


def _case(seed, S, Q, H, KVH, D, bs, num_blocks, seq_lens, new_lens,
          num_layers=None):
    """Sequences with seq_lens[i] total context of which the LAST
    new_lens[i] tokens are the queries of this step (chunked prefill)."""
    rng = np.random.default_rng(seed)
    F = KVH * D
    shape = ((num_blocks * bs, F) if num_layers is None
             else (num_layers, num_blocks * bs, F))
    k_cache = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v_cache = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    B = max(-(-int(max(seq_lens)) // bs), 1)
    perm = rng.permutation(num_blocks - 1)[: S * B] + 1
    bt = jnp.asarray(perm.reshape(S, B), jnp.int32)

    qs = np.zeros((S, Q, H, D), np.float32)
    q_pos = np.full((S, Q), -1, np.int32)
    for s in range(S):
        n = new_lens[s]
        qs[s, :n] = rng.standard_normal((n, H, D))
        q_pos[s, :n] = np.arange(seq_lens[s] - n, seq_lens[s])
    return (jnp.asarray(qs, jnp.bfloat16), jnp.asarray(q_pos), k_cache,
            v_cache, bt, jnp.asarray(seq_lens, jnp.int32))


def _reference(qs, q_pos, k_cache, v_cache, bt, lens, bs, scale,
               soft_cap=None, layer=None):
    """Flatten the per-seq layout into the [T, H, D] ragged reference."""
    S, Q, H, D = qs.shape
    rows = [(s, qslot) for s in range(S) for qslot in range(Q)
            if int(q_pos[s, qslot]) >= 0]
    q_flat = jnp.stack([qs[s, t] for s, t in rows])
    positions = jnp.asarray([int(q_pos[s, t]) for s, t in rows], jnp.int32)
    token_seq = jnp.asarray([s for s, _ in rows], jnp.int32)
    out = A.ragged_paged_attention_reference(
        q_flat, k_cache, v_cache, token_seq, positions, bt, lens,
        block_size=bs, scale=scale, soft_cap=soft_cap, layer=layer)
    full = np.zeros((S, Q, H, D), np.float32)
    for i, (s, t) in enumerate(rows):
        full[s, t] = np.asarray(out[i], np.float32)
    return full


@pytest.mark.parametrize("H,KVH,D,bs", [
    (8, 8, 64, 16),     # MHA
    (8, 2, 64, 32),     # GQA 4
    (4, 1, 128, 16),    # MQA, d128
])
def test_prefill_kernel_matches_reference(H, KVH, D, bs):
    # Fresh prefills and chunked continuations, lengths crossing pages.
    seq_lens = [1, bs // 2, bs, 2 * bs + 3, 3 * bs]
    new_lens = [1, bs // 2, bs // 2, 5, 3 * bs]   # some with prior context
    S, Q = len(seq_lens), 3 * bs
    case = _case(hash((H, KVH, D, bs)) % 2**32, S, Q, H, KVH, D, bs,
                 num_blocks=S * 3 + 1, seq_lens=seq_lens, new_lens=new_lens)
    qs, q_pos, k_cache, v_cache, bt, lens = case
    out = flash_prefill_paged(
        qs, q_pos, k_cache, v_cache, bt, lens, block_size=bs,
        num_kv_heads=KVH, scale=0.17, interpret=True)
    ref = _reference(qs, q_pos, k_cache, v_cache, bt, lens, bs, 0.17)
    mask = np.asarray(q_pos) >= 0
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[mask], ref[mask], atol=2e-2, rtol=2e-2)


def test_prefill_kernel_q_tiling_and_pad_rows():
    """Explicit small q-tile: tiles spanning pad slots and pad sequences."""
    H, KVH, D, bs = 8, 2, 64, 16
    seq_lens = [2 * bs + 5, 7, 0, 0]              # two pad sequences
    new_lens = [2 * bs + 5, 7, 0, 0]
    S, Q = 4, 64
    qs, q_pos, k_cache, v_cache, bt, lens = _case(
        5, S, Q, H, KVH, D, bs, num_blocks=16, seq_lens=[max(l, 1) for l in seq_lens],
        new_lens=new_lens)
    lens = jnp.asarray(seq_lens, jnp.int32)
    bt = bt.at[2:].set(0)
    for qt in (8, 32, 64):
        out = flash_prefill_paged(
            qs, q_pos, k_cache, v_cache, bt, lens, block_size=bs,
            num_kv_heads=KVH, scale=0.2, interpret=True, q_tile=qt)
        ref = _reference(qs, q_pos, k_cache, v_cache, bt, lens, bs, 0.2)
        mask = np.asarray(q_pos) >= 0
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[mask], ref[mask],
            atol=2e-2, rtol=2e-2)


def test_prefill_kernel_soft_cap_and_layer():
    H, KVH, D, bs, L = 4, 2, 64, 16, 3
    seq_lens = [bs + 2, 2 * bs]
    new_lens = [bs + 2, bs]
    S, Q = 2, 2 * bs
    qs, q_pos, k_cache, v_cache, bt, lens = _case(
        9, S, Q, H, KVH, D, bs, num_blocks=8, seq_lens=seq_lens,
        new_lens=new_lens, num_layers=L)
    layer = jnp.asarray(2, jnp.int32)
    out = flash_prefill_paged(
        qs, q_pos, k_cache, v_cache, bt, lens, block_size=bs,
        num_kv_heads=KVH, scale=0.13, soft_cap=30.0, layer=layer,
        interpret=True)
    ref = _reference(qs, q_pos, k_cache, v_cache, bt, lens, bs, 0.13,
                     soft_cap=30.0, layer=layer)
    mask = np.asarray(q_pos) >= 0
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[mask], ref[mask], atol=2e-2, rtol=2e-2)


# ---- key blocks of several pages ------------------------------------------

def _rows_case(seed, rows, H, KVH, D, bs, Q, poison=False):
    """``rows``: (context, new tokens) a sequence.  ``poison``: every cache
    page that no row owns (the null page 0 among them) is NaN."""
    case = _case(seed, len(rows), Q, H, KVH, D, bs,
                 num_blocks=len(rows) * -(-max(c for c, _ in rows) // bs) + 3,
                 seq_lens=[c for c, _ in rows], new_lens=[n for _, n in rows])
    qs, q_pos, k_cache, v_cache, bt, lens = case
    # A row's table beyond its own pages names the null page, as the
    # engine's does.
    pages = np.asarray([-(-c // bs) for c, _ in rows])
    owned = np.arange(bt.shape[1])[None, :] < pages[:, None]
    bt = jnp.where(owned, bt, 0)
    if poison:
        mine = np.zeros(k_cache.shape[0] // bs, bool)
        mine[np.asarray(bt)[owned]] = True
        dead = jnp.asarray(np.repeat(~mine, bs))[:, None]
        k_cache = jnp.where(dead, jnp.nan, k_cache)
        v_cache = jnp.where(dead, jnp.nan, v_cache)
    return qs, q_pos, k_cache, v_cache, bt, lens


# Key blocks of KB = 64 keys = 4 pages of 16 (a block as the cells' 256 keys
# are 8 pages of 32), GQA 8 / 2 heads: D = 128 runs one KV-head group a
# dot, D = 64 the zero-expanded dot over the same block.
KB_CASES = {
    # contexts one key short of, on and one past a key-block edge, fresh
    # prompts and continuing chunks
    "edge-short": dict(rows=[(127, 127), (63, 9)]),
    "edge-on": dict(rows=[(128, 128), (64, 9)]),
    "edge-past": dict(rows=[(129, 129), (65, 9)]),
    # a walk that is one partly filled block
    "one-partial-block": dict(rows=[(23, 23), (40, 5)]),
    # the window's first visible page in the middle of a block: the chunk's
    # first query at 150 sees keys from 111 on, page 6 of block 1
    "window-mid-block": dict(rows=[(200, 50), (97, 1)], window=40),
    # ... and a window narrower than a page, wider than a block
    "window-narrow": dict(rows=[(200, 50), (70, 70)], window=7),
    "window-wide": dict(rows=[(200, 50), (70, 70)], window=90),
    # one-query rows beside a chunk
    "decode-rows-beside-chunk": dict(
        rows=[(64, 1), (65, 1), (1, 1), (190, 60), (128, 1)]),
    "soft-cap": dict(rows=[(150, 40), (64, 1)], soft_cap=20.0),
    # every page no row owns is NaN, the null page too
    "poisoned": dict(rows=[(150, 40), (65, 1), (3, 3)], poison=True),
    "poisoned-window": dict(rows=[(150, 40), (65, 1), (3, 3)], poison=True,
                            window=33),
}


@pytest.mark.parametrize("D", [128, 64], ids=["grouped", "zero-expanded"])
@pytest.mark.parametrize("name", KB_CASES)
def test_key_blocks_match_reference(name, D):
    c = KB_CASES[name]
    H, KVH, bs, KB, Q = 8, 2, 16, 64, 136
    qs, q_pos, k_cache, v_cache, bt, lens = _rows_case(
        sum(map(ord, name)) + D, c["rows"], H, KVH, D, bs, Q,
        poison=c.get("poison", False))
    window = c.get("window")
    kw = dict(block_size=bs, scale=0.12, soft_cap=c.get("soft_cap"),
              window=None if window is None else jnp.int32(window))
    out = np.asarray(flash_prefill_paged(
        qs, q_pos, k_cache, v_cache, bt, lens, interpret=True, q_tile=8,
        num_kv_heads=KVH, key_block=KB, **kw), np.float32)
    mask = np.asarray(q_pos) >= 0
    assert np.all(np.isfinite(out))         # pad slots and pad rows too
    # The reference masks what it must not see with ``where``, so NaN pages
    # it gathers (the null page of a table's tail) do not reach it either.
    S, Q_, _, _ = qs.shape
    rows = [(s, t) for s in range(S) for t in range(Q_) if mask[s, t]]
    ref = A.ragged_paged_attention_reference(
        jnp.stack([qs[s, t] for s, t in rows]),
        jnp.nan_to_num(k_cache), jnp.nan_to_num(v_cache),
        jnp.asarray([s for s, _ in rows], jnp.int32),
        jnp.asarray([int(q_pos[s, t]) for s, t in rows], jnp.int32),
        bt, lens, **kw)
    np.testing.assert_allclose(out[mask], np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)
    # The key block changes the order of the flash recurrence, nothing
    # else: one page a block (the parent's loop) agrees to rounding.
    paged = np.asarray(flash_prefill_paged(
        qs, q_pos, k_cache, v_cache, bt, lens, interpret=True, q_tile=8,
        num_kv_heads=KVH, key_block=bs, **kw), np.float32)
    np.testing.assert_allclose(out[mask], paged[mask], atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("bs,F,rows,want", [
    (32, 512, 256, 512),     # the cells: 32 / 4 x 128, 32 slots a tile
    (32, 512, 64, 512),      #   ... 8 slots a tile (a mixed step's rows)
    (32, 512, 1024, 128),    # llama3-1b zero-expanded: 32 x 32 fused rows
    (32, 512, 512, 256),     #   ... 16 slots a tile
    (32, 128, 256, 512),     # one of four tp shards
    (32, 1024, 128, 256),    # llama3-8b: 8 KV heads of 128
    (16, 256, 64, 512),      # a 16-key page
    (48, 512, 256, 384),     # a page that is no power of two
    (1024, 512, 256, 1024),  # a page wider than the block: one page
])
def test_key_block_is_a_function_of_shapes(bs, F, rows, want):
    from llm_d_tpu.ops.pallas.flash_prefill import pick_key_block
    kb = pick_key_block(bs, F, rows)
    assert kb == want and kb % bs == 0


def test_dots_are_grouped_where_the_head_size_is_whole_lane_tiles():
    from llm_d_tpu.ops.pallas.flash_prefill import dot_rows
    assert dot_rows(32, 32, 4, 128) == 32 * 8      # the cells
    assert dot_rows(32, 8, 1, 128) == 32 * 8       # one of four tp shards
    assert dot_rows(32, 32, 8, 64) == 32 * 32      # llama3-1b: zero-expanded
    assert dot_rows(16, 32, 8, 256) == 16 * 4
