"""Pallas decode-kernel parity vs the jnp reference (interpret mode on CPU).

The kernel under test is the TPU differentiator (FlashInfer role,
reference: docker/Dockerfile.cuda:57-58); the benchmark's cells run it on
the chip, these tests pin its numerics on CPU via ``interpret=True`` across
block sizes, GQA ratios, KV widths on both sides of the 128-lane gate, and
the stacked-cache layer addressing — plus the fallback gate itself.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.ops import attention as A
from llm_d_tpu.ops.pallas.paged_attention import paged_attention_decode_update


def _make_decode_case(rng, S, H, KVH, D, block_size, num_blocks, seq_lens,
                      num_layers=None):
    """Random paged cache + one new decode token per sequence."""
    F = KVH * D
    num_slots = num_blocks * block_size
    shape = (num_slots, F) if num_layers is None else (
        num_layers, num_slots, F)
    k_cache = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v_cache = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    B = max(-(-int(max(seq_lens)) // block_size), 1)
    # Distinct physical blocks per sequence (block 0 is the null block).
    perm = rng.permutation(num_blocks - 1)[: S * B] + 1
    block_tables = jnp.asarray(perm.reshape(S, B), jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16)
    k_new = jnp.asarray(rng.standard_normal((S, F)), jnp.bfloat16)
    v_new = jnp.asarray(rng.standard_normal((S, F)), jnp.bfloat16)
    return q, k_new, v_new, k_cache, v_cache, block_tables, \
        jnp.asarray(seq_lens, jnp.int32)


def _reference_decode(q, k_new, v_new, k_cache, v_cache, block_tables,
                      seq_lens, block_size, layer=None):
    """Oracle: scatter the new rows, then full-softmax paged attention."""
    S, H, D = q.shape
    KVH = k_cache.shape[-1] // D
    slot_mapping = (jnp.take_along_axis(
        block_tables, ((seq_lens - 1) // block_size)[:, None], axis=1)[:, 0]
        * block_size + (seq_lens - 1) % block_size)
    k_cache, v_cache = A.write_kv(
        k_cache, v_cache, k_new.reshape(S, KVH, D), v_new.reshape(S, KVH, D),
        slot_mapping, layer=layer)
    out = A.ragged_paged_attention_reference(
        q, k_cache, v_cache,
        token_seq_ids=jnp.arange(S, dtype=jnp.int32),
        positions=seq_lens - 1,
        block_tables=block_tables, seq_lens=seq_lens,
        block_size=block_size, layer=layer)
    return out, k_cache, v_cache


@pytest.mark.parametrize("H,KVH,D,label", [
    (8, 8, 64, "mha-F512"),          # folded width 512 (lane-aligned)
    (8, 2, 64, "gqa4-F128"),         # exactly 128 lanes
    (4, 1, 64, "gqa4-F64-narrow"),   # BELOW the 128-lane gate
    (8, 4, 128, "gqa2-F512-d128"),
])
@pytest.mark.parametrize("block_size", [16, 32])
def test_kernel_matches_reference(H, KVH, D, label, block_size):
    rng = np.random.default_rng(hash((H, KVH, D, block_size)) % 2**32)
    # Lengths exercise: first token, mid-page, exact page boundary, multipage.
    seq_lens = [1, block_size // 2, block_size, block_size + 3,
                3 * block_size]
    S = len(seq_lens)
    case = _make_decode_case(rng, S, H, KVH, D, block_size,
                             num_blocks=S * 3 + 1, seq_lens=seq_lens)
    q, k_new, v_new, k_cache, v_cache, block_tables, lens = case

    out, k_upd, v_upd = paged_attention_decode_update(
        q, k_new, v_new, k_cache, v_cache, block_tables, lens,
        block_size=block_size, num_kv_heads=KVH, interpret=True)
    ref_out, k_ref, v_ref = _reference_decode(
        q, k_new, v_new, k_cache, v_cache, block_tables, lens, block_size)

    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref_out, np.float32),
        atol=2e-2, rtol=2e-2)
    # The fused page write-back must leave the cache exactly as the
    # scatter-then-attend oracle does.
    np.testing.assert_array_equal(
        np.asarray(k_upd, np.float32), np.asarray(k_ref, np.float32))
    np.testing.assert_array_equal(
        np.asarray(v_upd, np.float32), np.asarray(v_ref, np.float32))


@pytest.mark.parametrize("seq_group", [1, 4, 8, 16])
def test_kernel_sequence_grouping(seq_group):
    """Grouped grid programs (G sequences per program) must match the oracle
    with ragged lengths inside a group — including zero-length PAD rows,
    whose clamped page re-reads must neither score nor write back."""
    rng = np.random.default_rng(11 + seq_group)
    H, KVH, D, bs = 8, 2, 64, 16
    real_lens = [1, 7, bs, bs + 1, 2 * bs, 3 * bs - 1, 5, 2]
    S_real = len(real_lens)
    S = 16                                 # 8 real + 8 pad rows
    seq_lens = real_lens + [0] * (S - S_real)
    case = _make_decode_case(rng, S, H, KVH, D, bs, num_blocks=S * 3 + 1,
                             seq_lens=seq_lens)
    q, k_new, v_new, k_cache, v_cache, block_tables, lens = case
    # Pad rows point at the null block, as the engine builds them.
    block_tables = block_tables.at[S_real:].set(0)

    out, k_upd, v_upd = paged_attention_decode_update(
        q, k_new, v_new, k_cache, v_cache, block_tables, lens,
        block_size=bs, num_kv_heads=KVH, interpret=True,
        seq_group=seq_group)
    ref_out, k_ref, v_ref = _reference_decode(
        q[:S_real], k_new[:S_real], v_new[:S_real], k_cache, v_cache,
        block_tables[:S_real], lens[:S_real], bs)

    np.testing.assert_allclose(
        np.asarray(out[:S_real], np.float32),
        np.asarray(ref_out, np.float32), atol=2e-2, rtol=2e-2)
    # Pad rows must not have scattered anything: the caches match an oracle
    # that never saw them.
    np.testing.assert_array_equal(
        np.asarray(k_upd, np.float32), np.asarray(k_ref, np.float32))
    np.testing.assert_array_equal(
        np.asarray(v_upd, np.float32), np.asarray(v_ref, np.float32))


def test_kernel_stacked_cache_layer_addressing():
    """The stacked-cache form must touch ONLY the addressed layer plane."""
    rng = np.random.default_rng(7)
    H, KVH, D, bs, L = 8, 2, 64, 16, 3
    seq_lens = [5, 2 * bs + 1]
    S = len(seq_lens)
    case = _make_decode_case(rng, S, H, KVH, D, bs, num_blocks=8,
                             seq_lens=seq_lens, num_layers=L)
    q, k_new, v_new, k_cache, v_cache, block_tables, lens = case
    layer = jnp.asarray(1, jnp.int32)

    out, k_upd, v_upd = paged_attention_decode_update(
        q, k_new, v_new, k_cache, v_cache, block_tables, lens,
        block_size=bs, num_kv_heads=KVH, layer=layer, interpret=True)
    ref_out, k_ref, v_ref = _reference_decode(
        q, k_new, v_new, k_cache, v_cache, block_tables, lens, bs,
        layer=layer)

    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref_out, np.float32),
        atol=2e-2, rtol=2e-2)
    np.testing.assert_array_equal(
        np.asarray(k_upd, np.float32), np.asarray(k_ref, np.float32))
    # Planes 0 and 2 are untouched by construction of the oracle; assert the
    # kernel's write-back honored the same invariant.
    np.testing.assert_array_equal(
        np.asarray(k_upd[0], np.float32), np.asarray(k_cache[0], np.float32))
    np.testing.assert_array_equal(
        np.asarray(v_upd[2], np.float32), np.asarray(v_cache[2], np.float32))


def _decode_batch(S, T, block_tables, seq_lens):
    """Engine-shaped ragged decode batch (Q == 1) for the dispatch entry."""
    return dict(
        token_seq_ids=jnp.arange(S, dtype=jnp.int32),
        positions=seq_lens - 1,
        slot_mapping=(jnp.take_along_axis(
            block_tables,
            ((seq_lens - 1) // 16)[:, None], axis=1)[:, 0] * 16
            + (seq_lens - 1) % 16),
        block_tables=block_tables,
        seq_lens=seq_lens,
        qtok_idx=jnp.arange(S, dtype=jnp.int32)[:, None],
        token_qpos=jnp.zeros(S, jnp.int32),
    )


def test_lane_gate_falls_back_without_kernel():
    """KVH*D % 128 != 0 with backend='pallas' must take the chunked path.

    Running on CPU proves the fallback fired: the real Mosaic kernel cannot
    execute here, so a correct result means the gate routed around it.
    """
    rng = np.random.default_rng(3)
    H, KVH, D, bs = 4, 1, 64, 16          # F = 64 -> below the lane gate
    seq_lens = [9, 17]
    S = len(seq_lens)
    q, k_new, v_new, k_cache, v_cache, block_tables, lens = _make_decode_case(
        rng, S, H, KVH, D, bs, num_blocks=8, seq_lens=seq_lens)
    batch = _decode_batch(S, S, block_tables, lens)
    out, k_upd, v_upd = A.attention_with_kv_update(
        q, k_new.reshape(S, KVH, D), v_new.reshape(S, KVH, D),
        k_cache, v_cache, batch, block_size=bs, backend="pallas")
    ref_out, k_ref, v_ref = _reference_decode(
        q, k_new, v_new, k_cache, v_cache, block_tables, lens, bs)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref_out, np.float32),
        atol=2e-2, rtol=2e-2)
    np.testing.assert_array_equal(
        np.asarray(k_upd, np.float32), np.asarray(k_ref, np.float32))


def test_block_size_gate_falls_back_without_kernel():
    """block_size % 16 != 0 (bf16 sublane tiling) must also fall back."""
    rng = np.random.default_rng(4)
    H, KVH, D, bs = 8, 2, 64, 8           # F = 128 aligned, bs too small
    seq_lens = [3, 11]
    S = len(seq_lens)
    q, k_new, v_new, k_cache, v_cache, block_tables, lens = _make_decode_case(
        rng, S, H, KVH, D, bs, num_blocks=8, seq_lens=seq_lens)
    batch = dict(
        token_seq_ids=jnp.arange(S, dtype=jnp.int32),
        positions=lens - 1,
        slot_mapping=(jnp.take_along_axis(
            block_tables, ((lens - 1) // bs)[:, None], axis=1)[:, 0] * bs
            + (lens - 1) % bs),
        block_tables=block_tables, seq_lens=lens,
        qtok_idx=jnp.arange(S, dtype=jnp.int32)[:, None],
        token_qpos=jnp.zeros(S, jnp.int32))
    out, _, _ = A.attention_with_kv_update(
        q, k_new.reshape(S, KVH, D), v_new.reshape(S, KVH, D),
        k_cache, v_cache, batch, block_size=bs, backend="pallas")
    ref_out, _, _ = _reference_decode(
        q, k_new, v_new, k_cache, v_cache, block_tables, lens, bs)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref_out, np.float32),
        atol=2e-2, rtol=2e-2)
