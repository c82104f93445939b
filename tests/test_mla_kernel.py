"""MLA Pallas decode kernel: interpret-mode parity vs the jnp reference.

The kernel streams each latent page once for both score and value dots
(single-buffer MQA; ops/pallas/mla_attention.py).  Oracle: scatter the new
row, then full-softmax ragged paged attention with q-dim = F and the
v-cache aliased to the k-cache — exactly the math the chunked fallback
runs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.models.config import get_config
from llm_d_tpu.ops import attention as A
from llm_d_tpu.ops.pallas.mla_attention import mla_paged_decode_update


def _case(seed, S, H, F, block_size, num_blocks, seq_lens, num_layers=None):
    rng = np.random.default_rng(seed)
    shape = ((num_blocks * block_size, F) if num_layers is None
             else (num_layers, num_blocks * block_size, F))
    kv = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    B = max(-(-int(max(seq_lens)) // block_size), 1)
    perm = rng.permutation(num_blocks - 1)[: S * B] + 1
    bt = jnp.asarray(perm.reshape(S, B), jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, H, F)), jnp.bfloat16)
    row = jnp.asarray(rng.standard_normal((S, F)), jnp.bfloat16)
    return q, row, kv, bt, jnp.asarray(seq_lens, jnp.int32)


def _reference(q, row, kv, bt, lens, bs, scale, layer=None):
    S, H, F = q.shape
    slot = (jnp.take_along_axis(bt, ((lens - 1) // bs)[:, None],
                                axis=1)[:, 0] * bs + (lens - 1) % bs)
    kv, _ = A.write_kv(kv, kv, row.reshape(S, 1, F), row.reshape(S, 1, F),
                       slot, layer=layer)
    out = A.ragged_paged_attention_reference(
        q, kv, kv, token_seq_ids=jnp.arange(S, dtype=jnp.int32),
        positions=lens - 1, block_tables=bt, seq_lens=lens,
        block_size=bs, scale=scale, layer=layer)
    return out, kv


@pytest.mark.parametrize("H,F,bs", [(4, 128, 16), (8, 256, 32), (2, 640, 16)])
def test_mla_kernel_matches_reference(H, F, bs):
    seq_lens = [1, bs // 2, bs, bs + 3, 3 * bs]
    S = len(seq_lens)
    scale = 0.17
    q, row, kv, bt, lens = _case(hash((H, F, bs)) % 2**32, S, H, F, bs,
                                 num_blocks=S * 3 + 1, seq_lens=seq_lens)
    out, kv_upd = mla_paged_decode_update(
        q, row, kv, bt, lens, block_size=bs, scale=scale, interpret=True)
    ref_out, kv_ref = _reference(q, row, kv, bt, lens, bs, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               atol=2e-2, rtol=2e-2)
    np.testing.assert_array_equal(np.asarray(kv_upd, np.float32),
                                  np.asarray(kv_ref, np.float32))


@pytest.mark.parametrize("seq_group", [1, 4, 8])
def test_mla_kernel_sequence_grouping(seq_group):
    """Grouped programs must match the oracle with ragged lengths in a
    group, including zero-length PAD rows (clamped dead reads: no score,
    no write-back)."""
    H, F, bs = 4, 128, 16
    real_lens = [1, 7, bs, bs + 1, 2 * bs, 3 * bs - 1]
    S_real = len(real_lens)
    S = 8
    seq_lens = real_lens + [0] * (S - S_real)
    q, row, kv, bt, lens = _case(21 + seq_group, S, H, F, bs,
                                 num_blocks=S * 3 + 1, seq_lens=seq_lens)
    bt = bt.at[S_real:].set(0)     # pad rows point at the null block
    out, kv_upd = mla_paged_decode_update(
        q, row, kv, bt, lens, block_size=bs, scale=0.21, interpret=True,
        seq_group=seq_group)
    ref_out, kv_ref = _reference(
        q[:S_real], row[:S_real], kv, bt[:S_real], lens[:S_real], bs, 0.21)
    np.testing.assert_allclose(np.asarray(out[:S_real], np.float32),
                               np.asarray(ref_out, np.float32),
                               atol=2e-2, rtol=2e-2)
    np.testing.assert_array_equal(np.asarray(kv_upd, np.float32),
                                  np.asarray(kv_ref, np.float32))


def test_mla_kernel_stacked_layer_addressing():
    H, F, bs, L = 4, 128, 16, 3
    seq_lens = [5, 2 * bs + 1]
    S = len(seq_lens)
    q, row, kv, bt, lens = _case(9, S, H, F, bs, num_blocks=8,
                                 seq_lens=seq_lens, num_layers=L)
    layer = jnp.asarray(1, jnp.int32)
    out, kv_upd = mla_paged_decode_update(
        q, row, kv, bt, lens, block_size=bs, scale=0.2, layer=layer,
        interpret=True)
    ref_out, kv_ref = _reference(q, row, kv, bt, lens, bs, 0.2, layer=layer)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               atol=2e-2, rtol=2e-2)
    np.testing.assert_array_equal(np.asarray(kv_upd, np.float32),
                                  np.asarray(kv_ref, np.float32))
    np.testing.assert_array_equal(np.asarray(kv_upd[0], np.float32),
                                  np.asarray(kv[0], np.float32))


def test_lane_padding_is_score_neutral():
    """Padding the latent row with zero columns (and zero query columns)
    must not change the attention output — the invariant that lets the
    engine lane-pad V3's 576-wide row to 640 for the kernel."""
    H, F, bs = 4, 96, 16            # 96 -> pad to 128
    seq_lens = [7, bs + 2]
    S = len(seq_lens)
    q, row, kv, bt, lens = _case(11, S, H, F, bs, num_blocks=8,
                                 seq_lens=seq_lens)
    base, _ = _reference(q, row, kv, bt, lens, bs, 0.3)

    pad = 128 - F
    q_p = jnp.pad(q, ((0, 0), (0, 0), (0, pad)))
    row_p = jnp.pad(row, ((0, 0), (0, pad)))
    kv_p = jnp.pad(kv, ((0, 0), (0, pad)))
    out_p, _ = mla_paged_decode_update(
        q_p, row_p, kv_p, bt, lens, block_size=bs, scale=0.3,
        interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_p[..., :F], np.float32),
        np.asarray(base[..., :F], np.float32), atol=2e-2, rtol=2e-2)


ENGINE_KW = dict(model="tiny-mla", block_size=4, num_blocks=64,
                 max_num_seqs=4, max_num_batched_tokens=64,
                 min_token_bucket=16, min_seq_bucket=4)


def test_mla_seq_group_env_non_divisor_degrades_to_auto(monkeypatch):
    """Env-knob contract: LLMD_MLA_SEQ_GROUP that does not divide the
    current sequence bucket falls back to auto grouping instead of
    crashing the decode path (S varies with load, the knob must not)."""
    import llm_d_tpu.models.mla as mla_mod
    import llm_d_tpu.ops.pallas.mla_attention as ma

    monkeypatch.setenv("LLMD_MLA_SEQ_GROUP", "7")    # divides no pow2 S
    monkeypatch.setattr(A, "resolve_backend", lambda b: "pallas")
    real = ma.mla_paged_decode_update
    seen = {}

    def spy(*a, **kw):
        seen["seq_group"] = kw.get("seq_group")
        kw["interpret"] = True
        return real(*a, **kw)

    monkeypatch.setattr(ma, "mla_paged_decode_update", spy)
    c = get_config("tiny-mla")
    lp = {k: v[:1] for k, v in EngineCore(
        EngineConfig(**ENGINE_KW)).params["moe_layers"].items()}
    lp = {k: v[0] for k, v in lp.items()}
    S, bs = 2, 16
    F = -(-(c.kv_lora_rank + c.qk_rope_head_dim) // 128) * 128
    kv = jnp.zeros((1, 8 * bs, F), jnp.bfloat16)
    lens = jnp.asarray([3, 5], jnp.int32)
    batch = dict(
        token_ids=jnp.zeros(S, jnp.int32),
        positions=lens - 1,
        token_seq_ids=jnp.arange(S, dtype=jnp.int32),
        token_qpos=jnp.zeros(S, jnp.int32),
        slot_mapping=jnp.asarray([1 * bs + 2, 2 * bs + 4], jnp.int32),
        block_tables=jnp.asarray([[1], [2]], jnp.int32),
        seq_lens=lens,
        qtok_idx=jnp.arange(S, dtype=jnp.int32)[:, None],
    )
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (S, c.hidden_size)), jnp.bfloat16)
    out, _ = mla_mod.mla_attention_block(
        lp, c, x, batch, kv, bs, "pallas", layer=jnp.int32(0))
    assert out.shape == (S, c.hidden_size)
    assert seen["seq_group"] is None       # non-divisor degraded to auto
