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


# ---- key blocks of several pages ------------------------------------------

def _exact(q, row, kv, bt, lens, bs, scale):
    """softmax(q k^T scale) k in numpy float32 over each row's own pages
    with its new row in place, on the operands as the kernel is handed
    them; a pad row (context 0) reads zeros."""
    q, row, kv = (np.asarray(x, np.float32) for x in (q, row, kv))
    bt = np.asarray(bt)
    out = np.zeros(q.shape, np.float32)
    for s, n in enumerate(np.asarray(lens)):
        if n == 0:
            continue
        at = np.arange(n)
        keys = kv[bt[s, at // bs].astype(np.int64) * bs + at % bs]
        keys[-1] = row[s]
        sc = q[s] @ keys.T * scale                                # [H, n]
        p = np.exp(sc - sc.max(axis=-1, keepdims=True))
        out[s] = (p / p.sum(axis=-1, keepdims=True)) @ keys
    return out


def _scattered(row, kv, bt, lens, bs):
    """The cache with each live row's new latent row scattered in."""
    kv, bt, lens = np.array(kv, np.float32), np.asarray(bt), np.asarray(lens)
    for s in np.flatnonzero(lens):
        last = lens[s] - 1
        kv[bt[s, last // bs] * bs + last % bs] = np.asarray(
            row[s], np.float32)
    return kv


# Pages of 32 keys, 4 heads over a 128-lane row: a block of 128 keys is 4
# pages, of 256 keys 8, of 512 keys (kanana-2-30b-a3b's) 16.
MLA_DECODE_CASES = {
    # contexts around a page's and a block's edge, the cell's mean and its
    # longest; 33 and 257 put the new row on the first row of a new page
    # and of a new block (of 128 and of 256 keys)
    "edges": [1, 31, 32, 33, 255, 256, 257, 670],
    # ... and 513 on the first row of a new block of 512; a pad row (context
    # 0, its table naming the null page) beside live rows
    "longest-and-a-pad-row": [1536, 0, 513, 129],
    # one grid program whose rows end one, two and five blocks of 256 apart
    "rows-blocks-apart": [100, 356, 868, 2148],
}


@pytest.mark.parametrize("key_block", [32, 128, 256, 512],
                         ids=["one-page", "kb128", "kb256", "kb512"])
@pytest.mark.parametrize("name", MLA_DECODE_CASES)
def test_key_blocks_match_reference(name, key_block):
    seq_lens = MLA_DECODE_CASES[name]
    S, H, F, bs, scale = len(seq_lens), 4, 128, 32, 0.12
    q, row, kv, bt, lens = _case(
        sum(map(ord, name)), S, H, F, bs,
        num_blocks=S * -(-max(seq_lens) // bs) + 1, seq_lens=seq_lens)
    owned = (np.arange(bt.shape[1])[None, :]
             < -(-np.asarray(seq_lens) // bs)[:, None])
    bt = jnp.where(owned, bt, 0)       # beyond a row's own pages: the null
    # One program for "rows-blocks-apart"; two and four for the others.
    G = 4 if name == "rows-blocks-apart" else 2
    out, kv_upd = mla_paged_decode_update(
        q, row, kv, bt, lens, block_size=bs, scale=scale, interpret=True,
        key_block=key_block, seq_group=G)
    out = np.asarray(out, np.float32)
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out, _exact(q, row, kv, bt, lens, bs, scale),
                               atol=2e-2, rtol=2e-2)
    # The page written back is the scatter of the row, bit for bit, and no
    # other page moved.
    np.testing.assert_array_equal(np.asarray(kv_upd, np.float32),
                                  _scattered(row, kv, bt, lens, bs))


def test_one_page_a_block_is_the_loop_before_key_blocks():
    """``key_block = block_size`` scores the same keys in the same order as
    the kernel did a page at a time: its answers bit for bit, recorded here
    from the flash recurrence a page a step in numpy float32 (bf16
    operands, f32 statistics, the probabilities rounded to bf16 for the
    value dot), to bf16 rounding of the result."""
    H, F, bs, scale = 4, 128, 16, 0.12
    seq_lens = [100, 33, 16, 1]
    S = len(seq_lens)
    q, row, kv, bt, lens = _case(11, S, H, F, bs, num_blocks=S * 7 + 1,
                                 seq_lens=seq_lens)
    out, _ = mla_paged_decode_update(
        q, row, kv, bt, lens, block_size=bs, scale=scale, interpret=True,
        key_block=bs)
    out = np.asarray(out, np.float32)
    bf = lambda x: np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32)
    kvf = _scattered(row, kv, bt, lens, bs)
    for s, ctx in enumerate(seq_lens):
        q2 = bf(np.asarray(q[s], np.float32) * scale)
        m = np.full((H, 1), -1e29, np.float32)
        l = np.zeros((H, 1), np.float32)
        acc = np.zeros((H, F), np.float32)
        for j in range(-(-ctx // bs)):
            page = kvf[int(bt[s, j]) * bs:(int(bt[s, j]) + 1) * bs]
            key_pos = j * bs + np.arange(bs)[None, :]
            sc = np.where(key_pos < ctx, q2 @ page.T, np.float32(-1e30))
            m_new = np.maximum(m, sc.max(axis=-1, keepdims=True))
            p = np.exp(sc - m_new)
            corr = np.exp(m - m_new)
            l = l * corr + p.sum(axis=-1, keepdims=True)
            acc = acc * corr + bf(p) @ page
            m = m_new
        np.testing.assert_allclose(out[s], bf(acc / l), atol=1e-2, rtol=1e-2)


def _page_loop_kernel(block_tables_ref, seq_lens_ref, layer_ref, q_ref,
                      rn_ref, kv_hbm, o_ref, kv_out, kv_buf, sems, wsems, *,
                      block_size: int, scale: float, group: int):
    """The decode kernel as it was before key blocks (PR 36's tree): a
    32-key page a step for G sequences at once, the loop running to the
    group's longest sequence.  Kept here, and not in the package, as what
    the key-block kernel's results are held to."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    i = pl.program_id(0)
    G, bs = group, block_size
    H, F = q_ref.shape[1], q_ref.shape[2]
    li = layer_ref[0]
    base = i * G
    seq_len_g = [seq_lens_ref[base + g] for g in range(G)]
    n_pages_g = [pl.cdiv(sl, bs) for sl in seq_len_g]
    n_max = n_pages_g[0]
    for g in range(1, G):
        n_max = jnp.maximum(n_max, n_pages_g[g])
    write_page_g = [(sl - 1) // bs for sl in seq_len_g]
    w_row_g = [(sl - 1) % bs for sl in seq_len_g]

    def page_dma(slot, j):
        copies = []
        for g in range(G):
            jj = jnp.clip(j, 0, jnp.maximum(n_pages_g[g] - 1, 0))
            start = pl.multiple_of(block_tables_ref[base + g, jj] * bs, bs)
            copies.append(pltpu.make_async_copy(
                kv_hbm.at[li, pl.ds(start, bs)], kv_buf.at[slot, g],
                sems.at[slot, g, 0]))
        return copies

    @pl.when(n_max > 0)
    def _():
        for dma in page_dma(0, 0):
            dma.start()

    q = q_ref[...].astype(jnp.float32) * scale                # [G, H, F]
    row_ids2 = jax.lax.broadcasted_iota(jnp.int32, (bs, F), 0)
    g_ids = jax.lax.broadcasted_iota(jnp.int32, (G, 1, bs), 0)
    sl_arr = jnp.zeros((G, 1, bs), jnp.int32)
    for g in range(G):
        sl_arr = jnp.where(g_ids == g, seq_len_g[g], sl_arr)

    def wb_copy(g):
        wp = write_page_g[g]
        start = pl.multiple_of(
            block_tables_ref[base + g, jnp.maximum(wp, 0)] * bs, bs)
        return pltpu.make_async_copy(
            kv_buf.at[wp % 2, g], kv_out.at[li, pl.ds(start, bs)],
            wsems.at[g, 0])

    def body(j, carry):
        m, l, acc = carry
        slot = j % 2

        @pl.when(j + 1 < n_max)
        def _():
            for g in range(G):
                @pl.when((write_page_g[g] >= 0)
                         & (j == write_page_g[g] + 1))
                def _(g=g):
                    wb_copy(g).wait()
            for dma in page_dma((j + 1) % 2, j + 1):
                dma.start()

        for dma in page_dma(slot, j):
            dma.wait()
        for g in range(G):
            @pl.when(j == write_page_g[g])
            def _(g=g):
                is_wr = row_ids2 == w_row_g[g]
                kv_buf[slot, g] = jnp.where(is_wr, rn_ref[g], kv_buf[slot, g])
                wb_copy(g).start()

        page = kv_buf[slot]                                   # [G, bs, F] bf16
        s_hb = jax.lax.dot_general(
            q.astype(jnp.bfloat16), page, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)               # [G, H, bs]
        key_pos = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (G, 1, bs), 2)
        s_hb = jnp.where(key_pos < sl_arr, s_hb, -1e30)
        m_new = jnp.maximum(m, jnp.max(s_hb, axis=-1, keepdims=True))
        p = jnp.exp(s_hb - m_new)                             # [G, H, bs]
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(jnp.bfloat16), page, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)               # [G, H, F]
        return m_new, l_new, acc * corr + pv

    init = (jnp.full((G, H, 1), -1e29, jnp.float32),
            jnp.zeros((G, H, 1), jnp.float32),
            jnp.zeros((G, H, F), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, n_max, body, init)
    for g in range(G):
        @pl.when((write_page_g[g] >= 0)
                 & (write_page_g[g] + 2 >= n_max))
        def _(g=g):
            wb_copy(g).wait()
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _page_loop_decode(q, row, kv, bt, lens, bs, scale, G):
    """``_page_loop_kernel`` interpreted over a [slots, F] cache."""
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, H, F = q.shape

    def vspec(shape):
        return pl.BlockSpec(shape, lambda i, *_: (i,) + (0,) * (len(shape) - 1),
                            memory_space=pltpu.VMEM)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    out, kv = pl.pallas_call(
        functools.partial(_page_loop_kernel, block_size=bs, scale=scale,
                          group=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S // G,),
            in_specs=[vspec((G, H, F)), vspec((G, 1, F)), any_spec],
            out_specs=[vspec((G, H, F)), any_spec],
            scratch_shapes=[pltpu.VMEM((2, G, bs, F), kv.dtype),
                            pltpu.SemaphoreType.DMA((2, G, 1)),
                            pltpu.SemaphoreType.DMA((G, 1))]),
        out_shape=[jax.ShapeDtypeStruct((S, H, F), q.dtype),
                   jax.ShapeDtypeStruct((1,) + kv.shape, kv.dtype)],
        input_output_aliases={5: 1},
        interpret=True,
    )(bt, lens, jnp.zeros(1, jnp.int32), q, row.reshape(S, 1, F), kv[None])
    return out, kv[0]


@pytest.mark.parametrize("H,key_block", [(32, None), (8, 128), (8, 512)],
                         ids=["kanana-picked", "tp4-kb128", "tp4-kb512"])
def test_key_blocks_round_as_the_page_loop(H, key_block):
    """The kernel against the page loop it replaced, at the cell's geometry
    (pages of 32 over a 640-lane row; 32 heads at the block and the group
    the shapes pick, a tp-4 shard's 8 at a shorter and a longer block) and
    eight rows of the cell's contexts: the block keeps the page loop's
    rounding, so what differs is the order of the f32 sums: at most one
    element in 1,000, none by more than an ulp of bf16; and the same page
    written back."""
    F, bs, scale = 640, 32, 0.0625
    seq_lens = [131, 1536, 670, 257, 512, 1000, 37, 300]
    S = len(seq_lens)
    q, row, kv, bt, lens = _case(7, S, H, F, bs,
                                 num_blocks=S * 48 + 1, seq_lens=seq_lens)
    # Quarters, and a scale that is a power of two: every score is exact
    # in f32 in whatever order a dot sums it, so that the interpreter's
    # dots of two shapes hand both loops the same scores, as the MXU does.
    q, row, kv = (jnp.round(x.astype(jnp.float32) * 4) / 4 for x in
                  (q, row, kv))
    q, row, kv = (x.astype(jnp.bfloat16) for x in (q, row, kv))
    got, kv_got = mla_paged_decode_update(
        q, row, kv, bt, lens, block_size=bs, scale=scale, interpret=True,
        key_block=key_block)
    want, kv_want = _page_loop_decode(q, row, kv, bt, lens, bs, scale, G=8)
    np.testing.assert_array_equal(np.asarray(kv_got, np.float32),
                                  np.asarray(kv_want, np.float32))
    differ = np.asarray(got != want)
    assert differ.mean() <= 1e-3
    # An ulp apart: neighbours among bf16's values (same sign, patterns 1 apart).
    bits = lambda x: np.asarray(x).view(np.uint16).astype(np.int32)
    assert np.all(np.abs(bits(got) - bits(want))[differ] == 1)


@pytest.mark.parametrize("S,H,F,bs,kb,G", [
    (64, 32, 640, 32, 512, 4),     # kanana-2-30b-a3b: a full decode batch
    (8, 32, 640, 32, 512, 4),      #   ... its smallest sequence bucket
    (64, 8, 640, 32, 512, 4),      # a tp-4 shard's 8 heads
    (64, 16, 640, 32, 512, 4),
    (64, 128, 640, 32, 512, 2),    # DeepSeek's 128 heads: 8 H F binds
    (2, 32, 640, 32, 512, 2),      # fewer rows than a group
    (64, 32, 640, 16, 512, 4),     # a 16-key page
    (64, 32, 640, 48, 384, 4),     # a page that is no power of two
    (64, 32, 640, 1024, 1024, 2),  # a page wider than the block: one page
])
def test_block_and_group_are_functions_of_shapes(S, H, F, bs, kb, G):
    from llm_d_tpu.ops.pallas.mla_attention import (
        decode_key_block, decode_seq_group)
    assert decode_key_block(H, F, bs) == kb and kb % bs == 0
    assert decode_seq_group(S, H, F, kb) == G and S % G == 0
    assert A.mla_decode_walk(S, H, F, bs) == (kb, G)


def test_key_block_must_be_whole_pages():
    q, row, kv, bt, lens = _case(3, 2, 4, 128, 16, num_blocks=8,
                                 seq_lens=[20, 5])
    with pytest.raises(ValueError, match="whole pages"):
        mla_paged_decode_update(q, row, kv, bt, lens, block_size=16,
                                scale=0.1, interpret=True, key_block=40)


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
        lp, c, x, batch, (kv,), bs, "pallas", layer=jnp.int32(0))
    assert out.shape == (S, c.hidden_size)
    assert seen["seq_group"] is None       # non-divisor degraded to auto
