"""MLA flash-prefill kernel: interpret-mode parity vs the jnp reference.

The kernel streams each latent page once for BOTH the score and value dots
(single-buffer MQA; ops/pallas/mla_prefill.py).  Oracle: full-softmax
ragged paged attention with q-dim = F and the v-cache aliased to the
k-cache — exactly the math the chunked fallback runs (models/mla.py).
Covers ragged lengths, chunked prefill (prior cached context), q-tiling,
pad rows/sequences, and stacked-cache layer addressing; the key blocks of
several pages against a numpy float32 softmax that shares nothing with
``ops/``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.ops import attention as A
from llm_d_tpu.ops.pallas.mla_prefill import mla_flash_prefill


def _case(seed, S, Q, H, F, bs, num_blocks, seq_lens, new_lens,
          num_layers=None):
    rng = np.random.default_rng(seed)
    shape = ((num_blocks * bs, F) if num_layers is None
             else (num_layers, num_blocks * bs, F))
    kv_cache = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    B = max(-(-int(max(seq_lens)) // bs), 1)
    perm = rng.permutation(num_blocks - 1)[: S * B] + 1
    bt = jnp.asarray(perm.reshape(S, B), jnp.int32)

    qs = np.zeros((S, Q, H, F), np.float32)
    q_pos = np.full((S, Q), -1, np.int32)
    for s in range(S):
        n = new_lens[s]
        qs[s, :n] = rng.standard_normal((n, H, F))
        q_pos[s, :n] = np.arange(seq_lens[s] - n, seq_lens[s])
    return (jnp.asarray(qs, jnp.bfloat16), jnp.asarray(q_pos), kv_cache,
            bt, jnp.asarray(seq_lens, jnp.int32))


def _reference(qs, q_pos, kv_cache, bt, lens, bs, scale, layer=None):
    S, Q, H, F = qs.shape
    rows = [(s, t) for s in range(S) for t in range(Q)
            if int(q_pos[s, t]) >= 0]
    q_flat = jnp.stack([qs[s, t] for s, t in rows])
    positions = jnp.asarray([int(q_pos[s, t]) for s, t in rows], jnp.int32)
    token_seq = jnp.asarray([s for s, _ in rows], jnp.int32)
    out = A.ragged_paged_attention_reference(
        q_flat, kv_cache, kv_cache, token_seq, positions, bt, lens,
        block_size=bs, scale=scale, layer=layer)
    full = np.zeros((S, Q, H, F), np.float32)
    for i, (s, t) in enumerate(rows):
        full[s, t] = np.asarray(out[i], np.float32)
    return full


@pytest.mark.parametrize("H,F,bs", [
    (4, 128, 16),       # lane-minimal latent row
    (2, 640, 16),       # V3-like padded row (576 -> 640)
])
def test_mla_prefill_matches_reference(H, F, bs):
    seq_lens = [1, bs // 2, bs, 2 * bs + 3, 3 * bs]
    new_lens = [1, bs // 2, bs // 2, 5, 3 * bs]   # some with prior context
    S, Q = len(seq_lens), 3 * bs
    qs, q_pos, kv, bt, lens = _case(
        hash((H, F, bs)) % 2**32, S, Q, H, F, bs,
        num_blocks=S * 3 + 1, seq_lens=seq_lens, new_lens=new_lens)
    out = mla_flash_prefill(qs, q_pos, kv, bt, lens, block_size=bs,
                            scale=0.17, interpret=True)
    ref = _reference(qs, q_pos, kv, bt, lens, bs, 0.17)
    mask = np.asarray(q_pos) >= 0
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[mask], ref[mask], atol=2e-2, rtol=2e-2)


def test_mla_prefill_q_tiling_pads_and_layer():
    """Small q-tile forcing multi-tile sequences, pad sequences, and a
    stacked [L, slots, F] cache addressed at layer 1."""
    H, F, bs = 4, 128, 16
    seq_lens = [2 * bs + 5, 7, 0, 0]
    new_lens = [2 * bs + 5, 7, 0, 0]
    S, Q = 4, 64
    qs, q_pos, kv, bt, lens = _case(
        7, S, Q, H, F, bs, num_blocks=16,
        seq_lens=[max(l, 1) for l in seq_lens], new_lens=new_lens,
        num_layers=2)
    lens = jnp.asarray(seq_lens, jnp.int32)
    bt = bt.at[2:].set(0)
    layer = jnp.int32(1)
    out = mla_flash_prefill(qs, q_pos, kv, bt, lens, block_size=bs,
                            scale=0.21, layer=layer, interpret=True,
                            q_tile=16)
    ref = _reference(qs, q_pos, kv, bt, lens, bs, 0.21, layer=layer)
    mask = np.asarray(q_pos) >= 0
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[mask], ref[mask], atol=2e-2, rtol=2e-2)
    # Pad sequences produce zeros (flash stats never accumulate).
    assert np.all(np.asarray(out, np.float32)[2:] == 0.0)


# ---- key blocks of several pages ------------------------------------------

def _rows_case(seed, rows, H, F, bs, Q, poison=False):
    """``rows``: (context, new tokens) a sequence.  A row's table beyond its
    own pages names the null page, as the engine's does.  ``poison``: every
    cache page that no row owns (the null page 0 among them) is NaN."""
    qs, q_pos, kv, bt, lens = _case(
        seed, len(rows), Q, H, F, bs,
        num_blocks=len(rows) * -(-max(c for c, _ in rows) // bs) + 3,
        seq_lens=[c for c, _ in rows], new_lens=[n for _, n in rows])
    pages = np.asarray([-(-c // bs) for c, _ in rows])
    owned = np.arange(bt.shape[1])[None, :] < pages[:, None]
    bt = jnp.where(owned, bt, 0)
    if poison:
        mine = np.zeros(kv.shape[0] // bs, bool)
        mine[np.asarray(bt)[owned]] = True
        kv = jnp.where(jnp.asarray(np.repeat(~mine, bs))[:, None], jnp.nan, kv)
    return qs, q_pos, kv, bt, lens


def _exact(qs, q_pos, kv, bt, lens, bs, scale):
    """softmax(q k^T scale + causal mask) k in numpy float32 over each
    row's own pages, on the operands as the kernel is handed them."""
    qs, q_pos, kv, bt = (np.asarray(x, np.float32 if x.dtype == jnp.bfloat16
                                    else None) for x in (qs, q_pos, kv, bt))
    out = np.zeros(qs.shape, np.float32)
    for s, n in enumerate(np.asarray(lens)):
        keys = kv[(bt[s, np.arange(n) // bs] * bs + np.arange(n) % bs)
                  .astype(np.int64)]                              # [n, F]
        for t in np.flatnonzero(q_pos[s] >= 0):
            sc = qs[s, t] @ keys.T * scale                        # [H, n]
            sc = np.where(np.arange(n)[None, :] <= q_pos[s, t], sc, -np.inf)
            p = np.exp(sc - sc.max(axis=-1, keepdims=True))
            out[s, t] = (p / p.sum(axis=-1, keepdims=True)) @ keys
    return out


# Pages of 16 keys, 4 heads over a 128-lane row, 8 slots a tile (32 fused
# rows): a block of 128 keys is 8 pages, of 256 keys 16, as
# kanana-2-30b-a3b's 256 keys are 8 pages of 32.
MLA_KB_CASES = {
    # contexts shorter than any block: the walk is one partly filled block
    "shorter-than-a-block": [(23, 23), (40, 5), (1, 1)],
    # contexts that end inside a page
    "ends-inside-a-page": [(135, 135), (263, 9), (70, 1)],
    # contexts that end exactly on the edge of a block of 128 and of 256
    "ends-on-a-block-edge": [(128, 128), (256, 40), (256, 1)],
    # ... and one key short of it and one key past it
    "around-a-block-edge": [(127, 127), (129, 1), (257, 30)],
    # a prompt tile whose causal diagonal crosses a block edge: the chunk's
    # slots 120-135 and 248-263 sit in tiles that end on either side of it
    "diagonal-crosses-a-block": [(270, 160), (140, 24)],
    # one-query decode rows beside a prompt, as a mixed step holds them
    "decode-rows-beside-a-prompt": [(64, 1), (65, 1), (300, 1), (190, 60),
                                    (128, 1)],
    # every page no row owns is NaN, the null page too
    "poisoned": [(150, 40), (65, 1), (3, 3), (256, 1)],
}


@pytest.mark.parametrize("key_block", [16, 128, 256],
                         ids=["one-page", "kb128", "kb256"])
@pytest.mark.parametrize("name", MLA_KB_CASES)
def test_key_blocks_match_reference(name, key_block):
    rows = MLA_KB_CASES[name]
    H, F, bs, Q, scale = 4, 128, 16, 160, 0.12
    qs, q_pos, kv, bt, lens = _rows_case(
        sum(map(ord, name)), rows, H, F, bs, Q, poison=name == "poisoned")
    out = np.asarray(mla_flash_prefill(
        qs, q_pos, kv, bt, lens, block_size=bs, scale=scale, interpret=True,
        q_tile=8, key_block=key_block), np.float32)
    assert np.all(np.isfinite(out))         # pad slots and pad rows too
    mask = np.asarray(q_pos) >= 0
    ref = _exact(qs, q_pos, kv, bt, lens, bs, scale)
    np.testing.assert_allclose(out[mask], ref[mask], atol=2e-2, rtol=2e-2)
    assert np.all(out[~mask] == 0.0)
    # The key block changes the order of the flash recurrence, nothing
    # else: one page a block (the loop before key blocks) agrees to rounding.
    paged = np.asarray(mla_flash_prefill(
        qs, q_pos, kv, bt, lens, block_size=bs, scale=scale, interpret=True,
        q_tile=8, key_block=bs), np.float32)
    np.testing.assert_allclose(out[mask], paged[mask], atol=2e-2, rtol=2e-2)


def test_one_page_a_block_is_the_loop_before_key_blocks():
    """``key_block = block_size`` scores the same keys in the same order as
    the kernel did a page at a time: its answers bit for bit, recorded here
    from the flash recurrence a page a step in numpy float32 (bf16
    operands, f32 statistics, the probabilities rounded to bf16 for the
    value dot), to bf16 rounding of the result."""
    H, F, bs, Q, scale = 4, 128, 16, 48, 0.12
    rows = [(100, 40), (33, 1), (16, 16)]
    qs, q_pos, kv, bt, lens = _rows_case(11, rows, H, F, bs, Q)
    out = np.asarray(mla_flash_prefill(
        qs, q_pos, kv, bt, lens, block_size=bs, scale=scale, interpret=True,
        q_tile=8, key_block=bs), np.float32)
    bf = lambda x: np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32)
    kvf, qf = np.asarray(kv, np.float32), np.asarray(qs, np.float32)
    for s, (ctx, _) in enumerate(rows):
        for t in np.flatnonzero(np.asarray(q_pos[s]) >= 0):
            pos = int(q_pos[s, t])
            q2 = bf(qf[s, t] * scale)
            m = np.full((H, 1), -1e29, np.float32)
            l = np.zeros((H, 1), np.float32)
            acc = np.zeros((H, F), np.float32)
            for j in range(-(-min(ctx, pos + 1) // bs)):
                page = kvf[int(bt[s, j]) * bs:(int(bt[s, j]) + 1) * bs]
                key_pos = j * bs + np.arange(bs)[None, :]
                sc = np.where((key_pos <= pos) & (key_pos < ctx),
                              q2 @ page.T, np.float32(-1e30))
                m_new = np.maximum(m, sc.max(axis=-1, keepdims=True))
                p = np.exp(sc - m_new)
                corr = np.exp(m - m_new)
                l = l * corr + p.sum(axis=-1, keepdims=True)
                acc = acc * corr + bf(p) @ page
                m = m_new
            np.testing.assert_allclose(out[s, t], bf(acc / l), atol=1e-2,
                                       rtol=1e-2)


@pytest.mark.parametrize("key_block", [128, 256, 512])
def test_key_blocks_round_as_the_decode_kernel(key_block):
    """A row's last query through this kernel and through the decode kernel
    (``mla_attention.py``, a page a step): the key block weighs every key
    against the running max at the end of its own page and carries it to
    the block's max in f32 (three bf16 terms), so the two kernels round
    alike: what differs is the order of the f32 sums, one element in
    5,000 here.  A block weighed against ONE max agrees to 1e-3 all the
    same but leaves a fifth of the elements an ulp apart, which
    kanana-2-30b-a3b's routing turns into decode-against-prefill
    |d logprob| of 0.045, and two carry terms leave one in 1,500-2,500
    (PERF.md PR 35)."""
    from llm_d_tpu.ops.pallas.mla_attention import mla_paged_decode_update
    H, F, bs, scale = 8, 640, 32, 0.072
    lens = np.array([37, 300, 640, 1000, 257, 512], np.int32)
    S, B = len(lens), -(-int(lens.max()) // bs)
    rng = np.random.default_rng(0)
    cache = jnp.asarray(rng.standard_normal(((S * B + 1) * bs, F)),
                        jnp.bfloat16)
    bt = jnp.asarray((rng.permutation(S * B) + 1).reshape(S, B), jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, H, F)), jnp.bfloat16)
    last = lens - 1
    slots = np.asarray(bt)[np.arange(S), last // bs] * bs + last % bs
    want, _ = mla_paged_decode_update(
        q, cache[slots], cache, bt, jnp.asarray(lens), block_size=bs,
        scale=scale, interpret=True)
    got = mla_flash_prefill(
        q[:, None], jnp.asarray(last[:, None]), cache, bt, jnp.asarray(lens),
        block_size=bs, scale=scale, interpret=True, q_tile=8,
        key_block=key_block)[:, 0]
    want, got = (np.asarray(x, np.float32) for x in (want, got))
    assert np.mean(got != want) < 3e-4
    assert np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)) < 1e-5


@pytest.mark.parametrize("bs,F,rows,want", [
    (32, 640, 128, 256),     # kanana-2-30b-a3b: 4 slots x 32 heads
    (32, 640, 512, 256),     #   ... a 2,048-token chunk's 16 slots
    (32, 640, 64, 256),      # a tile of 64 fused rows
    (32, 640, 32, 256),      # the decode kernel: a sequence's 32 heads,
    (32, 640, 8, 256),       #   ... a tp-4 shard's 8
    (32, 640, 2048, 64),     # what a key costs bounds it: 32 B a fused row
    (16, 640, 256, 256),     # a 16-key page
    (48, 640, 256, 192),     # a page that is no power of two
    (512, 640, 256, 512),    # a page wider than the block: one page
])
def test_key_block_is_a_function_of_shapes(bs, F, rows, want):
    from llm_d_tpu.ops.pallas.mla_prefill import _pick_key_block
    kb = _pick_key_block(bs, F, rows)
    assert kb == want and kb % bs == 0
    assert A.prefill_key_block(rows // 32 or 1, min(rows, 32), F, 576, bs,
                               mla=True) == kb
    # The decode kernel picks by the same rule, its rows the heads, and
    # goes on to 512 keys (tests/test_mla_kernel.py has its table).
    from llm_d_tpu.ops.pallas.mla_attention import decode_key_block
    assert decode_key_block(rows, F, bs) == _pick_key_block(bs, F, rows,
                                                            most=512)


def test_key_block_must_be_whole_pages():
    qs, q_pos, kv, bt, lens = _rows_case(3, [(20, 20)], 4, 128, 16, 32)
    with pytest.raises(ValueError, match="whole pages"):
        mla_flash_prefill(qs, q_pos, kv, bt, lens, block_size=16, scale=0.1,
                          interpret=True, key_block=40)


def test_mla_model_routes_prefill_to_kernel(monkeypatch):
    """models/mla.py must dispatch eligible prefill batches to the kernel
    (backend pallas, Q > 1, lane-aligned row) — pin the routing, not just
    the kernel math."""
    import llm_d_tpu.models.mla as mla_mod

    calls = {}
    import llm_d_tpu.ops.pallas.mla_prefill as mp

    real = mp.mla_flash_prefill

    def spy(*a, **kw):
        calls["hit"] = True
        return real(*a, **kw, interpret=True) \
            if "interpret" not in kw else real(*a, **kw)

    monkeypatch.setattr(mp, "mla_flash_prefill", spy)
    monkeypatch.setattr(A, "resolve_backend", lambda b: "pallas")

    import jax

    from llm_d_tpu.models.config import get_config
    c = get_config("tiny-mla")
    lp = mla_mod.init_mla_params(c, 1, jax.random.PRNGKey(0), jnp.bfloat16)
    lp = {k: v[0] for k, v in lp.items()}
    T, S, Q, bs = 8, 2, 4, 16
    F = -(-(c.kv_lora_rank + c.qk_rope_head_dim) // 128) * 128
    kv = jnp.zeros((2, 8 * bs, F), jnp.bfloat16)
    batch = dict(
        token_ids=jnp.zeros(T, jnp.int32),
        positions=jnp.asarray(np.arange(T) % Q, jnp.int32),
        token_seq_ids=jnp.asarray(np.arange(T) // Q, jnp.int32),
        token_qpos=jnp.asarray(np.arange(T) % Q, jnp.int32),
        slot_mapping=jnp.asarray(np.arange(T), jnp.int32),
        block_tables=jnp.asarray([[1, 2], [3, 4]], jnp.int32),
        seq_lens=jnp.asarray([Q, Q], jnp.int32),
        qtok_idx=jnp.asarray(np.arange(T).reshape(S, Q), jnp.int32),
    )
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (T, c.hidden_size)), jnp.bfloat16)
    out, _ = mla_mod.mla_attention_block(
        lp, c, x, batch, (kv,), bs, "pallas", layer=jnp.int32(0))
    assert calls.get("hit"), "prefill batch did not reach the MLA kernel"
    assert out.shape == (T, c.hidden_size)
