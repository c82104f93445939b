"""The compact list of query tiles the Pallas prefill kernels walk.

``ops.attention.query_tiles`` lays a ragged step out as tiles of Qt query
slots of ONE row each, in place of the padded [S bucket x Q bucket]
rectangle.  Checked here: the list itself over random schedules; both
kernel families over it (interpret mode) bit for bit against the rectangle
call and within tolerance of the jnp reference; the two call sites with the
list derived once (``with_query_tiles``) and derived on the spot.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.ops import attention as A
from llm_d_tpu.ops.pallas.flash_prefill import flash_prefill_paged
from llm_d_tpu.ops.pallas.mla_prefill import mla_flash_prefill

BS = 32          # the cells' page


def _batch(news, ctx, T, Q):
    """The index arrays ``_fill_batch`` writes for rows of ``news[s]`` new
    tokens ending at context ``ctx[s]`` (0 new tokens: a pad row)."""
    S = len(news)
    qtok = np.full((S, Q), T, np.int32)
    seq = np.zeros(T, np.int32)
    qpos = np.zeros(T, np.int32)
    pos = np.zeros(T, np.int32)
    t = 0
    for s, n in enumerate(news):
        qtok[s, :n] = np.arange(t, t + n)
        seq[t:t + n] = s
        qpos[t:t + n] = np.arange(n)
        pos[t:t + n] = np.arange(ctx[s] - n, ctx[s])
        t += n
    return {k: jnp.asarray(v) for k, v in dict(
        qtok_idx=qtok, token_seq_ids=seq, token_qpos=qpos,
        positions=pos, seq_lens=np.asarray(ctx, np.int32)).items()}


# ---- the list alone ------------------------------------------------------

@pytest.mark.parametrize("q_tile", [1, 4, 5, 16])
@pytest.mark.parametrize("seed", range(6))
def test_query_tiles_over_random_schedules(seed, q_tile):
    rng = np.random.default_rng(seed)
    S, T = int(rng.choice([4, 8, 16])), int(rng.choice([32, 64, 128]))
    # A random schedule: decode rows, prompts of any length, pad rows.
    news, left = [], T - int(rng.integers(0, 8))
    for _ in range(S):
        n = int(rng.choice([0, 1, 1, 1, int(rng.integers(1, T))]))
        news.append(min(n, left))
        left -= news[-1]
    Q = max(max(news), q_tile)
    batch = _batch(news, [n + 3 for n in news], T, Q)
    tiles = {k: np.asarray(v)
             for k, v in A.query_tiles(batch, q_tile).items()}
    NT = -(-T // q_tile) + S
    assert tiles["tile_tok"].shape == (NT, q_tile)
    assert tiles["tile_seq"].shape == (NT,)
    real = sum(news)
    # Every real token in exactly one (tile, slot), pads everywhere else.
    flat = tiles["tile_tok"].ravel()
    assert sorted(flat[flat < T]) == list(range(real))
    pos = np.append(np.asarray(batch["positions"]), -1)
    np.testing.assert_array_equal(tiles["tile_pos"], pos[tiles["tile_tok"]])
    # The way back inverts the way in; a token in no row reads a dead slot.
    back = tiles["tile_tok"][tiles["tok_tile"], tiles["tok_slot"]]
    np.testing.assert_array_equal(back[:real], np.arange(real))
    assert np.all(back[real:] == (0 if news[0] else T))
    # A tile holds one row's tokens only, and names that row.
    seq = np.asarray(batch["token_seq_ids"])
    for n in range(NT):
        toks = tiles["tile_tok"][n][tiles["tile_tok"][n] < T]
        assert np.all(seq[toks] == tiles["tile_seq"][n])
    # ceil(n / Qt) tiles a row, in row order; the rest, the last among
    # them, are dead.
    used = sum(-(-n // q_tile) for n in news)
    assert used < NT
    assert np.all(tiles["tile_tok"][used:] == T)
    assert np.all(np.diff(tiles["tile_seq"][:used]) >= 0)
    assert 0 <= tiles["tile_seq"].min() and tiles["tile_seq"].max() < S


def test_tokens_outside_every_row_read_zeros():
    """A fused round's dead slots (``token_qpos`` past the row's list) and
    the rectangle's pad slots read the same thing: the dead last tile."""
    batch = _batch([3, 1], [10, 7], T=8, Q=4)
    qpos = np.asarray(batch["token_qpos"]).copy()
    seq = np.asarray(batch["token_seq_ids"]).copy()
    seq[4:6], qpos[4:6] = 1, [1, 2]          # row 1's stride is 3, 1 used
    tiles = A.query_tiles(dict(batch, token_seq_ids=jnp.asarray(seq),
                               token_qpos=jnp.asarray(qpos)), 2)
    NT = 8 // 2 + 2
    np.testing.assert_array_equal(np.asarray(tiles["tok_tile"])[4:6], NT - 1)
    assert np.all(np.asarray(tiles["tile_tok"])[NT - 1] == 8)


# ---- both kernels over the list ------------------------------------------

# Decode rows, two prompts of unequal length that no tile height divides,
# one row of several tiles, pad rows: (new tokens, context) a row.
NEWS = [1, 1, 37, 1, 70, 1, 21, 0, 0, 0]
CTX = [40, 17, 37, 5, 90, 33, 50, 0, 0, 0]
T, Q = 160, 96


def _paged(rng, F, L=2, B=3):
    S = len(NEWS)
    nb = S * B + 1
    cache = jnp.asarray(rng.standard_normal((L, nb * BS, F)), jnp.bfloat16)
    bt = jnp.asarray((rng.permutation(nb - 1)[:S * B] + 1).reshape(S, B),
                     jnp.int32)
    return cache, bt


@pytest.mark.parametrize("q_tile", [5, 8, 16])   # 5: divides neither Q nor a row
@pytest.mark.parametrize("family", ["gqa", "gqa-window", "mla"])
def test_tile_list_equals_rectangle(family, q_tile):
    rng = np.random.default_rng(sum(map(ord, family)))
    mla = family.startswith("mla")
    H, KVH, D = (4, 1, 128) if mla else (8, 2, 64)
    F = KVH * D
    layer = jnp.int32(1)
    batch = _batch(NEWS, CTX, T, Q)
    real = sum(NEWS)
    q = jnp.asarray(rng.standard_normal((T, H, D)), jnp.bfloat16)
    k_cache, bt = _paged(rng, F)
    v_cache = k_cache if mla else _paged(rng, F)[0]
    batch["block_tables"] = bt

    kw = dict(window=jnp.int32(24)) if family == "gqa-window" else {}

    if mla:
        def kernel(qs, q_pos, **more):
            return mla_flash_prefill(
                qs, q_pos, k_cache, bt, batch["seq_lens"], block_size=BS,
                scale=0.11, layer=layer, interpret=True, **kw, **more)
    else:
        def kernel(qs, q_pos, **more):
            return flash_prefill_paged(
                qs, q_pos, k_cache, v_cache, bt, batch["seq_lens"],
                block_size=BS, num_kv_heads=KVH, scale=0.11, layer=layer,
                interpret=True, **kw, **more)

    batch.update(A.query_tiles(batch, q_tile))
    q_tiles, _ = A.gather_query_tiles(q, batch, F, mla=mla)
    out_t = kernel(q_tiles, batch["tile_pos"], tile_seq=batch["tile_seq"])
    out = np.asarray(out_t[batch["tok_tile"], batch["tok_slot"]], np.float32)

    qs, q_pos = A.gather_per_seq_queries(q, batch["positions"],
                                         batch["qtok_idx"])
    out_s = kernel(qs, q_pos, q_tile=q_tile)
    rect = np.asarray(
        out_s[batch["token_seq_ids"], batch["token_qpos"]], np.float32)
    # The same recurrence over the same keys for every query: the same bits.
    np.testing.assert_array_equal(out[:real], rect[:real])

    used = sum(-(-n // q_tile) for n in NEWS)
    assert used < out_t.shape[0] == -(-T // q_tile) + len(NEWS)
    assert np.all(np.asarray(out_t[used:], np.float32) == 0.0)   # dead tiles

    ref = A.ragged_paged_attention_reference(
        q, k_cache, v_cache, batch["token_seq_ids"], batch["positions"], bt,
        batch["seq_lens"], block_size=BS, scale=0.11, layer=layer, **kw)
    np.testing.assert_allclose(out[:real], np.asarray(ref, np.float32)[:real],
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("Q,H,F,mla,want", [
    (512, 32, 640, True, 4),      # kanana-2-30b-a3b: 128 fused rows a tile
    (256, 32, 640, True, 4),      # ... at every bucket a prompt of the
    (1024, 32, 640, True, 8),     #     batch mix lands in; 128 tiles a row
    (2048, 32, 640, True, 16),    # ... a long chunk: its VMEM bound
    (512, 8, 640, True, 16),      # ... one of four tp shards: 128 rows too
    (128, 128, 640, True, 1),     # DeepSeek's 128 heads: one slot is a pass
    (2048, 128, 640, True, 4),    # ... their VMEM bound
    (2048, 32, 512, False, 32),   # qwen3-30b-a3b / trinity-mini: 64 tiles
    (1024, 32, 512, False, 16),   #     a row
    (128, 32, 512, False, 8),
    (1024, 8, 128, False, 32),    # llama3-1b, one of four tp shards
    (4, 32, 512, False, 4),       # --spec-k 3: k + 1 slots a row
    (3, 32, 640, True, 3),        # ... a bucket that is no power of two
    (512, 512, 640, True, 1),     # more heads than rows: one slot
])
def test_tile_height_follows_the_query_bucket_under_the_vmem_bound(
        Q, H, F, mla, want):
    assert A.prefill_q_tile(Q, H, F, mla) == want


# ---- the call sites -------------------------------------------------------

def _interpreted(monkeypatch):
    """The Pallas backend on the CPU: both prefill kernels interpreted."""
    import llm_d_tpu.ops.pallas.flash_prefill as fp
    import llm_d_tpu.ops.pallas.mla_prefill as mp
    calls = []
    for mod, name in ((fp, "flash_prefill_paged"), (mp, "mla_flash_prefill")):
        real = getattr(mod, name)

        def spy(*a, _real=real, _name=name, **kw):
            calls.append((_name, a[0].shape, kw.get("tile_seq") is not None))
            return _real(*a, **{**kw, "interpret": True})

        monkeypatch.setattr(mod, name, spy)
    monkeypatch.setattr(A, "resolve_backend", lambda b: "pallas")
    return calls


@pytest.mark.parametrize("derived", ["once", "on-the-spot", "stacked"])
def test_gqa_call_site_walks_the_tile_list(monkeypatch, derived):
    rng = np.random.default_rng(3)
    H, KVH, D = 8, 2, 64
    F = KVH * D
    batch = _batch(NEWS, CTX, T, Q)
    k_cache, bt = _paged(rng, F)
    v_cache, _ = _paged(rng, F)
    batch["block_tables"] = bt
    pos, seq = np.asarray(batch["positions"]), np.asarray(
        batch["token_seq_ids"])
    slot = np.asarray(bt)[seq, pos // BS] * BS + pos % BS
    slot[sum(NEWS):] = 0
    batch["slot_mapping"] = jnp.asarray(slot, jnp.int32)
    q = jnp.asarray(rng.standard_normal((T, H, D)), jnp.bfloat16)
    k_new = jnp.asarray(rng.standard_normal((T, KVH, D)), jnp.bfloat16)
    v_new = jnp.asarray(rng.standard_normal((T, KVH, D)), jnp.bfloat16)

    def attend(batch, backend):
        return A.attention_with_kv_update(
            q, k_new, v_new, k_cache, v_cache, batch, block_size=BS,
            backend=backend, layer=jnp.int32(0))[0]

    want = attend(batch, "reference")
    calls = _interpreted(monkeypatch)
    if derived == "once":
        tiled = A.with_query_tiles(batch, H, F, "pallas")
        assert set(A.QUERY_TILE_KEYS) <= set(tiled)
        got = attend(tiled, "pallas")
    elif derived == "stacked":
        # dp shards: one list a shard, each what the shard alone derives.
        two = {k: jnp.stack([v, v]) for k, v in batch.items()}
        tiled = A.with_query_tiles(two, H, F, "pallas")
        alone = A.with_query_tiles(batch, H, F, "pallas")
        for k in A.QUERY_TILE_KEYS:
            np.testing.assert_array_equal(tiled[k][1], alone[k])
        got = attend({k: v[1] for k, v in tiled.items()}, "pallas")
    else:
        got = attend(batch, "pallas")
    qt = A.prefill_q_tile(Q, H, F)
    assert calls == [("flash_prefill_paged",
                      (-(-T // qt) + len(NEWS), qt, H, D), True)]
    real = sum(NEWS)
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[:real],
        np.asarray(want, np.float32)[:real], atol=3e-2, rtol=3e-2)


# A block-diffusion step: every row holds whole blocks of 4 (passes over one
# block, prompt chunks), contexts and starts on block boundaries.
BLOCK = 4
BLOCK_NEWS = [4, 4, 36, 4, 68, 4, 20, 0, 0, 0]
BLOCK_CTX = [40, 20, 36, 8, 92, 36, 52, 0, 0, 0]


@pytest.mark.parametrize("path", ["pallas", "chunked"])
def test_block_visibility_through_the_call_site(monkeypatch, path):
    """The served entry point under block-causal visibility at the cells'
    head geometry (32 heads over 4 KV heads of 128), on the interpreted
    tile path and the chunked XLA path, against a numpy float32 softmax
    under the block mask over the same bf16 rows."""
    rng = np.random.default_rng(7)
    H, KVH, D = 32, 4, 128
    F = KVH * D
    batch = A.with_block_visibility(_batch(BLOCK_NEWS, BLOCK_CTX, T, Q),
                                    BLOCK)
    k_cache, bt = _paged(rng, F)
    v_cache, _ = _paged(rng, F)
    batch["block_tables"] = bt
    pos, seq = np.asarray(batch["positions"]), np.asarray(
        batch["token_seq_ids"])
    real = sum(BLOCK_NEWS)
    slot = np.asarray(bt)[seq, pos // BS] * BS + pos % BS
    slot[real:] = 0
    batch["slot_mapping"] = jnp.asarray(slot, jnp.int32)
    q = jnp.asarray(rng.standard_normal((T, H, D)), jnp.bfloat16)
    k_new = jnp.asarray(rng.standard_normal((T, KVH, D)), jnp.bfloat16)
    v_new = jnp.asarray(rng.standard_normal((T, KVH, D)), jnp.bfloat16)
    if path == "pallas":
        calls = _interpreted(monkeypatch)
        batch = A.with_query_tiles(batch, H, F, "pallas")
        # the tile list carries the limits, not the positions
        lim = np.append((pos // BLOCK + 1) * BLOCK - 1, -1)
        np.testing.assert_array_equal(
            batch["tile_pos"], lim[np.asarray(batch["tile_tok"])])
    got, kc, vc = A.attention_with_kv_update(
        q, k_new, v_new, k_cache, v_cache, batch, block_size=BS,
        backend=path, layer=jnp.int32(1))
    if path == "pallas":
        assert [c[0] for c in calls] == ["flash_prefill_paged"]
    got = np.asarray(got, np.float32)
    kc, vc = (np.asarray(c[1], np.float32) for c in (kc, vc))
    qf = np.asarray(q, np.float32)
    t = 0
    for s, (n, ctx) in enumerate(zip(BLOCK_NEWS, BLOCK_CTX)):
        if not n:
            continue
        at = np.arange(ctx)
        rows = np.asarray(bt)[s, at // BS] * BS + at % BS
        k = np.repeat(kc[rows].reshape(ctx, KVH, D), H // KVH, axis=1)
        v = np.repeat(vc[rows].reshape(ctx, KVH, D), H // KVH, axis=1)
        p = np.arange(ctx - n, ctx)
        sc = np.einsum("nhd,chd->nhc", qf[t:t + n], k) * D ** -0.5
        seen = at[None, :] // BLOCK <= p[:, None] // BLOCK
        sc = np.where(seen[:, None, :], sc, -np.inf)
        w = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("nhc,chd->nhd", w / w.sum(-1, keepdims=True), v)
        np.testing.assert_allclose(got[t:t + n], want, atol=3e-2, rtol=3e-2)
        # ... and it is not the causal answer: a block's first query sees
        # the three slots after it
        causal = np.where((at[None, :] <= p[:, None])[:, None, :], sc,
                          -np.inf)
        wc = np.exp(causal - causal.max(-1, keepdims=True))
        assert np.abs(np.einsum(
            "nhc,chd->nhd", wc / wc.sum(-1, keepdims=True), v)
            - got[t:t + n]).max() > 0.1
        t += n


def test_tile_list_is_derived_for_pallas_prefill_steps_only():
    batch = _batch(NEWS, CTX, T, Q)
    assert A.with_query_tiles(batch, 8, 128, "reference") is batch
    decode = _batch([1, 1, 0], [5, 9, 0], T=4, Q=1)
    assert A.with_query_tiles(decode, 8, 128, "pallas") is decode


def test_mla_forward_derives_the_list_once_a_step(monkeypatch):
    """A whole MLA + MoE forward over a mixed step with the Pallas backend:
    the tile list reaches every layer's kernel call from outside the layer
    scans, and the step's hidden states are the reference backend's."""
    from llm_d_tpu.models import get_config, get_model
    c = get_config("tiny-mla")
    model = get_model(c)
    params = model.init_params(c, jax.random.PRNGKey(0))
    bs, news, ctx = 16, [1, 9, 1, 0], [12, 9, 30, 0]
    Tn, Qn, B = 16, 16, 2
    batch = _batch(news, ctx, Tn, Qn)
    rng = np.random.default_rng(5)
    batch["token_ids"] = jnp.asarray(
        rng.integers(1, c.vocab_size, Tn), jnp.int32)
    bt = np.arange(1, 1 + len(news) * B, dtype=np.int32).reshape(-1, B)
    batch["block_tables"] = jnp.asarray(bt)
    pos, seq = np.asarray(batch["positions"]), np.asarray(
        batch["token_seq_ids"])
    slot = bt[seq, pos // bs] * bs + pos % bs
    slot[sum(news):] = 0
    batch["slot_mapping"] = jnp.asarray(slot, jnp.int32)
    batch["sample_idx"] = jnp.asarray([0, 9, 10, 0], jnp.int32)
    width = model.kv_cache_layout(c)["kv"]
    kv = {"kv": jnp.asarray(
        rng.standard_normal((c.num_layers, 10 * bs, width)) * 0.1,
        jnp.bfloat16)}

    want, _ = model.forward(params, kv, batch, c, bs, "reference")
    calls = _interpreted(monkeypatch)
    derived = []
    real_tiles = A.query_tiles
    monkeypatch.setattr(A, "query_tiles", lambda *a, **kw: (
        derived.append(1), real_tiles(*a, **kw))[1])
    got, _ = model.forward(params, kv, batch, c, bs, "pallas")
    assert len(derived) == 1          # not once a layer scan, nor a layer
    assert calls and all(name == "mla_flash_prefill" and tiled
                         for name, _, tiled in calls)
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[:3], np.asarray(want, np.float32)[:3],
        atol=5e-2, rtol=5e-2)


# ---- the counters on ``engine.step`` -------------------------------------

@pytest.mark.parametrize("model,dims,bucket,dp,slots", [
    # kanana2.batch's mixed step on the chip: (128 + 64) tiles of 4 slots
    # where the rectangle had 64 x 512.
    ("tiny-mla", (32, 640), (512, 64, 512), 1, 192 * 4),
    # trinity-mini.docqa's chunk: (64 + 8) tiles of 32.
    ("tiny", (32, 512), (2048, 8, 2048), 1, 72 * 32),
    # Two dp shards, each its own list.
    ("tiny", (32, 512), (2048, 8, 2048), 2, 2 * 72 * 32),
    # Another path serves prefill: the rectangle.
    ("tiny", None, (2048, 8, 2048), 1, 8 * 2048),
])
def test_engine_counts_the_slots_its_grid_holds(model, dims, bucket, dp,
                                                slots):
    from llm_d_tpu.engine.engine import EngineCore
    from llm_d_tpu.engine.packed_batch import BatchLayout
    from llm_d_tpu.models import get_config
    engine = EngineCore.__new__(EngineCore)
    engine.model_config = get_config(model)
    engine._prefill_tile_dims = dims
    assert engine._attn_q_counts(417, BatchLayout(*bucket, B=4, dp=dp)) == {
        "attn_q_real": 417, "attn_q_slots": slots}
