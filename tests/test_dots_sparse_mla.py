"""Latent attention of two geometries in one stack, FULL layers that attend
to the keys a learned indexer selects, SLIDING layers with a window on the
MLA path, cache buffers by layer kind and one rank's share of the routed
experts (models/mla.py, models/moe.py, ops/sparse_mla.py, ops/moe.py).

What this pins, on seeded random weights at the ``tiny-sparse-mla`` preset
on the CPU, against ``benchmarks/references/dots3_note.py`` (float32, no
cache, no kernels, the selection as a mask over a [T, T] score plane):

  - the engine's log-probabilities (the chosen token's and its
    alternatives': logits, not sampled tokens), prefill in chunks and then
    decode through the three cache buffers, contexts above the preset's
    top-k (16) and window (21), against the reference's full forward;
  - the indexer's scores and the exact top-k, attention over the selected
    rows and the window at page edges, at op level against numpy;
  - the three cache buffers' shapes by layer kind, and the pool's size;
  - THE SHARE TEST: the partial results of all eight shares of a layer's
    experts, the shared expert counted once, add up to the uncut layer;
  - ``ModelConfig``'s new raises, and what the engine refuses at
    construction.

Tolerances.  With random weights the index scores near rank ``index_topk``
and the router's scores lie close together, so the bf16 rounding of the
cached rows flips members of a selection and of an expert set, and at a
top-k of 16 one flipped key is a sixteenth of a softmax: served through the
bf16 cache the preset differs from the reference by tenths of a nat at some
positions, which says nothing of the mathematics.  The exact comparison
therefore holds the SAME engine with its cache buffers in float32 (the
program writes whatever dtype the buffers have): measured 1e-5.  The served
bf16 cache is held to a median, as the benchmark's check (d) holds it.
"""

import dataclasses
import functools
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.engine.engine import (EngineConfig, EngineCore,
                                     derive_num_blocks)
from llm_d_tpu.engine.request import Request
from llm_d_tpu.models import get_model, moe as moe_model
from llm_d_tpu.models.config import FULL, SLIDING, ModelConfig, get_config
from llm_d_tpu.ops import moe as moe_ops
from llm_d_tpu.ops import sparse_mla
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.parallel.mesh import MeshConfig
from llm_d_tpu.utils import tracing

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "benchmarks"))

import references.dots3_note as reference  # noqa: E402
import references.plain as plain  # noqa: E402

EXACT_TOL = 2e-4
CTX = tracing.TraceContext("a" * 32, "b" * 16, True)


def _config(**kw):
    return dataclasses.replace(get_config("tiny-sparse-mla"),
                               dtype="float32", **kw)


@functools.lru_cache(maxsize=None)
def _params():
    c = _config()
    return get_model(c).init_params(c, jax.random.PRNGKey(7))


def _engine(f32_cache=False, **kw):
    tracing.reset()     # the engine takes its tracer at construction
    kw = {"block_size": 8, "num_blocks": 128, "max_num_seqs": 4,
          "max_num_batched_tokens": 32, "min_seq_bucket": 4, **kw}
    eng = EngineCore(EngineConfig(model="tiny-sparse-mla",
                                  model_config=_config(), **kw),
                     params=_params())
    if f32_cache:
        eng.kv_cache = {k: v.astype(jnp.float32)
                        for k, v in eng.kv_cache.items()}
    return eng


def _reference(tokens, k, config=None):
    """The plain reference, jitted anew each call (``FAULTS`` is read while
    it traces)."""
    c = config or _config()
    return np.asarray(jax.jit(
        lambda p, t: reference.tail_logprobs(p, c, t, k))(
        _params(), jnp.asarray(tokens, jnp.int32)))


def _prompt(i, n):
    return [(37 * i + 7 * j + j * j) % 500 + 1 for j in range(n)]


def _req(rid, prompt, n=6, **sampling):
    sampling.setdefault("temperature", 0.0)
    sampling.setdefault("logprobs", 0)
    r = Request(request_id=rid, prompt_token_ids=list(prompt),
                sampling=SamplingParams(max_tokens=n, ignore_eos=True,
                                        **sampling))
    r.trace_ctx = CTX
    return r


def _run(eng, reqs):
    for r in reqs:
        eng.add_request(r)
    got = {r.request_id: ([], [], []) for r in reqs}
    for _ in range(3000):
        if not eng.has_work():
            break
        for out in eng.step():
            ids, lps, tops = got[out.request_id]
            ids += out.new_token_ids
            lps += out.logprobs or []
            tops += out.top_logprobs or []
    assert not eng.has_work()
    return got


def _steps(eng):
    return [s["attrs"] for s in eng.tracer.snapshot()
            if s["name"] == "engine.step"]


# ---------------------------------------------------------------------------
# the served engine against the plain reference
# ---------------------------------------------------------------------------

def test_engine_logits_against_the_reference():
    """Prompts under the top-k and the window (5), above both (30, 70) and
    several chunks long (150 in steps of 32 tokens), decoded in mixed steps
    through the latent, index and sliding buffers: the chosen token's
    log-probability AND its two alternatives' against the reference's full
    forward over prompt + answer.  The cache in float32 (module
    docstring)."""
    eng = _engine(f32_cache=True)
    reqs = [_req(f"r{i}", _prompt(i, n), logprobs=2)
            for i, n in enumerate((5, 30, 70, 150))]
    got = _run(eng, reqs)
    for r in reqs:
        ids, lps, tops = got[r.request_id]
        assert len(ids) == 6
        want = _reference(r.prompt_token_ids + ids[:-1], 6)
        np.testing.assert_allclose(lps, want[np.arange(6), ids],
                                   atol=EXACT_TOL)
        for j, alt in enumerate(tops):
            assert len(alt) == 2
            for tok, lp in alt.items():
                assert abs(lp - want[j, tok]) <= EXACT_TOL, (
                    r.request_id, j, tok)
    assert {"mixed", "decode"} <= {s["kind"] for s in _steps(eng)}
    assert all(v.dtype == jnp.float32 for v in eng.kv_cache.values())
    # A prefix-cache hit needs nothing new: latent rows and index keys of
    # the cached blocks are there.
    again = _run(eng, [_req("again", _prompt(3, 150), logprobs=2)])
    assert again["again"][0] == got["r3"][0]
    np.testing.assert_allclose(again["again"][1], got["r3"][1],
                               atol=EXACT_TOL)
    assert eng.metrics.prefix_cache_hits._value.get() >= 144    # 18 blocks


@pytest.fixture(scope="module")
def served():
    """One engine as served (bf16 cache) and what three requests got."""
    eng = _engine()
    reqs = [_req(f"r{i}", _prompt(i, n), n=8)
            for i, n in enumerate((12, 40, 150))]
    return eng, reqs, _run(eng, reqs)


def test_served_bf16_cache_against_the_reference(served):
    """The cache as served: the median over all generated positions, as the
    benchmark's check (d) holds it (measured 0.02-0.1; a hidden state that
    has nothing to do with the reference's reads 2.5 and more)."""
    eng, reqs, got = served
    diffs = []
    for r in reqs:
        ids, lps, _ = got[r.request_id]
        want = _reference(r.prompt_token_ids + ids[:-1], 8)
        diffs += list(np.abs(np.asarray(lps) - want[np.arange(8), ids]))
    assert np.median(diffs) < 0.3
    assert all(v.dtype == jnp.bfloat16 for v in eng.kv_cache.values())


def test_reference_faults_move_the_answer():
    """What benchmarks/tools/dsa_mechanism_check.py gets wrong on the chip
    is wrong here too: each fault moves the reference's log-probabilities
    by far more than the engine's distance from the right ones."""
    c, p = _config(), _params()
    tokens = _prompt(3, 48)
    right = _reference(tokens, 4)
    try:
        for fault in ("no_rescale", "no_gate", "dense_full",
                      "window_plus_one", "int8_weights"):
            reference.FAULTS = {fault}
            wrong = _reference(tokens, 4)
            assert np.abs(wrong - right).max() > 20 * EXACT_TOL, fault
    finally:
        reference.FAULTS = set()
    margins = np.asarray(jax.jit(
        lambda p, t: reference.selection_margins(p, c, t))(
        p, jnp.asarray(tokens, jnp.int32)))
    assert margins.shape == (3, 48)                 # three full layers
    assert np.isinf(margins[:, :16]).all()          # nothing left out yet
    assert (margins[:, 16:] >= 0).all() and np.isfinite(
        margins[:, 16:]).all()


def test_a_prompt_in_one_chunk_and_in_five():
    """A chunk boundary may fall anywhere: the index keys and latent rows
    of earlier chunks come from the cache."""
    a = _run(_engine(f32_cache=True, max_num_batched_tokens=256),
             [_req("a", _prompt(1, 150), n=3)])
    b = _run(_engine(f32_cache=True), [_req("a", _prompt(1, 150), n=3)])
    assert a["a"][0] == b["a"][0]
    np.testing.assert_allclose(a["a"][1], b["a"][1], atol=EXACT_TOL)


# ---------------------------------------------------------------------------
# op level: the indexer, the exact top-k, attention over a set and a band
# ---------------------------------------------------------------------------

BS = 8          # block size of the op-level batches


def _batch(rows, tables_width=8, seed=0):
    """A packed batch of ``rows`` = [(context end, new tokens)], each row's
    new tokens its last: the keys the ops read of ``batch``.  Pages are
    dealt out of order so that a row's blocks do not lie together."""
    S = len(rows)
    T = sum(n for _, n in rows)
    Q = max(n for _, n in rows)
    rng = np.random.default_rng(seed)
    pages = rng.permutation(S * tables_width) + 1
    tables = pages.reshape(S, tables_width).astype(np.int32)
    seq, pos, qpos = [], [], []
    qtok = np.full((S, Q), T, np.int32)
    for s, (end, n) in enumerate(rows):
        for j in range(n):
            qtok[s, j] = len(seq)
            seq.append(s)
            pos.append(end - n + j)
            qpos.append(j)
    pos = np.asarray(pos, np.int32)
    seq = np.asarray(seq, np.int32)
    slot = tables[seq, pos // BS] * BS + pos % BS
    return {k: jnp.asarray(v) for k, v in dict(
        block_tables=tables, token_seq_ids=seq, positions=pos,
        token_qpos=np.asarray(qpos, np.int32), qtok_idx=qtok,
        seq_lens=np.asarray([e for e, _ in rows], np.int32),
        slot_mapping=slot.astype(np.int32)).items()}


def _rows_of(cache, batch, s, end):
    """Row ``s``'s first ``end`` cached rows, through its block table."""
    t = np.asarray(batch["block_tables"])[s]
    p = np.arange(end)
    return np.asarray(cache)[t[p // BS] * BS + p % BS]


ROWS = [(40, 40), (23, 1), (64, 9), (17, 17), (8, 1)]


def _index_case(seed=1, ties=True):
    rng = np.random.default_rng(seed)
    batch = _batch(ROWS)
    T, Hi, Di = int(batch["positions"].shape[0]), 3, 8
    slots = (8 * len(ROWS) + 1) * BS
    q = jnp.asarray(rng.standard_normal((T, Hi, Di)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((T, Hi)), jnp.float32)
    cache = jnp.asarray(rng.standard_normal((2, slots, Di)), jnp.float32)
    if ties:    # exact ties: a key repeated
        page = np.asarray(batch["block_tables"])[0, 0] * BS
        cache = cache.at[1, page + 3].set(cache[1, page + 1])
    return batch, q, w, cache


def _by_token(per_tile, batch, q_tile):
    """[NT, Qt, ...] of the step's tiles -> [T, ...] by token."""
    from llm_d_tpu.ops.attention import query_tiles
    tiles = query_tiles(batch, min(q_tile, batch["qtok_idx"].shape[1]))
    return np.asarray(per_tile)[np.asarray(tiles["tok_tile"]),
                                np.asarray(tiles["tok_slot"])]


def test_index_select_against_numpy():
    """Scores I(t, s) = sum_j w[t, j] relu(q[t, j] . k[s]) over the visible
    keys of the query's own sequence, the exact top-k with ties to the
    lower position, all keys while fewer than k are visible: the set a
    stable descending sort keeps, as a mask over the block table's
    positions."""
    topk = 16
    batch, q, w, cache = _index_case()
    scores, live = jax.jit(functools.partial(
        sparse_mla.index_scores, block_size=BS))(
        q, w, cache, batch, layer=jnp.int32(1))
    chosen = jax.jit(functools.partial(
        sparse_mla.index_select, block_size=BS, topk=topk))(
        q, w, cache, batch, layer=jnp.int32(1))
    assert chosen.shape == scores.shape and chosen.dtype == jnp.bool_
    assert int(live) >= max(e for e, _ in ROWS)
    scores = _by_token(scores, batch, sparse_mla.SELECT_Q_TILE)
    chosen = _by_token(chosen, batch, sparse_mla.SELECT_Q_TILE)
    seq, qpos = np.asarray(batch["token_seq_ids"]), np.asarray(
        batch["positions"])
    for t in range(len(seq)):
        keys = _rows_of(cache[1], batch, seq[t], qpos[t] + 1)
        s = (np.maximum(np.einsum("hd,kd->hk", np.asarray(q[t]), keys), 0)
             * np.asarray(w[t])[:, None]).sum(0)
        np.testing.assert_allclose(scores[t, :qpos[t] + 1], s, rtol=1e-5,
                                   atol=1e-5)
        assert np.isneginf(scores[t, qpos[t] + 1:]).all()
        # the engine's own scores, so that the order of equals is the test
        order = np.argsort(-scores[t], kind="stable")[:min(qpos[t] + 1, topk)]
        assert sorted(np.flatnonzero(chosen[t])) == sorted(order), t


@pytest.mark.parametrize("k", [1, 3, 16, 40, 64])
def test_choose_topk_is_the_stable_sorts_set(k):
    """``choose_topk`` against a stable descending sort on rows made to
    hurt: many equal scores across the threshold (also -0.0 beside 0.0,
    which rank alike), negative and huge values, fewer visible columns
    than k, none at all."""
    rng = np.random.default_rng(k)
    W = 64
    x = rng.standard_normal((12, W)).astype(np.float32)
    x[1] = np.round(x[1])                      # few distinct values
    x[2] = 0.0
    x[2, ::3] = -0.0
    x[3, :] = 1.5                              # all equal
    x[4, rng.permutation(W)[:50]] = -np.inf    # 14 visible
    x[5] = -np.inf                             # a pad slot
    x[6] *= 1e30
    x[7] = -np.abs(x[7])
    x[8, 10:] = -np.inf
    x[9] = np.where(rng.random(W) < 0.5, 2.0, x[9])
    got = np.asarray(jax.jit(functools.partial(
        sparse_mla.choose_topk, k=k))(jnp.asarray(x)))
    for r in range(x.shape[0]):
        n = min(k, int(np.isfinite(x[r]).sum()))
        want = np.argsort(-x[r], kind="stable")[:n]
        assert sorted(np.flatnonzero(got[r])) == sorted(want), r


def _dense(q, rows, seen, scale, R):
    s = np.einsum("hf,kf->hk", q, rows) * scale
    s = np.where(seen[None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return p @ rows[:, :R]


def _attend_case(H, R, F, rows=ROWS, seed=2, tables_width=8):
    rng = np.random.default_rng(seed)
    batch = _batch(rows, tables_width=tables_width)
    T = int(batch["positions"].shape[0])
    slots = (tables_width * len(rows) + 1) * BS
    q = jnp.asarray(rng.standard_normal((T, H, F)), jnp.float32)
    cache = jnp.asarray(rng.standard_normal((3, slots, F)), jnp.float32)
    from llm_d_tpu.ops.attention import query_tiles
    qt = min(sparse_mla.SELECT_Q_TILE, batch["qtok_idx"].shape[1])
    tiles = {k: np.asarray(v) for k, v in query_tiles(batch, qt).items()}
    seq, qpos = np.asarray(batch["token_seq_ids"]), np.asarray(
        batch["positions"])
    C = tables_width * BS
    chosen = np.zeros((tiles["tile_seq"].shape[0], qt, C), bool)
    for t in range(T):
        n = min(qpos[t] + 1, 16)
        chosen[tiles["tok_tile"][t], tiles["tok_slot"][t],
               rng.permutation(qpos[t] + 1)[:n]] = True
    return batch, q, cache, chosen, tiles


@pytest.mark.parametrize("kernel", [False, True])
def test_attend_chosen_against_numpy(kernel, monkeypatch):
    """Attention dense under the selection as a mask, the XLA path and the
    Pallas kernel (interpreted; key blocks of two pages so that rows walk
    one to five blocks and the last is filled up): prefill chunks, decode
    rows and a chunk that continues a cached context."""
    H, R, F = (8, 128, 128) if kernel else (2, 8, 12)
    batch, q, cache, chosen, tiles = _attend_case(H, R, F)
    if kernel:
        from llm_d_tpu.ops.pallas import mla_masked
        monkeypatch.setattr(mla_masked, "KEY_BLOCK", 2 * BS)
        monkeypatch.setattr(
            mla_masked, "mla_masked_attention", functools.partial(
                mla_masked.mla_masked_attention, interpret=True))
    out = np.asarray(jax.jit(functools.partial(
        sparse_mla.attend_chosen, block_size=BS, scale=0.3, R=R,
        kernel=kernel))(q, cache, jnp.asarray(chosen), batch,
                        layer=jnp.int32(2)))
    seq, qpos = np.asarray(batch["token_seq_ids"]), np.asarray(
        batch["positions"])
    for t in range(len(seq)):
        rows = _rows_of(cache[2], batch, seq[t], qpos[t] + 1)
        seen = chosen[tiles["tok_tile"][t], tiles["tok_slot"][t],
                      :qpos[t] + 1]
        np.testing.assert_allclose(
            out[t], _dense(np.asarray(q[t]), rows, seen, 0.3, R),
            rtol=2e-5, atol=2e-5)


def test_masked_kernel_decode_row_is_its_prefill_row():
    """One kernel serves a pure-decode step (tiles of one slot) and a
    prefill chunk (tiles of eight): a query's result does not depend on
    the tile it rides in, so decode against a fresh prefill of the same
    position differs by nothing the kernel adds.  On the chip the two are
    bit-equal (PERF.md PR 39); the interpreter's dots of two heights sum
    in two orders, an f32 ulp apart."""
    from llm_d_tpu.ops.pallas.mla_masked import mla_masked_attention
    H, R, F, KB = 8, 128, 128, 2 * BS
    batch, q, cache, chosen, tiles = _attend_case(H, R, F, rows=[(40, 40)])
    cache = cache.astype(jnp.bfloat16)
    q = q.astype(jnp.bfloat16)
    NT, qt, C = chosen.shape
    q_t = np.concatenate([np.asarray(q, np.float32),
                          np.zeros((1, H, F), np.float32)])[tiles["tile_tok"]]
    bias = np.where(chosen, 0.0, -1e30).astype(np.float32)
    pos = np.concatenate([np.asarray(batch["positions"]), [-1]])[
        tiles["tile_tok"]]
    run = functools.partial(
        mla_masked_attention, kv_cache=cache,
        block_tables=batch["block_tables"], layer=jnp.int32(1),
        block_size=BS, scale=0.3, value_width=R, interpret=True)
    whole = np.asarray(run(
        jnp.asarray(q_t, jnp.bfloat16),
        jnp.asarray(bias.reshape(NT, qt, C // KB, KB).transpose(0, 2, 1, 3)),
        jnp.asarray(tiles["tile_seq"]),
        jnp.asarray(pos.max(1) + 1, jnp.int32), jnp.zeros(NT, jnp.int32)))
    # every slot as a tile of its own
    alone = np.asarray(run(
        jnp.asarray(q_t.reshape(NT * qt, 1, H, F), jnp.bfloat16),
        jnp.asarray(bias.reshape(NT * qt, 1, C // KB, KB).transpose(
            0, 2, 1, 3)),
        jnp.asarray(np.repeat(tiles["tile_seq"], qt)),
        jnp.asarray(pos.reshape(-1) + 1, jnp.int32),
        jnp.zeros(NT * qt, jnp.int32)))
    np.testing.assert_allclose(whole.reshape(alone.shape), alone, rtol=0,
                               atol=2e-6)
    assert np.abs(whole).max() > 0


# The indexer's kernel (ops/pallas/dsa_index.py), interpreted in float32 on
# inputs of eighths, so that every product and every sum of the scores is
# exact and the two forms' sets can be compared bit for bit: key blocks of
# two pages (16 keys), a top-k of 20 that a chunk crosses inside a block.
INDEX_ROWS = [(50, 45), (23, 1), (64, 9), (17, 17), (8, 1), (37, 1)]


def _index_kernel_case(monkeypatch, index_block, rows=INDEX_ROWS, coarse=False,
                       seed=5, Hi=3, Di=8):
    """(batch, q, w, cache) of one step and the patched kernel modules:
    ``index_block`` keys a step of the walk, ``coarse``: keys of -1, 0, 1
    and weights of halves, so that many scores tie at every threshold."""
    from llm_d_tpu.ops.pallas import dsa_index, mla_masked
    monkeypatch.setattr(mla_masked, "KEY_BLOCK", 2 * BS)
    monkeypatch.setattr(dsa_index, "INDEX_BLOCK", index_block)
    for name in ("index_bias", "unwritten"):
        monkeypatch.setattr(dsa_index, name, functools.partial(
            getattr(dsa_index, name), interpret=True))
    rng = np.random.default_rng(seed)
    batch = _batch(rows)
    T = int(batch["positions"].shape[0])
    slots = (8 * len(rows) + 1) * BS
    step = 1.0 if coarse else 0.125
    grid = lambda shape, scale: jnp.asarray(np.round(
        rng.standard_normal(shape) * scale / step) * step, jnp.float32)
    return (batch, grid((T, Hi, Di), 1.0),
            grid((T, Hi), 1.0) if not coarse else jnp.asarray(
                rng.integers(-1, 3, (T, Hi)) / 2, jnp.float32),
            grid((2, slots, Di), 0.6 if coarse else 1.0))


def _bias_of(chosen):
    """``attend_chosen``'s rewrite of a [NT, Qt, C] mask as the masked
    kernel's bias [NT, C / KB, Qt, KB]."""
    from llm_d_tpu.ops.pallas import mla_masked
    NT, qt, C = chosen.shape
    KB = mla_masked.KEY_BLOCK
    return np.where(np.asarray(chosen), 0.0, mla_masked.NEG_INF).astype(
        np.float32).reshape(NT, qt, C // KB, KB).transpose(0, 2, 1, 3)


def _select_both(batch, q, w, cache, topk, q_tile=sparse_mla.SELECT_Q_TILE):
    """(the kernel's bias, the XLA form's mask as a bias, each tile's
    ``live``, the tile list) of one step."""
    tiles = sparse_mla.with_tiles(batch, q_tile)
    run = lambda fn: jax.jit(functools.partial(
        fn, block_size=BS, topk=topk))(q, w, cache, tiles,
                                       layer=jnp.int32(1))
    return (np.asarray(run(sparse_mla.index_bias)),
            _bias_of(run(sparse_mla.index_select)),
            np.asarray(sparse_mla._tile_live(batch, tiles)), tiles)


def _assert_same_under_live(got, want, live):
    """Equal over the key blocks the masked kernel walks (it ends at the
    tile's ``live``; the indexer's kernel writes no further)."""
    KB = got.shape[-1]
    for n in range(len(live)):
        nb = -(-int(live[n]) // KB)
        np.testing.assert_array_equal(got[n, :nb], want[n, :nb], str(n))
        assert (want[n, nb:] < 0).all()


@pytest.mark.parametrize("coarse", [False, True], ids=["eighths", "ties"])
@pytest.mark.parametrize("index_block", [2 * BS, 4 * BS])
def test_index_kernel_writes_the_xla_forms_bias(index_block, coarse,
                                                monkeypatch):
    """The kernel's bias against ``index_select`` + ``attend_chosen``'s on
    a mixed step: a chunk that crosses the top-k inside a key block and
    ends inside another, decode rows under and over the top-k, a fresh
    prompt shorter than it, pad slots and pad tiles; index blocks of one
    key block and of two.  ``ties``: scores on a coarse grid, so that equal
    scores straddle nearly every threshold and the lower positions must be
    the ones kept (the XLA form's own tests hold it to the stable sort)."""
    topk = 20
    batch, q, w, cache = _index_kernel_case(monkeypatch, index_block,
                                            coarse=coarse)
    got, want, live, tiles = _select_both(batch, q, w, cache, topk)
    assert got.shape == want.shape and got.dtype == np.float32
    _assert_same_under_live(got, want, live)
    if coarse:      # the case is what it says: ties across a threshold
        scores, _ = sparse_mla.index_scores(q, w, cache, tiles, BS,
                                            jnp.int32(1))
        s = _by_token(scores, batch, sparse_mla.SELECT_Q_TILE)
        edge = np.sort(s, axis=1)[:, -topk]
        assert ((s == edge[:, None]).sum(1)[np.isfinite(edge)] > 1).sum() > 20


def test_index_kernel_one_slot_tile_is_its_eight_slot_tile(monkeypatch):
    """A pure-decode step's tiles of one slot and a mixed step's of eight
    go through one body, padded to the same eight sublanes: every query
    selects the same set alone in its tile as among seven others."""
    batch, q, w, cache = _index_kernel_case(monkeypatch, 4 * BS)
    whole, _, _, tiles8 = _select_both(batch, q, w, cache, 20)
    alone, want, live, tiles1 = _select_both(batch, q, w, cache, 20, q_tile=1)
    assert alone.shape[2] == 1 and whole.shape[2] == 8
    _assert_same_under_live(alone, want, live)
    KB = whole.shape[-1]
    pos = np.asarray(batch["positions"])
    for t in range(len(pos)):
        nb = pos[t] // KB + 1
        np.testing.assert_array_equal(
            alone[tiles1["tok_tile"][t], :nb, tiles1["tok_slot"][t]],
            whole[tiles8["tok_tile"][t], :nb, tiles8["tok_slot"][t]])


@pytest.mark.parametrize("index_block", [2 * BS, 4 * BS])
def test_index_kernel_reads_nothing_past_a_tiles_live(index_block,
                                                      monkeypatch):
    """Every cache slot at and past a tile's ``live`` (the rest of its
    walk's last block, the row's later pages, the trash page) holds huge
    finite index keys that would score highest: none is selected, none
    counted into a threshold (the sets are the XLA form's on the clean
    cache, and every query keeps min(visible, top-k) keys)."""
    topk = 20
    batch, q, w, cache = _index_kernel_case(monkeypatch, index_block)
    _, want, live, tiles = _select_both(batch, q, w, cache, topk)
    tables, lens = (np.asarray(batch[k]) for k in ("block_tables",
                                                   "seq_lens"))
    dirty = np.full(cache.shape, 1e6, np.float32)
    for s in range(len(lens)):
        at = np.arange(lens[s])
        slots = tables[s, at // BS] * BS + at % BS
        dirty[:, slots] = np.asarray(cache)[:, slots]
    got, _, _, _ = _select_both(batch, q, w, jnp.asarray(dirty), topk)
    _assert_same_under_live(got, want, live)
    pos = np.asarray(batch["positions"])
    kept = (got == 0).transpose(0, 2, 1, 3).reshape(
        got.shape[0], got.shape[2], -1)[tiles["tok_tile"], tiles["tok_slot"]]
    for t in range(len(pos)):
        assert kept[t, :pos[t] + 1].sum() == min(pos[t] + 1, topk), t
        assert not kept[t, pos[t] + 1:-(-(pos[t] + 1) // index_block)
                        * index_block].any(), t


def test_index_kernel_feeds_the_masked_kernel(monkeypatch):
    """The two kernels of a full layer in a row, as ``models/mla.py`` calls
    them where they serve: the bias ``index_bias`` wrote, unwritten past
    each tile's ``live``, read by ``mla_masked_attention`` (interpreted),
    against the XLA forms' selection and attention on the same caches."""
    from llm_d_tpu.ops.pallas import mla_masked
    H, R, F, topk = 8, 128, 128, 20
    batch, q, w, cache = _index_kernel_case(monkeypatch, 4 * BS)
    monkeypatch.setattr(
        mla_masked, "mla_masked_attention", functools.partial(
            mla_masked.mla_masked_attention, interpret=True))
    rng = np.random.default_rng(11)
    q_eff = jnp.asarray(rng.standard_normal((q.shape[0], H, F)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((2, cache.shape[1], F)), jnp.float32)
    tiles = sparse_mla.with_tiles(batch, sparse_mla.SELECT_Q_TILE)

    def layer(select, kernel):
        chosen = select(q, w, cache, tiles, BS, jnp.int32(1), topk)
        return sparse_mla.attend_chosen(q_eff, kv, chosen, tiles, BS,
                                        jnp.int32(1), 0.3, R, kernel=kernel)

    got = jax.jit(lambda: layer(sparse_mla.index_bias, True))()
    want = jax.jit(lambda: layer(sparse_mla.index_select, False))()
    assert np.abs(np.asarray(want)).max() > 0
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def _index_walk_by_hand(ends, news, qt, block, layers):
    """The indexer's kernel a tile at a time (the loop bounds of
    ``ops.pallas.dsa_index._index_kernel``): eight slots a tile, whole
    index blocks from key 0 to the tile's last query's own."""
    real = slots = 0
    for end, n in zip(ends, news):
        for lo in range(end - n, end, qt):
            q = range(lo, min(lo + qt, end))
            real += sum(p + 1 for p in q)
            slots += -(-(q[-1] + 1) // block) * block * 8
    return {"idx_k_real": layers * real, "idx_k_slots": layers * slots}


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("Q,qt", [(2048, 8), (4, 4), (1, 1)])
def test_engine_counts_the_pairs_its_index_walk_covers(Q, qt, seed):
    from llm_d_tpu.engine.packed_batch import BatchLayout
    eng, _ = _announced(_published_window())
    assert eng._select_kernel is True
    rng = np.random.default_rng(seed)
    news = [int(rng.choice([1, int(rng.integers(1, Q + 1))]))
            for _ in range(6)]
    ends = [n + int(rng.choice([0, int(rng.integers(0, 9000 - n))]))
            for n in news]
    got = eng._idx_k_counts(ends, news, BatchLayout(2048, 8, Q, B=4))
    assert got == _index_walk_by_hand(ends, news, qt, 2048, 3)
    assert got["idx_k_real"] == eng._kv_counts(ends, news)["index_pairs"]
    assert 0 < got["idx_k_real"] <= got["idx_k_slots"]
    # Where the XLA form serves the full layers there is no walk to count.
    eng, _ = _announced(_published_window(), backend="reference")
    assert eng._select_kernel is False
    assert eng._idx_k_counts(ends, news, None) == {}


def _window_case(kernel, seed, monkeypatch):
    """(batch with its tile list, q, cache, H, R) for ``attend_window``:
    the XLA form over pages of 8 keys, or the kernel's walk (interpreted)
    over key blocks of 256 keys = 32 pages, tiles of 16 slots.  Rows: a
    prefill chunk that crosses a block edge, a row shorter than any window
    here but 1 and 8, decode rows whose contexts end one before, on and one
    past a page edge and a key-block edge (256, 512), a chunk whose first
    tile's band of 16 + 512 keys straddles four blocks (keys 250-777) and
    whose second tile is mostly pad slots; the tile list ends in pad
    tiles."""
    rng = np.random.default_rng(3)
    if kernel:
        from llm_d_tpu.ops.pallas import mla_masked
        monkeypatch.setattr(
            mla_masked, "mla_masked_attention", functools.partial(
                mla_masked.mla_masked_attention, interpret=True))
        rows = [(300, 300), (9, 9), (255, 1), (256, 1), (257, 1), (511, 1),
                (512, 1), (513, 1), (514, 1), (782, 20)]
        H, R, F, width, qt = 8, 128, 128, 128, 16
    else:
        rows = [(40, 40), (24, 1), (25, 1), (64, 9), (17, 17), (8, 1),
                (33, 2)]
        H, R, F, width, qt = 2, 8, 12, 8, sparse_mla.WINDOW_Q_TILE
    batch = _batch(rows, tables_width=width, seed=seed)
    T = int(batch["positions"].shape[0])
    q = jnp.asarray(rng.standard_normal((T, H, F)), jnp.float32)
    cache = jnp.asarray(
        rng.standard_normal((2, (width * len(rows) + 1) * BS, F)),
        jnp.float32)
    return sparse_mla.with_tiles(batch, qt), q, cache, R


def _assert_window(out, q, cache, batch, window, R, holds=True):
    """``out`` [T, H, R] is (``holds``) or is not attention over the keys
    t - window < s <= t of each query's own row."""
    seq, qpos = np.asarray(batch["token_seq_ids"]), np.asarray(
        batch["positions"])
    off = 0.0
    for t in range(len(seq)):
        keys = _rows_of(cache[1], batch, seq[t], qpos[t] + 1)
        seen = np.arange(qpos[t] + 1) > qpos[t] - window
        assert seen.sum() == min(window, qpos[t] + 1)
        want = _dense(np.asarray(q[t]), keys, seen, 0.25, R)
        if holds:
            np.testing.assert_allclose(out[t], want, rtol=2e-5, atol=2e-5)
        off = max(off, float(np.abs(out[t] - want).max()))
    assert holds or off > 1e-3


WINDOWS = [(False, w) for w in (1, 7, 8, 9, 16, 17, 21)] + [
    (True, w) for w in (1, 8, 9, 255, 256, 257, 513)]


@pytest.mark.parametrize("kernel,window", WINDOWS)
def test_window_on_the_mla_path_at_page_edges(kernel, window, monkeypatch):
    """``attend_window``: a query sees keys t - window < s <= t.  The XLA
    form: windows of a page, a page less and more one, two pages; rows
    whose band starts on a page edge, inside a page, before position 0;
    prefill chunks, decode rows and a chunk that continues a cached
    context.  The kernel's walk (``_window_case``): windows of a page and
    of a key block, one less and one more, and the published 513, whose
    bands end on, before and past page and key-block edges."""
    batch, q, cache, R = _window_case(kernel, window, monkeypatch)
    out = np.asarray(jax.jit(functools.partial(
        sparse_mla.attend_window, window=window, block_size=BS, scale=0.25,
        R=R, kernel=kernel))(q, cache, batch, layer=jnp.int32(1)))
    _assert_window(out, q, cache, batch, window, R)


@pytest.mark.parametrize("kernel,window,served", [
    (False, 16, 17), (False, 16, 15), (True, 256, 257), (True, 256, 255),
    (True, 513, 514), (True, 513, 512)])
def test_a_window_one_key_off_is_caught(kernel, window, served, monkeypatch):
    """What the comparison above would not forgive: the layer served with
    a window one key too wide or too narrow."""
    batch, q, cache, R = _window_case(kernel, 0, monkeypatch)
    out = np.asarray(jax.jit(functools.partial(
        sparse_mla.attend_window, window=served, block_size=BS, scale=0.25,
        R=R, kernel=kernel))(q, cache, batch, layer=jnp.int32(1)))
    _assert_window(out, q, cache, batch, window, R, holds=False)


def test_masked_kernel_window_decode_row_is_its_prefill_row(monkeypatch):
    """The twin of the test above for a layer with a window: a query as a
    tile of one slot (a pure-decode step) and inside a prefill tile of 16.
    The walk starts on absolute multiples of the key block, so the tile of
    16 walks the same blocks for the query, at most one more before them
    that is wholly masked for it and leaves its statistics untouched.
    Bit-equal on the chip (chip_smoke.py); the interpreter's dots of two
    heights sum in two orders, an f32 ulp apart."""
    from llm_d_tpu.ops.pallas import mla_masked
    monkeypatch.setattr(
        mla_masked, "mla_masked_attention", functools.partial(
            mla_masked.mla_masked_attention, interpret=True))
    rng = np.random.default_rng(5)
    H, R, F, width = 8, 128, 128, 128
    batch = _batch([(800, 150)], tables_width=width)
    q = jnp.asarray(rng.standard_normal((150, H, F)), jnp.bfloat16)
    cache = jnp.asarray(
        rng.standard_normal((2, (width + 1) * BS, F)), jnp.bfloat16)
    whole, alone = (np.asarray(jax.jit(functools.partial(
        sparse_mla.attend_window, window=513, block_size=BS, scale=0.25,
        R=R, kernel=True))(q, cache, sparse_mla.with_tiles(batch, qt),
                           layer=jnp.int32(1))) for qt in (16, 1))
    np.testing.assert_allclose(whole, alone, rtol=0, atol=2e-6)
    assert np.abs(whole).max() > 0


def test_the_window_tile_follows_the_geometry():
    """About 1,024 fused rows a tile under the scoped VMEM: 16 slots at the
    published sliding geometry (64 heads, rows of 1,152, values of 1,024),
    8 at the full layers' 128 heads, halved where the blocks would not
    fit; the XLA form keeps its 128; a band of 16 + 512 keys is at most
    four key blocks, a decode row's 513 three."""
    from llm_d_tpu.ops.pallas import mla_masked
    g = _config().mla_geometry(SLIDING)._replace(
        num_heads=64, kv_lora_rank=1024, qk_rope_head_dim=64)
    assert g.row_width == 1152
    assert sparse_mla.window_q_tile(g, True) == 16
    assert sparse_mla.window_q_tile(g, False) == sparse_mla.WINDOW_Q_TILE
    assert mla_masked.pick_q_tile(128, 640, 512) == sparse_mla.SELECT_Q_TILE
    assert mla_masked.pick_q_tile(64, 4096, 4096) == 8
    assert mla_masked.pick_q_tile(2048, 128, 128) == 1
    assert mla_masked.window_bias_blocks(16, 513) == 4
    assert mla_masked.window_bias_blocks(1, 513) == 3
    assert mla_masked.window_bias_blocks(1, 1) == 1
    assert sparse_mla.kernel_refusal(g, 32, 32768) is None
    assert "key blocks" in sparse_mla.kernel_refusal(g, 32, 32768 + 32)
    assert "128-lane" in sparse_mla.kernel_refusal(
        g._replace(kv_lora_rank=1000), 32, 32768)
    assert not sparse_mla.kernel_serves(g, "reference", 32, 32768)
    assert sparse_mla.kernel_serves(g, "pallas", 32, 32768)


# ---------------------------------------------------------------------------
# cache buffers by layer kind
# ---------------------------------------------------------------------------

def test_three_cache_buffers_by_layer_kind(served):
    c = _config()
    assert c.mla_layer_kinds == (FULL, SLIDING)
    assert moe_model.kv_cache_layout(c) == {"kv": 128, "idx": 16,
                                            "kv_swa": 128}
    assert moe_model.kv_cache_layers(c) == {"kv": 3, "idx": 3, "kv_swa": 3}
    assert moe_model.kind_buffers(c, FULL) == ("kv", "idx")
    assert moe_model.kind_buffers(c, SLIDING) == ("kv_swa",)
    eng = served[0]
    slots = eng.config.num_blocks * eng.config.block_size
    assert {k: v.shape for k, v in eng.kv_cache.items()} == {
        "kv": (3, slots, 128), "idx": (3, slots, 16),
        "kv_swa": (3, slots, 128)}
    # the published geometry: 512 + 64 -> 640, 128, 1024 + 64 -> 1152
    big = ModelConfig(
        num_layers=4, layer_types=(FULL, SLIDING, SLIDING, FULL),
        sliding_window=513, hidden_size=5120, num_heads=128,
        q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, swa_num_heads=64,
        swa_kv_lora_rank=1024, swa_qk_nope_head_dim=192, index_topk=2048,
        index_n_heads=64, index_head_dim=128)
    layout, planes = (moe_model.kv_cache_layout(big),
                      moe_model.kv_cache_layers(big))
    assert layout == {"kv": 640, "idx": 128, "kv_swa": 1152}
    assert planes == {"kv": 2, "idx": 2, "kv_swa": 2}
    assert big.mla_geometry(SLIDING).num_heads == 64
    assert big.mla_geometry(SLIDING).q_lora_rank == 1024    # the full one's
    assert big.mla_geometry(FULL).index_topk == 2048
    assert big.mla_geometry(SLIDING).index_topk == 0
    # a block's bytes: each buffer only on the layers of its kind
    per_block = 32 * 2 * (2 * (640 + 128) + 2 * 1152)
    assert derive_num_blocks(1 << 30, layout, 4, 32, planes) == (
        (1 << 30) // per_block)
    # one geometry: the stack is its dense run, then its MoE run, as ever
    k = get_config("tiny-mla")
    assert not k.mla_layer_kinds
    assert [(r.moe, r.start, r.layer0, r.plane0)
            for r in moe_model.layer_runs(k)] == [
        (False, 0, 0, 0), (True, 0, k.first_dense_layers,
                           k.first_dense_layers)]
    assert moe_model.kv_cache_layout(k) == {"kv": 128}
    # two kinds: runs of consecutive layers of one group
    assert [(r.kind, r.moe, r.start, r.stop, r.layer0, r.plane0)
            for r in moe_model.layer_runs(c)] == [
        (FULL, False, 0, 1, 0, 0), (FULL, True, 0, 1, 1, 1),
        (SLIDING, True, 0, 3, 2, 0), (FULL, True, 1, 2, 5, 2)]


def test_step_counts_selected_and_scored_pairs(served):
    """``kv_selected_tokens`` = sum over rows and full layers of
    min(visible, index_topk) a query, ``index_pairs`` = sum of visible keys
    a query, on the host from what the scheduler knows: the first step
    takes the 12-token prompt whole and the first 20 tokens of the next."""
    first = _steps(served[0])[0]
    n_full, k, w = 3, 16, 21
    vis = np.concatenate([np.arange(1, 13), np.arange(1, 21)])
    assert first["index_pairs"] == n_full * vis.sum()
    assert first["kv_selected_tokens"] == n_full * np.minimum(vis, k).sum()
    assert first["kv_read_tokens"] == (
        n_full * np.minimum(vis, k).sum() + 3 * np.minimum(vis, w).sum())
    assert first["kv_ctx_tokens"] == 6 * vis.sum()
    last = _steps(served[0])[-1]            # the 150-token row decodes alone
    assert last["kind"] == "decode"
    assert last["kv_selected_tokens"] == n_full * 16
    assert last["index_pairs"] * 6 == last["kv_ctx_tokens"] * n_full


# ---------------------------------------------------------------------------
# one rank's share of the routed experts
# ---------------------------------------------------------------------------

def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The guide's share test: every one of the 8 shares of the preset's 8
    experts computed apart (the program's op on one share's weights, the
    router at full width), the shared expert counted once, against the
    uncut reference's layer; and the reference's own shares likewise."""
    c = _config()
    uncut = dataclasses.replace(c, num_local_experts=0, first_local_expert=0)
    E, H, Im = c.num_experts, c.hidden_size, c.moe_intermediate_size
    ks = jax.random.split(jax.random.PRNGKey(11), 8)

    def w(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5

    lp = {"router": w(ks[0], (H, E)), "e_bias": jnp.zeros((E,), jnp.float32),
          "w_gate": w(ks[1], (E, H, Im)), "w_up": w(ks[2], (E, H, Im)),
          "w_down": w(ks[3], (E, Im, H)), "shared_gate": w(ks[4], (H, Im)),
          "shared_up": w(ks[5], (H, Im)), "shared_down": w(ks[6], (Im, H))}
    x = jax.random.normal(ks[7], (300, H), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(plain.experts(lp, uncut, x))
        shared = np.asarray(plain.swiglu(x, lp["shared_gate"],
                                         lp["shared_up"], lp["shared_down"]))
        weights, idx = moe_ops.route(x @ lp["router"], c,
                                     e_bias=lp["e_bias"])
        parts, ref_parts = [], []
        group = {k: v[None] for k, v in lp.items()}
        for e0 in range(E):
            parts.append(np.asarray(jax.jit(functools.partial(
                moe_ops.expert_ffn, mesh=None, held=(e0, 1)))(
                x, weights, idx, lp["w_gate"][e0:e0 + 1],
                lp["w_up"][e0:e0 + 1], lp["w_down"][e0:e0 + 1])))
            one = dict(group, **{k: group[k][:, e0:e0 + 1]
                                 for k in ("w_gate", "w_up", "w_down")})
            ref_parts.append(np.asarray(
                reference.experts(one, 0, c, x, share=(e0, 1))) - shared)
    assert sum(np.abs(p).sum() > 0 for p in parts) == E
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5)
    np.testing.assert_allclose(sum(ref_parts) + shared, want, atol=2e-5)
    # a share of two, stacked over layers with the layer's plane given
    stack = {k: jnp.stack([jnp.zeros_like(lp[k][2:4]), lp[k][2:4]])
             for k in ("w_gate", "w_up", "w_down")}
    got = jax.jit(functools.partial(
        moe_ops.expert_ffn, mesh=None, held=(2, 2)))(
        x, weights, idx, stack["w_gate"], stack["w_up"], stack["w_down"],
        held_plane=jnp.int32(1))
    np.testing.assert_allclose(np.asarray(got), parts[2] + parts[3],
                               atol=2e-5)


@pytest.mark.parametrize("T", [4, 300])
def test_a_share_that_overflows_its_quarter(T):
    """Static shapes: the grouped product runs over a quarter of the slots
    where the held ones fit it, over all of them where they do not (every
    token routed to the held experts)."""
    H, Im, k = 16, 24, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    wg = jax.random.normal(ks[0], (2, H, Im)) * 0.2
    wu = jax.random.normal(ks[1], (2, H, Im)) * 0.2
    wd = jax.random.normal(ks[2], (2, Im, H)) * 0.2
    x = jax.random.normal(ks[3], (T, H))
    weights = jax.nn.softmax(jax.random.normal(ks[4], (T, k)))
    for idx in (jnp.tile(jnp.asarray([[4, 5]]), (T, 1)),       # all held
                jnp.tile(jnp.asarray([[0, 7]]), (T, 1)),       # none
                jnp.tile(jnp.asarray([[5, 1]]), (T, 1))):      # half
        got = moe_ops.expert_ffn(x, weights, idx, wg, wu, wd, mesh=None,
                                 held=(4, 2))
        want = jnp.zeros((T, H))
        for j in range(k):
            for e in (0, 1):
                y = plain.swiglu(x, wg[e], wu[e], wd[e])
                want += jnp.where((idx[:, j] == 4 + e)[:, None],
                                  weights[:, j:j + 1] * y, 0.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


def test_experts_touched_counts_over_the_experts_held(served):
    steps = _steps(served[0])
    assert steps and all(
        s["moe_experts_held"] == 5 * 2 for s in steps)     # MoE layers x held
    assert all(0 <= s["moe_experts_touched"] <= 10 for s in steps)
    idx = jnp.asarray([[0, 3], [2, 7], [3, 3]])
    assert int(moe_ops.experts_touched(
        idx - 2, jnp.asarray([True, True, True]), 2)) == 2   # ids 2 and 3
    assert int(moe_ops.experts_touched(
        idx - 2, jnp.asarray([True, False, False]), 2)) == 1


# ---------------------------------------------------------------------------
# what raises
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw,match", [
    (dict(attn_output_gate=True), "MLA path gates by head"),
    (dict(sandwich_norm=True), "MLA path gates by head"),
    (dict(rope_on_full_attention=False), "MLA path gates by head"),
    (dict(index_n_heads=0), "index_topk needs"),
    (dict(index_head_dim=0), "index_topk needs"),
    (dict(index_topk=16, q_lora_rank=0, swa_q_lora_rank=0),
     "index_topk needs"),
    (dict(num_local_experts=3, first_local_expert=6), "no share"),
    (dict(num_local_experts=0, first_local_expert=2), "no share"),
    (dict(num_local_experts=9, first_local_expert=0), "no share"),
    (dict(sliding_window=0), "sliding layers need"),
])
def test_wrong_model_combinations_raise(kw, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(get_config("tiny-sparse-mla"), **kw)


@pytest.mark.parametrize("kw", [
    dict(swa_num_heads=2), dict(swa_kv_lora_rank=16), dict(swa_rope_theta=5.0),
    dict(attn_head_gate=True), dict(mla_lora_rescale=True),
    dict(index_topk=8, index_n_heads=2, index_head_dim=8),
])
def test_mla_fields_raise_without_mla(kw):
    with pytest.raises(ValueError, match="belong to the MLA attention"):
        dataclasses.replace(get_config("tiny"), **kw)


@pytest.mark.parametrize("what,kw", [
    ("multistep", dict(num_scheduler_steps=4)),
    ("sharded_mesh", dict(mesh=MeshConfig(tp=2), allow_device_subset=True)),
    ("sharded_mesh", dict(mesh=MeshConfig(dp=2), allow_device_subset=True)),
    ("kv_offload", dict(kv_offload_blocks=16)),
    ("int8_experts", dict(quantization="int8")),
    ("spec_decode", dict(spec_k=2)),
])
def test_unsupported_combinations_refuse_at_construction(what, kw):
    with pytest.raises(ValueError, match=f"{what} requested but unavailable "
                                         r"\(layer_kinds"):
        _engine(**kw)


def test_a_share_alone_refuses_what_assumes_every_expert():
    tracing.reset()
    c = dataclasses.replace(get_config("tiny-moe"), num_local_experts=2,
                            first_local_expert=2)
    with pytest.raises(ValueError, match=r"eplb requested but unavailable "
                                         r"\(expert_share"):
        EngineCore(EngineConfig(model="tiny-moe", model_config=c,
                                num_blocks=32, enable_eplb=True))
    with pytest.raises(ValueError, match="one device from bf16"):
        moe_ops.expert_ffn(
            jnp.zeros((4, 8)), jnp.zeros((4, 2)), jnp.zeros((4, 2), jnp.int32),
            None, None, None, mesh=None, held=(0, 2), quant={"layer": 0})


def test_a_kv_connector_is_refused(served):
    eng = served[0]
    with pytest.raises(ValueError, match="go by layer kind"):
        eng.kv_connector = object()
    assert eng.kv_connector is None


def test_the_kernels_are_not_claimed_for_layers_they_do_not_serve(served):
    eng = served[0]
    assert eng._prefill_tile_dims is None
    assert eng._attn_k_counts([40], [40], None) == {}
    # The CPU serves the window through XLA: no walk to count.
    assert eng._window_kernel is False
    assert eng._attn_wk_counts([40], [40], None) == {}
    assert not any(k.startswith("attn_wk") for k in _steps(eng)[0])


def _published_window(**kw):
    """The preset with the sliding layers' published geometry: 64 heads
    over rows of 1,024 + 64 (1,152 cached), a window of 513; the full
    layers' 128 heads over 512 + 64 (640)."""
    return _config(**{**dict(
        num_heads=128, kv_lora_rank=512, qk_rope_head_dim=64,
        swa_num_heads=64, swa_kv_lora_rank=1024, swa_qk_rope_head_dim=64,
        sliding_window=513, index_head_dim=128, max_model_len=32768), **kw})


def _announced(config, backend="pallas", block_size=32):
    """An engine shell after ``_announce_attention_path``, and what it
    counted as disabled."""
    from types import SimpleNamespace
    eng = EngineCore.__new__(EngineCore)
    eng.model_config = config
    eng.config = SimpleNamespace(attn_backend=backend, mesh=None,
                                 block_size=block_size)
    seen = []
    eng._disable_feature = lambda f, why, startup=False: seen.append((f, why))
    eng._announce_attention_path(get_model(config).kv_cache_layout(config))
    return eng, seen


def test_a_window_the_kernel_serves_is_not_counted_as_disabled():
    """``llmd_tpu:engine_feature_disabled_total{feature="pallas_attention"}``
    speaks for a window layer only where the kernel refuses its geometry;
    the decision is the geometry's and the backend's, nothing else."""
    eng, seen = _announced(_published_window())
    assert seen == [] and eng._window_kernel is True
    eng, seen = _announced(_published_window(swa_num_heads=36))
    assert eng._window_kernel is False
    assert seen == [("pallas_attention",
                     f"{SLIDING}: 36 heads are not whole sublane tiles of 8")]
    eng, seen = _announced(_published_window(max_model_len=32768 + 32))
    assert eng._window_kernel is False
    assert sorted(k.split(":")[0] for _, k in seen) == sorted([FULL, SLIDING])
    eng, seen = _announced(_published_window(), backend="reference")
    assert seen == [] and eng._window_kernel is False


def _window_walk_by_hand(ends, news, qt, window, layers):
    """``attend_window``'s kernel path, a tile at a time (the loop bounds
    of ``ops.pallas.mla_masked._masked_kernel`` under ``tile_first``)."""
    real = slots = 0
    for end, n in zip(ends, news):
        for lo in range(end - n, end, qt):
            q = range(lo, min(lo + qt, end))
            real += sum(min(window, p + 1) for p in q)
            first = max(q[0] - (window - 1), 0) // 256
            slots += (q[-1] // 256 + 1 - first) * 256 * qt
    return {"attn_wk_real": layers * real, "attn_wk_slots": layers * slots}


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("Q,qt", [(2048, 16), (4, 4), (1, 1)])
def test_engine_counts_the_keys_its_window_walk_covers(Q, qt, seed):
    from llm_d_tpu.engine.packed_batch import BatchLayout
    eng, _ = _announced(_published_window())
    rng = np.random.default_rng(seed)
    news = [int(rng.choice([1, int(rng.integers(1, Q + 1))]))
            for _ in range(6)]
    ends = [n + int(rng.choice([0, int(rng.integers(0, 3000 - n))]))
            for n in news]
    got = eng._attn_wk_counts(ends, news, BatchLayout(2048, 8, Q, B=4))
    assert got == _window_walk_by_hand(ends, news, qt, 513, 3)
    assert 0 < got["attn_wk_real"] <= got["attn_wk_slots"]


def test_the_window_tiles_are_counted_at_the_height_the_grid_holds():
    """``attn_q_slots``: a mean over the layers of the slots the two tile
    lists hold, the window layers' at ``window_q_tile``: (128 + 16) tiles
    of 16 slots where the kernel serves, (16 + 16) of 128 where XLA does;
    the full layers (256 + 16) of 8."""
    from llm_d_tpu.engine.packed_batch import BatchLayout
    layout = BatchLayout(2048, 16, 2048, B=4)
    for backend, window_slots in (("pallas", 144 * 16), ("reference", 32 * 128)):
        eng, _ = _announced(_published_window(), backend=backend)
        assert eng._attn_q_counts(2000, layout) == {
            "attn_q_real": 2000,
            "attn_q_slots": (3 * 272 * 8 + 3 * window_slots) // 6}
