"""The served attention entry points against plain float32 attention.

``ops.attention.attention_with_kv_update`` (GQA) and ``models.mla``'s
``_mla_attend`` (the absorbed latent row) are what every step program
calls once a layer: they write the step's new rows into the paged cache
and attend.  Here each is handed a filled cache at the head geometry the
benchmark's cells and ``chip_smoke.py --chips 4`` serve, and its output
is held against a numpy softmax(q k^T scale + mask) v on the SAME q, k, v
before any of them was rounded to bf16 — a reference that shares nothing
with ``ops/``.  The kernel parity tests compare one implementation with
another; this one bounds what the served path costs against exact
attention, which is what an end-to-end ``correct`` cannot see (PERF.md
Open question 14).

The bound is one number a family, set from what bf16 rows, bf16 MXU
operands and a bf16 result cost against float32 (at most 0.0039 over the
GQA cases, 0.0049 over the MLA ones, whose row is 576 wide) with about
half as much again for head-room.  Three cases prove it bites: the same
check over a cache whose rows went through int8 and back (0.0074 to
0.0115) must exceed it.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.models import mla as mla_mod
from llm_d_tpu.ops import attention as A

BS = 32           # the cells' page
LAYERS, LAYER = 2, 1   # a stacked cache, attended at a plane that is not 0

# Head geometry as ONE shard sees it, and the family's bound on
# rms(out - exact) / rms(exact) over the step's real tokens.
FAMILIES = {
    # qwen3-30b-a3b and trinity-mini: 32 heads over 4 KV heads of 128.
    "gqa": dict(H=32, KVH=4, D=128, tol=5.5e-3),
    # trinity-mini's sliding layers, the window cut to 64 so that contexts
    # of a few pages cross it.
    "gqa-window": dict(H=32, KVH=4, D=128, window=64, tol=5.5e-3),
    # chip_smoke --chips 4, llama3-1b under --tensor-parallel-size 4.
    "gqa-tp4": dict(H=8, KVH=1, D=128, tol=5.5e-3),
    # kanana-2-30b-a3b: 32 heads over one latent row of 512 + 64, padded
    # to 640 lanes; softmax scale of the unabsorbed 128 + 64 query.
    # sdar-30b-a3b: the same heads under block-causal visibility, blocks
    # of 4 aligned on absolute positions (its own phases: BLOCK_PHASES).
    "gqa-block4": dict(H=32, KVH=4, D=128, block=4, tol=5.5e-3),
    "mla": dict(H=32, R=512, ROPE=64, F=640, tol=7e-3),
    "mla-tp4": dict(H=8, R=512, ROPE=64, F=640, tol=7e-3),
}

# (cached tokens, new tokens) a row; the buckets (T, S, Q) the engine
# would pad the step to.
PHASES = {
    # contexts 1, 31, 32, 33, 150 with the new token: page edges at 32.
    "decode": dict(rows=[(0, 1), (30, 1), (31, 1), (32, 1), (149, 1)],
                   T=8, S=8, Q=1),
    "prefill": dict(rows=[(0, 70)], T=128, S=4, Q=128),
    "chunk": dict(rows=[(90, 70)], T=128, S=4, Q=128),
    "mixed": dict(rows=[(30, 1), (149, 1), (90, 70), (32, 1)],
                  T=128, S=8, Q=128),
    # A continuing chunk whose context crosses an edge of the GQA prefill
    # kernel's key block (512 keys at these geometries), beside rows that
    # end on the edge and one key past it.
    "chunk-over-key-block": dict(rows=[(470, 90), (511, 1), (512, 1)],
                                 T=128, S=4, Q=128),
    # A mixed step around an edge of the MLA prefill kernel's key block (256
    # keys): decode rows that end one key short of it, on it and past it
    # beside a prompt's chunk whose causal diagonal crosses it.
    "mixed-over-mla-key-block": dict(
        rows=[(254, 1), (255, 1), (256, 1), (200, 100), (30, 1)],
        T=128, S=8, Q=128),
}


# A block-diffusion engine's steps: every row holds whole blocks of 4, none
# has one query (Q >= 16, the smallest bucket).
BLOCK_PHASES = {
    # denoising / commit passes: contexts of a block, one page less a block,
    # a page, and past the GQA kernel's key block of 512 keys.
    "denoise": dict(rows=[(0, 4), (28, 4), (32, 4), (88, 4), (508, 4),
                          (512, 4)], T=32, S=8, Q=16),
    # a prompt's chunk (whole blocks) beside passes over single blocks
    "chunk-and-denoise": dict(rows=[(128, 4), (92, 68), (0, 4), (508, 4)],
                              T=128, S=8, Q=128),
}


def _batch(rows, T, S, Q, bt):
    """The index arrays ``_fill_batch`` writes for ``rows``, pad rows and
    pad tokens included."""
    qtok = np.full((S, Q), T, np.int32)
    seq, qpos, pos, slot = (np.zeros(T, np.int32) for _ in range(4))
    lens = np.zeros(S, np.int32)
    t = 0
    for s, (cached, n) in enumerate(rows):
        p = np.arange(cached, cached + n)
        qtok[s, :n] = np.arange(t, t + n)
        seq[t:t + n], qpos[t:t + n], pos[t:t + n] = s, np.arange(n), p
        slot[t:t + n] = bt[s, p // BS] * BS + p % BS
        lens[s] = cached + n
        t += n
    return {k: jnp.asarray(v) for k, v in dict(
        qtok_idx=qtok, token_seq_ids=seq, token_qpos=qpos, positions=pos,
        slot_mapping=slot, seq_lens=lens, block_tables=bt).items()}


def _through_int8(rows):
    """Rows through symmetric int8 with one scale a row, and back."""
    scale = np.maximum(np.abs(rows).max(axis=-1, keepdims=True), 1e-8) / 127.0
    return np.clip(np.round(rows / scale), -127, 127) * scale


def _exact(q, keys, values, pos, scale, group, window, block=0):
    """softmax(q k^T scale + causal / window mask) v in float32: ``q``
    [n, H, D] at positions ``pos`` over one row's ``keys`` [C, KVH, D] and
    ``values`` [C, KVH, Dv]; head h reads KV head h // group.  ``block``:
    the block mask in place of the causal one (key j is seen iff
    j // block <= pos // block)."""
    kh = np.repeat(keys, group, axis=1)
    vh = np.repeat(values, group, axis=1)
    s = np.einsum("nhd,chd->nhc", q, kh) * scale
    j = np.arange(keys.shape[0])[None, :]
    seen = j <= pos[:, None]
    if block:
        seen = j // block <= pos[:, None] // block
    if window is not None:
        seen &= j > pos[:, None] - window
    s = np.where(seen[:, None, :], s, -np.inf)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("nhc,chd->nhd", p, vh)


def _interpreted(monkeypatch):
    """The Pallas backend on the CPU: all four kernels interpreted.
    Returns the list their names are appended to, a call each."""
    import llm_d_tpu.ops.pallas.flash_prefill as fp
    import llm_d_tpu.ops.pallas.mla_attention as ma
    import llm_d_tpu.ops.pallas.mla_prefill as mp
    import llm_d_tpu.ops.pallas.paged_attention as pa
    calls = []
    for mod, name in ((pa, "paged_attention_decode_update"),
                      (fp, "flash_prefill_paged"),
                      (ma, "mla_paged_decode_update"),
                      (mp, "mla_flash_prefill")):
        real = getattr(mod, name)

        def interpreted(*a, _real=real, _name=name, **kw):
            calls.append(_name)
            return _real(*a, **{**kw, "interpret": True})

        monkeypatch.setattr(mod, name, interpreted)
    return calls


def _served_against_exact(monkeypatch, family, phase, path, cache="bf16"):
    """rms(served - exact) / rms(exact) over the step's real tokens."""
    g, ph = FAMILIES[family], {**PHASES, **BLOCK_PHASES}[phase]
    mla = family.startswith("mla")
    rng = np.random.default_rng(sum(map(ord, family + phase)))
    rows, T, S, Q = ph["rows"], ph["T"], ph["S"], ph["Q"]
    H = g["H"]
    KVH, D = (1, g["R"] + g["ROPE"]) if mla else (g["KVH"], g["D"])
    F = g["F"] if mla else KVH * D
    window = g.get("window")
    scale = (128 + g["ROPE"]) ** -0.5 if mla else D ** -0.5

    pages = -(-max(c + n for c, n in rows) // BS)
    bt = np.zeros((S, pages), np.int32)
    bt[:len(rows)] = (rng.permutation(len(rows) * pages) + 1).reshape(
        len(rows), pages)
    batch = A.with_block_visibility(_batch(rows, T, S, Q, bt),
                                    g.get("block", 0))
    calls = None
    if path == "pallas":
        calls = _interpreted(monkeypatch)
        batch = A.with_query_tiles(batch, H, F, "pallas", mla=mla)

    # float32 q, k, v of every row's whole context; the cache holds the
    # cached part, the step is handed the new part, both rounded to bf16.
    n_caches = 1 if mla else 2
    caches = [np.zeros((LAYERS, (len(rows) * pages + 1) * BS, F), np.float32)
              for _ in range(n_caches)]
    q_new = np.zeros((T, H, D), np.float32)
    kv_new = [np.zeros((T, KVH, D), np.float32) for _ in range(n_caches)]
    exact = []
    t = 0
    for s, (cached, n) in enumerate(rows):
        ctx = cached + n
        q = rng.standard_normal((n, H, D)).astype(np.float32)
        kv = [rng.standard_normal((ctx, KVH, D)).astype(np.float32)
              for _ in range(n_caches)]
        pos = np.arange(cached, ctx)
        exact.append(_exact(
            q, kv[0], kv[0][..., :g["R"]] if mla else kv[1], pos, scale,
            H // KVH, window, g.get("block", 0)))
        slots = bt[s, np.arange(cached) // BS] * BS + np.arange(cached) % BS
        for c, new, x in zip(caches, kv_new, kv):
            old = x[:cached].reshape(cached, KVH * D)
            if cache == "int8":
                old = _through_int8(old)
            c[LAYER, slots, :KVH * D] = old
            new[t:t + n] = x[cached:]
        q_new[t:t + n] = q
        t += n
    exact = np.concatenate(exact)

    caches = [jnp.asarray(c, jnp.bfloat16) for c in caches]
    layer = jnp.int32(LAYER)
    if mla:
        pad = ((0, 0), (0, 0), (0, F - D))
        out, _ = mla_mod._mla_attend(
            jnp.asarray(np.pad(q_new, pad), jnp.bfloat16),
            jnp.asarray(np.pad(kv_new[0], pad)[:, 0], jnp.bfloat16),
            caches[0], batch, layer, block_size=BS, backend=path,
            scale=scale, R=g["R"])
    else:
        out, _, _ = A.attention_with_kv_update(
            jnp.asarray(q_new, jnp.bfloat16),
            jnp.asarray(kv_new[0], jnp.bfloat16),
            jnp.asarray(kv_new[1], jnp.bfloat16), caches[0], caches[1],
            batch, block_size=BS, backend=path, layer=layer,
            window=None if window is None else jnp.int32(window))
    if calls is not None:       # the kernel served it, not a fallback
        assert calls == [{
            (False, True): "paged_attention_decode_update",
            (False, False): "flash_prefill_paged",
            (True, True): "mla_paged_decode_update",
            (True, False): "mla_flash_prefill"}[mla, Q == 1]]
    out = np.asarray(out, np.float32)[:t]
    assert out.shape == exact.shape and np.all(np.isfinite(out))
    return float(np.sqrt(np.mean((out - exact) ** 2) / np.mean(exact ** 2)))


CASES = [(family, phase, path, "bf16")
         for family in FAMILIES
         for phase in (BLOCK_PHASES if "block" in FAMILIES[family]
                       else PHASES)
         for path in ("pallas", "chunked")]
# The guard that the bound bites: rows that went through int8 exceed it.
CASES += [(family, "chunk", "chunked", "int8")
          for family in ("gqa", "gqa-window", "mla")]


@pytest.mark.parametrize(
    "family,phase,path,cache", CASES,
    ids=["-".join(c[:3]) + ("-int8-rows" if c[3] == "int8" else "")
         for c in CASES])
def test_served_attention_against_exact(monkeypatch, family, phase, path,
                                        cache):
    err = _served_against_exact(monkeypatch, family, phase, path, cache)
    tol = FAMILIES[family]["tol"]
    if cache == "bf16":
        assert err < tol, (err, tol)
    else:
        assert err > tol, (err, tol)


def test_block_mask_differs_from_the_causal_mask(monkeypatch):
    """The guard that the block cases bite: the same step held against the
    CAUSAL exact attention is far outside the bound."""
    block = FAMILIES["gqa-block4"]
    monkeypatch.setitem(FAMILIES, "gqa-block4", dict(block, block=0))
    real = A.with_block_visibility
    monkeypatch.setattr(A, "with_block_visibility",
                        lambda batch, _: real(batch, 4))
    err = _served_against_exact(monkeypatch, "gqa-block4", "denoise",
                                "chunked")
    assert err > 10 * block["tol"], err


@pytest.mark.parametrize("path", ["pallas", "chunked", "reference"])
def test_limits_equal_to_positions_change_nothing(monkeypatch, path):
    """An autoregressive step is bit-equal to what it was: the visibility
    operand, handed each query's own position, gives the very same numbers
    as the program without it (which is what block length 0 lowers to)."""
    seen = []

    def capture(batch, block):
        seen.append(batch)
        return batch

    monkeypatch.setattr(A, "with_block_visibility", capture)
    real = A.attention_with_kv_update
    taken = []

    def keep(*args, **kw):
        taken.append((args, kw))
        return real(*args, **kw)

    monkeypatch.setattr(A, "attention_with_kv_update", keep)
    _served_against_exact(monkeypatch, "gqa", "mixed", path)
    (args, kw), = taken
    batch = args[5]
    assert "vis_limit" not in seen[0] and "vis_limit" not in batch
    limited = real(*args[:5], dict(
        {k: v for k, v in batch.items() if k not in A.QUERY_TILE_KEYS},
        vis_limit=batch["positions"]), **kw)
    np.testing.assert_array_equal(np.asarray(real(*args, **kw)[0], np.float32),
                                  np.asarray(limited[0], np.float32))


# ---- the keys the prefill kernels' inner loop covers ----------------------

def _walk_by_hand(ends, news, qt, kb, bs, windows):
    """The kernels' walk, a tile and a layer at a time: ``attn_k_real``
    counts the keys from the first one the tile's first query sees to its
    last query's own, ``attn_k_slots`` the blocks walked times their keys
    (the loop bounds of ``ops.pallas.flash_prefill._prefill_kernel``, and of
    ``ops.pallas.mla_prefill._mla_prefill_kernel`` with no window)."""
    real = slots = 0
    for end, n in zip(ends, news):
        for lo in range(end - n, end, qt):
            q = range(lo, min(lo + qt, end))
            for w in windows:
                seen = [k for k in range(end)
                        if q[0] - w < k and k <= q[-1]]
                real += len(seen)
                n_pages = -(-min(end, q[-1] + 1) // bs)
                first = min(max(q[0] - w + 1, 0) // bs, n_pages)
                slots += -(-(n_pages - first) // (kb // bs)) * kb
    return {"attn_k_real": real, "attn_k_slots": slots}


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("model,dims,bs,Q,qt,kb", [
    # the cells' 32 / 4 x 128 under a window-and-full stack: 512 keys a
    # block at a chunk's 32 slots a tile and at a mixed step's 8
    ("tiny-swa-moe", (32, 512), 32, 2048, 32, 512),
    ("tiny-swa-moe", (32, 512), 32, 256, 8, 512),
    # a 16-key page
    ("tiny-swa-moe", (32, 512), 16, 2048, 32, 512),
    # every layer full
    ("tiny", (32, 512), 32, 1024, 16, 512),
    # the MLA kernel: 256 keys a block at kanana-2-30b-a3b's 4 x 32 fused
    # rows, at a 2,048-token chunk's 16 slots and at a tp-4 shard's 8 heads
    ("tiny-mla", (32, 640), 32, 512, 4, 256),
    ("tiny-mla", (32, 640), 32, 2048, 16, 256),
    ("tiny-mla", (8, 640), 32, 512, 16, 256),
    ("tiny-mla", (32, 640), 16, 512, 4, 256),
])
def test_engine_counts_the_keys_its_prefill_walks(model, dims, bs, Q, qt,
                                                   kb, seed):
    import dataclasses
    from types import SimpleNamespace

    from llm_d_tpu.engine.engine import EngineCore
    from llm_d_tpu.engine.packed_batch import BatchLayout
    from llm_d_tpu.models import get_config
    c = get_config(model)
    if c.sliding_window:        # a window that several tiles' walks cross
        c = dataclasses.replace(c, sliding_window=300, head_dim=128)
    elif not c.use_mla:
        c = dataclasses.replace(c, head_dim=128)
    engine = EngineCore.__new__(EngineCore)
    engine.model_config = c
    engine.config = SimpleNamespace(block_size=bs)
    engine._prefill_tile_dims = dims
    assert A.prefill_q_tile(Q, *dims, c.use_mla) == qt
    assert A.prefill_key_block(qt, *dims, c.head_dim_, bs, c.use_mla) == kb
    rng = np.random.default_rng(seed)
    # Decode rows, fresh prompts, continuing chunks: contexts to 3,000.
    news = [int(rng.choice([1, 1, int(rng.integers(1, Q + 1))]))
            for _ in range(6)]
    ends = [n + int(rng.choice([0, int(rng.integers(0, 3000 - n))]))
            for n in news]
    layout = BatchLayout(Q, 8, Q, B=4)
    got = engine._attn_k_counts(ends, news, layout)
    assert got == _walk_by_hand(ends, news, qt, kb, bs, c.layer_windows or (
        (1 << 30,) * c.num_layers))
    assert 0 < got["attn_k_real"] <= got["attn_k_slots"]
    # Another path serves prefill: no walk to count.
    engine._prefill_tile_dims = None
    assert engine._attn_k_counts(ends, news, layout) == {}


# ---- the keys the MLA decode kernel's inner loop covers -------------------

@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("dims,bs,S,kb,G", [
    ((32, 640), 32, 64, 512, 4),     # kanana-2-30b-a3b: a full decode batch
    ((32, 640), 32, 8, 512, 4),      #   ... its smallest sequence bucket
    ((8, 640), 32, 64, 512, 4),      # a tp-4 shard's 8 heads
    ((32, 640), 16, 16, 512, 4),     # a 16-key page
])
def test_engine_counts_the_keys_its_mla_decode_walks(dims, bs, S, kb, G,
                                                      seed):
    from types import SimpleNamespace

    from llm_d_tpu.engine.engine import EngineCore
    from llm_d_tpu.engine.packed_batch import BatchLayout
    from llm_d_tpu.models import get_config
    c = get_config("tiny-mla")
    engine = EngineCore.__new__(EngineCore)
    engine.model_config = c
    engine.config = SimpleNamespace(block_size=bs)
    engine._prefill_tile_dims = dims
    assert A.mla_decode_walk(S, *dims, bs) == (kb, G)
    rng = np.random.default_rng(seed)
    # The cell's contexts in most of the bucket's rows, the rest padding.
    ends = rng.integers(1, 1537, size=S - int(rng.integers(0, G + 1))).tolist()
    got = engine._attn_dk_counts(ends, BatchLayout(S, S, 1, B=48))
    # The kernel's walk by hand: rows by length (pad rows, context 0,
    # first), G to a program, every program to its longest row's last block.
    rows = sorted(ends + [0] * (S - len(ends)))
    slots = sum(-(-max(rows[i:i + G]) // kb) * kb * G
                for i in range(0, S, G))
    assert got == {"attn_dk_real": c.num_layers * sum(ends),
                   "attn_dk_slots": c.num_layers * slots}
    assert 0 < got["attn_dk_real"] <= got["attn_dk_slots"]
    # Another path serves decode, or another attention family: no walk.
    engine._prefill_tile_dims = None
    assert engine._attn_dk_counts(ends, BatchLayout(S, S, 1, B=48)) == {}
    engine._prefill_tile_dims = dims
    engine.model_config = get_config("tiny")
    assert engine._attn_dk_counts(ends, BatchLayout(S, S, 1, B=48)) == {}
