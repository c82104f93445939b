"""Mixed attention stacks: sliding-window and full layers in one scan, the
attention gate, full layers without a rotary embedding, four norms a layer,
the embedding multiplier (``tiny-swa-moe``).

The served engine is held against the benchmark's plain reference
(``benchmarks/references/afmoe.py``: float32, its own decoder loop and a
[T, T] mask); the attention op, in all three backends and both Pallas
kernels (interpret mode), against a dense masked softmax.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.request import Request
from llm_d_tpu.models import get_config, get_model
from llm_d_tpu.models.config import FULL, NO_WINDOW, SLIDING, ModelConfig
from llm_d_tpu.ops import attention as A
from llm_d_tpu.ops.pallas.flash_prefill import flash_prefill_paged
from llm_d_tpu.ops.pallas.paged_attention import paged_attention_decode_update
from llm_d_tpu.ops.sampling import SamplingParams

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from references import afmoe  # noqa: E402

# float32 weights and activations: the comparison is then tight enough to
# see one wrong key in a window.
SWA = dataclasses.replace(get_config("tiny-swa-moe"), dtype="float32")
WINDOW = SWA.sliding_window          # 48: three blocks of 16, 1.5 of 32
TOL = 1e-4


def make_engine(config, budget=64, block_size=16, **kw):
    engine = EngineCore(EngineConfig(
        model=config.name, model_config=config, block_size=block_size,
        num_blocks=160, max_num_seqs=4, max_num_batched_tokens=budget, **kw))
    # The engine's cache is bfloat16 whatever the model's dtype; a float32
    # one (the step programs take the buffers' dtype) leaves no rounding
    # that could flip a near-tied expert choice.
    engine.kv_cache = {name: buf.astype(jnp.float32)
                       for name, buf in engine.kv_cache.items()}
    return engine


def serve(engine, prompt, n_gen, rid="r"):
    """Greedy ``n_gen`` tokens: (ids, chosen-token logprobs, cached tokens)."""
    req = Request(request_id=rid, prompt_token_ids=list(prompt),
                  sampling=SamplingParams(temperature=0.0, max_tokens=n_gen,
                                          ignore_eos=True, logprobs=0))
    engine.add_request(req)
    ids, lps = [], []
    while engine.has_work():
        for out in engine.step():
            ids.extend(out.new_token_ids)
            lps.extend(out.logprobs or [])
    return ids, np.asarray(lps), req.num_cached_prompt_tokens


def reference_logprobs(engine, config, prompt, ids):
    lp = afmoe.tail_logprobs(engine.params, config,
                             jnp.asarray(list(prompt) + ids[:-1], jnp.int32),
                             len(ids))
    return np.asarray(lp)[np.arange(len(ids)), ids]


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, SWA.vocab_size, n).tolist()


# (a) the served engine against the plain reference: prompts shorter than,
# just over and several times the window; prefill chunks larger and smaller
# than the window; block sizes the window is and is not a multiple of.
@pytest.mark.parametrize("budget,block_size,backend,lens", [
    # chunk > window, window = 3 blocks
    (64, 16, "reference", (20, WINDOW + 3, 4 * WINDOW + 9)),
    # chunk < window, window = 1.5 blocks
    (32, 32, "reference", (4 * WINDOW + 9,)),
    (64, 32, "chunked", (4 * WINDOW + 9,)),
])
def test_engine_matches_plain_reference(budget, block_size, backend, lens):
    engine = make_engine(SWA, budget, block_size, attn_backend=backend)
    for n in lens:
        prompt = prompt_of(n, seed=n)
        ids, lps, _ = serve(engine, prompt, 6, rid=f"r{n}")
        want = reference_logprobs(engine, SWA, prompt, ids)
        np.testing.assert_allclose(lps, want, atol=TOL, err_msg=f"prompt {n}")


# (c) each switch alone changes the output; what stays the family's (gate
# and four norms on) still matches the reference.
@pytest.fixture(scope="module")
def base_run():
    prompt = prompt_of(3 * WINDOW)
    base = make_engine(SWA)
    return prompt, base.params, serve(base, prompt, 4)


@pytest.mark.parametrize("switch", [
    {"attn_output_gate": False}, {"rope_on_full_attention": True},
    {"sandwich_norm": False}, {"embed_scale": 1.0}],
    ids=lambda s: next(iter(s)))
def test_each_switch_moves_the_output(switch, base_run):
    prompt, base_params, (base_ids, base_lps, _) = base_run
    other = dataclasses.replace(SWA, **switch)
    engine = make_engine(other)
    # The same weights wherever the trees share a leaf.
    engine.params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: _leaf_at(base_params, path, leaf), engine.params)
    ids, lps, _ = serve(engine, prompt, 4)
    assert ids != base_ids or np.abs(lps - base_lps).max() > 1e-2
    if other.attn_output_gate and other.sandwich_norm:
        np.testing.assert_allclose(
            lps, reference_logprobs(engine, other, prompt, ids), atol=TOL)


def _leaf_at(tree, path, default):
    for key in path:
        tree = tree.get(key.key) if isinstance(tree, dict) else None
        if tree is None:
            return default
    return tree


# (d) the guard for the shared code: without ``layer_types`` and with every
# switch off the program is today's; a stack declared all-full with the
# rotary embedding everywhere goes through the per-layer tables and must
# give the same bits.
def test_no_layer_types_is_the_plain_moe_path():
    plain = get_config("tiny-moe")
    assert plain.layer_types == () and plain.layer_windows == ()
    model = get_model(plain)
    params = model.init_params(plain, jax.random.PRNGKey(0))
    assert not {"attn_gate", "attn_out_norm", "mlp_out_norm"} & set(
        params["moe_layers"]) and not {"attn_gate", "attn_out_norm"} & set(
        params["dense_layers"])
    all_full = dataclasses.replace(
        plain, layer_types=(FULL,) * plain.num_layers, sliding_window=0)
    prompt = prompt_of(70)
    ids_a, lps_a, _ = serve(make_engine(plain), prompt, 5)
    ids_b, lps_b, _ = serve(make_engine(all_full), prompt, 5)
    assert ids_a == ids_b
    np.testing.assert_array_equal(lps_a, lps_b)


# (e) a prefix-cache hit returns what a cold run returns.
def test_prefix_cache_hit_matches_cold_run():
    engine = make_engine(SWA)
    prompt = prompt_of(3 * WINDOW + 5)
    cold_ids, cold_lps, cached = serve(engine, prompt, 5, rid="cold")
    assert cached == 0
    warm_ids, warm_lps, cached = serve(engine, prompt, 5, rid="warm")
    assert cached >= 3 * WINDOW - 16
    assert warm_ids == cold_ids
    np.testing.assert_allclose(warm_lps, cold_lps, atol=TOL)


# (f) the config: hashable with a list, a wrong length or kind raises.
def test_model_config_layer_types():
    c = ModelConfig(num_layers=4, layer_types=[SLIDING, SLIDING, SLIDING,
                                               FULL], sliding_window=8,
                    rope_on_full_attention=False)
    assert isinstance(c.layer_types, tuple) and hash(c) == hash(
        dataclasses.replace(c))
    assert c.layer_windows == (8, 8, 8, NO_WINDOW)
    assert c.layer_rope == (True, True, True, False)
    with pytest.raises(ValueError, match="layer_types"):
        ModelConfig(num_layers=4, layer_types=[SLIDING, FULL],
                    sliding_window=8)
    with pytest.raises(ValueError, match="layer_types"):
        ModelConfig(num_layers=1, layer_types=["chunked_attention"])
    with pytest.raises(ValueError, match="sliding_window"):
        ModelConfig(num_layers=1, layer_types=[SLIDING])
    # MLA with layer kinds is served since PR 39; the GQA block's gate is not
    with pytest.raises(ValueError, match="MLA"):
        ModelConfig(num_layers=1, layer_types=[FULL], kv_lora_rank=8,
                    attn_output_gate=True)
    assert ModelConfig(num_layers=1, layer_types=[FULL],
                       kv_lora_rank=8).mla_layer_kinds == (FULL,)
    assert get_model(c).__name__.endswith("llama")
    assert get_model(SWA).__name__.endswith("moe")


# (b) op level: windowed decode and prefill against a dense masked softmax.
H, KVH, D = 4, 2, 64


def _dense(q, k, v, q_pos, window, scale):
    """q [n, H, D] at positions q_pos over keys k, v [C, KVH, D]."""
    j = np.arange(k.shape[0])[None, :]
    mask = (j <= q_pos[:, None]) & (j > q_pos[:, None] - window)
    kk, vv = (np.repeat(a, H // KVH, axis=1) for a in (k, v))
    s = np.einsum("nhd,chd->nhc", q, kk) * scale
    s = np.where(mask[:, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("nhc,chd->nhd", p / p.sum(-1, keepdims=True), vv)


def _paged_case(seed, bs, seq_lens, new_lens):
    """A paged cache that holds ``seq_lens[s]`` tokens of each sequence, the
    last ``new_lens[s]`` of them this step's queries; layer 1 of 2."""
    rng = np.random.default_rng(seed)
    S, F = len(seq_lens), KVH * D
    B = -(-max(seq_lens) // bs)
    nb = S * B + 1
    bt = (rng.permutation(nb - 1)[:S * B] + 1).reshape(S, B).astype(np.int32)
    k_seq = [rng.standard_normal((n, KVH, D)).astype(np.float32)
             for n in seq_lens]
    v_seq = [rng.standard_normal((n, KVH, D)).astype(np.float32)
             for n in seq_lens]
    cache = np.zeros((2, 2, nb * bs, F), np.float32)     # (k|v, layer, ...)
    for s, n in enumerate(seq_lens):
        slots = bt[s, np.arange(n) // bs] * bs + np.arange(n) % bs
        cache[0, 1, slots] = k_seq[s].reshape(n, F)
        cache[1, 1, slots] = v_seq[s].reshape(n, F)
    q = [rng.standard_normal((n, H, D)).astype(np.float32) for n in new_lens]
    return q, k_seq, v_seq, cache, bt


DECODE_LENS = [1, 16, 17, 33, 64, 100, 47, 0]   # on and off page boundaries


@pytest.mark.parametrize("window", [16, 40, 1000, NO_WINDOW])
def test_windowed_decode_kernel(window):
    bs, scale = 16, 0.2
    lens = DECODE_LENS
    q, k_seq, v_seq, cache, bt = _paged_case(
        1, bs, [max(n, 1) for n in lens], [1] * len(lens))
    bf = jnp.bfloat16
    # The kernel writes the new row itself: hand it a cache without it.
    k_cache, v_cache = (jnp.asarray(c, bf) for c in cache)
    k_new = jnp.asarray(np.stack([k[-1].reshape(-1) for k in k_seq]), bf)
    v_new = jnp.asarray(np.stack([v[-1].reshape(-1) for v in v_seq]), bf)
    out, k2, _ = paged_attention_decode_update(
        jnp.asarray(np.concatenate(q), bf), k_new, v_new, k_cache, v_cache,
        jnp.asarray(bt), jnp.asarray(lens, jnp.int32), block_size=bs,
        num_kv_heads=KVH, scale=scale, layer=jnp.int32(1), interpret=True,
        window=jnp.int32(window), seq_group=4)
    for s, n in enumerate(lens):
        if n == 0:
            continue
        want = _dense(q[s], k_seq[s][:n], v_seq[s][:n], np.asarray([n - 1]),
                      window, scale)
        np.testing.assert_allclose(np.asarray(out[s], np.float32), want[0],
                                   atol=3e-2, rtol=3e-2, err_msg=f"seq {s}")
    np.testing.assert_array_equal(np.asarray(k2[0]), np.asarray(k_cache[0]))


PREFILL = ([70, 48, 100, 16, 5], [70, 16, 33, 1, 5])    # (context, new)


@pytest.mark.parametrize("window", [16, 40, 1000])
@pytest.mark.parametrize("q_tile", [None, 16])
def test_windowed_prefill_kernel(window, q_tile):
    bs, scale, Q = 16, 0.2, 80
    seq_lens, new_lens = PREFILL
    q, k_seq, v_seq, cache, bt = _paged_case(2, bs, seq_lens, new_lens)
    qs = np.zeros((len(seq_lens), Q, H, D), np.float32)
    q_pos = np.full((len(seq_lens), Q), -1, np.int32)
    for s, (n, new) in enumerate(zip(seq_lens, new_lens)):
        qs[s, :new], q_pos[s, :new] = q[s], np.arange(n - new, n)
    bf = jnp.bfloat16
    out = flash_prefill_paged(
        jnp.asarray(qs, bf), jnp.asarray(q_pos), jnp.asarray(cache[0], bf),
        jnp.asarray(cache[1], bf), jnp.asarray(bt),
        jnp.asarray(seq_lens, jnp.int32), block_size=bs, num_kv_heads=KVH,
        scale=scale, layer=jnp.int32(1), interpret=True, q_tile=q_tile,
        window=jnp.int32(window))
    for s, (n, new) in enumerate(zip(seq_lens, new_lens)):
        want = _dense(q[s], k_seq[s], v_seq[s], q_pos[s, :new], window, scale)
        np.testing.assert_allclose(np.asarray(out[s, :new], np.float32), want,
                                   atol=3e-2, rtol=3e-2, err_msg=f"seq {s}")


@pytest.mark.parametrize("backend", ["reference", "chunked"])
@pytest.mark.parametrize("window", [16, 40, 1000])
def test_windowed_xla_backends(backend, window):
    bs, scale = 16, 0.2
    seq_lens, new_lens = PREFILL
    q, k_seq, v_seq, cache, bt = _paged_case(3, bs, seq_lens, new_lens)
    S, T, Q = len(seq_lens), sum(new_lens), 80
    starts = np.concatenate([[0], np.cumsum(new_lens)[:-1]])
    qtok = np.full((S, Q), T, np.int32)
    for s, new in enumerate(new_lens):
        qtok[s, :new] = starts[s] + np.arange(new)
    positions = np.concatenate([np.arange(n - new, n)
                                for n, new in zip(seq_lens, new_lens)])
    batch = {
        "positions": positions, "qtok_idx": qtok, "block_tables": bt,
        "token_seq_ids": np.repeat(np.arange(S), new_lens),
        "token_qpos": np.concatenate([np.arange(n) for n in new_lens]),
        "seq_lens": np.asarray(seq_lens),
        "slot_mapping": np.concatenate([
            bt[s, np.arange(n - new, n) // bs] * bs
            + np.arange(n - new, n) % bs
            for s, (n, new) in enumerate(zip(seq_lens, new_lens))])}
    batch = {k: jnp.asarray(v, jnp.int32) for k, v in batch.items()}
    k_new = np.concatenate([k[-new:] for k, new in zip(k_seq, new_lens)])
    v_new = np.concatenate([v[-new:] for v, new in zip(v_seq, new_lens)])
    out, _, _ = A.attention_with_kv_update(
        jnp.asarray(np.concatenate(q)), jnp.asarray(k_new),
        jnp.asarray(v_new), jnp.asarray(cache[0]), jnp.asarray(cache[1]),
        batch, block_size=bs, scale=scale, backend=backend,
        layer=jnp.int32(1), window=jnp.int32(window))
    for s, (n, new) in enumerate(zip(seq_lens, new_lens)):
        want = _dense(q[s], k_seq[s], v_seq[s],
                      np.arange(n - new, n), window, scale)
        np.testing.assert_allclose(
            np.asarray(out[starts[s]:starts[s] + new]), want, atol=2e-4,
            rtol=2e-4, err_msg=f"seq {s}")


def test_ring_attention_refuses_a_window():
    from llm_d_tpu.ops.ring_attention import ring_attention
    with pytest.raises(NotImplementedError, match="window"):
        ring_attention(None, None, None, None, window=8)


# (g) the counters: what a step asks of the cache, against a count by hand.
def _by_hand(config, ends, news, held_from=None):
    """``held_from``: per row, the first token whose page the window layers
    still hold (a cache in groups by layer kind); None: all of them."""
    kinds = config.layer_types or (FULL,) * config.num_layers
    ctx = read = held = dead = 0
    for end, new, first in zip(ends, news, held_from or [0] * len(ends)):
        for kind in kinds:
            w = config.sliding_window if kind == SLIDING else 1 << 40
            ctx += sum(p + 1 for p in range(end - new, end))
            read += sum(min(p + 1, w) for p in range(end - new, end))
            first_held = first if kind == SLIDING else 0
            held += end - first_held
            # the row's next query, at position ``end``, sees keys > end - w
            dead += sum(1 for j in range(first_held, end) if j <= end - w)
    return {"kv_ctx_tokens": ctx, "kv_read_tokens": read,
            "kv_held_tokens": held, "kv_dead_tokens": dead}


@pytest.mark.parametrize("config", [SWA, get_config("tiny-moe")],
                         ids=lambda c: c.name)
def test_kv_counts_closed_form(config):
    engine = EngineCore.__new__(EngineCore)
    engine.model_config = config
    ends, news = [1, 47, 48, 49, 200, 130, 64], [1, 1, 48, 20, 1, 64, 64]
    got = engine._kv_counts(ends, news)
    assert got == _by_hand(config, ends, news)
    if not config.layer_types:
        assert got["kv_read_tokens"] == got["kv_ctx_tokens"]
        assert got["kv_dead_tokens"] == 0
    else:
        assert got["kv_read_tokens"] < got["kv_ctx_tokens"]


def test_step_span_carries_the_counts_and_shapes_precompile():
    engine = EngineCore(EngineConfig(
        model=SWA.name, model_config=SWA, block_size=16, num_blocks=64,
        max_num_seqs=4, max_num_batched_tokens=64,
        precompile_step_shapes=True))
    shapes = engine.step_shapes()
    assert engine._step_fn._cache_size() == len(shapes) == len(set(shapes))
    prompt = prompt_of(150)
    req = Request(request_id="r", prompt_token_ids=prompt,
                  sampling=SamplingParams(temperature=0.0, max_tokens=3,
                                          ignore_eos=True))
    engine.add_request(req)
    ends = []
    # The window layers' pages go back as the window passes them: a step
    # holds what the one before left, from the page of the first key that
    # step's last query could see (pages of 16, window 48).
    held_from = {64: 0, 128: 16, 150: 80, 151: 96, 152: 96}
    while engine.has_work():
        before = req.num_computed_tokens
        engine.step()
        ends.append(req.num_computed_tokens)
        new = ends[-1] - before
        kv = dict(engine._step_kv)
        pages = {k: kv.pop(k) for k in list(kv) if k.startswith("kv_pages_")
                 or k == "kv_window_pages_released"}
        assert pages["kv_pages_window"] <= pages["kv_pages_window_total"] \
            == engine.kv_manager.groups[1].num_blocks - 1
        # A step with prefill tokens also says how full its attention grid
        # is; off the chip that grid is the [S, Q] rectangle of its bucket.
        grid = (kv.pop("attn_q_real", None), kv.pop("attn_q_slots", None))
        assert grid == {64: (64, 4 * 64), 22: (22, 4 * 32),
                        1: (None, None)}[new]
        assert kv == _by_hand(SWA, [ends[-1]], [new], [held_from[ends[-1]]])
    assert ends == [64, 128, 150, 151, 152]
    # Serving compiled nothing that the start had not.
    assert engine._step_fn._cache_size() == len(shapes)
