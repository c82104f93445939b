"""Window and full GQA layers 3:1 over sparse experts in every layer, YaRN on
the full layers only (``tiny-mellum``), through the cache in groups by layer
kind: the served engine (prefill, chunked prefill, decode) against the
benchmark's plain reference (``benchmarks/references/mellum.py``: float32,
no cache, a [T, T] mask, its own YaRN tables), logprobs and not tokens."""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.request import Request
from llm_d_tpu.models import get_config, get_model
from llm_d_tpu.models.config import FULL, SLIDING, ModelConfig, RopeRule
from llm_d_tpu.ops import layers as L
from llm_d_tpu.ops.sampling import SamplingParams

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from references import mellum  # noqa: E402

# float32 weights and activations: tight enough to see one wrong key in a
# window or one wrong rotary dimension.
TINY = dataclasses.replace(get_config("tiny-mellum"), dtype="float32")
WINDOW = TINY.sliding_window         # 48: three pages of 16, 1.5 of 32
TOL = 1e-4


def make_engine(config=TINY, budget=64, block_size=16, **kw):
    engine = EngineCore(EngineConfig(
        model=config.name, model_config=config, block_size=block_size,
        num_blocks=160, max_num_seqs=4, max_num_batched_tokens=budget, **kw))
    engine.kv_cache = {name: buf.astype(jnp.float32)
                       for name, buf in engine.kv_cache.items()}
    return engine


def serve(engine, prompt, n_gen, rid="r"):
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


def reference_logprobs(params, config, prompt, ids, rules=None):
    lp = mellum.tail_logprobs(params, config, jnp.asarray(
        list(prompt) + ids[:-1], jnp.int32), len(ids), rules=rules)
    return np.asarray(lp)[np.arange(len(ids)), ids]


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, TINY.vocab_size, n).tolist()


# (a) prompts below, just over and several times the window; a chunk
# boundary inside the window (chunks of 32 < 48) and outside it (64 > 48);
# pages the window is (16) and is not (32) a multiple of.
@pytest.mark.parametrize("budget,block_size,backend,n", [
    (64, 16, "reference", 20), (64, 16, "reference", WINDOW + 3),
    (64, 16, "reference", 4 * WINDOW + 9),
    (32, 32, "reference", 4 * WINDOW + 9),
    (32, 16, "chunked", 3 * WINDOW + 1),
    (64, 32, "chunked", 4 * WINDOW + 9)])
def test_engine_matches_plain_reference(budget, block_size, backend, n):
    engine = make_engine(budget=budget, block_size=block_size,
                         attn_backend=backend)
    prompt = prompt_of(n, seed=n)
    ids, lps, _ = serve(engine, prompt, 8)
    want = reference_logprobs(engine.params, TINY, prompt, ids)
    np.testing.assert_allclose(lps, want, atol=TOL)
    # Pages went back on the way (a prompt over the window), and all of
    # them when the request finished.
    kvm = engine.kv_manager
    assert (kvm.window_pages_released > 0) == (n > WINDOW + 16)
    assert kvm.groups[0].ref == kvm.groups[1].ref == {}


@pytest.mark.parametrize("block_size", [16, 32])
def test_prefix_hit_through_both_groups_matches_the_cold_run(block_size):
    engine = make_engine(block_size=block_size)
    prompt = prompt_of(4 * WINDOW + 5)
    cold_ids, cold_lps, cached = serve(engine, prompt, 6, rid="cold")
    assert cached == 0
    warm_ids, warm_lps, cached = serve(engine, prompt, 6, rid="warm")
    assert cached == (4 * WINDOW + 4) // block_size * block_size
    assert warm_ids == cold_ids
    np.testing.assert_allclose(warm_lps, cold_lps, atol=TOL)
    # A prompt that shares only the first 100 tokens: the hit ends there,
    # on the pages under the window before it, and the answer is the
    # reference's.
    other = prompt[:100] + prompt_of(60, seed=9)
    ids, lps, cached = serve(engine, other, 6, rid="other")
    assert cached == 100 // block_size * block_size
    np.testing.assert_allclose(
        lps, reference_logprobs(engine.params, TINY, other, ids), atol=TOL)


def test_int8_experts_hold_to_the_reference():
    """The served path's int8 experts against the reference reading the same
    int8 payloads as q x scale: what is left is the served path's bf16
    dequantisation and the order of the sums."""
    bf = dataclasses.replace(TINY, dtype="float32")
    engine = make_engine(bf, quantization="int8")
    prompt = prompt_of(2 * WINDOW + 7)
    ids, lps, _ = serve(engine, prompt, 6)
    assert "w_gate_q" in engine.params["moe_layers"]
    np.testing.assert_allclose(
        lps, reference_logprobs(engine.params, bf, prompt, ids), atol=5e-2)


# (b) the rotary rule by kind is served: each wrong rule moves the output,
# and the reference handed the same wrong rule agrees again.
def _rules(**over):
    by_kind = dict(TINY.rope_parameters)
    by_kind.update(over)
    return by_kind


YARN, PLAIN = dict(TINY.rope_parameters)[FULL], dict(
    TINY.rope_parameters)[SLIDING]


@pytest.fixture(scope="module")
def base_run():
    prompt = prompt_of(3 * WINDOW)
    base = make_engine()
    return prompt, base.params, serve(base, prompt, 4)


@pytest.mark.parametrize("wrong", [
    {FULL: PLAIN}, {SLIDING: YARN},
    {FULL: YARN._replace(attention_factor=1.0)},
    {FULL: YARN._replace(factor=16.0)}],
    ids=["no_yarn", "yarn_everywhere", "attention_factor_1", "factor_16"])
def test_each_rotary_rule_moves_the_output(wrong, base_run):
    prompt, params, (base_ids, base_lps, _) = base_run
    other = dataclasses.replace(
        TINY, rope_parameters=tuple(_rules(**wrong).items()))
    engine = make_engine(other)
    engine.params = params
    ids, lps, _ = serve(engine, prompt, 4)
    assert ids != base_ids or np.abs(lps - base_lps).max() > 1e-2
    np.testing.assert_allclose(
        lps, reference_logprobs(params, other, prompt, ids), atol=TOL)


def test_window_off_moves_the_output(base_run):
    prompt, params, (base_ids, base_lps, _) = base_run
    every_full = dataclasses.replace(TINY, sliding_window=10_000)
    engine = make_engine(every_full)
    engine.params = params
    ids, lps, _ = serve(engine, prompt, 4)
    assert ids != base_ids or np.abs(lps - base_lps).max() > 1e-2


# (c) the tables.
def _by_hand(d, theta, factor, original_max, beta_fast, beta_slow):
    def correction(turns):
        return d * math.log(original_max / (turns * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), d - 1)
    out = []
    for i in range(d // 2):
        plain = theta ** (-2 * i / d)
        g = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append((1 - g) * plain + g * plain / factor)
    return low, high, out


def test_yarn_tables_against_values_by_hand():
    # tiny-mellum's rule: d 16, theta 1e4, factor 4 over 64 positions.
    low, high, want = _by_hand(16, 1e4, 4.0, 64, 32, 1)
    assert (low, high) == (0, 3)
    got = L.yarn_inv_freq(16, 1e4, 4.0, 64, 32, 1)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    # dimension 0 keeps its frequency, 3 and above take a quarter of it
    assert got[0] == 1.0 and got[1] == pytest.approx(
        (2 / 3 + 1 / 3 / 4) * 1e4 ** (-2 / 16))
    np.testing.assert_allclose(
        got[3:], [1e4 ** (-2 * i / 16) / 4 for i in range(3, 8)], rtol=1e-12)
    # the published rule: d 128, theta 5e5, factor 16 over 8192
    low, high, want = _by_hand(128, 5e5, 16.0, 8192, 32, 1)
    assert (low, high) == (18, 35)
    np.testing.assert_allclose(
        L.yarn_inv_freq(128, 5e5, 16.0, 8192, 32, 1), want, rtol=1e-12)
    pos = jnp.asarray([0, 1, 70, 511], jnp.int32)
    cos, sin = L.rope_tables(pos, 16, TINY.rope_rules)
    assert cos.shape == sin.shape == (2, 4, 8)
    f = np.asarray(pos, np.float64)[:, None] * np.asarray(got)[None, :]
    scale = TINY.rope_rules[0].attention_factor
    assert scale == pytest.approx(0.1 * math.log(4.0) + 1)
    np.testing.assert_allclose(cos[0], np.cos(f) * scale, atol=2e-5)
    np.testing.assert_allclose(sin[0], np.sin(f) * scale, atol=2e-5)
    # the sliding layers' tables are the plain ones, bit for bit
    plain = L.rope_cos_sin(pos, 16, 1e4)
    np.testing.assert_array_equal(cos[1], plain[0])
    np.testing.assert_array_equal(sin[1], plain[1])


def test_yarn_at_factor_one_is_the_plain_table():
    pos = jnp.arange(0, 600, 7, dtype=jnp.int32)
    rule = RopeRule(1e4, 1.0, 64, 32.0, 1.0, 1.0)
    cos, sin = L.rope_tables(pos, 16, (rule,))
    plain = L.rope_cos_sin(pos, 16, 1e4)
    np.testing.assert_allclose(cos[0], plain[0], atol=5e-6)
    np.testing.assert_allclose(sin[0], plain[1], atol=5e-6)
    # and the reference's own tables agree with the program's
    freq, mscale = mellum.inv_freq(tuple(TINY.rope_rules[0]), 16)
    np.testing.assert_allclose(freq, L.yarn_inv_freq(16, 1e4, 4.0, 64, 32, 1),
                               rtol=1e-6)
    assert mscale == TINY.rope_rules[0].attention_factor


# (d) the config.
def test_rope_parameters_on_the_config():
    c = TINY
    hash(c)                                     # from a dict, hashable
    assert c.rope_rules == (YARN, PLAIN)
    assert c.layer_rope_rule == (1, 1, 1, 0) * 2
    assert c.kv_cache_groups == (FULL, SLIDING)
    assert get_config("tiny-moe").rope_rules == ()
    assert get_config("tiny-moe").kv_cache_groups == ()
    assert get_config("tiny-swa-moe").kv_cache_groups == (FULL, SLIDING)
    for name in ("tiny-hybrid-decoder", "tiny-ssm", "tiny-sdar"):
        assert get_config(name).kv_cache_groups == ()
    published = {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    big = dataclasses.replace(c, head_dim=128, rope_parameters=published)
    assert big.rope_rules == (
        RopeRule(5e5, 16.0, 8192, 32.0, 1.0, 1.2772588722239782),
        RopeRule(5e5))
    # attention_factor left out: 0.1 ln(factor) + 1
    del published["full_attention"]["attention_factor"]
    assert dataclasses.replace(c, rope_parameters=published).rope_rules[
        0].attention_factor == pytest.approx(1.2772588722239782)
    with pytest.raises(ValueError, match="rope_type 'llama3'"):
        dataclasses.replace(c, rope_parameters={
            FULL: {"rope_type": "llama3"}, SLIDING: {"rope_type": "default"}})
    with pytest.raises(ValueError, match="every kind of layer_types"):
        dataclasses.replace(c, rope_parameters={
            FULL: {"rope_type": "default"}})
    with pytest.raises(ValueError, match="every kind of layer_types"):
        dataclasses.replace(get_config("tiny-mla"), rope_parameters={
            FULL: {"rope_type": "default"}})


def test_no_rope_parameters_lowers_the_tables_every_stack_had():
    """A stack without ``rope_parameters`` computes its tables as before:
    the same jaxpr for the model's forward with and without this field's
    code in reach (the field is empty, ``with_layer_tables`` returns the
    batch it was given)."""
    from llm_d_tpu.models.llama import with_layer_tables
    plain = get_config("tiny-moe")
    batch = {"positions": jnp.arange(8, dtype=jnp.int32)}
    assert with_layer_tables(batch, plain) is batch
    grouped = with_layer_tables(dict(
        batch, block_tables_w=jnp.zeros((2, 4), jnp.int32),
        kv_group_blocks=jnp.asarray([50, 20], jnp.int32)), TINY)
    assert grouped["layer_page0"].tolist() == [
        0, 20, 40, 60, 110, 130, 150, 170]
    assert len(grouped["rope_tables"]) == 2
    model = get_model(plain)
    assert model.__name__.endswith("models.moe")
