"""A decoder-hybrid-decoder stack (the SambaY family): Mamba-1 layers,
window and full differential attention, gated memory units and cross-layer
attention over ONE layer's cache plane, the cross-decoder on the sampled
rows only (models/hybrid_decoder.py, ops/ssm.py's ``ssm1_*`` forms,
ops/attention.py's one-query read and head pairing).

What this pins, on seeded random weights at the ``tiny-hybrid-decoder``
preset on the CPU, against ``benchmarks/references/phi4flash.py`` (float32,
every position through every layer, the recurrence token by token from a
zero state, attention a masked softmax with the pairs written out):

  - the engine's log-probabilities, prefill in chunks and then decode
    through the cache planes and the state pool, against the reference;
    with a float32 cache the two agree to rounding;
  - the forward that puts only the sampled rows through ``cross_kv_layer``'s
    attention and the layers after it equals the forward that puts every
    row through, at the sampled rows;
  - a chunk boundary anywhere, a mixed step, a slot reused after a dropped
    row, a full batch a step ahead: the same tokens;
  - every new ``ModelConfig`` raise, every refusal at start-up, the step's
    counts.

The device computations at op level and at the published geometry:
``tests/test_hybrid_decoder_ops.py``.
"""

import dataclasses
import functools
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.request import Request
from llm_d_tpu.models import get_model, hybrid_decoder
from llm_d_tpu.models.config import (
    CROSS, FULL, GMU, MAMBA, SLIDING, get_config)
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.parallel.mesh import MeshConfig
from llm_d_tpu.utils import tracing

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "benchmarks"))

import references.phi4flash as reference  # noqa: E402

PRESET = "tiny-hybrid-decoder"
# A bf16 cache under float32 weights (measured 1e-2 on a log-probability at
# 150 tokens); bf16 everywhere; a float32 cache (measured 2e-5).
F32_TOL, BF16_TOL, EXACT_TOL = 3e-2, 1.5e-1, 5e-4
CTX = tracing.TraceContext("a" * 32, "b" * 16, True)


def _config(dtype="float32"):
    return dataclasses.replace(get_config(PRESET), dtype=dtype)


@functools.lru_cache(maxsize=None)
def _params(dtype="float32"):
    c = _config(dtype)
    return get_model(c).init_params(c, jax.random.PRNGKey(7))


def _engine(dtype="float32", float32_cache=False, **kw):
    tracing.reset()     # the engine takes its tracer at construction
    kw = {"block_size": 8, "num_blocks": 128, "max_num_seqs": 8,
          "max_num_batched_tokens": 64, "min_seq_bucket": 4, **kw}
    eng = EngineCore(EngineConfig(model=PRESET, model_config=_config(dtype),
                                  **kw), params=_params(dtype))
    if float32_cache:
        eng.kv_cache = {name: a.astype(jnp.float32)
                        for name, a in eng.kv_cache.items()}
    return eng


def _prompt(i, n):
    return [(37 * i + 11 * j + j * j) % 500 + 1 for j in range(n)]


def _req(rid, prompt, n=8, ignore_eos=True, **sampling):
    sampling.setdefault("temperature", 0.0)
    sampling.setdefault("logprobs", 0)
    r = Request(request_id=rid, prompt_token_ids=list(prompt),
                sampling=SamplingParams(max_tokens=n, ignore_eos=ignore_eos,
                                        **sampling))
    r.trace_ctx = CTX
    return r


def _run(eng, reqs, each_step=None):
    """Step the engine dry; {request id: (ids, logprobs, top logprobs)}."""
    for r in reqs:
        eng.add_request(r)
    got = {r.request_id: ([], [], []) for r in reqs}
    for i in range(3000):
        if not eng.has_work():
            break
        if each_step is not None:
            each_step(eng, i)
        for out in eng.step():
            ids, lps, tops = got.setdefault(out.request_id, ([], [], []))
            ids += out.new_token_ids
            lps += out.logprobs or []
            tops += out.top_logprobs or []
    assert not eng.has_work()
    return got


def _same(a, b, tol=F32_TOL):
    assert a.keys() == b.keys()
    for rid in a:
        assert a[rid][0] == b[rid][0], rid
        np.testing.assert_allclose(a[rid][1], b[rid][1], atol=tol)


def _steps(eng):
    return [s["attrs"] for s in eng.tracer.snapshot()
            if s["name"] == "engine.step"]


def _slots_free(eng):
    km = eng.kv_manager
    assert sorted(km._free_state_slots) == list(
        range(1, eng.config.max_num_seqs + 1))


# ---------------------------------------------------------------------------
# against the plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,f32_cache,tol", [
    ("float32", True, EXACT_TOL), ("float32", False, F32_TOL),
    ("bfloat16", False, BF16_TOL)])
def test_engine_logprobs_against_the_reference(dtype, f32_cache, tol):
    """Prompts under the window (24) and a scan piece (8), across both, and
    longer than a step's budget (chunked: 64; three chunks), decoded through
    the cache planes and the state pool in mixed steps: the chosen token's
    log-probability and its two alternatives' against the reference's full
    forward over prompt + answer."""
    eng = _engine(dtype, float32_cache=f32_cache)
    reqs = [_req(f"r{i}", _prompt(i, n), n=7, logprobs=2)
            for i, n in enumerate((3, 29, 150))]
    got = _run(eng, reqs)
    c = _config(dtype)
    for r in reqs:
        ids, lps, tops = got[r.request_id]
        assert len(ids) == 7
        want = np.asarray(reference.tail_logprobs(
            _params(dtype), c,
            jnp.asarray(r.prompt_token_ids + ids[:-1], jnp.int32), 7))
        np.testing.assert_allclose(lps, want[np.arange(7), ids], atol=tol)
        for j, alt in enumerate(tops):
            for tok, lp in alt.items():
                assert abs(lp - want[j, tok]) <= tol, (r.request_id, j, tok)
    assert {"mixed", "decode"} <= {s["kind"] for s in _steps(eng)}
    _slots_free(eng)


@pytest.mark.parametrize("fault", [
    "no_diff_term", "window_off_by_one", "gmu_reads_gated",
    "cross_misses_chunk", "int8_weights"])
def test_reference_faults_move_the_answer(fault, monkeypatch):
    """Each mechanism the reference can get wrong moves a log-probability
    by more than the float32 engine differs from the right reference."""
    c = _config()
    tokens = jnp.asarray(_prompt(3, 90), jnp.int32)
    right = np.asarray(reference.tail_logprobs(_params(), c, tokens, 8))
    monkeypatch.setattr(reference, "FAULTS", {fault})
    monkeypatch.setattr(reference, "FAULT_CHUNK", 64)
    wrong = np.asarray(reference.tail_logprobs(_params(), c, tokens, 8))
    assert np.abs(wrong - right).max() > 10 * EXACT_TOL


# ---------------------------------------------------------------------------
# the cross-decoder on the sampled rows only
# ---------------------------------------------------------------------------

def test_sampled_rows_alone_equal_every_row_at_the_sampled_rows():
    """A mixed step (two decode rows with context, a prompt's last chunk, a
    chunk that samples nothing): the hidden states of the forward that
    gathers the sampled rows after ``gmu_memory_layer`` against the forward
    that takes every token's row through every layer."""
    eng = _engine(float32_cache=True, max_num_batched_tokens=32)
    for i, n in enumerate((6, 11)):
        eng.add_request(_req(f"d{i}", _prompt(i, n), n=20))
    for _ in range(4):
        eng.step()
    eng.add_request(_req("p", _prompt(5, 19), n=3))
    eng.add_request(_req("long", _prompt(6, 90), n=3))
    sched = eng.scheduler.schedule()
    assert sorted(sr.num_new_tokens for sr in sched.scheduled)[:2] == [1, 1]
    assert any(sr.request.num_computed_tokens + sr.num_new_tokens
               < sr.request.num_tokens for sr in sched.scheduled)
    packed, layout, scheduled, _ = eng._build_batch(sched)
    batch = layout.unpack(packed)
    c = eng.model_config

    def run(every_row):
        return jax.jit(functools.partial(
            hybrid_decoder.forward, config=c,
            block_size=eng.config.block_size, every_row=every_row))(
                eng.params, dict(eng.kv_cache), batch)

    (few, cache_a), (every, cache_b) = run(False), run(True)
    n = len(scheduled)
    assert few.shape == (layout.S, c.hidden_size)
    assert every.shape == (layout.T, c.hidden_size)
    np.testing.assert_allclose(
        few[:n], every[batch["sample_idx"][:n]], atol=1e-5)
    for name in cache_a:
        assert jnp.array_equal(cache_a[name], cache_b[name]), name
    kv = eng._step_kv
    sampled = [sr for sr in scheduled if sr.request.num_computed_tokens
               + sr.num_new_tokens == sr.request.num_tokens]
    assert kv["xdec_rows"] == len(sampled) == 3
    assert kv["xattn_read_tokens"] == 3 * sum(
        sr.request.num_tokens for sr in sampled)    # layer 5 and 2 CROSS


# ---------------------------------------------------------------------------
# chunk boundaries, slots, run-ahead
# ---------------------------------------------------------------------------

def test_a_prompt_in_one_chunk_and_in_three():
    one = _run(_engine(max_num_batched_tokens=64),
               [_req("r", _prompt(2, 50), n=6)])
    three = _run(_engine(max_num_batched_tokens=16, min_token_bucket=16),
                 [_req("r", _prompt(2, 50), n=6)])
    _same(one, three)


def test_a_mixed_step_and_its_rows_stepped_apart():
    def reqs():
        return [_req(f"r{i}", _prompt(i, n), n=6)
                for i, n in enumerate((4, 37, 21))]

    together = _run(_engine(), reqs())
    apart = {}
    for r in reqs():
        apart.update(_run(_engine(), [r]))
    _same(together, apart)


def test_decode_against_a_fresh_prefill():
    """o_1..o_6 through the decode path; then one token asked after prompt
    + o_1..o_k, which the chunked prefill computes: the same logprob."""
    prompt = _prompt(4, 41)
    ids, lps, _ = _run(_engine(), [_req("r", prompt, n=6)])["r"]
    for k in (1, 3, 5):
        one, lp, _ = _run(_engine(), [_req("q", prompt + ids[:k], n=1)])["q"]
        assert one[0] == ids[k]
        assert abs(lp[0] - lps[k]) <= F32_TOL


def test_a_row_dropped_at_retire_leaves_a_slot_that_starts_from_zero():
    """Four requests on four slots run ahead; ``r1`` stops on an EOS the
    host cannot foresee, so the step already launched has advanced its slot
    (``wasted_rows``).  The request that takes the slot answers as on a
    fresh engine."""
    base = _run(_engine(), [_req(f"r{i}", _prompt(i, 5 + i), n=14)
                            for i in range(4)])
    stream = base["r1"][0]
    k = next(i for i in range(3, 12) if stream[i] not in stream[:i])
    late = _req("late", _prompt(9, 11), n=9)
    want = _run(_engine(), [_req("late", _prompt(9, 11), n=9)])

    eng = _engine(max_num_seqs=4)
    eng.eos_token_id = stream[k]
    reqs = [_req(f"r{i}", _prompt(i, 5 + i), n=14) for i in range(4)]
    reqs[1] = _req("r1", _prompt(1, 6), n=14, ignore_eos=False)
    slot_of = {}

    def each_step(e, i):
        if i == 2:
            slot_of["r1"] = reqs[1].state_slot
            e.add_request(late)
        if late.state_slot and "late" not in slot_of:
            slot_of["late"] = late.state_slot

    got = _run(eng, reqs, each_step=each_step)
    assert got["r1"][0] == stream[:k + 1]
    assert sum(s["wasted_rows"] for s in _steps(eng)) == 1
    assert slot_of["late"] == slot_of["r1"] != 0
    _same({"late": got["late"]}, want)
    _slots_free(eng)


def test_a_full_batch_runs_ahead_and_gives_the_in_order_tokens():
    def reqs():
        return [_req(f"r{i}", _prompt(i, (70, 9, 45, 7, 90, 5)[i]),
                     n=5 + 2 * i) for i in range(6)]

    kw = dict(max_num_batched_tokens=32, min_token_bucket=16)
    full, spare = _engine(max_num_seqs=4, **kw), _engine(max_num_seqs=16, **kw)
    _same(_run(full, reqs()), _run(spare, reqs()))
    assert sum(s["run_ahead"] for s in _steps(full)) >= 4
    assert not any(s["run_ahead"] for s in _steps(spare))
    _slots_free(full)


def test_step_counts_of_the_window_and_the_shared_plane():
    """One request of 40 tokens in one chunk, then decode rows: 2 window
    planes (24) and the shared plane held, the window planes' dead tokens,
    the cross-decoder's reads by 3 layers."""
    eng = _engine()
    _run(eng, [_req("r", _prompt(1, 40), n=3)])
    first, second = _steps(eng)[:2]
    assert first["prefill_tokens"] == 40 and first["xdec_rows"] == 1
    assert first["xattn_read_tokens"] == 3 * 40
    assert first["kv_held_tokens"] == 3 * 40
    assert first["kv_dead_tokens"] == 2 * (40 - 24 + 1)
    windowed = sum(min(p + 1, 24) for p in range(40))
    assert first["kv_read_tokens"] == 2 * windowed + 3 * 40
    assert first["kv_ctx_tokens"] == 2 * (40 * 41 // 2) + 3 * 40
    assert first["ssm_prefill_tokens"] == 40 and first["ssm_resets"] == 1
    assert second["decode_tokens"] == 1 and second["xdec_rows"] == 1
    assert second["kv_read_tokens"] == 2 * 24 + 3 * 41
    assert second["ssm_decode_rows"] == 1


# ---------------------------------------------------------------------------
# what is switched off, and what is refused
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("what,kw", [
    ("multistep", dict(num_scheduler_steps=4)),
    ("spec_decode", dict(spec_k=2)),
    ("stacked_dp", dict(mesh=MeshConfig(dp=2), allow_device_subset=True,
                        num_blocks=128)),
    ("tensor_parallel", dict(mesh=MeshConfig(tp=2),
                             allow_device_subset=True)),
    ("kv_offload", dict(kv_offload_blocks=16)),
])
def test_unsupported_combinations_refuse_at_construction(what, kw):
    with pytest.raises(ValueError, match=f"{what} requested but unavailable "
                                         r"\(recurrent_state"):
        _engine(**kw)


def test_no_prefix_hit_and_no_kv_connector():
    eng = _engine()
    assert not eng.kv_manager.enable_prefix_caching
    with pytest.raises(ValueError, match="recurrent state"):
        eng.kv_connector = object()


@pytest.mark.parametrize("field,kw", [
    ("layer_types", dict(layer_types=("mamba", "attention") * 5)),
    ("cross_kv_layer", dict(cross_kv_layer=4)),
    ("gmu_memory_layer", dict(gmu_memory_layer=2)),
    ("layer_types", dict(layer_types=(MAMBA, SLIDING) * 2 + (MAMBA, FULL)
                         + (CROSS, GMU) * 2)),
    ("layer_types", dict(layer_types=(MAMBA, SLIDING) * 2 + (GMU, FULL)
                         + (GMU, CROSS) * 2)),
    ("use_rope", dict(use_rope=True)),
    ("ssm_dt_rank", dict(ssm_dt_rank=0)),
    ("ssm_dt_rank", dict(ssm_num_heads=4, ssm_head_dim=24)),
    ("norm_kind", dict(norm_kind="batch")),
    ("qk_norm", dict(qk_norm=True)),
    ("num_experts", dict(num_experts=8, num_experts_per_tok=2,
                         moe_intermediate_size=32)),
    ("kv_lora_rank", dict(kv_lora_rank=32, qk_nope_head_dim=16,
                          qk_rope_head_dim=8, v_head_dim=16)),
    ("diffusion_block_length", dict(diffusion_block_length=4,
                                    mask_token_id=5)),
    ("diff_attention", dict(num_heads=6, num_kv_heads=4)),
    ("sliding_window", dict(sliding_window=0)),
])
def test_wrong_model_combinations_raise(field, kw):
    with pytest.raises(ValueError, match=field):
        dataclasses.replace(get_config(PRESET), **kw)


@pytest.mark.parametrize("field,value", [
    ("cross_kv_layer", 1), ("gmu_memory_layer", 0), ("diff_attention", True),
    ("norm_kind", "layer"), ("use_rope", False),
    ("attention_out_bias", True), ("ssm_dt_rank", 4)])
def test_hybrid_fields_are_refused_on_a_plain_stack(field, value):
    with pytest.raises(ValueError, match=field):
        dataclasses.replace(get_config("tiny"), **{field: value})


def test_a_mixer_beside_attention_names_what_it_is_not_served_with():
    for field, kw in (("layer_types", dict(layer_types=(FULL, FULL))),
                      ("num_experts", dict(num_experts=8,
                                           num_experts_per_tok=2,
                                           moe_intermediate_size=32))):
        with pytest.raises(ValueError, match=field):
            dataclasses.replace(get_config("tiny-ssm"), **kw)


def test_config_fields_by_mechanism():
    c = get_config(PRESET)
    assert c.has_recurrent_state and c.mixer_by_layer
    assert not get_config("tiny-ssm").mixer_by_layer
    assert {MAMBA, SLIDING, FULL, GMU, CROSS} == set(c.layer_types)
    assert get_model(c).__name__.endswith("models.hybrid_decoder")
    assert get_model(get_config("tiny-ssm")).__name__.endswith("models.ssm")
    assert c.ssm_conv_channels == 96 and c.attn_head_dim == 16
    assert hybrid_decoder.kv_cache_layers(c) == {"k": 3, "v": 3}
    pool = hybrid_decoder.state_pool_shapes(c, 5)
    assert pool["ssm"].shape == (3, 5, 4, 96)
    assert pool["ssm"].dtype == jnp.float32         # ONE dtype, no option
    assert pool["conv"].shape == (3, 5, 3, 96)
    big = get_config("phi4-mini-flash")
    assert big.layer_types.count(MAMBA) == 9
    assert big.layer_types.count(SLIDING) == 8
    assert big.layer_types.count(GMU) == big.layer_types.count(CROSS) == 7
    assert big.layer_types[17] == FULL and big.layer_types[16] == MAMBA
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda k: hybrid_decoder.init_params(big, k),
                       jax.random.PRNGKey(0))))
    assert 3.84e9 < n < 3.87e9                      # the published 3.85 B
