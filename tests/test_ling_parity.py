"""Linear-attention (KDA) layers five to every MLA layer over group-limited
routed experts (``tiny-linear-moe``), through the state pool beside ONE
latent cache plane: the served engine (prefill in chunks, mixed steps,
decode, run ahead, preemption and recompute) against the benchmark's plain
reference (``benchmarks/references/ling_linear.py``: float32, the recurrence
token by token from a zero state, no cache, no chunks), logprobs and not
tokens; the four shares of the experts against the uncut layer; what the
stack is not served with."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.request import Request
from llm_d_tpu.models import get_config, get_model
from llm_d_tpu.models.config import FULL, LINEAR, ModelConfig
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.parallel.mesh import MeshConfig
from llm_d_tpu.utils import tracing

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from references import ling_linear  # noqa: E402

# float32 weights, activations and cache: tight enough to see one token's
# decay or one lost convolution input.
TINY = dataclasses.replace(get_config("tiny-linear-moe"), dtype="float32")
TOL = 1e-4
CTX = tracing.TraceContext("a" * 32, "b" * 16, True)


@functools.lru_cache(maxsize=None)
def params_of(config=TINY):
    return get_model(config).init_params(config, jax.random.PRNGKey(7))


def make_engine(config=TINY, budget=64, **kw):
    tracing.reset()     # the engine takes its tracer at construction
    metrics = kw.pop("metrics", None)
    kw = {"block_size": 16, "num_blocks": 160, "max_num_seqs": 4,
          "max_num_batched_tokens": budget, **kw}
    engine = EngineCore(EngineConfig(model=config.name, model_config=config,
                                     **kw), params=params_of(config),
                        metrics=metrics)
    engine.kv_cache = {name: buf.astype(jnp.float32)
                       for name, buf in engine.kv_cache.items()}
    return engine


def request(rid, prompt, n_gen):
    req = Request(request_id=rid, prompt_token_ids=list(prompt),
                  sampling=SamplingParams(temperature=0.0, max_tokens=n_gen,
                                          ignore_eos=True, logprobs=0))
    req.trace_ctx = CTX         # engine.step spans are a traced request's
    return req


def run(engine, reqs):
    """Step the engine dry; {request id: (ids, logprobs)}."""
    for r in reqs:
        engine.add_request(r)
    got = {r.request_id: ([], []) for r in reqs}
    while engine.has_work():
        for out in engine.step():
            got[out.request_id][0].extend(out.new_token_ids)
            got[out.request_id][1].extend(out.logprobs or [])
    return got


def reference_logprobs(prompt, ids, config=TINY):
    lp = ling_linear.tail_logprobs(params_of(config), config, jnp.asarray(
        list(prompt) + ids[:-1], jnp.int32), len(ids))
    return np.asarray(lp)[np.arange(len(ids)), ids]


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, TINY.vocab_size, n).tolist()


def steps(engine):
    return [s["attrs"] for s in engine.tracer.snapshot()
            if s["name"] == "engine.step"]


# (a) against the plain reference: a prompt under one chunk, across a chunk
# boundary inside a piece and inside a sub-block (budget 40, pieces of 32,
# sub-blocks of 16), in many chunks; both attention backends of the CPU.
@pytest.mark.parametrize("budget,backend,n", [
    (64, "reference", 20), (64, "reference", 75), (40, "reference", 150),
    (40, "chunked", 90), (16, "chunked", 70)])
def test_engine_matches_plain_reference(budget, backend, n):
    engine = make_engine(budget=budget, attn_backend=backend,
                         min_token_bucket=8)
    prompt = prompt_of(n, seed=n)
    ids, lps = run(engine, [request("r", prompt, 8)])["r"]
    np.testing.assert_allclose(lps, reference_logprobs(prompt, ids), atol=TOL)
    chunks = [s["prefill_tokens"] for s in steps(engine)
              if s["prefill_tokens"]]
    assert len(chunks) == -(-n // budget)
    assert sum(s["ssm_resets"] for s in steps(engine)) == 1


def test_mixed_steps_run_ahead_and_recompute_hold_to_the_reference():
    """Six requests of staggered lengths over four slots (decode rows ride
    steps with other rows' chunks, a full batch runs a step ahead), and the
    same through a pool too small for them (preempted, recomputed from
    position 0): every answer is the reference's."""
    lens = (70, 9, 45, 7, 90, 5)

    def reqs():
        return [request(f"r{i}", prompt_of(n, seed=i), 6 + 2 * i)
                for i, n in enumerate(lens)]

    engine = make_engine(budget=32, min_token_bucket=16)
    got = run(engine, reqs())
    for i, n in enumerate(lens):
        ids, lps = got[f"r{i}"]
        np.testing.assert_allclose(
            lps, reference_logprobs(prompt_of(n, seed=i), ids), atol=TOL)
    mixed = [s for s in steps(engine) if s["kind"] == "mixed"]
    assert mixed and all(s["ssm_decode_rows"] and s["ssm_prefill_tokens"]
                         for s in mixed)
    assert sum(s["run_ahead"] for s in steps(engine)) >= 4

    def growing():
        return [request(f"g{i}", prompt_of(20 + 3 * i, seed=20 + i), 30)
                for i in range(4)]

    roomy = run(make_engine(), growing())
    tight = make_engine(num_blocks=11)      # 10 usable pages: not for four
    again = run(tight, growing())
    assert tight.scheduler.num_preemptions > 0
    for rid in roomy:
        assert again[rid][0] == roomy[rid][0]
        np.testing.assert_allclose(again[rid][1], roomy[rid][1], atol=TOL)
    assert sum(s["ssm_resets"] for s in steps(tight)) \
        == 4 + tight.scheduler.num_preemptions
    assert tight.kv_manager.state_slots_in_use == 0


def test_a_pool_full_of_garbage_changes_nothing():
    engine = make_engine()
    prompt = prompt_of(60, seed=3)
    want = run(engine, [request("a", prompt, 6)])["a"]
    engine.kv_cache = dict(
        engine.kv_cache, ssm=jnp.full_like(engine.kv_cache["ssm"], 1e4),
        conv=jnp.full_like(engine.kv_cache["conv"], -50.0))
    got = run(engine, [request("b", prompt, 6)])["b"]
    assert got[0] == want[0]
    np.testing.assert_allclose(got[1], want[1], atol=TOL)


# (b) each mechanism moves the answer: the reference with one thing wrong
# leaves the served logprobs.
@pytest.mark.parametrize("fault", [
    "no_decay", "no_delta", "beta_one", "stale_state", "no_out_gate",
    "no_group_limit", "conv_tail_zeroed"])
def test_each_mechanism_moves_the_answer(fault, monkeypatch):
    engine = make_engine(budget=64)
    prompt = prompt_of(100, seed=11)
    ids, lps = run(engine, [request("r", prompt, 8)])["r"]
    np.testing.assert_allclose(lps, reference_logprobs(prompt, ids), atol=TOL)
    monkeypatch.setattr(ling_linear, "FAULTS", {fault})
    monkeypatch.setattr(ling_linear, "CHUNK", 64)
    assert np.abs(reference_logprobs(prompt, ids) - lps).max() > 1e-2


# (c) one rank's share: the FOUR shares of the routed experts, the shared
# expert counted once, add up to the uncut layer under the group-limited
# router, in the reference and in the program's own op.
def test_four_shares_add_up_to_the_uncut_layer():
    c = TINY
    group = params_of()["lin_moe_layers"]
    whole = dataclasses.replace(c, num_local_experts=0)
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(40, c.hidden_size)), jnp.float32)
    E = c.num_experts
    all_experts = {k: jnp.concatenate(
        [params_of(dataclasses.replace(c, first_local_expert=e0))[
            "lin_moe_layers"][k] for e0 in range(0, E, 2)], axis=1)
        for k in ("w_gate", "w_up", "w_down")}
    full_group = dict(group, **all_experts)
    with jax.default_matmul_precision("highest"):
        uncut = ling_linear.experts(full_group, 0, whole, x)
        shares = sum(
            ling_linear.experts(
                dict(group, **{k: v[:, e0:e0 + 2]
                               for k, v in all_experts.items()}),
                0, c, x, share=(e0, 2), shared=e0 == 0)
            for e0 in range(0, E, 2))
        np.testing.assert_allclose(shares, uncut, atol=1e-5)
        # the program's op on each share, against the same sum
        from llm_d_tpu.ops import moe as moe_ops
        weights, idx = moe_ops.route(x @ group["router"][0], c,
                                     e_bias=group["e_bias"][0])
        routed = sum(moe_ops.expert_ffn(
            x, weights, idx, *(all_experts[k][0, e0:e0 + 2]
                               for k in ("w_gate", "w_up", "w_down")),
            held=(e0, 2)) for e0 in range(0, E, 2))
        shared = ling_linear.plain.swiglu(
            x, group["shared_gate"][0], group["shared_up"][0],
            group["shared_down"][0])
        np.testing.assert_allclose(routed + shared, uncut, atol=1e-4)
    # the group limit binds: without it another expert set is chosen
    combine = ling_linear.combine_weights(group, 0, c, x)
    assert ((combine > 0).sum(-1) == c.num_experts_per_tok).all()
    per_group = (combine.reshape(40, c.n_group, -1) > 0).any(-1).sum(-1)
    assert int(per_group.max()) <= c.topk_group


# (d) the config, the model and the pool.
def test_config_fields_by_mechanism():
    c = TINY
    assert c.linear_by_layer and c.has_recurrent_state and c.mla_by_kind
    assert c.mla_layer_kinds == (FULL,) and not c.mixer_by_layer
    assert c.kv_cache_groups == ()
    assert c.lin_conv_channels == 4 * (2 * 16 + 8)
    model = get_model(c)
    assert model.__name__.endswith("models.moe")
    assert model.kv_cache_layers(c) == {"kv": 1}       # ONE latent plane
    assert model.kv_cache_layout(c) == {"kv": 128}
    pool = model.state_pool_shapes(c, 5)
    assert pool["ssm"].shape == (6, 5, 4, 16, 8)
    assert pool["ssm"].dtype == jnp.float32             # ONE dtype, no option
    assert pool["conv"].shape == (6, 5, 3, 160)
    runs = [(r.kind, r.moe, r.stop - r.start, r.plane0)
            for r in model.layer_runs(c)]
    assert runs == [(LINEAR, False, 1, 0), (LINEAR, True, 3, 1),
                    (FULL, True, 1, 0), (LINEAR, True, 2, 4)]
    assert set(params_of()) == {"embed", "final_norm", "lm_head",
                                "lin_dense_layers", "lin_moe_layers",
                                "moe_layers"}
    for name in ("tiny-moe", "tiny-mla", "tiny-sparse-mla", "tiny-ssm"):
        assert not get_config(name).linear_by_layer
    assert get_model(get_config("tiny-ssm")).__name__.endswith("models.ssm")


def test_the_decay_is_drawn_over_its_published_range():
    """Random weights give half-lives from a few tokens to thousands: the
    log-decay of a token spans about -0.001 to -0.5 (a plain normal init
    gives -2.5 everywhere and nothing a check reads would cross a chunk)."""
    from llm_d_tpu.models import linear_attention as lin
    c = TINY
    lp = {k: v[0] for k, v in params_of()["lin_moe_layers"].items()
          if k.startswith("lin_")}
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(64, c.hidden_size)), jnp.float32)
    rate = jnp.exp(lp["lin_A_log"])[None, :, None]
    g = c.lin_gate_floor * jax.nn.sigmoid(rate * (
        x @ lp["lin_f_proj"] + lp["lin_dt_bias"]).reshape(64, 4, 16))
    assert -5.0 < float(g.min()) and float(g.max()) < 0
    assert -1.5 < float(jnp.quantile(g, 0.02))
    assert float(jnp.quantile(g, 0.98)) < -3e-4
    assert lin.param_shapes(c, 3)["lin_g_proj"] == (3, 64, 4)


@pytest.mark.parametrize("kw,match", [
    (dict(kv_lora_rank=0, qk_nope_head_dim=0, qk_rope_head_dim=0,
          v_head_dim=0), "kv_lora_rank"),
    (dict(num_experts=0, num_experts_per_tok=0, num_local_experts=0,
          n_group=0, topk_group=0, num_shared_experts=0,
          first_dense_layers=0), "num_experts"),
    (dict(lin_num_heads=0), "lin_num_heads"),
    (dict(lin_key_dim=0), "lin_key_dim"),
    (dict(lin_value_dim=0), "lin_value_dim"),
    (dict(layer_types=(LINEAR,) * 7), "latent rows"),
    (dict(layer_types=(LINEAR,) * 6 + ("sliding_attention",),
          sliding_window=8), "sliding_window"),
    (dict(ssm_state_size=16, ssm_num_heads=4, ssm_head_dim=8),
     "ssm_state_size"),
    (dict(diffusion_block_length=4), "diffusion_block_length|block diffusion"),
    (dict(index_topk=16, index_n_heads=2, index_head_dim=8, q_lora_rank=16),
     "index_topk"),
    (dict(lin_conv_kernel=1), "lin_conv_kernel"),
    (dict(lin_gate_floor=0.0), "lin_gate_floor"),
    (dict(lin_gate_floor=-20.0), "lin_gate_floor"),
    (dict(lin_gate_floor=0.5), "lin_gate_floor"),
])
def test_wrong_model_combinations_raise(kw, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(get_config("tiny-linear-moe"), **kw)


def test_lin_fields_belong_to_linear_layers():
    with pytest.raises(ValueError, match="lin_num_heads belongs"):
        dataclasses.replace(get_config("tiny-mla"), lin_num_heads=4)
    assert ModelConfig().lin_num_heads == 0


# (e) what the stack is not served with: one refusal a field, counted.
def disabled(engine_or_metrics):
    metrics = getattr(engine_or_metrics, "metrics", engine_or_metrics)
    return {(s.labels["feature"], s.labels["blocker"].split(":")[0])
            for m in metrics._feature_disabled.collect() for s in m.samples
            if s.name.endswith("_total") and s.value}


@pytest.mark.parametrize("what,blocker,kw", [
    ("multistep", "recurrent_state", dict(num_scheduler_steps=4)),
    ("spec_decode", "recurrent_state", dict(spec_k=2)),
    ("stacked_dp", "recurrent_state", dict(
        mesh=MeshConfig(dp=2), allow_device_subset=True)),
    ("tensor_parallel", "recurrent_state", dict(
        mesh=MeshConfig(tp=2), allow_device_subset=True)),
    ("sequence_parallel", "recurrent_state", dict(
        mesh=MeshConfig(sp=2), allow_device_subset=True)),
    ("kv_offload", "recurrent_state", dict(kv_offload_blocks=16)),
    ("eplb", "expert_share", dict(enable_eplb=True)),
    ("int8_experts", "layer_kinds", dict(quantization="int8")),
])
def test_unsupported_combinations_refuse_at_construction(what, blocker, kw):
    from llm_d_tpu.utils.metrics import EngineMetrics
    metrics = EngineMetrics("tiny-linear-moe")
    with pytest.raises(ValueError, match=f"{what} requested but unavailable "
                                         rf"\({blocker}"):
        make_engine(metrics=metrics, **kw)
    assert (what, blocker) in disabled(metrics)


def test_prefix_caching_is_off_and_counted_and_a_connector_refused():
    engine = make_engine(block_size=4)
    shared = prompt_of(24, seed=1)
    for i in range(3):
        run(engine, [request(f"r{i}", shared + prompt_of(3 + i, seed=9), 4)])
    assert engine.metrics.prefix_cache_hits._value.get() == 0
    assert engine.kv_manager.enable_prefix_caching is False
    assert ("prefix_caching", "recurrent_state") in disabled(engine)
    with pytest.raises(ValueError, match="cache buffers go by layer kind"):
        engine.kv_connector = object()
    assert engine.kv_connector is None
    assert ("kv_transfer", "layer_kinds") in disabled(engine)
