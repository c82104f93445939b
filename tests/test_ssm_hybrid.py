"""A state-space mixer in parallel with attention in every layer (the
Falcon-H1 family), its recurrent state held per sequence in the engine's
state pool beside the paged KV cache (models/ssm.py, ops/ssm.py,
engine/kv_cache.py).

What this pins, on seeded random weights at the ``tiny-ssm`` preset on the
CPU, against ``benchmarks/references/falcon_h1.py`` (float32, the recurrence
token by token from a zero state: no chunks, no cache, no slots):

  - the engine's log-probabilities (the chosen token's and its
    alternatives': logits, not sampled tokens), prefill and then decode
    through the cache and the state pool, against the reference's full
    forward;
  - a chunk boundary may fall anywhere: a prompt in one chunk, in three, in
    a mixed step beside other rows, preempted and recomputed, all give the
    same tokens;
  - a slot is never cleaned by the host: the PROGRAM zeroes a row's state at
    its first chunk, so a slot a finished row leaves, a slot a row dropped
    at retire (``wasted_rows``) has advanced, and a pool full of garbage all
    start the next request from zero;
  - a full batch runs a step ahead and gives the in-order loop's tokens;
  - no prefix hit on a stack with recurrent layers, hits as before on one
    without; every unsupported combination raises at construction;
  - the two device computations (Pallas kernels interpreted) against a
    token-by-token float32 scan at the published geometry.

Tolerances: the float32 preset differs from the reference only by the bf16
KV cache (measured 1e-3 to 3e-3 on a log-probability; with the attention
output switched off the mixer path agrees to 2e-6); the bf16 preset by bf16
activations everywhere (measured 0.02).
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
from llm_d_tpu.models import get_model
from llm_d_tpu.models.config import FULL, ModelConfig, get_config
from llm_d_tpu.ops import ssm as ssm_ops
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.parallel.mesh import MeshConfig
from llm_d_tpu.utils import tracing

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "benchmarks"))

import references.falcon_h1 as reference  # noqa: E402

F32_TOL, BF16_TOL = 6e-3, 6e-2
CTX = tracing.TraceContext("a" * 32, "b" * 16, True)


def _config(dtype="float32"):
    return dataclasses.replace(get_config("tiny-ssm"), dtype=dtype)


@functools.lru_cache(maxsize=None)
def _params(dtype="float32"):
    c = _config(dtype)
    return get_model(c).init_params(c, jax.random.PRNGKey(7))


def _engine(dtype="float32", **kw):
    tracing.reset()     # the engine takes its tracer at construction
    kw = {"block_size": 8, "num_blocks": 128, "max_num_seqs": 8,
          "max_num_batched_tokens": 64, "min_seq_bucket": 4, **kw}
    return EngineCore(EngineConfig(model="tiny-ssm",
                                   model_config=_config(dtype), **kw),
                      params=_params(dtype))


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
    assert km.state_slots_in_use == 0


# ---------------------------------------------------------------------------
# against the plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL),
                                       ("bfloat16", BF16_TOL)])
def test_engine_logprobs_against_the_reference(dtype, tol):
    """Prompts shorter than a scan piece (8), across several, longer than a
    step's budget (chunked: 64), decoded through cache and state pool in
    mixed steps: the chosen token's log-probability AND its two
    alternatives' against the reference's full forward over prompt +
    answer."""
    eng = _engine(dtype)
    reqs = [_req(f"r{i}", _prompt(i, n), n=7, logprobs=2)
            for i, n in enumerate((3, 8, 29, 150))]
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
            assert len(alt) == 2
            for tok, lp in alt.items():
                assert abs(lp - want[j, tok]) <= tol, (r.request_id, j, tok)
    kinds = {s["kind"] for s in _steps(eng)}
    assert {"mixed", "decode"} <= kinds
    _slots_free(eng)


def test_reference_faults_move_the_answer():
    """What benchmarks/tools/ssm_mechanism_check.py gets wrong on the chip
    is wrong here too: each fault moves the reference's log-probabilities
    by more than the engine's distance from the right ones."""
    c, p = _config(), _params()
    tokens = jnp.asarray(_prompt(3, 60), jnp.int32)
    right = reference.tail_logprobs(p, c, tokens, 8)
    try:
        for fault in ("bf16_state", "no_mup_vector", "no_key_multiplier",
                      "swap_groups", "no_softplus"):
            reference.FAULTS = {fault}
            wrong = reference.tail_logprobs(p, c, tokens, 8)
            moved = float(jnp.abs(wrong - right).max())
            # (without softplus dt goes negative, the state grows without
            # bound and the answer is not finite: moved all the same)
            assert not moved <= (F32_TOL if fault != "bf16_state"
                                 else 1e-4), (fault, moved)
    finally:
        reference.FAULTS = set()


# ---------------------------------------------------------------------------
# a chunk boundary may fall anywhere
# ---------------------------------------------------------------------------

def test_a_prompt_in_one_chunk_and_in_three():
    prompt = _prompt(5, 50)
    one = _run(_engine(max_num_batched_tokens=64), [_req("p", prompt)])
    eng = _engine(max_num_batched_tokens=20, min_token_bucket=4)
    three = _run(eng, [_req("p", prompt)])
    _same(one, three)
    chunks = [s["prefill_tokens"] for s in _steps(eng) if s["prefill_tokens"]]
    assert chunks == [20, 20, 10]
    resets = [s["ssm_resets"] for s in _steps(eng)]
    assert sum(resets) == 1 and resets[0] == 1


def test_a_mixed_step_and_its_rows_stepped_apart():
    """Five requests of staggered lengths through one engine (decode rows
    ride steps with other rows' chunks) and each alone through its own."""
    def reqs():
        return [_req(f"r{i}", _prompt(i, (70, 9, 45, 7, 90)[i]), n=5 + 2 * i)
                for i in range(5)]

    eng = _engine(max_num_batched_tokens=32, min_token_bucket=16)
    together = _run(eng, reqs())
    apart = {}
    for r in reqs():
        apart.update(_run(_engine(max_num_batched_tokens=32,
                                  min_token_bucket=16), [r]))
    _same(together, apart)
    mixed = [s for s in _steps(eng) if s["kind"] == "mixed"]
    assert mixed and all(s["ssm_decode_rows"] and s["ssm_prefill_tokens"]
                         for s in mixed)
    assert all(s["ssm_prefill_tokens"] == 0 for s in _steps(eng)
               if s["kind"] == "decode")


def test_preempt_and_recompute_gives_the_same_tokens():
    def reqs():
        return [_req(f"r{i}", _prompt(i, 20 + 3 * i), n=30) for i in range(4)]

    roomy = _run(_engine(), reqs())
    tight = _engine(num_blocks=17)     # 16 usable blocks of 8: not for four
    got = _run(tight, reqs())
    assert tight.scheduler.num_preemptions > 0
    _same(roomy, got)
    # a recompute starts from position 0: the program zeroed its state
    assert sum(s["ssm_resets"] for s in _steps(tight)) \
        == 4 + tight.scheduler.num_preemptions
    _slots_free(tight)


# ---------------------------------------------------------------------------
# slots are never cleaned by the host
# ---------------------------------------------------------------------------

def test_a_freed_slot_starts_the_next_request_from_zero():
    """Two slots, six requests one after the other through them; and a
    pool the test fills with garbage: the same answers as a fresh engine's."""
    def reqs():
        return [_req(f"r{i}", _prompt(i, 6 + 5 * i), n=6 + i)
                for i in range(6)]

    fresh = {}
    for r in reqs():
        fresh.update(_run(_engine(), [r]))
    eng = _engine(max_num_seqs=2, min_seq_bucket=2)
    _same(_run(eng, reqs()), fresh)
    assert eng.kv_cache["ssm"].shape[1] == 3            # two slots + trash
    assert float(jnp.abs(eng.kv_cache["ssm"][:, 1:]).max()) > 0
    eng.kv_cache = dict(
        eng.kv_cache,
        ssm=jnp.full_like(eng.kv_cache["ssm"], 1e4),
        conv=jnp.full_like(eng.kv_cache["conv"], -50.0))
    _same(_run(eng, reqs()), fresh)
    assert eng.metrics.ssm_state_resets._value.get() == 12
    _slots_free(eng)


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
    assert sum(s["run_ahead"] for s in _steps(eng)) >= 4
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
    assert all(s["wasted_rows"] == 0 for s in _steps(full) + _steps(spare))
    assert full.metrics.ssm_state_slots_in_use._value.get() == 0
    _slots_free(full)


# ---------------------------------------------------------------------------
# what is switched off, and what is refused
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model,hits", [("tiny-ssm", False), ("tiny", True)])
def test_prefix_hits_only_without_recurrent_layers(model, hits):
    tracing.reset()
    eng = EngineCore(EngineConfig(
        model=model, block_size=4, num_blocks=256, max_num_seqs=8,
        max_num_batched_tokens=64))
    shared = _prompt(99, 24)            # six whole blocks of four
    for i in range(4):                  # one after the other: hits possible
        _run(eng, [_req(f"r{i}", shared + _prompt(i, 3 + i), n=4)])
    got = eng.metrics.prefix_cache_hits._value.get()
    assert (got >= 3 * 24) if hits else got == 0
    assert eng.kv_manager.enable_prefix_caching is hits
    disabled = [
        s.labels for m in eng.metrics._feature_disabled.collect()
        for s in m.samples if s.name.endswith("_total") and s.value]
    assert any(d["feature"] == "prefix_caching"
               and d["blocker"].startswith("recurrent_state")
               for d in disabled) is not hits


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


def test_a_kv_connector_is_refused():
    eng = _engine()
    with pytest.raises(ValueError, match="recurrent state"):
        eng.kv_connector = object()
    assert eng.kv_connector is None
    tracing.reset()
    plain = EngineCore(EngineConfig(model="tiny", num_blocks=32))
    marker = object()
    plain.kv_connector = marker         # an engine without state takes one
    assert plain.kv_connector is marker


@pytest.mark.parametrize("kw", [
    dict(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
         v_head_dim=16),
    dict(layer_types=(FULL, FULL)),
    dict(diffusion_block_length=4, mask_token_id=5),
    dict(num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32),
    dict(ssm_num_groups=3),
    dict(ssm_inner_size=48),
    dict(ssm_multipliers=(1.0, 1.0)),
])
def test_wrong_model_combinations_raise(kw):
    with pytest.raises(ValueError):
        dataclasses.replace(get_config("tiny-ssm"), **kw)


def test_config_fields_by_mechanism():
    c = get_config("tiny-ssm")
    assert c.has_recurrent_state and not get_config("tiny").has_recurrent_state
    assert c.ssm_conv_channels == 32 + 2 * 2 * 16
    assert get_model(c).__name__.endswith("models.ssm")
    assert isinstance(ModelConfig(rope_theta=100000000000).rope_theta, float)
    pool = get_model(c).state_pool_shapes(c, 5)
    assert pool["ssm"].shape == (2, 5, 4, 16, 8)
    assert pool["ssm"].dtype == jnp.float32         # ONE dtype, no option
    assert pool["conv"].shape == (2, 5, 3, 96)


# ---------------------------------------------------------------------------
# the two device computations at the published geometry
# ---------------------------------------------------------------------------

H, P, N, G, K, CHUNK = 32, 128, 256, 2, 4, 128


def _token_by_token(x, dt, A, B, C, D, s0):
    """One row, float32: the recurrence a token at a time.  x [n, H, P],
    dt [n, H], B, C [n, G, N], s0 [H, N, P] -> (y [n, H, P], s_n)."""
    Bh = np.repeat(B, H // G, axis=1)
    Ch = np.repeat(C, H // G, axis=1)
    s, ys = s0.copy(), []
    for t in range(x.shape[0]):
        s = s * np.exp(dt[t] * A)[:, None, None] \
            + Bh[t][:, :, None] * (dt[t][:, None] * x[t])[:, None, :]
        ys.append(np.einsum("hnp,hn->hp", s, Ch[t]) + D[:, None] * x[t])
    return np.stack(ys), s


@pytest.fixture
def interpreted(monkeypatch):
    """``state_update``'s Pallas branch on the CPU: kernels interpreted."""
    from llm_d_tpu.ops.pallas import ssm_scan, ssm_update
    monkeypatch.setattr(ssm_update, "ssm_decode_update", functools.partial(
        ssm_update.ssm_decode_update, interpret=True))
    monkeypatch.setattr(ssm_scan, "ssm_chunk_scan", functools.partial(
        ssm_scan.ssm_chunk_scan, interpret=True))


def test_state_kernels_against_a_token_by_token_scan(interpreted):
    """A mixed step at 32 heads x 128 x 256, 2 groups, pieces of 128: rows
    of one token (a decode row with context, a one-token prompt from zero),
    a chunk of 200 (no multiple of 128) that continues a prompt, a chunk of
    130 from position 0, a chunk of 3, padded rows: y and the states the
    slots hold afterwards, and slots of rows not in the step untouched."""
    assert not ssm_ops.pallas_ineligible_reason(H, P, N, G, CHUNK)
    qlen = np.array([1, 200, 1, 130, 3, 0, 0, 0])
    ctx = np.array([9, 300, 0, 0, 37, 0, 0, 0])    # tokens before the chunk
    slot = np.array([1, 2, 3, 4, 6, 0, 0, 0])
    S, T, L, slots = len(qlen), 512, 2, 8
    n = int(qlen.sum())
    qstart = np.cumsum(qlen) - qlen
    rows = np.repeat(np.arange(S), qlen)
    k = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    x = jax.random.normal(next(k), (T, H, P)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(next(k), (T, H)) - 3.0)
    A = -jnp.exp(jax.random.uniform(next(k), (H,), maxval=2.7))
    B = (jax.random.normal(next(k), (T, G, N)) * 0.3).astype(jnp.bfloat16)
    C = (jax.random.normal(next(k), (T, G, N)) * 0.3).astype(jnp.bfloat16)
    D = jax.random.normal(next(k), (H,))
    pool = jax.random.normal(next(k), (L, slots, H, N, P))
    pad = np.zeros(T - n, int)
    batch = {
        "query_start": qstart, "query_len": qlen, "state_slot": slot,
        "seq_lens": ctx + qlen,
        "token_seq_ids": np.concatenate([rows, pad]),
        "token_qpos": np.concatenate([np.arange(n) - qstart[rows], pad]),
        "qtok_idx": np.zeros((S, 256))}
    batch = {name: jnp.asarray(v, jnp.int32) for name, v in batch.items()}
    y, new = jax.jit(ssm_ops.state_update, static_argnums=(9, 10))(
        x, dt, A, B, C, D, pool, batch, jnp.int32(1), CHUNK, "pallas")
    f = [np.asarray(a, np.float32) for a in (x, dt, A, B, C, D)]
    for r in range(S):
        if not qlen[r]:
            continue
        tok = slice(qstart[r], qstart[r] + qlen[r])
        s0 = np.asarray(pool[1, slot[r]]) * (ctx[r] > 0)
        want_y, want_s = _token_by_token(
            f[0][tok], f[1][tok], f[2], f[3][tok], f[4][tok], f[5], s0)
        # bf16 operands on the MXU inside a piece, float32 on the VPU for a
        # row of one token: measured 0.03 of 16 and 2e-5.
        tol = 0.1 if qlen[r] > 1 else 1e-3
        np.testing.assert_allclose(np.asarray(y[tok], np.float32), want_y,
                                   atol=tol, rtol=0.02)
        np.testing.assert_allclose(np.asarray(new[1, slot[r]]), want_s,
                                   atol=0.05 if qlen[r] > 1 else 1e-4,
                                   rtol=0.01)
    untouched = [s for s in range(1, slots) if s not in slot]
    assert jnp.array_equal(new[1, jnp.asarray(untouched)],
                           pool[1, jnp.asarray(untouched)])
    assert jnp.array_equal(new[0], pool[0])             # the other layer


def test_a_pure_decode_step_holds_no_scan(interpreted):
    S, T, L, slots = 4, 16, 1, 6
    k = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    x = jax.random.normal(next(k), (T, H, P)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(next(k), (T, H)) - 3.0)
    A = -jnp.exp(jax.random.uniform(next(k), (H,), maxval=2.7))
    B = jax.random.normal(next(k), (T, G, N)).astype(jnp.bfloat16)
    C = jax.random.normal(next(k), (T, G, N)).astype(jnp.bfloat16)
    pool = jax.random.normal(next(k), (L, slots, H, N, P))
    batch = {name: jnp.asarray(v, jnp.int32) for name, v in {
        "query_start": [0, 1, 2, 0], "query_len": [1, 1, 1, 0],
        "state_slot": [5, 2, 4, 0], "seq_lens": [7, 1, 90, 0],
        "token_seq_ids": [0, 1, 2] + [0] * 13, "token_qpos": [0] * 16,
        "qtok_idx": np.zeros((S, 1))}.items()}
    args = (x, dt, A, B, C, jnp.zeros((H,)), pool, batch, jnp.int32(0), CHUNK)
    fn = jax.jit(ssm_ops.state_update, static_argnums=(9, 10))
    assert "ssm_chunk_scan" not in fn.lower(*args, "pallas").as_text()
    y, new = fn(*args, "pallas")
    y0, new0 = fn(*args, "reference")
    np.testing.assert_allclose(np.asarray(y[:3], np.float32),
                               np.asarray(y0[:3], np.float32), atol=2e-2)
    np.testing.assert_allclose(np.asarray(new[:, 1:]),
                               np.asarray(new0[:, 1:]), atol=1e-5)


def test_causal_conv_carries_its_tail_across_chunks():
    """The convolution over a prompt in one chunk, and in chunks of 1, 2,
    5 and 1 tokens through the slot's tail: the same activations."""
    Cw, T = 12, 9
    k = iter(jax.random.split(jax.random.PRNGKey(2), 4))
    u = jax.random.normal(next(k), (T, Cw))
    w, b = jax.random.normal(next(k), (Cw, K)), jax.random.normal(next(k), (Cw,))
    want = jax.nn.silu(reference.causal_conv(u, w, b))
    tails = jnp.full((1, 3, K - 1, Cw), 7.0)        # garbage: a fresh row
    out, at = [], 0
    for n in (1, 2, 5, 1):
        batch = {name: jnp.asarray(v, jnp.int32) for name, v in {
            "token_seq_ids": [0] * 8, "token_qpos": list(range(n)) + [0] * (8 - n),
            "state_slot": [2, 0], "query_len": [n, 0], "query_start": [0, 0],
            "seq_lens": [at + n, 0]}.items()}
        chunk = jnp.pad(u[at:at + n], ((0, 8 - n), (0, 0)))
        y, tails = ssm_ops.causal_conv(chunk, w, b, tails, batch, jnp.int32(0))
        out.append(y[:n])
        at += n
    np.testing.assert_allclose(jnp.concatenate(out), want, atol=1e-5)
    np.testing.assert_allclose(tails[0, 2], u[-3:], atol=0)
    assert float(tails[0, 1].min()) == 7.0          # another slot: untouched
