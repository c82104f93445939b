"""Chunked-prefill/decode fusion: one mixed-round program (round 15).

The round-15 tentpole: prefill-chunk rows, plain-decode rows and spec
K+1 verify rows ride ONE jitted program per engine step — the classic
mixed-round fallback (and its draft-allocation rollback) is deleted, so
speculative decode stays armed while prefills join and every layer's
expert weights stream from HBM once per step for BOTH populations.

The correctness contract this suite pins (fail-fast in ci-gate):

  - fused output is BYTE-IDENTICAL to the plain engine for pure-prefill,
    pure-decode and mixed rounds, greedy AND seeded, spec on or off;
  - spec decode keeps drafting/accepting across prefill joins (the old
    engine fell back to classic rounds and rolled drafts back);
  - a prefill-completing row leaves the step spec-ARMED (drafts primed
    from its last chunk's hidden state) — no cold first decode step;
  - rejected drafts leak no KV blocks (trim_request settles the
    speculative over-allocation; there is no rollback path anymore);
  - decode-priority budgeting: decodes fund before chunks, the
    per-chunk cap (LLMD_PREFILL_CHUNK / the step-latency model under
    LLMD_STEP_TIME_TARGET_MS) bounds chunks only, budget is conserved;
  - logprobs rows ride the fused program (they used to demote the whole
    batch to classic) with identical values.

All CPU, tier-1 safe.
"""

import pathlib

import pytest

from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.request import Request
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.predictor.model import StepTimeModel
from llm_d_tpu.utils import tracing

REPO = pathlib.Path(__file__).resolve().parent.parent

ENGINE_KW = dict(model="tiny", block_size=4, num_blocks=64, max_num_seqs=8,
                 max_num_batched_tokens=64, min_token_bucket=16,
                 min_seq_bucket=4)


def greedy_req(rid, prompt, n=12, **kw):
    return Request(request_id=rid, prompt_token_ids=list(prompt),
                   sampling=SamplingParams(temperature=0.0, max_tokens=n,
                                           ignore_eos=True), **kw)


def seeded_req(rid, prompt, n=12, seed=7, **kw):
    return Request(request_id=rid, prompt_token_ids=list(prompt),
                   sampling=SamplingParams(temperature=0.9, top_p=0.95,
                                           top_k=20, max_tokens=n,
                                           seed=seed, ignore_eos=True),
                   **kw)


def _free_blocks(engine):
    return engine.kv_manager.num_free_blocks


def _run_staggered(engine, first, rest, warm_steps=4):
    """Add ``first``, let it reach decode, then add ``rest`` one per
    step — every joiner's prefill chunks share rounds with decodes.
    Returns the per-pass scheduler stats observed along the way."""
    stats = []
    engine.add_request(first)
    for _ in range(warm_steps):
        engine.step()
        stats.append(dict(engine.scheduler.last_schedule_stats))
    pending = list(rest)
    while engine.has_work() or pending:
        if pending:
            engine.add_request(pending.pop(0))
        engine.step()
        stats.append(dict(engine.scheduler.last_schedule_stats))
    return stats


# ---------------------------------------------------------------------------
# parity: pure-prefill / pure-decode / mixed rounds, greedy + seeded
# ---------------------------------------------------------------------------

# Identical config seed 0 => identical params across all tiny engines in
# this file, so parity comparisons against plain_engine are exact.
@pytest.fixture(scope="module")
def plain_engine():
    return EngineCore(EngineConfig(**ENGINE_KW))


@pytest.fixture(scope="module")
def spec_engine():
    eng = EngineCore(EngineConfig(spec_k=4, **ENGINE_KW))
    assert eng.spec_k == 4
    return eng


@pytest.fixture(scope="module")
def fixed_engine():
    return EngineCore(EngineConfig(spec_k=4, spec_fixed_accept=0.8,
                                   **ENGINE_KW))


PROMPTS = {"a": [1, 5, 9, 200, 3, 17, 42], "b": [4, 4, 4, 8],
           "c": list(range(40, 55))}


def test_fused_parity_simultaneous_greedy(plain_engine, spec_engine):
    """Simultaneous adds: the fused program serves pure-prefill rounds,
    then pure-decode rounds — byte-identical to the plain engine."""
    want = plain_engine.generate(
        [greedy_req(r, p) for r, p in PROMPTS.items()])
    got = spec_engine.generate(
        [greedy_req(r, p) for r, p in PROMPTS.items()])
    assert got == want


def test_fused_parity_mixed_rounds_greedy(plain_engine, spec_engine):
    """Staggered adds force MIXED rounds (prefill chunks + spec-decode
    rows in one program); greedy output depends only on the prefix, so
    solo plain runs are the oracle for every request."""
    first = greedy_req("ma", PROMPTS["a"], n=14)
    rest = [greedy_req("mb", PROMPTS["b"], n=10),
            greedy_req("mc", PROMPTS["c"], n=10)]
    stats = _run_staggered(spec_engine, first, rest)
    assert any(s["prefill_tokens"] > 0 and s["decode_tokens"] > 0
               for s in stats), "no mixed round was ever scheduled"
    for req, n in ((first, 14), (rest[0], 10), (rest[1], 10)):
        rid = req.request_id
        want = plain_engine.generate(
            [greedy_req(f"{rid}w", req.prompt_token_ids, n)])[f"{rid}w"]
        assert list(req.output_token_ids) == want, rid


def test_fused_parity_mixed_rounds_seeded(plain_engine, spec_engine):
    """Seeded sampling in mixed rounds: fold_in(seed, gen_idx)
    continuity holds for decode rows AND for the first token a
    prefill-completing row samples inside the fused program."""
    first = seeded_req("sa", PROMPTS["a"], n=10, seed=7)
    rest = [seeded_req("sb", PROMPTS["b"], n=8, seed=99)]
    stats = _run_staggered(spec_engine, first, rest)
    assert any(s["prefill_tokens"] > 0 and s["decode_tokens"] > 0
               for s in stats)
    for req, n, seed in ((first, 10, 7), (rest[0], 8, 99)):
        rid = req.request_id
        want = plain_engine.generate(
            [seeded_req(f"{rid}w", req.prompt_token_ids, n,
                        seed=seed)])[f"{rid}w"]
        assert list(req.output_token_ids) == want, rid


# ---------------------------------------------------------------------------
# spec decode stays armed across prefill joins; leak freedom
# ---------------------------------------------------------------------------

def test_spec_stays_on_across_prefill_joins(fixed_engine):
    """Mixed rounds really carry draft tokens (the old engine's fallback
    zeroed them), and a joiner that finished its prefill mid-decode
    drafts and accepts too — its first decode step was primed by the
    fused prefill row, not cold."""
    first = greedy_req("j0", [1, 2, 3, 4, 5], n=20)
    rest = [greedy_req("j1", [9, 8, 7, 6, 5, 4, 3, 2, 1], n=16)]
    stats = _run_staggered(fixed_engine, first, rest)
    mixed_spec = [s for s in stats
                  if s["prefill_tokens"] > 0 and s["spec_tokens"] > 0]
    assert mixed_spec, "no mixed round scheduled draft tokens"
    assert len(first.output_token_ids) == 20
    assert len(rest[0].output_token_ids) == 16
    assert first.spec_accepted > 0
    assert rest[0].spec_drafted > 0 and rest[0].spec_accepted > 0


def test_rejected_drafts_leak_free_in_mixed_rounds(plain_engine):
    """spec_fixed_accept=0.0 rejects every draft in every mixed round:
    output stays correct and every block returns to the pool — the
    trim-after-verify settlement, with no rollback path left to lean
    on."""
    eng = EngineCore(EngineConfig(spec_k=4, spec_fixed_accept=0.0,
                                  **ENGINE_KW))
    free0 = _free_blocks(eng)
    first = greedy_req("z0", [1, 5, 9, 200, 3], n=12)
    rest = [greedy_req(f"z{i}", [i + 1, 7, 9, 2, 5], n=8)
            for i in range(1, 4)]
    _run_staggered(eng, first, rest)
    assert _free_blocks(eng) == free0
    assert eng.kv_manager._ref == {}
    want = plain_engine.generate(
        [greedy_req("z0w", [1, 5, 9, 200, 3], 12)])["z0w"]
    assert list(first.output_token_ids) == want


# ---------------------------------------------------------------------------
# chunk budgeting: fixed kill switch + adaptive step-latency model
# ---------------------------------------------------------------------------

def test_fixed_chunk_kill_switch_byte_identical(monkeypatch, plain_engine):
    """LLMD_PREFILL_CHUNK=8: every prefill chunk is capped at 8 tokens
    (observable in the scheduler stats) and output is byte-identical —
    chunking changes step composition, never content."""
    monkeypatch.setenv("LLMD_PREFILL_CHUNK", "8")
    eng = EngineCore(EngineConfig(spec_k=4, **ENGINE_KW))
    assert eng._prefill_chunk_fixed == 8
    req = greedy_req("k", list(range(100, 130)), n=6)
    eng.add_request(req)
    max_chunk = 0
    while eng.has_work():
        eng.step()
        s = eng.scheduler.last_schedule_stats
        if s["prefill_tokens"] > 0:
            assert s["chunk_cap"] == 8
            max_chunk = max(max_chunk, s["prefill_tokens"])
    assert max_chunk == 8                       # capped, and cap reached
    want = plain_engine.generate(
        [greedy_req("kw", list(range(100, 130)), 6)])["kw"]
    assert list(req.output_token_ids) == want


def test_invalid_chunk_env_falls_back_to_auto(monkeypatch):
    monkeypatch.setenv("LLMD_PREFILL_CHUNK", "banana")
    eng = EngineCore(EngineConfig(**ENGINE_KW))
    assert eng._prefill_chunk_fixed is None
    assert eng._prefill_chunk_cap(0) is None    # no target, no model


def test_step_time_model_learns_and_sizes_chunks():
    """The online ridge model recovers a linear step-latency law and
    chunk_for binary-searches the largest chunk under the target —
    monotone in the decode load already funded."""
    m = StepTimeModel(min_samples=16)
    assert not m.trained and m.predict(100, 100) == 0.0
    for p in range(0, 160, 10):
        for d in (0, 64, 128):
            m.observe(p, d, 2.0 + 0.01 * p + 0.05 * d)
    assert m.trained
    assert abs(m.predict(100, 64) - (2.0 + 1.0 + 3.2)) < 0.1
    # Budget 5 ms: after 128 decode tokens (8.4 ms baseline) no chunk
    # fits -> lo; after 0 decode tokens ~200 prefill tokens do.
    assert m.chunk_for(128, 5.0, lo=16, hi=512) == 16
    c = m.chunk_for(0, 5.0, lo=16, hi=512)
    assert 16 < c < 512
    assert m.predict(c, 0) <= 5.0 < m.predict(c + 8, 0)
    assert m.chunk_for(0, 5.0, lo=16, hi=512) >= \
        m.chunk_for(64, 5.0, lo=16, hi=512)
    # Untrained / no target / degenerate bounds -> hi (budget-bound).
    assert StepTimeModel().chunk_for(0, 5.0, 16, 512) == 512
    assert m.chunk_for(0, 0.0, 16, 512) == 512
    assert m.chunk_for(0, 5.0, 512, 512) == 512


def test_engine_adaptive_cap_engages_when_model_trains(monkeypatch):
    """LLMD_STEP_TIME_TARGET_MS: the engine's cap callable returns None
    until the step-latency model has samples, then sizes chunks between
    min_token_bucket and max_num_batched_tokens."""
    monkeypatch.setenv("LLMD_STEP_TIME_TARGET_MS", "5.0")
    monkeypatch.delenv("LLMD_PREFILL_CHUNK", raising=False)
    eng = EngineCore(EngineConfig(**ENGINE_KW))
    assert eng._step_time_target_ms == 5.0
    assert eng._prefill_chunk_cap(8) is None    # untrained: budget-bound
    for p in range(0, 160, 10):
        for d in (0, 8):
            eng.step_time_model.observe(p, d, 2.0 + 0.05 * p + 0.1 * d)
    cap = eng._prefill_chunk_cap(8)
    assert cap is not None
    assert eng.config.min_token_bucket <= cap \
        <= eng.config.max_num_batched_tokens
    # A fixed chunk wins over the model.
    monkeypatch.setenv("LLMD_PREFILL_CHUNK", "8")
    eng2 = EngineCore(EngineConfig(**ENGINE_KW))
    eng2.step_time_model = eng.step_time_model
    assert eng2._prefill_chunk_cap(8) == 8


# ---------------------------------------------------------------------------
# logprobs rows ride the fused program (no batch demotion)
# ---------------------------------------------------------------------------

def test_logprobs_rows_fused_with_identical_values(plain_engine):
    """A logprobs request decoding alongside plain spec rows: outputs
    AND logprob values match the plain engine, and the rounds that
    served it still scheduled draft tokens — the batch was not demoted
    to the classic path.  Real verification (no fixed_accept): since
    round 16 the logprobs row DRAFTS like any other, so a fixed-accept
    coin would rewrite its output (that mode emits accepted drafts
    verbatim) — real accept/reject keeps byte parity while the row
    rides the spec path end to end."""
    def lp_req(rid):
        return Request(request_id=rid, prompt_token_ids=[5, 6, 7],
                       sampling=SamplingParams(temperature=0.0,
                                               max_tokens=6,
                                               ignore_eos=True,
                                               logprobs=5))

    eng = EngineCore(EngineConfig(spec_k=4, **ENGINE_KW))
    plain = greedy_req("pl", [1, 5, 9, 200, 3], n=10)
    eng.add_request(plain)
    for _ in range(3):
        eng.step()
    req = lp_req("lp")
    eng.add_request(req)
    outs, saw_spec_round = [], False
    while eng.has_work():
        outs.extend(eng.step())
        s = eng.scheduler.last_schedule_stats
        saw_spec_round |= s["spec_tokens"] > 0
    assert saw_spec_round, "logprobs row demoted the batch off spec"
    assert plain.spec_drafted > 0
    lp_outs = [o for o in outs if o.request_id == "lp"]
    got_tokens = [t for o in lp_outs for t in o.new_token_ids]
    got_lps = [v for o in lp_outs for v in (o.logprobs or [])]
    got_tops = [t for o in lp_outs for t in (o.top_logprobs or [])]
    assert len(got_tokens) == len(got_lps) == len(got_tops) == 6

    want_outs = []
    wreq = lp_req("lpw")
    plain_engine.add_request(wreq)
    while plain_engine.has_work():
        want_outs.extend(plain_engine.step())
    want_outs = [o for o in want_outs if o.request_id == "lpw"]
    want_tokens = [t for o in want_outs for t in o.new_token_ids]
    want_lps = [v for o in want_outs for v in (o.logprobs or [])]
    want_tops = [t for o in want_outs for t in (o.top_logprobs or [])]
    assert got_tokens == want_tokens
    for g, w in zip(got_lps, want_lps):
        assert abs(g - w) < 1e-4
    for g, w in zip(got_tops, want_tops):
        assert set(g) == set(w)
        assert all(abs(g[t] - w[t]) < 1e-4 for t in g)


# ---------------------------------------------------------------------------
# observability: fused spans + step-composition counters
# ---------------------------------------------------------------------------

def test_fused_spans_and_composition_counters(fixed_engine):
    """engine.step spans under fusion carry fused=True and the step's
    prefill/decode token composition; the per-step composition counters
    export under the llmd_tpu:step_*_tokens_total names."""
    root = tracing.get_tracer("server").start_span(
        "server.request", request_id="req-mixed", criticality="standard")
    first = greedy_req("t0", [1, 2, 3, 4, 5], n=12)
    first.trace_ctx = root.ctx()
    rest = [greedy_req("t1", [5, 4, 3, 2, 1, 9, 9], n=8)]
    rest[0].trace_ctx = root.ctx()
    _run_staggered(fixed_engine, first, rest)
    root.end()
    steps = [s for s in tracing.get_tracer("engine").snapshot()
             if s["name"] == "engine.step"
             and s.get("attrs", {}).get("fused")]
    assert steps, "no fused engine.step spans recorded"
    kinds = {s["attrs"]["kind"] for s in steps}
    assert "mixed" in kinds, kinds
    for s in steps:
        assert "prefill_tokens" in s["attrs"]
        assert "decode_tokens" in s["attrs"]
        assert "accepted" in s["attrs"]
    m = fixed_engine.metrics.render().decode()
    assert 'llmd_tpu:step_prefill_tokens_total{model_name="tiny"}' in m
    assert 'llmd_tpu:step_decode_tokens_total{model_name="tiny"}' in m
