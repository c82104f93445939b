"""MTP speculative decoding: draft-and-verify with variable tokens/step.

The round-12 tentpole: an MTP-style drafter proposes K tokens, one fused
target-model forward verifies all K positions (spending decode's idle
MXU FLOPs on the weight stream it already pays for), on-device
accept/reject + bonus sampling emits 1..K+1 tokens per engine step, and
rejected drafts' KV blocks roll back to the pool the same step.

The correctness contract this suite pins (fail-fast in ci-gate):

  - spec output is BYTE-IDENTICAL to non-spec decode for greedy and
    seeded sampling (``fold_in(seed, gen_idx)`` continuity), whatever
    the drafter proposes — drafter quality moves throughput only;
  - rejection rollback leaves the paged-KV pool leak-free and the
    prefix cache consistent across block boundaries (PR 9's
    restore-or-recompute resume lands on a clean prefix);
  - adaptive K backs off to 1 when measured acceptance is low;
  - ``LLMD_SPEC_DECODE=off`` / ``LLMD_SPEC_K=0`` is today's engine;
  - chaos acceptance: a seeded mid-stream engine kill during spec
    decode resumes through PR 9's journaled failover with ZERO client
    breaks and exact multi-token journal offsets;
  - JIT meta-gate: the spec path adds no host sync beyond its one
    documented batched fetch.

All CPU, tier-1 safe.
"""

import asyncio
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.analysis.core import Context, run_passes
from llm_d_tpu.analysis.passes.jit_hygiene import JitHygienePass
from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.request import Request
from llm_d_tpu.models import get_config, get_model
from llm_d_tpu.ops import sampling as sampling_ops
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.predictor.model import SpecAcceptanceTracker
from llm_d_tpu.sim.simulator import SimConfig, build_sim_server
from llm_d_tpu.server.stream_resume import (
    parse_stream_payload,
    verify_continuity,
)
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


# ---------------------------------------------------------------------------
# units: on-device verifier, drafter, acceptance tracker
# ---------------------------------------------------------------------------

def test_spec_verify_greedy_prefix_acceptance():
    """Greedy verification: acceptance is the longest prefix where the
    drafts equal the target argmax, bounded by each row's live-draft
    count; emitted ids are the target's own samples at every position."""
    S, K, V = 2, 3, 8
    Q = K + 1
    logits = np.full((S * Q, V), -10.0, np.float32)
    # Row 0 target argmax sequence: 5, 2, 7, 1.
    for q, t in enumerate([5, 2, 7, 1]):
        logits[q, t] = 10.0
    # Row 1 target argmax sequence: 3, 3, 3, 3.
    for q in range(Q):
        logits[Q + q, 3] = 10.0
    ids, accepted = sampling_ops.spec_verify(
        jnp.asarray(logits),
        jnp.asarray([[5, 2, 0],           # matches 2 then diverges
                     [3, 3, 3]]),         # matches all 3
        jnp.asarray([3, 2]),              # row 1 only has 2 live drafts
        jnp.zeros(S), jnp.zeros(S, jnp.int32), jnp.ones(S),
        jax.random.PRNGKey(0), seeds=jnp.full(S, -1, jnp.int32),
        gen0=jnp.zeros(S, jnp.int32))
    assert list(np.asarray(accepted)) == [2, 2]
    assert list(np.asarray(ids)[0]) == [5, 2, 7, 1]
    assert list(np.asarray(ids)[1]) == [3, 3, 3, 3]


def test_spec_verify_seeded_rows_match_sample_contract():
    """Seeded rows draw exactly what ``sample`` draws at the same
    (seed, gen_idx) — the fold_in continuity that makes spec output
    byte-identical to single-step seeded decode."""
    S, K, V = 1, 2, 32
    Q = K + 1
    key = jax.random.PRNGKey(9)
    logits = jax.random.normal(key, (S * Q, V), jnp.float32) * 3
    seeds = jnp.asarray([123], jnp.int32)
    gen0 = jnp.asarray([5], jnp.int32)
    temp = jnp.asarray([0.8])
    ids, _ = sampling_ops.spec_verify(
        logits, jnp.zeros((S, K), jnp.int32), jnp.zeros(S, jnp.int32),
        temp, jnp.zeros(S, jnp.int32), jnp.ones(S), key,
        seeds=seeds, gen0=gen0)
    for q in range(Q):
        want = sampling_ops.sample(
            logits[q][None], temp, jnp.zeros(1, jnp.int32), jnp.ones(1),
            jax.random.PRNGKey(q + 77),       # step key must not matter
            seeds=seeds, gen_idx=gen0 + q)
        assert int(np.asarray(ids)[0, q]) == int(want[0])


def test_drafter_shapes_and_determinism():
    c = get_config("tiny")
    model = get_model(c)
    params = model.init_params(c, jax.random.PRNGKey(0))
    dparams = model.init_draft_params(c, jax.random.PRNGKey(1))
    hidden = jax.random.normal(jax.random.PRNGKey(2), (3, c.hidden_size),
                               c.jax_dtype)
    last = jnp.asarray([1, 2, 3], jnp.int32)
    d1 = model.draft_propose(params, dparams, hidden, last, 4, c)
    d2 = model.draft_propose(params, dparams, hidden, last, 4, c)
    assert d1.shape == (3, 4)
    assert (np.asarray(d1) == np.asarray(d2)).all()
    assert ((np.asarray(d1) >= 0) & (np.asarray(d1) < c.vocab_size)).all()


def test_moe_model_exposes_drafter():
    from llm_d_tpu.models import moe
    assert hasattr(moe, "init_draft_params")
    assert hasattr(moe, "draft_propose")


def test_acceptance_tracker_backoff_and_recovery():
    tr = SpecAcceptanceTracker(k_max=4, low=0.35, alpha=0.5)
    assert tr.suggest_k("r") == 4            # optimistic start
    for _ in range(6):
        tr.observe("r", 4, 0)                # nothing accepted
    assert tr.suggest_k("r") == 1            # backed off
    for _ in range(8):
        tr.observe("r", 1, 1)                # K=1 keeps measuring
    assert tr.suggest_k("r") == 4            # recovered
    tr.forget("r")
    assert tr.rate("r") is None


def test_acceptance_tracker_table_is_bounded():
    tr = SpecAcceptanceTracker(k_max=4, cap=8)
    for i in range(50):
        tr.observe(f"r{i}", 4, 2)
    assert len(tr._rate) <= 8


# ---------------------------------------------------------------------------
# engine: byte-identical parity, rollback, prefix-cache integrity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def plain_engine():
    return EngineCore(EngineConfig(**ENGINE_KW))


# Shared spec engines (module scope): every EngineCore compiles its own
# program set, so tests reuse two instances — one with the REAL verifier
# (byte-parity tests) and one with the seeded fixed-accept coin
# (multi-token-step mechanics).  Identical config seed 0 => identical
# params across all tiny engines in this file, so parity comparisons
# against plain_engine are exact.
@pytest.fixture(scope="module")
def spec_engine():
    eng = EngineCore(EngineConfig(spec_k=4, **ENGINE_KW))
    assert eng.spec_k == 4
    return eng


@pytest.fixture(scope="module")
def fixed_engine():
    return EngineCore(EngineConfig(spec_k=4, spec_fixed_accept=0.8,
                                   **ENGINE_KW))


def test_spec_greedy_byte_identical_parity(plain_engine, spec_engine):
    """Across block boundaries (block_size 4, 12 output tokens) the spec
    engine's greedy output matches the plain engine token for token —
    the drafter is random-init (near-zero acceptance) and it still
    cannot perturb output, only throughput."""
    prompts = {"a": [1, 5, 9, 200, 3, 17, 42], "b": [4, 4, 4, 8],
               "c": list(range(40, 55))}
    want = plain_engine.generate(
        [greedy_req(r, p) for r, p in prompts.items()])
    got = spec_engine.generate(
        [greedy_req(r, p) for r, p in prompts.items()])
    assert got == want


def test_spec_seeded_byte_identical_parity(plain_engine, spec_engine):
    reqs = lambda: [seeded_req("sa", [1, 5, 9, 200, 3], seed=7),  # noqa: E731
                    seeded_req("sb", [4, 4, 4, 8], seed=99)]
    want = plain_engine.generate(reqs())
    got = spec_engine.generate(reqs())
    assert got == want


def test_spec_fixed_accept_emits_multi_token_steps(fixed_engine):
    """The bench harness's seeded-acceptance mode: accepted runs really
    are multi-token (the variable tokens-per-step machinery engages) and
    the per-request draft/accept bookkeeping records them."""
    reqs = [greedy_req(f"fr{i}", [3 * i + 1, 2, 9], n=24)
            for i in range(3)]
    out = fixed_engine.generate(reqs)
    assert all(len(v) == 24 for v in out.values())
    drafted = sum(r.spec_drafted for r in reqs)
    accepted = sum(r.spec_accepted for r in reqs)
    assert drafted > 0 and accepted > 0
    m = fixed_engine.metrics.render().decode()
    assert 'llmd_tpu:spec_draft_tokens_total{model_name="tiny"}' in m
    assert 'llmd_tpu:spec_accepted_tokens_total{model_name="tiny"}' in m


def test_spec_rollback_leaves_pool_leak_free(fixed_engine):
    """After every request finishes, every block is back in the pool and
    no refcounts linger — the rejection rollback (kv_cache.trim_request)
    settled each step's speculative over-allocation."""
    free0 = _free_blocks(fixed_engine)
    reqs = [greedy_req(f"lk{i}", [i + 1, 7, 9, 2, 5], n=13)
            for i in range(5)]
    fixed_engine.generate(reqs)
    assert _free_blocks(fixed_engine) == free0
    assert fixed_engine.kv_manager._ref == {}
    assert all(r.block_ids == [] for r in reqs)


def test_spec_midstream_pool_never_holds_rejected_tail(fixed_engine):
    """DURING decode the pool never holds more than the accepted content
    plus the pending token's slot per request — stepping manually and
    checking after each step that block counts never exceed
    ceil(num_tokens / block_size), i.e. the up-to-K+1-token speculative
    allocation's rejected tail went back the same step."""
    req = greedy_req("mid", [1, 2, 3], n=20)
    fixed_engine.add_request(req)
    bs = fixed_engine.config.block_size
    while fixed_engine.has_work():
        fixed_engine.step()
        if req.state.value == "running":
            assert len(req.block_ids) <= -(-req.num_tokens // bs)
            assert len(req.block_ids) >= \
                -(-req.num_computed_tokens // bs)


def _generate_with_oracle_drafts(spec, req, want, K=4):
    """Drive a spec engine feeding the KNOWN-correct future tokens as
    drafts (the greedy oracle sequence), so the REAL verifier accepts at
    full depth — multi-token accepted runs with byte-identical output,
    no fixed-accept shortcut."""
    spec.add_request(req)
    while spec.has_work():
        j = len(req.output_token_ids)
        if (req.state.value == "running"
                and req.num_computed_tokens == req.num_tokens - 1
                and j < len(want)):
            req.spec_drafts = list(want[j:j + K])
            req.spec_drafts_at = req.num_tokens
        spec.step()
    return list(req.output_token_ids)


def test_spec_oracle_drafts_full_acceptance_parity(plain_engine,
                                                   spec_engine):
    """With a perfect drafter the REAL verifier accepts whole runs
    (multi-token steps, no fixed-accept shortcut) and output stays
    byte-identical — acceptance moved throughput, not content."""
    prompt = [2, 5, 9, 201, 3, 17, 42]
    want = plain_engine.generate([greedy_req("ow", prompt, 12)])["ow"]
    req = greedy_req("o", prompt, 12)
    got = _generate_with_oracle_drafts(spec_engine, req, want)
    assert got == want
    assert req.spec_accepted > 0, "oracle drafts were not accepted"
    assert req.spec_accepted == req.spec_drafted   # all of them, in fact


def test_spec_prefix_cache_consistent_across_block_boundaries(
        plain_engine, spec_engine):
    """The prefix cache after a spec run indexes ONLY accepted content:
    a second request sharing the first's full (prompt + generated)
    prefix — the PR 9 resume admission shape — restores through the
    generated region and continues byte-identically.  The first run
    uses oracle drafts so accepted multi-token runs really crossed
    block boundaries (block_size 4 vs up-to-5-token steps)."""
    prompt = [9, 8, 7, 6, 5, 4, 3, 2, 1, 9, 8, 7]
    want = plain_engine.generate([greedy_req("pw", prompt, 12)])["pw"]
    r1 = greedy_req("first", prompt, 12)
    out = _generate_with_oracle_drafts(spec_engine, r1, want)
    assert out == want
    assert r1.spec_accepted > 0
    # Fresh same-prompt request: hits the cached prompt blocks.
    r2 = greedy_req("second", prompt, 12)
    out2 = spec_engine.generate([r2])["second"]
    assert out2 == out
    assert r2.num_cached_prompt_tokens >= 8
    # Resume shape: output pre-populated from a journal, restore-first
    # through the GENERATED region the spec run cached.
    r3 = greedy_req("resume", prompt, 12)
    r3.output_token_ids = list(out[:6])
    r3.resume_offset = 6
    got = spec_engine.generate([r3])["resume"]
    assert got[6:] == out[6:]
    assert r3.resume_restored_tokens >= 0   # restored or recomputed: clean


def test_spec_adaptive_k_backs_off_on_rejection():
    """spec_fixed_accept=0.0 rejects every draft: after a few steps the
    tracker pins the request at K=1 and the scheduler stops paying for
    depth-4 verification."""
    spec = EngineCore(EngineConfig(spec_k=4, spec_fixed_accept=0.0,
                                   **ENGINE_KW))
    req = greedy_req("r", [1, 2, 3], n=16)
    out = spec.generate([req])["r"]
    assert len(out) == 16                   # still correct, one tok/step
    # The tracker state is dropped at finish (leak-free); back off is
    # observable mid-run via the lookahead the last steps actually used.
    assert req.spec_drafted < 4 * 15        # not every step paid depth 4


def test_spec_mixed_round_runs_fused_with_correct_output(plain_engine,
                                                         spec_engine):
    """A prefill admitted mid-decode rides the SAME fused program as the
    decode/verify rows (round 15: no classic fallback, no draft
    rollback) — both requests finish with byte-correct output and the
    pool is leak-free afterwards."""
    free0 = _free_blocks(spec_engine)
    a = greedy_req("ma", [1, 5, 9, 200, 3], n=14)
    b = greedy_req("mb", [4, 4, 4, 8], n=10)
    spec_engine.add_request(a)
    for _ in range(4):                      # let a reach spec decode
        spec_engine.step()
    spec_engine.add_request(b)              # forces mixed rounds
    while spec_engine.has_work():
        spec_engine.step()
    assert _free_blocks(spec_engine) == free0
    # Parity vs a plain engine run with the same staggering-free inputs:
    # greedy output depends only on the prefix, so solo runs are the
    # oracle for both.
    want_a = plain_engine.generate(
        [greedy_req("ma2", [1, 5, 9, 200, 3], 14)])["ma2"]
    want_b = plain_engine.generate(
        [greedy_req("mb2", [4, 4, 4, 8], 10)])["mb2"]
    assert a.output_token_ids == want_a
    assert b.output_token_ids == want_b


def test_spec_respects_max_tokens_and_model_len():
    """max_tokens not a multiple of the emitted chunk sizes: the engine
    never over-emits, and the lookahead never drafts past the request's
    own budget."""
    spec = EngineCore(EngineConfig(spec_k=4, spec_fixed_accept=1.0,
                                   **ENGINE_KW))
    for n in (1, 2, 5, 7):
        out = spec.generate([greedy_req(f"n{n}", [1, 2, 3], n)])
        assert len(out[f"n{n}"]) == n


# ---------------------------------------------------------------------------
# knobs: env resolution, kill switch, flag
# ---------------------------------------------------------------------------

def test_env_off_is_todays_engine(monkeypatch, plain_engine):
    monkeypatch.setenv("LLMD_SPEC_DECODE", "off")
    eng = EngineCore(EngineConfig(spec_k=4, **ENGINE_KW))
    assert eng.spec_k == 0 and eng._spec_fn is None
    assert eng.scheduler.spec_lookahead is None
    out = eng.generate([greedy_req("a", [1, 5, 9, 200, 3])])
    want = plain_engine.generate([greedy_req("a", [1, 5, 9, 200, 3])])
    assert out == want


def test_env_k_resolution_and_invalid_fallback(monkeypatch):
    monkeypatch.setenv("LLMD_SPEC_K", "3")
    eng = EngineCore(EngineConfig(**ENGINE_KW))
    assert eng.spec_k == 3
    monkeypatch.setenv("LLMD_SPEC_K", "banana")    # env_int fallback -> 0
    eng = EngineCore(EngineConfig(**ENGINE_KW))
    assert eng.spec_k == 0


def test_default_engine_has_spec_off():
    eng = EngineCore(EngineConfig(**ENGINE_KW))
    assert eng.spec_k == 0 and eng._spec_fn is None


def test_spec_stays_on_under_multistep_and_async():
    # Round 16: the composition gate is gone — spec decode IS the body
    # of the fused-multistep pipeline, so requesting both keeps both.
    eng = EngineCore(EngineConfig(spec_k=4, num_scheduler_steps=4,
                                  async_scheduling=True, **ENGINE_KW))
    assert eng.spec_k == 4 and eng._spec_fn is not None


def test_server_flag_threads_spec_k():
    from llm_d_tpu.server.openai import (
        build_arg_parser, engine_config_from_args)
    p = build_arg_parser()
    cfg = engine_config_from_args(p.parse_args(["--spec-k", "4"]))
    assert cfg.spec_k == 4
    cfg = engine_config_from_args(p.parse_args([]))
    assert cfg.spec_k is None               # defer to LLMD_SPEC_K


# ---------------------------------------------------------------------------
# observability: step spans carry drafted/accepted
# ---------------------------------------------------------------------------

def test_engine_step_spans_carry_spec_attrs(fixed_engine):
    root = tracing.get_tracer("server").start_span(
        "server.request", request_id="req-spec", criticality="standard")
    req = greedy_req("traced", [1, 2, 3, 4, 5], n=12)
    req.trace_ctx = root.ctx()
    fixed_engine.generate([req])
    root.end()
    steps = [s for s in tracing.get_tracer("engine").snapshot()
             if s["name"] == "engine.step"
             and s.get("attrs", {}).get("spec")]
    assert steps, "no spec engine.step spans recorded"
    assert any(s["attrs"].get("drafted", 0) > 0 for s in steps)
    assert all("accepted" in s["attrs"] for s in steps)


def test_jit_meta_gate_spec_adds_no_host_sync():
    """The spec path's only sync is its one documented batched fetch
    (ids + accepted counts + next drafts): the JIT hygiene pass stays
    green and the suppressed deliberate sync points now number three."""
    ctx = Context(REPO)
    findings, suppressed, _ = run_passes(ctx, [JitHygienePass()])
    assert findings == [], "\n".join(f.render() for f in findings)
    assert suppressed >= 3


# ---------------------------------------------------------------------------
# sim mirror + chaos acceptance: PR 9 resume during spec decode
# ---------------------------------------------------------------------------

def _sim_text(sim, prompt, max_tokens):
    from llm_d_tpu.sim.simulator import _LOREM
    pids = sim._tokenize(prompt)
    return "".join(_LOREM[(len(pids) + i) % len(_LOREM)] + " "
                   for i in range(max_tokens))


def test_sim_spec_mirror_multi_token_chunks():
    """The sim's seeded acceptance model emits multi-token SSE frames
    with exact offsets — same text as a non-spec sim, clean continuity,
    spec metrics exported."""
    from test_stream_recovery import _cleanup, _start_app, free_port
    import aiohttp

    async def run():
        port = free_port()
        srv = build_sim_server(SimConfig(ttft_ms=1.0, tpot_ms=1.0,
                                         spec_k=4, spec_acceptance=0.8))
        runner = await _start_app(srv.build_app(), port)
        try:
            async with aiohttp.ClientSession() as sess:
                for _ in range(100):
                    async with sess.get(
                            f"http://127.0.0.1:{port}/v1/models") as r:
                        if r.status == 200:
                            break
                    await asyncio.sleep(0.02)
                async with sess.post(
                        f"http://127.0.0.1:{port}/v1/completions",
                        json={"prompt": "spec sim smoke", "max_tokens": 10,
                              "stream": True}) as r:
                    assert r.status == 200
                    payload = await r.read()
                async with sess.get(
                        f"http://127.0.0.1:{port}/metrics") as r:
                    mtext = await r.text()
        finally:
            await _cleanup([runner])
        text, metas, done = parse_stream_payload(payload)
        assert done
        assert verify_continuity(metas, expect_total=10) == []
        assert max(len(m["tok"]) for m in metas) > 1
        assert text == _sim_text(srv.sim, "spec sim smoke", 10)
        assert "llmd_tpu:spec_draft_tokens_total" in mtext

    asyncio.run(asyncio.wait_for(run(), timeout=60))


def test_chaos_spec_decode_resume_zero_stream_breaks(inject=None):
    """THE chaos acceptance bar for round 12: a 4-replica SPEC-mode sim
    fleet behind the gateway under streaming load; a seeded mid-stream
    ``engine.step`` kill.  Multi-token chunks make journal offsets
    coarser — the resume must still splice at EXACT offsets: zero
    client-visible breaks, zero duplicate/missing token indices,
    byte-identical text, recovery recorded."""
    import aiohttp
    from test_stream_recovery import (
        _cleanup, _metric_value, _start_app, free_port)
    from llm_d_tpu.epp.datastore import EndpointState
    from llm_d_tpu.epp.service import build_gateway
    from llm_d_tpu.utils.faultinject import FaultInjector, install, reset

    inj = install(FaultInjector.from_spec("", seed=0))
    inj.add_rule("engine.step", after=25, count=1)

    async def run():
        ports = [free_port() for _ in range(4)]
        runners, sims = [], []
        for i, port in enumerate(ports):
            srv = build_sim_server(SimConfig(
                model=f"sim-{i}", ttft_ms=1.0, tpot_ms=2.0,
                spec_k=4, spec_acceptance=0.8))
            sims.append(srv.sim)
            runners.append(await _start_app(srv.build_app(), port))
        endpoints = [EndpointState(address=f"127.0.0.1:{p}")
                     for p in ports]
        gw = build_gateway(endpoints, scrape_interval_s=0.05,
                           retry_attempts=3)
        gw_port = free_port()
        gw_runner = await _start_app(gw.build_app(), gw_port)
        url = f"http://127.0.0.1:{gw_port}/v1/completions"
        for _ in range(200):
            if all(e.ready for e in gw.datastore.candidates()):
                break
            await asyncio.sleep(0.02)

        max_tokens = 8
        results = []
        stop = asyncio.Event()

        async def load_worker(sess, wid):
            i = 0
            while not stop.is_set():
                i += 1
                prompt = f"spec chaos {wid} {i} tail"
                try:
                    async with sess.post(url, json={
                            "prompt": prompt, "max_tokens": max_tokens,
                            "stream": True}) as r:
                        payload = await r.read()
                        text, metas, done = parse_stream_payload(payload)
                        results.append(
                            (prompt, r.status, text, metas, done))
                except aiohttp.ClientError as e:
                    results.append((prompt, f"error:{type(e).__name__}",
                                    "", [], False))
                await asyncio.sleep(0.005)

        try:
            async with aiohttp.ClientSession(
                    timeout=aiohttp.ClientTimeout(total=30)) as sess:
                workers = [asyncio.create_task(load_worker(sess, w))
                           for w in range(3)]
                for _ in range(600):
                    await asyncio.sleep(0.02)
                    if inj.stats().get("engine.step", {}).get(
                            "fired", 0) >= 1 and len(results) > 25:
                        break
                await asyncio.sleep(0.3)
                stop.set()
                await asyncio.gather(*workers, return_exceptions=True)
        finally:
            mtext = gw.scheduler.metrics.render().decode()
            await _cleanup(runners + [gw_runner])

        assert inj.stats()["engine.step"]["fired"] >= 1
        assert any(s.dead for s in sims), "no sim died"
        bad = [(p, s) for p, s, *_ in results if s != 200]
        assert not bad, f"client-visible failures: {bad[:5]}"
        breaks = [p for p, _s, _t, _m, done in results if not done]
        assert not breaks, f"{len(breaks)} stream break(s): {breaks[:3]}"
        saw_multi = False
        for prompt, _s, text, metas, _d in results:
            assert verify_continuity(metas, expect_total=max_tokens) \
                == [], prompt
            assert text == _sim_text(sims[0], prompt, max_tokens), \
                f"token sequence diverged for {prompt!r}"
            saw_multi |= any(len(m.get("tok") or []) > 1 for m in metas)
        assert saw_multi, "no multi-token spec chunk observed under load"
        assert _metric_value(
            mtext, "llmd_tpu:stream_resume_total") >= 1.0
        assert _metric_value(
            mtext, 'llmd_tpu:stream_resume_total{outcome="failed"}') \
            == 0.0

    try:
        asyncio.run(asyncio.wait_for(run(), timeout=120))
    finally:
        reset()
