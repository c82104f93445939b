"""Mid-stream request recovery: journaled decode failover.

The ungraceful-failure half of the resilience story (PR 4's drain covers
graceful events): a decode replica dying MID-STREAM must be invisible to
a streaming client.  The relays (EPP gateway, DP leader) journal emitted
token ids, detect death (upstream break / token-gap watchdog), resume on
a surviving replica through the breaker-aware scheduler path, and the
resume replica admits prompt+generated as a prefill satisfied
restore-first from the prefix cache / host KV tier with recompute
fallback.  Dedupe is by token offset — no duplicated or missing token
indices ever reach the client.

Acceptance (wired fail-fast into ci-gate): 8-replica sim stack under
sustained streaming load with a seeded mid-run decode kill
(``engine.step`` fault) → ZERO client-visible stream breaks, every
affected stream byte-identical to an unfaulted run, recovery visible in
``llmd_tpu:stream_resume_total``; with ``LLMD_STREAM_RESUME=0`` behavior
is exactly today's fail-fast contract.  All CPU, tier-1 safe.
"""

import asyncio
import json
import socket

import pytest

from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.request import Request
from llm_d_tpu.epp.datastore import EndpointBreaker, EndpointState
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.server.stream_resume import (
    OUTCOME_RECOMPUTED,
    OUTCOME_RESTORED,
    StreamJournal,
    parse_stream_payload,
    resume_policy,
    verify_continuity,
)
from llm_d_tpu.sim.simulator import _LOREM, SimConfig, build_sim_server
from llm_d_tpu.utils.faultinject import (
    FAULT_POINTS,
    FaultInjector,
    install,
    reset,
)

ENGINE_KW = dict(model="tiny", block_size=4, num_blocks=64, max_num_seqs=8,
                 max_num_batched_tokens=64, min_token_bucket=16,
                 min_seq_bucket=4)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def greedy_req(rid, prompt, n=8, **kw):
    return Request(request_id=rid, prompt_token_ids=list(prompt),
                   sampling=SamplingParams(temperature=0.0, max_tokens=n,
                                           ignore_eos=True), **kw)


def resume_req(rid, prompt, emitted, n=8, **kw):
    """A relay-journal resume admission: output pre-populated, offset set."""
    req = greedy_req(rid, prompt, n, **kw)
    req.output_token_ids = list(emitted)
    req.resume_offset = len(emitted)
    return req


@pytest.fixture()
def inject():
    def make(spec: str = "", seed: int = 0) -> FaultInjector:
        return install(FaultInjector.from_spec(spec, seed=seed))
    yield make
    reset()


async def _start_app(app, port):
    from aiohttp import web
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", port)
    await site.start()
    return runner


def expected_sim_text(sim, prompt: str, max_tokens: int) -> str:
    """The deterministic word sequence any healthy sim replica produces
    for this prompt — the continuity oracle's ground truth."""
    pids = sim._tokenize(prompt)
    return "".join(_LOREM[(len(pids) + i) % len(_LOREM)] + " "
                   for i in range(max_tokens))


# ---------------------------------------------------------------------------
# units: fault points, journal, continuity oracle, policy knobs
# ---------------------------------------------------------------------------

def test_new_fault_points_registered():
    assert "stream.relay" in FAULT_POINTS
    assert "kv.restore" in FAULT_POINTS
    inj = FaultInjector.from_spec(
        "stream.relay:p=1,count=2;kv.restore:p=0.5", seed=1)
    assert "stream.relay" in inj._rules and "kv.restore" in inj._rules


def test_verify_continuity_oracle():
    good = [{"off": 0, "tok": [1, 2]}, {"off": 2, "tok": [3]},
            {"off": 3, "tok": [4]}]
    assert verify_continuity(good) == []
    assert verify_continuity(good, expect_total=4) == []
    dup = [{"off": 0, "tok": [1, 2]}, {"off": 1, "tok": [2, 3]}]
    assert any("duplicate" in p for p in verify_continuity(dup))
    gap = [{"off": 0, "tok": [1]}, {"off": 2, "tok": [3]}]
    assert any("missing" in p for p in verify_continuity(gap))
    short = [{"off": 0, "tok": [1]}]
    assert any("expected 3" in p
               for p in verify_continuity(short, expect_total=3))


def _frame(chunk) -> bytes:
    return b"data: " + json.dumps(chunk).encode() + b"\n\n"


def test_journal_dedupe_and_resume_handshake():
    body = {"prompt": "hi", "stream": True, "max_tokens": 4}
    j = StreamJournal(body, criticality="standard")
    assert j.resumable and j.offset == 0
    # Two delivered tokens journal; stream id captured.
    assert j.admit_frame(_frame({
        "id": "cmpl-1", "choices": [{"text": "a "}],
        "llmd": {"off": 0, "tok": [11]}}))
    assert j.admit_frame(_frame({
        "id": "cmpl-1", "choices": [{"text": "b "}],
        "llmd": {"off": 1, "tok": [12]}}))
    assert j.offset == 2 and j.token_ids == [11, 12]
    assert j.stream_id == "cmpl-1"
    rb = j.resume_body()
    assert rb["resume"] == {"offset": 2, "token_ids": [11, 12]}
    assert rb["request_id"] == "cmpl-1"
    hdrs = j.resume_headers()
    assert hdrs["x-llmd-resume-offset"] == "2"
    # A resumed upstream replaying token 1 is DROPPED; new tokens pass.
    assert not j.admit_frame(_frame({
        "id": "cmpl-1", "choices": [{"text": "b "}],
        "llmd": {"off": 1, "tok": [12]}}))
    assert j.admit_frame(_frame({
        "id": "cmpl-1", "choices": [{"text": "c "}],
        "llmd": {"off": 2, "tok": [13], "src": "restored",
                 "restored": 2}}))
    assert j.offset == 3 and j.last_src == "restored"
    # Usage frames (no tokens) relay without disqualifying the journal.
    assert j.admit_frame(_frame({"id": "cmpl-1", "choices": [],
                                 "usage": {"completion_tokens": 3}}))
    assert j.resumable
    # A token-carrying frame WITHOUT meta (foreign server) disqualifies.
    assert j.admit_frame(_frame({"id": "x", "choices": [{"text": "q"}]}))
    assert not j.resumable
    # [DONE] latches completion.
    assert j.admit_frame(b"data: [DONE]\n\n")
    assert j.done


def test_journal_seeds_from_inherited_resume_body():
    """Chained resume: a relay journaling a body that ALREADY carries
    resume state (an upstream relay resuming through it) must seed its
    journal — a second break re-resumes with the FULL token history."""
    body = {"prompt": "hi", "stream": True,
            "resume": {"offset": 3, "token_ids": [7, 8, 9]}}
    j = StreamJournal(body)
    assert j.offset == 3 and j.token_ids == [7, 8, 9]
    # The resumed worker's frames start at off=3 and align.
    assert j.admit_frame(_frame({"id": "c", "choices": [{"text": "d "}],
                                 "llmd": {"off": 3, "tok": [10]}}))
    assert j.resume_body()["resume"] == {"offset": 4,
                                         "token_ids": [7, 8, 9, 10]}
    # Garbage resume state degrades to an empty journal, not a crash.
    assert StreamJournal({"resume": {"token_ids": ["x", None]}}).offset == 0


def test_journal_tracks_delivered_finish_reason():
    """A break between the finish chunk and [DONE] must NOT resume: the
    journal records the delivered finish_reason so the relay closes the
    stream itself instead of decoding past a delivered EOS/stop."""
    j = StreamJournal({"stream": True})
    j.admit_frame(_frame({"choices": [{"text": "a", "finish_reason": None}],
                          "llmd": {"off": 0, "tok": [1]}}))
    assert j.finish_reason is None
    j.admit_frame(_frame({"choices": [{"text": "", "finish_reason": "stop"}],
                          "llmd": {"off": 1, "tok": [2]}}))
    assert j.finish_reason == "stop" and not j.done


def test_journal_recovery_accounting():
    j = StreamJournal({"stream": True})
    j.admit_frame(_frame({"choices": [{"text": "a"}],
                          "llmd": {"off": 0, "tok": [1]}}))
    j.mark_break()
    assert j.take_recoveries() == []          # nothing resumed yet
    j.admit_frame(_frame({"choices": [{"text": "b"}],
                          "llmd": {"off": 1, "tok": [2],
                                   "src": "recomputed", "restored": 0}}))
    recs = j.take_recoveries()
    assert len(recs) == 1
    outcome, secs = recs[0]
    assert outcome == OUTCOME_RECOMPUTED and secs >= 0.0
    assert j.take_recoveries() == []          # drained


def test_parse_stream_payload():
    payload = (_frame({"choices": [{"text": "a "}],
                       "llmd": {"off": 0, "tok": [5]}})
               + _frame({"choices": [{"delta": {"content": "b "}}],
                         "llmd": {"off": 1, "tok": [6]}})
               + b"data: [DONE]\n\n")
    text, metas, done = parse_stream_payload(payload)
    assert text == "a b " and done
    assert [m["off"] for m in metas] == [0, 1]
    _text, _metas, done2 = parse_stream_payload(payload[:-16])
    assert not done2


def test_resume_policy_env_knobs(monkeypatch):
    p = resume_policy()
    assert p.enabled and p.max_attempts == 2 and p.stall_timeout_s == 0.0
    monkeypatch.setenv("LLMD_STREAM_RESUME", "0")
    monkeypatch.setenv("LLMD_RESUME_MAX_ATTEMPTS", "5")
    monkeypatch.setenv("LLMD_STREAM_STALL_TIMEOUT_S", "1.5")
    p = resume_policy()
    assert not p.enabled and p.max_attempts == 5 \
        and p.stall_timeout_s == 1.5
    # Invalid-value fallback doctrine.
    monkeypatch.setenv("LLMD_STREAM_RESUME", "banana")
    monkeypatch.setenv("LLMD_RESUME_MAX_ATTEMPTS", "x")
    p = resume_policy()
    assert p.enabled and p.max_attempts == 2


# ---------------------------------------------------------------------------
# engine: resume admission — restore-first / recompute-fallback parity
# ---------------------------------------------------------------------------

PROMPT = [3, 1, 4, 1, 5, 9]          # 6 tokens; block_size 4


def test_engine_resume_recompute_parity():
    """Tier miss: the resumed prefill recomputes prompt+generated and the
    continuation is token-identical to the uninterrupted run."""
    a = EngineCore(EngineConfig(**ENGINE_KW))
    want = a.generate([greedy_req("base", PROMPT, 8)])["base"]
    b = EngineCore(EngineConfig(**ENGINE_KW), params=a.params)
    dreq = resume_req("res", PROMPT, want[:4], 8)
    got = b.generate([dreq])["res"]
    assert got == want
    assert dreq.resume_offset == 4
    assert dreq.resume_restored_tokens == 0          # nothing cached on B


def test_engine_resume_restored_from_shared_tier():
    """Restore-first: the dead replica's host tier (shared-tier peer)
    hands the generated-region blocks back — the resume replica restores
    instead of recomputing, and the continuation still matches."""
    kw = dict(ENGINE_KW, num_blocks=32, kv_offload_blocks=64)
    a = EngineCore(EngineConfig(**dict(kw, kv_shared_tier_port=0)))
    try:
        want = a.generate([greedy_req("base", PROMPT, 8)])["base"]
        assert a.host_tier.saves > 0          # blocks staged to the tier
        b = EngineCore(EngineConfig(**dict(
            kw, kv_shared_tier_peers=(
                f"127.0.0.1:{a.host_tier.port}",))), params=a.params)
        try:
            dreq = resume_req("res", PROMPT, want[:4], 8)
            got = b.generate([dreq])["res"]
            assert got == want
            # prompt (6) + emitted (4) = 10 tokens -> 2 full blocks (8
            # tokens) restorable: past the prompt into the generated
            # region.
            assert dreq.resume_restored_tokens > 0
            assert b.host_tier.remote_hits > 0
        finally:
            b.host_tier.close()
    finally:
        a.host_tier.close()


def test_engine_resume_kv_restore_fault_degrades_to_recompute(inject):
    """kv.restore fault = tier restore failure during resume: the
    admission falls back to recompute at full parity."""
    kw = dict(ENGINE_KW, num_blocks=32, kv_offload_blocks=64)
    a = EngineCore(EngineConfig(**dict(kw, kv_shared_tier_port=0)))
    inj = inject()
    inj.add_rule("kv.restore")               # p=1: every restore fails
    try:
        want = a.generate([greedy_req("base", PROMPT, 8)])["base"]
        b = EngineCore(EngineConfig(**dict(
            kw, kv_shared_tier_peers=(
                f"127.0.0.1:{a.host_tier.port}",))), params=a.params)
        try:
            dreq = resume_req("res", PROMPT, want[:4], 8)
            got = b.generate([dreq])["res"]
            assert got == want                        # recompute parity
            assert dreq.resume_restored_tokens == 0   # tier "missed"
            assert b.host_tier.remote_hits == 0
            assert inj.stats()["kv.restore"]["fired"] >= 1
        finally:
            b.host_tier.close()
    finally:
        a.host_tier.close()


@pytest.mark.parametrize("model", ["tiny", "tiny-mla"])
def test_engine_resume_parity_by_family(model):
    """Resume is cache-layout-clean: dense K/V and the single MLA latent
    buffer both resume to parity with their own baseline, over both the
    restore and recompute admission paths."""
    kw = dict(ENGINE_KW, model=model, num_blocks=32, kv_offload_blocks=64)
    a = EngineCore(EngineConfig(**dict(kw, kv_shared_tier_port=0)))
    try:
        want = a.generate([greedy_req("base", PROMPT, 8)])["base"]
        # Restore path (the family's slab over the shared tier's wire).
        b = EngineCore(EngineConfig(**dict(
            kw, kv_shared_tier_peers=(
                f"127.0.0.1:{a.host_tier.port}",))), params=a.params)
        try:
            dreq = resume_req("res", PROMPT, want[:4], 8)
            assert b.generate([dreq])["res"] == want
            assert dreq.resume_restored_tokens > 0
        finally:
            b.host_tier.close()
        # Recompute path (no tier).
        c = EngineCore(EngineConfig(**dict(ENGINE_KW, model=model)),
                       params=a.params)
        creq = resume_req("res2", PROMPT, want[:4], 8)
        assert c.generate([creq])["res2"] == want
        assert creq.resume_restored_tokens == 0
    finally:
        a.host_tier.close()


def test_engine_resume_seeded_sampling_continuity():
    """The journaled RNG contract: seeded sampling folds (seed, position)
    so a resumed request draws the SAME continuation tokens the original
    would have — stochastic streams recover byte-identically too."""
    sp = SamplingParams(temperature=1.0, top_k=0, max_tokens=8,
                        ignore_eos=True, seed=1234)
    a = EngineCore(EngineConfig(**ENGINE_KW))
    base = Request(request_id="base", prompt_token_ids=list(PROMPT),
                   sampling=sp)
    want = a.generate([base])["base"]
    b = EngineCore(EngineConfig(**ENGINE_KW), params=a.params)
    dreq = Request(request_id="res", prompt_token_ids=list(PROMPT),
                   sampling=sp)
    dreq.output_token_ids = list(want[:4])
    dreq.resume_offset = 4
    assert b.generate([dreq])["res"] == want


# ---------------------------------------------------------------------------
# gateway: mid-stream kill -> resume on a surviving replica
# ---------------------------------------------------------------------------

async def _sim_fleet(n, gw_kwargs=None, tpot_ms=2.0):
    """(runners, sims, endpoints, gateway, gw_runner, url)."""
    from llm_d_tpu.epp.service import build_gateway
    ports = [free_port() for _ in range(n)]
    runners, sims = [], []
    for i in range(n):
        srv = build_sim_server(SimConfig(
            model=f"sim-{i}", ttft_ms=1.0, tpot_ms=tpot_ms))
        sims.append(srv.sim)
        runners.append(await _start_app(srv.build_app(), ports[i]))
    endpoints = [EndpointState(address=f"127.0.0.1:{p}") for p in ports]
    gw = build_gateway(endpoints, scrape_interval_s=0.05,
                       retry_attempts=3, **(gw_kwargs or {}))
    gw_port = free_port()
    gw_runner = await _start_app(gw.build_app(), gw_port)
    url = f"http://127.0.0.1:{gw_port}/v1/completions"
    for _ in range(200):
        if all(e.ready for e in gw.datastore.candidates()):
            break
        await asyncio.sleep(0.02)
    assert all(e.ready for e in gw.datastore.candidates())
    return runners, sims, endpoints, gw, gw_runner, url


async def _cleanup(runners):
    for r in runners:
        try:
            await r.cleanup()
        except Exception:
            pass


def _metric_value(text: str, needle: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith("#") or needle not in line:
            continue
        try:
            total += float(line.rsplit(" ", 1)[1])
        except (ValueError, IndexError):
            continue
    return total


def test_gateway_resumes_stream_across_replica_death(inject):
    """One replica dies mid-stream (engine.step fault): the client's SSE
    stream completes with byte-identical text, no duplicate/missing token
    indices, the resume is visible in llmd_tpu:stream_resume_total, and
    the dead endpoint took the breaker failure."""
    import aiohttp

    inj = inject()
    # Kill the serving replica's engine on the stream's 3rd token
    # iteration (matchless: it lands on whichever sim was picked).
    inj.add_rule("engine.step", after=2, count=1)

    async def run():
        runners, sims, endpoints, gw, gw_runner, url = await _sim_fleet(3)
        try:
            async with aiohttp.ClientSession(
                    timeout=aiohttp.ClientTimeout(total=20)) as sess:
                prompt = "recover me mid stream please"
                async with sess.post(url, json={
                        "prompt": prompt, "max_tokens": 8,
                        "stream": True}) as r:
                    assert r.status == 200
                    payload = await r.read()
            text, metas, done = parse_stream_payload(payload)
            assert done, "stream did not reach [DONE]"
            assert verify_continuity(metas, expect_total=8) == []
            assert text == expected_sim_text(sims[0], prompt, 8)
            dead_idx = [i for i, s_ in enumerate(sims) if s_.dead]
            assert len(dead_idx) == 1            # the kill really happened
            # The resumed chunks came from a DIFFERENT replica and said so.
            srcs = [m.get("src") for m in metas if m.get("src")]
            assert srcs and srcs[0] in (OUTCOME_RESTORED,
                                        OUTCOME_RECOMPUTED)
            mtext = gw.scheduler.metrics.render().decode()
            assert _metric_value(
                mtext, "llmd_tpu:stream_resume_total") >= 1.0
            assert _metric_value(
                mtext, "llmd_tpu:request_recovery_seconds_count") >= 1.0
            # Breaker-aware exclusion: the death was recorded.
            b = gw.datastore.breaker
            dead_addr = endpoints[dead_idx[0]].address
            assert b._ep.get(dead_addr, [None, 0])[1] >= 1 \
                or b.state(dead_addr) != "closed"
        finally:
            await _cleanup(runners + [gw_runner])

    asyncio.run(asyncio.wait_for(run(), timeout=60))


def test_gateway_resume_disabled_is_todays_fail_fast(inject, monkeypatch):
    """LLMD_STREAM_RESUME=0: the mid-stream break reaches the client
    exactly as today — truncated stream, no [DONE], no resume metrics."""
    import aiohttp

    monkeypatch.setenv("LLMD_STREAM_RESUME", "0")
    inj = inject()
    inj.add_rule("engine.step", after=2, count=1)

    async def run():
        runners, sims, endpoints, gw, gw_runner, url = await _sim_fleet(2)
        try:
            async with aiohttp.ClientSession(
                    timeout=aiohttp.ClientTimeout(total=20)) as sess:
                broke = False
                payload = b""
                try:
                    async with sess.post(url, json={
                            "prompt": "fail fast", "max_tokens": 8,
                            "stream": True}) as r:
                        assert r.status == 200
                        payload = await r.read()
                except aiohttp.ClientError:
                    broke = True
                if not broke:
                    _text, _metas, done = parse_stream_payload(payload)
                    assert not done, "stream completed despite resume=0"
            mtext = gw.scheduler.metrics.render().decode()
            assert _metric_value(
                mtext, "llmd_tpu:stream_resume_total") == 0.0
        finally:
            await _cleanup(runners + [gw_runner])

    asyncio.run(asyncio.wait_for(run(), timeout=60))


def test_gateway_sheddable_stream_not_resumed(inject):
    """Degradation ladder: sheddable-class streams are never journaled —
    the break reaches the client."""
    import aiohttp

    inj = inject()
    inj.add_rule("engine.step", after=2, count=1)

    async def run():
        runners, sims, endpoints, gw, gw_runner, url = await _sim_fleet(2)
        try:
            async with aiohttp.ClientSession(
                    timeout=aiohttp.ClientTimeout(total=20)) as sess:
                broke = False
                payload = b""
                try:
                    async with sess.post(url, json={
                            "prompt": "shed me", "max_tokens": 8,
                            "stream": True},
                            headers={"x-llmd-criticality":
                                     "sheddable"}) as r:
                        assert r.status == 200
                        payload = await r.read()
                except aiohttp.ClientError:
                    broke = True
                if not broke:
                    _t, _m, done = parse_stream_payload(payload)
                    assert not done
        finally:
            await _cleanup(runners + [gw_runner])

    asyncio.run(asyncio.wait_for(run(), timeout=60))


def test_gateway_resume_attempts_exhausted_falls_back_clean(
        inject, monkeypatch):
    """LLMD_RESUME_MAX_ATTEMPTS=0: detection happens but no resume is
    attempted — today's truncated stream, counted as outcome=failed."""
    import aiohttp

    monkeypatch.setenv("LLMD_RESUME_MAX_ATTEMPTS", "0")
    inj = inject()
    inj.add_rule("engine.step", after=2, count=1)

    async def run():
        runners, sims, endpoints, gw, gw_runner, url = await _sim_fleet(2)
        try:
            async with aiohttp.ClientSession(
                    timeout=aiohttp.ClientTimeout(total=20)) as sess:
                payload = b""
                try:
                    async with sess.post(url, json={
                            "prompt": "exhausted", "max_tokens": 8,
                            "stream": True}) as r:
                        payload = await r.read()
                except aiohttp.ClientError:
                    pass
                _t, _m, done = parse_stream_payload(payload)
                assert not done
            mtext = gw.scheduler.metrics.render().decode()
            assert 'outcome="failed"' in mtext
            assert _metric_value(
                mtext, 'llmd_tpu:stream_resume_total{outcome="failed"}'
                ) >= 1.0
        finally:
            await _cleanup(runners + [gw_runner])

    asyncio.run(asyncio.wait_for(run(), timeout=60))


def test_gateway_resume_deadline_budget_exhausted_falls_back(inject):
    """A break past the request's deadline is NOT resumed (the budget is
    gone): clean degradation to the truncated stream, outcome=failed.
    The fault rule stalls 0.5s before killing, so the 200ms budget is
    deterministically spent at detection time."""
    import aiohttp

    inj = inject()
    inj.add_rule("engine.step", after=2, count=1, latency_s=0.5)

    async def run():
        runners, sims, endpoints, gw, gw_runner, url = await _sim_fleet(2)
        try:
            async with aiohttp.ClientSession(
                    timeout=aiohttp.ClientTimeout(total=20)) as sess:
                payload = b""
                try:
                    async with sess.post(url, json={
                            "prompt": "late", "max_tokens": 8,
                            "stream": True},
                            headers={"x-llmd-deadline-ms": "200"}) as r:
                        payload = await r.read()
                except aiohttp.ClientError:
                    pass
                _t, _m, done = parse_stream_payload(payload)
                assert not done
            mtext = gw.scheduler.metrics.render().decode()
            assert _metric_value(
                mtext, 'llmd_tpu:stream_resume_total{outcome="failed"}'
                ) >= 1.0
        finally:
            await _cleanup(runners + [gw_runner])

    asyncio.run(asyncio.wait_for(run(), timeout=60))


def test_gateway_stream_relay_fault_and_stall_watchdog(
        inject, monkeypatch):
    """stream.relay (gateway->backend wire drop, backend healthy) and the
    token-gap watchdog both take the resume path: the stream completes
    continuously either way."""
    import aiohttp

    monkeypatch.setenv("LLMD_STREAM_STALL_TIMEOUT_S", "0.2")
    inj = inject()
    # Wire drop mid-relay on the first stream...
    inj.add_rule("stream.relay", after=2, count=1)
    # ...and a wedged (not dead) replica later in the run: a latency-only
    # engine.step stall longer than the watchdog, on whichever sim makes
    # the fleet's 13th token iteration.
    inj.add_rule("engine.step", after=12, count=1,
                 latency_s=0.8, label="none")

    async def run():
        runners, sims, endpoints, gw, gw_runner, url = await _sim_fleet(3)
        try:
            async with aiohttp.ClientSession(
                    timeout=aiohttp.ClientTimeout(total=30)) as sess:
                for i in range(6):
                    prompt = f"watchdog {i}"
                    async with sess.post(url, json={
                            "prompt": prompt, "max_tokens": 6,
                            "stream": True}) as r:
                        assert r.status == 200
                        payload = await r.read()
                    text, metas, done = parse_stream_payload(payload)
                    assert done, f"stream {i} broke"
                    assert verify_continuity(metas, expect_total=6) == []
                    assert text == expected_sim_text(sims[0], prompt, 6)
            stats = inj.stats()
            assert stats["stream.relay"]["fired"] >= 1
            assert stats["engine.step"]["fired"] >= 1
            mtext = gw.scheduler.metrics.render().decode()
            assert _metric_value(
                mtext, "llmd_tpu:stream_resume_total") >= 2.0
        finally:
            await _cleanup(runners + [gw_runner])

    asyncio.run(asyncio.wait_for(run(), timeout=90))


def test_gateway_resume_target_excluded_and_breaker_recorded(inject):
    """Resume-target exclusion: with only TWO replicas, the resume must
    land on the one surviving replica (never back on the dead one) and
    the dead one accumulates breaker failures."""
    import aiohttp

    inj = inject()
    inj.add_rule("engine.step", after=1, count=1)

    async def run():
        breaker = EndpointBreaker(failure_threshold=2, open_s=60)
        runners, sims, endpoints, gw, gw_runner, url = await _sim_fleet(
            2, gw_kwargs={"breaker": breaker})
        try:
            async with aiohttp.ClientSession(
                    timeout=aiohttp.ClientTimeout(total=20)) as sess:
                prompt = "exclusion check"
                async with sess.post(url, json={
                        "prompt": prompt, "max_tokens": 6,
                        "stream": True}) as r:
                    payload = await r.read()
            text, metas, done = parse_stream_payload(payload)
            assert done and verify_continuity(metas, expect_total=6) == []
            assert text == expected_sim_text(sims[0], prompt, 6)
            # Exactly one replica died; the survivor finished the
            # stream — i.e. the resume was never routed back to the dead
            # replica — and the death is on the breaker's books.
            dead_idx = [i for i, s_ in enumerate(sims) if s_.dead]
            assert len(dead_idx) == 1
            dead_addr = endpoints[dead_idx[0]].address
            b = gw.datastore.breaker
            assert b._ep.get(dead_addr, [None, 0])[1] >= 1 \
                or b.state(dead_addr) != "closed"
        finally:
            await _cleanup(runners + [gw_runner])

    asyncio.run(asyncio.wait_for(run(), timeout=60))


def test_gateway_client_disconnect_is_not_resumed(inject):
    """A CLIENT that hangs up mid-stream must not trigger recovery: no
    resume attempt, no breaker failure on the healthy replica, no
    stream_resume metric — the relay aborts (ClientGone), exactly the
    generate_load --faults abort traffic shape."""
    import aiohttp

    inject()                      # empty injector: replicas stay healthy

    async def run():
        runners, sims, endpoints, gw, gw_runner, url = await _sim_fleet(
            2, tpot_ms=30.0)
        try:
            async with aiohttp.ClientSession(
                    timeout=aiohttp.ClientTimeout(total=20)) as sess:
                async with sess.post(url, json={
                        "prompt": "abandoned", "max_tokens": 50,
                        "stream": True}) as r:
                    assert r.status == 200
                    async for _chunk in r.content.iter_any():
                        break                 # one chunk, then hang up
                    r.close()
            await asyncio.sleep(0.4)          # let the abort settle
            mtext = gw.scheduler.metrics.render().decode()
            assert _metric_value(
                mtext, "llmd_tpu:stream_resume_total") == 0.0
            b = gw.datastore.breaker
            for ep in endpoints:
                assert b._ep.get(ep.address, [None, 0])[1] == 0, \
                    "healthy replica penalized for a client disconnect"
            assert not any(s.dead for s in sims)
        finally:
            await _cleanup(runners + [gw_runner])

    asyncio.run(asyncio.wait_for(run(), timeout=60))


# ---------------------------------------------------------------------------
# DP-leader relay: worker death mid-stream -> local resume + slot accounting
# ---------------------------------------------------------------------------

def test_dp_relay_resumes_locally_and_settles_accounting(
        inject, monkeypatch):
    """The DP leader's worker relay journals streams too: when the (only)
    worker host's engine dies mid-stream, the leader resumes on its
    LOCAL engine — same stream id, continuous token indices, identical
    tokens (same seed -> same weights) — and the dead worker's streaming
    slot is released (counted exactly once; satellite: no phantom
    load)."""
    import aiohttp

    from llm_d_tpu.server.openai import DPWorkerPool, build_server

    inj = inject()
    # Latency-only rule: slows every engine step so the kill lands
    # mid-stream (the leader idles until the resume, so this throttles
    # only the worker first, then the short local continuation).
    inj.add_rule("engine.step", latency_s=0.05, label="none")

    async def run():
        leader = build_server(EngineConfig(**ENGINE_KW))
        worker = build_server(EngineConfig(**ENGINE_KW))
        lp, wp = free_port(), free_port()
        worker_runner = await _start_app(worker.build_app(), wp)
        leader_runner = await _start_app(leader.build_app(), lp)
        pool = DPWorkerPool([f"http://127.0.0.1:{wp}"])
        leader.dp_pool = pool
        # Force the dispatch decision remote (an idle leader otherwise
        # serves locally).
        monkeypatch.setattr(DPWorkerPool, "pick",
                            lambda self, engine: self.workers[0])
        killed = False
        try:
            async with aiohttp.ClientSession(
                    timeout=aiohttp.ClientTimeout(total=60)) as sess:
                async with sess.post(
                        f"http://127.0.0.1:{lp}/v1/completions",
                        json={"prompt": [7, 3, 9, 1], "max_tokens": 12,
                              "temperature": 0, "ignore_eos": True,
                              "stream": True}) as r:
                    assert r.status == 200
                    payload = b""
                    async for chunk in r.content.iter_any():
                        payload += chunk
                        if not killed and payload.count(b"\n\n") >= 2:
                            # Kill the WORKER engine mid-stream: only it
                            # is stepping right now, so the one-shot
                            # error rule lands there (the real
                            # engine-death path: its streams fail, the
                            # connection breaks abruptly).
                            inj.add_rule("engine.step", count=1)
                            killed = True
                        if b"[DONE]" in payload:
                            break
            assert killed
            assert worker.async_engine.dead is not None, \
                "worker engine survived the kill"
            text, metas, done = parse_stream_payload(payload)
            assert done, "stream did not complete after worker death"
            assert verify_continuity(metas, expect_total=12) == []
            # Same seed -> same weights: the local continuation is token-
            # identical to what one healthy engine produces end to end.
            solo = EngineCore(EngineConfig(**ENGINE_KW))
            want = solo.generate([greedy_req("solo", [7, 3, 9, 1],
                                             12)])["solo"]
            got = [t for m in metas for t in m.get("tok", [])]
            assert got == want
            # Local resume produced the recovery metrics on the LEADER.
            mtext = leader.engine.metrics.render().decode()
            assert _metric_value(
                mtext, "llmd_tpu:stream_resume_total") >= 1.0
            # Accounting satellite: the dead worker's slot is settled —
            # nothing left dispatching, depth not negative, inflight 0.
            w = pool.workers[0]
            assert w["dispatching"] == set()
            assert w["inflight"] == 0 and w["depth"] >= 0
        finally:
            leader.async_engine.stop()
            worker.async_engine.stop()
            await _cleanup([leader_runner, worker_runner])

    asyncio.run(asyncio.wait_for(run(), timeout=120))


# ---------------------------------------------------------------------------
# acceptance: 8-replica chaos — sustained streaming load, mid-run decode
# kill, ZERO client-visible breaks, byte-identical continuity
# ---------------------------------------------------------------------------

def test_chaos_acceptance_zero_stream_breaks_under_engine_death(inject):
    """THE acceptance bar: 8 sim replicas behind the gateway under
    sustained streaming load; a seeded mid-run decode-engine kill
    (engine.step fault on sim-3).  Every stream completes 200 with
    [DONE], every token sequence is byte-identical to an unfaulted run,
    no duplicated/missing token indices anywhere, and the recovery shows
    up in llmd_tpu:stream_resume_total{restored|recomputed}."""
    import aiohttp

    inj = inject()
    # Seeded mid-run decode kill: fires once, on whichever replica makes
    # the fleet's 41st token iteration — mid-stream on a busy replica.
    inj.add_rule("engine.step", after=40, count=1)

    async def run():
        runners, sims, endpoints, gw, gw_runner, url = await _sim_fleet(8)
        max_tokens = 6
        results = []              # (prompt, status, text, metas, done)
        stop = asyncio.Event()

        async def load_worker(sess, wid):
            i = 0
            while not stop.is_set():
                i += 1
                prompt = f"chaos stream {wid} {i} tail"
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
                           for w in range(4)]
                # Run until the kill fired and recovery had time to show,
                # with a floor of traffic volume.
                for _ in range(600):
                    await asyncio.sleep(0.02)
                    if inj.stats().get("engine.step", {}).get(
                            "fired", 0) >= 1 and len(results) > 40:
                        break
                await asyncio.sleep(0.3)      # let in-flight resumes land
                stop.set()
                await asyncio.gather(*workers, return_exceptions=True)
        finally:
            mtext = gw.scheduler.metrics.render().decode()
            await _cleanup(runners + [gw_runner])

        assert inj.stats()["engine.step"]["fired"] >= 1, \
            "the seeded kill never fired"
        assert any(s.dead for s in sims), "no sim died"
        assert len(results) > 40, "load generator barely ran"
        bad = [(p, s) for p, s, *_ in results if s != 200]
        assert not bad, f"client-visible failures: {bad[:5]}"
        breaks = [p for p, _s, _t, _m, done in results if not done]
        assert not breaks, (f"{len(breaks)} client-visible stream "
                            f"break(s): {breaks[:3]}")
        for prompt, _s, text, metas, _d in results:
            assert verify_continuity(metas, expect_total=max_tokens) \
                == [], prompt
            assert text == expected_sim_text(
                sims[0], prompt, max_tokens), \
                f"token sequence diverged for {prompt!r}"
        resumed = _metric_value(mtext, "llmd_tpu:stream_resume_total")
        failed = _metric_value(
            mtext, 'llmd_tpu:stream_resume_total{outcome="failed"}')
        assert resumed >= 1.0, "no resume recorded despite the kill"
        assert failed == 0.0, "a recovery was abandoned"

    asyncio.run(asyncio.wait_for(run(), timeout=180))


# ---------------------------------------------------------------------------
# load generator: --stream continuity mode drives the same oracle
# ---------------------------------------------------------------------------

def test_generate_load_stream_mode_counts_continuity(inject):
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "scripts"))
    import generate_load as gl

    async def run():
        port = free_port()
        srv = build_sim_server(SimConfig(model="sim-load", ttft_ms=1.0,
                                         tpot_ms=0.5))
        runner = await _start_app(srv.build_app(), port)
        try:
            args = gl.argparse.Namespace(
                url=f"http://127.0.0.1:{port}", model="sim-load",
                qps=40.0, duration=0.5, shape="uniform", prompt_words=6,
                prefix_groups=4, prefix_len=8, max_tokens=4,
                temperature=0.0, slo_ttft_ms=500.0, slo_tpot_ms=50.0,
                error_rate=0.0, deadline_ms=0.0, criticality_mix="",
                faults="", stream=True, seed=0,
                fault_map={}, criticality_list=[])
            stats = {}
            import aiohttp
            async with aiohttp.ClientSession(
                    timeout=aiohttp.ClientTimeout(total=20)) as sess:
                rng = gl.random.Random(0)
                for i in range(5):
                    await gl.one_request(sess, args, rng, stats)
            assert stats.get(200, 0) == 5
            assert stats.get("stream_breaks", 0) == 0
            assert stats.get("continuity_errors", 0) == 0
        finally:
            await runner.cleanup()

    asyncio.run(asyncio.wait_for(run(), timeout=60))
