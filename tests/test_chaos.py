"""Chaos suite: the serving path under injected fault schedules.

Runs the sim-backed multi-replica stack (and real tiny engines for the KV
plane) under deterministic fault injection (``llm_d_tpu.utils.faultinject``)
and asserts the resilience contract:

  - every request TERMINATES (no hangs) whatever the fault schedule;
  - the success rate meets the policy bound (gateway retry-on-alternate,
    sidecar prefill failover + local-prefill fallback, KV pull retry +
    recompute mask individual failures);
  - failed endpoints trip the circuit breaker and recover via half-open
    probing after the fault clears;
  - the same seed reproduces the same fault sequence.

Scenario sources: P/D-Serve (arxiv 2408.08147) — failed P->D transfers and
dying decode instances dominate per-request failures at scale; the ROADMAP
north star ("as many scenarios as you can imagine").  All CPU, tier-1 safe.
"""

import asyncio
import socket

import pytest

from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.request import Request, RequestState
from llm_d_tpu.epp.datastore import Datastore, EndpointBreaker, EndpointState
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.transfer import KVConnectorConfig, TpuConnector
from llm_d_tpu.utils.faultinject import (
    FaultInjected,
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


def greedy_req(rid, prompt, n=4, **kw):
    return Request(request_id=rid, prompt_token_ids=list(prompt),
                   sampling=SamplingParams(temperature=0.0, max_tokens=n,
                                           ignore_eos=True), **kw)


@pytest.fixture()
def inject():
    """Install a fresh process-global injector; always reset after."""
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


# ---------------------------------------------------------------------------
# fault injector: grammar + determinism (the reproducibility contract)
# ---------------------------------------------------------------------------

def _fire_pattern(inj, point, key, n):
    out = []
    for _ in range(n):
        try:
            inj.check(point, key=key)
            out.append(0)
        except FaultInjected:
            out.append(1)
    return out


def test_fault_schedule_is_seed_deterministic():
    a = FaultInjector.from_spec("kv.pull:p=0.3", seed=42)
    b = FaultInjector.from_spec("kv.pull:p=0.3", seed=42)
    pa = _fire_pattern(a, "kv.pull", "x", 200)
    assert pa == _fire_pattern(b, "kv.pull", "x", 200)
    assert 0 < sum(pa) < 200        # it is a schedule, not a constant
    c = FaultInjector.from_spec("kv.pull:p=0.3", seed=43)
    assert pa != _fire_pattern(c, "kv.pull", "x", 200)


def test_fault_rule_fields():
    inj = FaultInjector(seed=1)
    rule = inj.add_rule("gateway.forward", match="10.0.0.7:8200",
                        count=2, after=1)
    # match= scopes the rule to one endpoint key.
    inj.check("gateway.forward", key="10.0.0.8:8200")
    # after=1 skips the first matching call; count=2 spends the rule.
    inj.check("gateway.forward", key="10.0.0.7:8200")
    fired = _fire_pattern(inj, "gateway.forward", "10.0.0.7:8200", 10)
    assert sum(fired) == 2 and fired[0] == 1
    assert rule.fired == 2
    assert [p for p, _k, _n in inj.fired_log] == ["gateway.forward"] * 2


def test_fault_spec_malformed_entries_dropped():
    # Invalid-value fallback: a typo must not take down the process.
    inj = FaultInjector.from_spec(
        "kv.pull:p=banana;gateway.forward:p=0.5,count=x;engine.step:count=1",
        seed=0)
    assert "kv.pull" not in inj._rules
    assert "gateway.forward" not in inj._rules
    assert "engine.step" in inj._rules


def test_fault_latency_only_rule():
    import time
    inj = FaultInjector(seed=0)
    inj.add_rule("kv.pull", latency_s=0.05, label="none")
    t0 = time.monotonic()
    inj.check("kv.pull")            # stalls, must NOT raise
    assert time.monotonic() - t0 >= 0.045


def test_fault_latency_rule_is_loop_safe():
    """A latency rule firing through sync check() on an EVENT-LOOP thread
    must not block the loop (that would stall every request on the
    component and distort the chaos suite's p99): the stall is skipped
    with a warning; acheck() awaits the stall without blocking."""
    import asyncio
    import time

    inj = FaultInjector(seed=0)
    inj.add_rule("kv.pull", latency_s=0.2, label="none")

    async def sync_check_on_loop():
        t0 = time.monotonic()
        inj.check("kv.pull")        # loop-guarded: no 0.2s stall
        return time.monotonic() - t0

    assert asyncio.run(sync_check_on_loop()) < 0.15

    async def acheck_keeps_loop_alive():
        # The awaited stall must suspend only THIS coroutine: a
        # concurrent ticker keeps running while acheck sleeps.
        ticks = 0

        async def ticker():
            nonlocal ticks
            for _ in range(10):
                await asyncio.sleep(0.01)
                ticks += 1

        t = asyncio.ensure_future(ticker())
        t0 = time.monotonic()
        await inj.acheck("kv.pull")
        stalled = time.monotonic() - t0
        # Snapshot BEFORE awaiting the ticker: if acheck regressed to a
        # blocking sleep, the ticker would only run afterwards and this
        # count would be 0.
        ticks_during_stall = ticks
        await t
        return stalled, ticks_during_stall

    stalled, ticks_during_stall = asyncio.run(acheck_keeps_loop_alive())
    assert stalled >= 0.15 and ticks_during_stall > 0

    # Off-loop (worker thread) sync check still blocks — that is the
    # point of a latency fault against a thread-context hop.
    t0 = time.monotonic()
    inj.check("kv.pull")
    assert time.monotonic() - t0 >= 0.15


# ---------------------------------------------------------------------------
# circuit breaker: lifecycle + filter semantics (no servers)
# ---------------------------------------------------------------------------

def test_breaker_lifecycle_half_open_probing():
    import time
    b = EndpointBreaker(failure_threshold=2, open_s=0.1,
                        probe_interval_s=0.05)
    addr = "10.0.0.1:8200"
    b.record_failure(addr)
    assert b.state(addr) == "closed"        # below threshold
    b.record_success(addr)
    b.record_failure(addr)
    b.record_failure(addr)                  # consecutive failures trip it
    assert b.state(addr) == "open" and not b.admissible(addr)
    time.sleep(0.12)
    assert b.state(addr) == "half-open" and b.admissible(addr)
    b.note_pick(addr)                       # probe in flight
    assert not b.admissible(addr)           # window armed: one probe only
    b.record_failure(addr)                  # probe failed -> open again
    assert b.state(addr) == "open"
    time.sleep(0.12)
    assert b.admissible(addr)               # half-open again
    b.note_pick(addr)
    b.record_success(addr)                  # probe succeeded -> closed
    assert b.state(addr) == "closed" and b.admissible(addr)


def test_breaker_filter_drops_tripped_but_fails_open():
    from llm_d_tpu.epp.plugins import CircuitBreakerFilter, RequestCtx
    eps = [EndpointState(address=f"10.0.0.{i}:8200", ready=True)
           for i in range(3)]
    ds = Datastore(eps, scrape_interval_s=999,
                   breaker=EndpointBreaker(failure_threshold=1, open_s=60))
    filt = CircuitBreakerFilter("cb", {}, ds)
    ctx = RequestCtx(body={})
    assert filt.filter(ctx, eps) == eps
    ds.breaker.record_failure(eps[0].address)
    assert filt.filter(ctx, eps) == eps[1:]
    for e in eps[1:]:
        ds.breaker.record_failure(e.address)
    # Everything tripped: fail open (keep probing; a recovered fleet must
    # not stay black-holed behind its own breakers).
    assert filt.filter(ctx, eps) == eps


# ---------------------------------------------------------------------------
# gateway chaos: 8-replica sim stack, mid-run replica kill + injected
# faults; retry-on-alternate masks failures, breaker trips and recovers
# ---------------------------------------------------------------------------

def test_chaos_sim_stack_kill_flap_and_breaker_convergence(inject):
    """The acceptance scenario: 8 sim replicas behind the gateway; one
    replica killed mid-run (its scrape view frozen ready, so only
    request-level resilience can save traffic), another flapping via an
    injected fault schedule.  Every request terminates, success stays at
    100% (the retry budget covers first-failure exclusion), the killed
    replica's breaker trips, and after restart ("fault clears") it
    recovers through half-open probing."""
    import aiohttp

    from llm_d_tpu.epp.service import RETRY_BUDGET_HEADER, build_gateway
    from llm_d_tpu.sim.simulator import SimConfig, build_sim_server

    inj = inject()      # empty injector; the flap rule is added mid-run

    async def run():
        n = 8
        ports = [free_port() for _ in range(n)]
        runners = []

        async def start_sim(i):
            srv = build_sim_server(SimConfig(
                model=f"sim-{i}", ttft_ms=1.0, tpot_ms=0.2))
            return await _start_app(srv.build_app(), ports[i])

        for i in range(n):
            runners.append(await start_sim(i))
        endpoints = [EndpointState(address=f"127.0.0.1:{p}") for p in ports]
        victim, flapper = endpoints[0].address, endpoints[1].address
        breaker = EndpointBreaker(failure_threshold=2, open_s=0.3,
                                  probe_interval_s=0.05)
        gw = build_gateway(endpoints, scrape_interval_s=0.05,
                           retry_attempts=3, breaker=breaker)
        gw_port = free_port()
        gw_runner = await _start_app(gw.build_app(), gw_port)
        url = f"http://127.0.0.1:{gw_port}/v1/completions"
        statuses = []
        try:
            async with aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(
                    total=15)) as sess:
                for _ in range(100):
                    if all(e.ready for e in gw.datastore.candidates()):
                        break
                    await asyncio.sleep(0.05)
                assert all(e.ready for e in gw.datastore.candidates())
                # Freeze scraping: the dead replica must keep LOOKING ready
                # so only the breaker/retry path (not the probe loop) can
                # protect traffic — the worst case at scrape-interval
                # granularity.
                gw.datastore.scrape_interval_s = 999
                await asyncio.sleep(0.1)

                async def post(i):
                    try:
                        async with sess.post(url, json={
                                "prompt": f"chaos load {i} tail",
                                "max_tokens": 4}) as r:
                            await r.read()
                            statuses.append(r.status)
                            return r
                    except asyncio.TimeoutError:
                        statuses.append("hang")

                # Phase 1: healthy fleet.
                for i in range(8):
                    await post(i)
                # Phase 2: kill replica 0 mid-run (decode instance death),
                # and make replica 1 flap via an injected fault schedule.
                await runners[0].cleanup()
                inj.add_rule("gateway.forward", match=flapper,
                             probability=0.7, count=6)
                while breaker.state(victim) != "open" \
                        and len(statuses) < 150:
                    await post(len(statuses))
                assert breaker.state(victim) == "open", \
                    f"victim breaker never tripped: {statuses}"
                for i in range(10):
                    await post(100 + i)

                # No hangs, and the retry budget masked every failure.
                assert "hang" not in statuses
                ok = sum(1 for s in statuses if s == 200)
                assert ok / len(statuses) >= 0.95, statuses

                # Phase 3: the faults clear — replica 0 restarts, the flap
                # rule is spent.  The breaker must converge back to closed
                # via half-open probing.
                runners[0] = await start_sim(0)
                inj.clear("gateway.forward")
                await asyncio.sleep(0.35)       # open_s elapses
                for i in range(240):
                    await post(200 + i)
                    if breaker.state(victim) == "closed" and \
                            breaker.state(flapper) == "closed":
                        break
                    await asyncio.sleep(0.01)
                assert breaker.state(victim) == "closed", breaker.states()
                assert breaker.state(flapper) == "closed", breaker.states()

                # Observability: retry budget header + breaker metrics.
                async with sess.post(url, json={
                        "prompt": "after", "max_tokens": 2}) as r:
                    assert r.status == 200
                    assert RETRY_BUDGET_HEADER in r.headers
                async with sess.get(
                        f"http://127.0.0.1:{gw_port}/metrics") as r:
                    text = await r.text()
                assert "llmd_tpu:endpoint_breaker_state" in text
                assert "llmd_tpu:gateway_retries_total" in text
        finally:
            for r in runners[1:] + [runners[0], gw_runner]:
                try:
                    await r.cleanup()
                except Exception:
                    pass

    asyncio.run(run())


def test_gateway_error_body_carries_request_id():
    """x-request-id must survive into gateway error bodies (satellite:
    observability of failures across hops)."""
    import aiohttp

    from llm_d_tpu.epp.service import build_gateway

    async def run():
        # One endpoint that is never scraped ready (nothing listens).
        gw = build_gateway(
            [EndpointState(address=f"127.0.0.1:{free_port()}")],
            scrape_interval_s=999)
        gw_port = free_port()
        runner = await _start_app(gw.build_app(), gw_port)
        try:
            async with aiohttp.ClientSession() as sess:
                async with sess.post(
                        f"http://127.0.0.1:{gw_port}/v1/completions",
                        json={"prompt": "x", "max_tokens": 1},
                        headers={"x-request-id": "rid-404"}) as r:
                    assert r.status == 503
                    body = await r.json()
                    assert body["request_id"] == "rid-404"
        finally:
            await runner.cleanup()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# sidecar chaos: prefill failover along the hint list, flapping prefiller,
# local-prefill fallback when the whole pool is down
# ---------------------------------------------------------------------------

def _sidecar_stack():
    """(decode sim, prefill sims A+B) behind a RoutingSidecar — all sims."""
    from llm_d_tpu.sim.simulator import SimConfig, build_sim_server
    ports = {k: free_port() for k in ("decode", "pfa", "pfb", "sidecar")}
    apps = {k: build_sim_server(SimConfig(
        model=f"sim-{k}", ttft_ms=1.0, tpot_ms=0.2)).build_app()
        for k in ("decode", "pfa", "pfb")}
    return ports, apps


def test_sidecar_prefill_failover_to_next_prefiller(inject):
    from llm_d_tpu.sidecar.proxy import PREFILLER_HEADER, RoutingSidecar
    import aiohttp

    ports, apps = _sidecar_stack()
    pfa, pfb = (f"127.0.0.1:{ports['pfa']}", f"127.0.0.1:{ports['pfb']}")
    inj = inject()
    inj.add_rule("sidecar.prefill", match=pfa)   # prefiller A is down

    async def run():
        runners = [await _start_app(app, ports[k])
                   for k, app in apps.items()]
        sidecar = RoutingSidecar(f"http://127.0.0.1:{ports['decode']}",
                                 prefill_retries=1, prefill_backoff_s=0.01)
        runners.append(await _start_app(sidecar.build_app(),
                                        ports["sidecar"]))
        try:
            async with aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(
                    total=15)) as sess:
                async with sess.post(
                        f"http://127.0.0.1:{ports['sidecar']}"
                        "/v1/completions",
                        json={"prompt": "hello failover", "max_tokens": 3},
                        headers={PREFILLER_HEADER: f"{pfa},{pfb}"}) as r:
                    assert r.status == 200, await r.text()
                    body = await r.json()
                    assert body["choices"][0]["text"]
                # The fault fired on A and the request still succeeded (via
                # B) WITHOUT the local fallback.
                assert inj.stats()["sidecar.prefill"]["fired"] >= 1
                # B actually served a prefill (its token counters moved).
                async with sess.get(
                        f"http://127.0.0.1:{ports['pfb']}/metrics") as r:
                    assert "vllm:prompt_tokens_total" in await r.text()
        finally:
            for r in runners:
                await r.cleanup()

    asyncio.run(run())


def test_sidecar_local_prefill_fallback_when_all_down():
    """Whole prefill pool down -> the decode pod recomputes locally
    (P/D-Serve's recompute path) instead of the old immediate 502."""
    from llm_d_tpu.sidecar.proxy import (
        FALLBACK_HEADER, PREFILLER_HEADER, RoutingSidecar)
    import aiohttp

    ports, apps = _sidecar_stack()
    dead = f"127.0.0.1:{free_port()}"        # nothing listens
    dead2 = f"127.0.0.1:{free_port()}"

    async def run():
        runners = [await _start_app(apps["decode"], ports["decode"])]
        sidecar = RoutingSidecar(f"http://127.0.0.1:{ports['decode']}",
                                 prefill_retries=1, prefill_backoff_s=0.01,
                                 prefill_timeout_s=2.0)
        runners.append(await _start_app(sidecar.build_app(),
                                        ports["sidecar"]))
        try:
            async with aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(
                    total=20)) as sess:
                async with sess.post(
                        f"http://127.0.0.1:{ports['sidecar']}"
                        "/v1/completions",
                        json={"prompt": "survive the outage",
                              "max_tokens": 3},
                        headers={PREFILLER_HEADER: f"{dead},{dead2}",
                                 "x-request-id": "rid-fallback"}) as r:
                    assert r.status == 200, await r.text()
                    assert r.headers.get(FALLBACK_HEADER) == "local"
                    body = await r.json()
                    assert body["choices"][0]["text"]
        finally:
            for r in runners:
                await r.cleanup()

    asyncio.run(run())


def test_sidecar_flapping_prefiller_bounded_errors(inject):
    """A flapping prefiller (seeded 50% fault rate) behind retry rounds:
    every request terminates 200; most are served by the REMOTE prefiller
    (the local fallback only catches all-rounds-failed tails)."""
    from llm_d_tpu.sidecar.proxy import (
        FALLBACK_HEADER, PREFILLER_HEADER, RoutingSidecar)
    import aiohttp

    ports, apps = _sidecar_stack()
    pfa = f"127.0.0.1:{ports['pfa']}"
    inj = inject()
    inj.add_rule("sidecar.prefill", match=pfa, probability=0.5)

    async def run():
        runners = [await _start_app(apps[k], ports[k])
                   for k in ("decode", "pfa")]
        sidecar = RoutingSidecar(f"http://127.0.0.1:{ports['decode']}",
                                 prefill_retries=3, prefill_backoff_s=0.01)
        runners.append(await _start_app(sidecar.build_app(),
                                        ports["sidecar"]))
        try:
            async with aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(
                    total=15)) as sess:
                statuses, fallbacks = [], 0
                for i in range(10):
                    async with sess.post(
                            f"http://127.0.0.1:{ports['sidecar']}"
                            "/v1/completions",
                            json={"prompt": f"flap {i}", "max_tokens": 2},
                            headers={PREFILLER_HEADER: pfa}) as r:
                        await r.read()
                        statuses.append(r.status)
                        fallbacks += r.headers.get(FALLBACK_HEADER) \
                            == "local"
                assert statuses == [200] * 10, statuses   # zero hung/failed
                # Mostly remote prefill (the local fallback only catches
                # all-rounds-failed tails).  Bound is loose because real
                # transient connect errors under parallel-suite socket
                # pressure add to the injected schedule.
                assert fallbacks <= 4, fallbacks
                assert inj.stats()["sidecar.prefill"]["fired"] >= 2
        finally:
            for r in runners:
                await r.cleanup()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# KV plane chaos: real tiny engines, injected pull drops
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pd_engines():
    baseline = EngineCore(EngineConfig(**ENGINE_KW))
    producer = EngineCore(EngineConfig(**ENGINE_KW), params=baseline.params)
    producer.kv_connector = TpuConnector(
        KVConnectorConfig(kv_role="kv_producer", host="127.0.0.1"))
    yield baseline, producer
    producer.kv_connector.close()


def _drive(engine, until, max_steps=2000):
    import time
    outs = []
    for _ in range(max_steps):
        outs.extend(engine.step())
        if until():
            return outs
        if not engine.scheduler.has_work():
            time.sleep(0.002)
    raise AssertionError("condition not reached (hung request?)")


def _remote_prefill(producer, rid, prompt):
    preq = greedy_req(rid, prompt, 1, do_remote_decode=True)
    producer.add_request(preq)
    _drive(producer,
           lambda: preq.state == RequestState.FINISHED_REMOTE_PREFILL)
    return preq.kv_transfer_params


def test_kv_pull_drops_30pct_all_requests_survive(pd_engines, inject):
    """30% of KV pulls dropped (seeded): the retry budget recovers the
    transient drops, policy=recompute catches the exhausted tails, and
    every request decodes to token parity with the aggregated baseline."""
    baseline, producer = pd_engines
    inj = inject()
    inj.add_rule("kv.pull", probability=0.3)
    consumer = EngineCore(EngineConfig(**ENGINE_KW), params=baseline.params)
    consumer.kv_connector = TpuConnector(KVConnectorConfig(
        kv_role="kv_consumer", kv_load_failure_policy="recompute",
        timeout_ms=2000, pull_retries=2, pull_backoff_s=0.01))
    try:
        prompts = {f"kvchaos-{i}": [3 + i, 1, 4, 1, 5, 9, 2 + i]
                   for i in range(8)}
        expected = {rid: baseline.generate(
            [greedy_req("b" + rid, p, 4)])["b" + rid]
            for rid, p in prompts.items()}
        for rid, prompt in prompts.items():
            params = _remote_prefill(producer, rid, prompt)
            dreq = greedy_req(rid, prompt, 4, do_remote_prefill=True,
                              kv_transfer_params=params)
            out = consumer.generate([dreq])
            assert out[rid] == expected[rid], rid
        stats = inj.stats()["kv.pull"]
        assert stats["fired"] >= 1, stats      # the schedule really fired
    finally:
        consumer.kv_connector.close()


def test_kv_pull_drops_mla_latent(inject):
    """The single-buffer MLA wire (one latent ``kv`` segment) is
    resilience-clean too — injected pull drops recover through the retry
    budget / recompute fallback exactly as over the dense k + v wire, and
    every request decodes to parity with an aggregated baseline."""
    kw = dict(ENGINE_KW, model="tiny-mla")
    baseline = EngineCore(EngineConfig(**kw))
    producer = EngineCore(EngineConfig(**kw), params=baseline.params)
    producer.kv_connector = TpuConnector(
        KVConnectorConfig(kv_role="kv_producer", host="127.0.0.1"))
    inj = inject()
    inj.add_rule("kv.pull", probability=0.3)
    consumer = EngineCore(EngineConfig(**kw), params=baseline.params)
    consumer.kv_connector = TpuConnector(KVConnectorConfig(
        kv_role="kv_consumer", kv_load_failure_policy="recompute",
        timeout_ms=2000, pull_retries=2, pull_backoff_s=0.01))
    try:
        prompts = {f"mlakv-{i}": [5 + i, 1, 4, 1, 5, 9, 2 + i]
                   for i in range(6)}
        expected = {rid: baseline.generate(
            [greedy_req("b" + rid, p, 4)])["b" + rid]
            for rid, p in prompts.items()}
        for rid, prompt in prompts.items():
            params = _remote_prefill(producer, rid, prompt)
            dreq = greedy_req(rid, prompt, 4, do_remote_prefill=True,
                              kv_transfer_params=params)
            out = consumer.generate([dreq])
            assert out[rid] == expected[rid], rid
        assert inj.stats()["kv.pull"]["fired"] >= 1
    finally:
        consumer.kv_connector.close()
        producer.kv_connector.close()


def test_kv_pull_total_outage_terminates_under_policy_fail(
        pd_engines, inject):
    """100% pull drops + policy=fail: the request ABORTS loudly (bounded
    time, engine lives) — never hangs."""
    baseline, producer = pd_engines
    inj = inject()
    inj.add_rule("kv.pull")                   # p=1.0: every pull drops
    consumer = EngineCore(EngineConfig(**ENGINE_KW), params=baseline.params)
    consumer.kv_connector = TpuConnector(KVConnectorConfig(
        kv_role="kv_consumer", kv_load_failure_policy="fail",
        timeout_ms=2000, pull_retries=1, pull_backoff_s=0.01))
    try:
        params = _remote_prefill(producer, "doomed-chaos", [9, 8, 7, 6])
        dreq = greedy_req("doomed-chaos", [9, 8, 7, 6], 4,
                          do_remote_prefill=True, kv_transfer_params=params)
        consumer.add_request(dreq)
        outs = _drive(consumer, lambda: dreq.state.finished)
        assert [o for o in outs if o.request_id == "doomed-chaos"
                and o.finish_reason == "abort"]
        assert not consumer.scheduler.has_work()
        # 1 first attempt + 1 retry, both injected.
        assert inj.stats()["kv.pull"]["fired"] >= 2
    finally:
        consumer.kv_connector.close()


def test_peer_fetch_faults_degrade_to_recompute(inject):
    """Shared-tier peer fetches all fail (injected): requests recompute
    locally at parity and the failing peer trips into backoff."""
    offload_kw = dict(ENGINE_KW, num_blocks=16, max_num_seqs=4,
                      kv_offload_blocks=64)
    prompt = [7, 3, 9, 1, 4, 6, 2, 8, 5, 0, 11, 13]
    pod_a = EngineCore(EngineConfig(**dict(offload_kw,
                                           kv_shared_tier_port=0)))
    try:
        want = pod_a.generate([greedy_req("a", prompt, 4)])["a"]
        inj = inject()
        inj.add_rule("kv.peer_fetch")
        pod_b = EngineCore(EngineConfig(**dict(
            offload_kw,
            kv_shared_tier_peers=(f"127.0.0.1:{pod_a.host_tier.port}",))),
            params=pod_a.params)
        try:
            got = pod_b.generate([greedy_req("b", prompt, 4)])["b"]
            assert got == want                 # recompute parity
            assert pod_b.host_tier.remote_hits == 0
            # Each prefix chain stops at its first miss (one fetch per
            # request); distinct prompts accumulate consecutive failures
            # until the peer trips into backoff.
            for i in range(pod_b.host_tier.peer_failure_limit - 1):
                pod_b.generate([greedy_req(
                    f"b{i}", [20 + i, 21, 22, 23, 24, 25, 26, 27], 2)])
            assert any(f >= pod_b.host_tier.peer_failure_limit
                       for f, _ in pod_b.host_tier._peer_health.values())
        finally:
            pod_b.host_tier.close()
    finally:
        pod_a.host_tier.close()


# ---------------------------------------------------------------------------
# lifecycle chaos: rolling restart under load + class-aware overload shed
# ---------------------------------------------------------------------------

def test_rolling_restart_drain_zero_client_failures():
    """Acceptance scenario: an 8-replica sim fleet behind the gateway is
    roll-restarted one replica at a time under sustained load — drain
    (readiness flips, drain-filter excludes, in-flight completes), kill,
    restart, rejoin — with ZERO client-visible failures.  Races between
    the drain POST and the scrape are covered by the 503-from-draining
    retry path."""
    import aiohttp

    from llm_d_tpu.epp.service import build_gateway
    from llm_d_tpu.sim.simulator import SimConfig, build_sim_server

    async def run():
        n = 8
        ports = [free_port() for _ in range(n)]
        sims: list = [None] * n                   # (runner, server) pairs

        async def start_sim(i):
            srv = build_sim_server(SimConfig(
                model=f"sim-{i}", ttft_ms=1.0, tpot_ms=0.2))
            return (await _start_app(srv.build_app(), ports[i]), srv)

        for i in range(n):
            sims[i] = await start_sim(i)
        endpoints = [EndpointState(address=f"127.0.0.1:{p}") for p in ports]
        gw = build_gateway(endpoints, scrape_interval_s=0.03,
                           retry_attempts=3)
        gw_port = free_port()
        gw_runner = await _start_app(gw.build_app(), gw_port)
        url = f"http://127.0.0.1:{gw_port}/v1/completions"
        statuses: list = []
        stop = asyncio.Event()

        async def load_worker(sess, wid):
            i = 0
            while not stop.is_set():
                i += 1
                try:
                    async with sess.post(url, json={
                            "prompt": f"roll {wid} {i} tail",
                            "max_tokens": 3}) as r:
                        await r.read()
                        statuses.append(r.status)
                except asyncio.TimeoutError:
                    statuses.append("hang")
                except aiohttp.ClientError as e:
                    statuses.append(f"error:{type(e).__name__}")
                await asyncio.sleep(0.01)

        try:
            async with aiohttp.ClientSession(
                    timeout=aiohttp.ClientTimeout(total=15)) as sess:
                for _ in range(100):
                    if all(e.ready for e in gw.datastore.candidates()):
                        break
                    await asyncio.sleep(0.05)
                assert all(e.ready for e in gw.datastore.candidates())
                workers = [asyncio.create_task(load_worker(sess, w))
                           for w in range(4)]
                try:
                    for i in range(n):
                        addr = endpoints[i].address
                        async with sess.post(
                                f"http://{addr}/admin/drain") as r:
                            assert r.status == 200
                        sim = sims[i][1].sim
                        # Wait until the EPP sees the drain AND the
                        # replica's in-flight work hits zero.
                        for _ in range(300):
                            ep = gw.datastore.endpoints.get(addr)
                            if ep is not None and ep.draining \
                                    and sim._running + sim._waiting == 0:
                                break
                            await asyncio.sleep(0.02)
                        assert gw.datastore.endpoints[addr].draining, \
                            f"gateway never saw replica {i} draining"
                        assert sim._running + sim._waiting == 0, \
                            f"replica {i} still had in-flight work"
                        # Kill + restart ("the pod is replaced").
                        await sims[i][0].cleanup()
                        sims[i] = await start_sim(i)
                        for _ in range(300):
                            ep = gw.datastore.endpoints.get(addr)
                            if ep is not None and ep.ready \
                                    and not ep.draining:
                                break
                            await asyncio.sleep(0.02)
                        assert gw.datastore.endpoints[addr].ready
                finally:
                    stop.set()
                    await asyncio.gather(*workers,
                                         return_exceptions=True)
            assert len(statuses) > n, "load generator barely ran"
            bad = [s for s in statuses if s != 200]
            assert not bad, (f"client-visible failures during rolling "
                             f"restart: {bad[:10]} "
                             f"({len(bad)}/{len(statuses)})")
        finally:
            for pair in sims:
                try:
                    await pair[0].cleanup()
                except Exception:
                    pass
            await gw_runner.cleanup()

    asyncio.run(run())


def test_overload_sheds_only_sheddable_class():
    """Seeded overload: with one upstream slot saturated, sheddable
    requests 429 immediately while every critical and standard request
    completes 200 — only the sheddable class is shed.  The critical
    queue reserve also admits a critical request past a full standard
    queue."""
    import aiohttp

    from llm_d_tpu.epp.service import build_gateway
    from llm_d_tpu.sim.simulator import SimConfig, build_sim_server

    async def run():
        sim_port = free_port()
        srv = build_sim_server(SimConfig(
            model="sim", ttft_ms=150.0, tpot_ms=0.2))
        runners = [await _start_app(srv.build_app(), sim_port)]
        gw = build_gateway(
            [EndpointState(address=f"127.0.0.1:{sim_port}")],
            scrape_interval_s=0.05,
            max_inflight=1, max_queue=8, queue_timeout_s=10.0)
        gw_port = free_port()
        runners.append(await _start_app(gw.build_app(), gw_port))
        url = f"http://127.0.0.1:{gw_port}/v1/completions"
        try:
            async with aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(
                    total=20)) as sess:
                for _ in range(100):
                    if all(e.ready for e in gw.datastore.candidates()):
                        break
                    await asyncio.sleep(0.05)

                async def post(criticality):
                    async with sess.post(url, json={
                            "prompt": f"overload {criticality}",
                            "max_tokens": 2},
                            headers={"x-llmd-criticality":
                                     criticality}) as r:
                        await r.read()
                        return r.status

                hog = asyncio.create_task(post("standard"))
                await asyncio.sleep(0.05)       # slot taken, sim is slow
                others = [asyncio.create_task(post(c)) for c in
                          ["critical"] * 2 + ["standard"] * 4]
                await asyncio.sleep(0.05)       # all queued behind the hog
                sheds = [await post("sheddable") for _ in range(3)]
                assert sheds == [429, 429, 429], sheds
                results = await asyncio.gather(hog, *others)
                assert results == [200] * 7, results

                # Critical queue reserve: a full standard queue still
                # admits critical (max_queue=1 here; reserve default 8).
                gw2 = build_gateway(
                    [EndpointState(address=f"127.0.0.1:{sim_port}")],
                    scrape_interval_s=0.05,
                    max_inflight=1, max_queue=1, queue_timeout_s=10.0)
                gw2_port = free_port()
                runners.append(await _start_app(gw2.build_app(), gw2_port))
                url2 = f"http://127.0.0.1:{gw2_port}/v1/completions"
                for _ in range(100):
                    if all(e.ready for e in gw2.datastore.candidates()):
                        break
                    await asyncio.sleep(0.05)

                async def post2(criticality):
                    async with sess.post(url2, json={
                            "prompt": f"reserve {criticality}",
                            "max_tokens": 2},
                            headers={"x-llmd-criticality":
                                     criticality}) as r:
                        await r.read()
                        return r.status

                hog2 = asyncio.create_task(post2("standard"))
                await asyncio.sleep(0.05)
                queued = asyncio.create_task(post2("standard"))
                await asyncio.sleep(0.05)       # standard queue now full
                overflow = await post2("standard")
                assert overflow == 503, overflow     # queue_full
                crit_task = asyncio.create_task(post2("critical"))
                await asyncio.sleep(0.05)
                results2 = await asyncio.gather(hog2, queued, crit_task)
                assert results2 == [200, 200, 200], results2
        finally:
            for r in runners:
                await r.cleanup()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# engine death: simulated step crash must fail streams, never hang them
# ---------------------------------------------------------------------------

def test_engine_death_fails_requests_instead_of_hanging(inject):
    from llm_d_tpu.engine.async_engine import AsyncEngine

    inj = inject()
    inj.add_rule("engine.step", after=2, count=1)   # dies on the 3rd step

    async def run():
        engine = EngineCore(EngineConfig(**ENGINE_KW))
        ae = AsyncEngine(engine)
        await ae.start()
        try:
            req = greedy_req("dying", [1, 2, 3, 4], 8)
            with pytest.raises(RuntimeError, match="engine died"):
                async for _out in ae.generate(req):
                    pass
            assert ae.dead is not None
            # Later submissions fail fast, they don't queue into the void.
            with pytest.raises(RuntimeError, match="engine is dead"):
                async for _out in ae.generate(
                        greedy_req("after-death", [1], 1)):
                    pass
        finally:
            ae.stop()

    asyncio.run(asyncio.wait_for(run(), timeout=60))


# ---------------------------------------------------------------------------
# round 15: engine death MID-MIXED-ROUND (prefill chunks riding decode
# steps) must still resume at exact offsets
# ---------------------------------------------------------------------------

def test_chaos_mid_mixed_round_kill_resumes_exact(inject):
    """A sim fleet with the mixed-round mirror ACTIVE (prefill chunks
    stretch concurrent decode steps via ``step_prefill_token_ms``) under
    overlapping streaming load; a seeded mid-stream ``engine.step`` kill
    lands while prefill and decode genuinely share rounds.  The PR 9
    resume must splice at EXACT offsets: zero client-visible breaks,
    clean continuity, byte-identical text, recovery recorded — chunked
    prefill riding a decode round adds no new failure mode."""
    import aiohttp
    from test_stream_recovery import (
        _cleanup, _metric_value, _start_app, free_port)
    from llm_d_tpu.epp.service import build_gateway
    from llm_d_tpu.sim.simulator import SimConfig, build_sim_server
    from test_spec_decode import _sim_text, parse_stream_payload, \
        verify_continuity

    inj = inject()
    inj.add_rule("engine.step", after=25, count=1)

    async def run():
        ports = [free_port() for _ in range(2)]
        runners, sims = [], []
        mixed_extras = []                 # surcharge values actually used
        for i, port in enumerate(ports):
            # Slow-ish TTFT keeps a prefill in flight across several
            # concurrent decode steps -> real mixed rounds in the mirror.
            srv = build_sim_server(SimConfig(
                model=f"sim-{i}", ttft_ms=8.0, tpot_ms=2.0,
                spec_k=4, spec_acceptance=0.8,
                prefill_chunk=64, step_prefill_token_ms=0.02))
            orig = srv.sim._mixed_step_extra_ms
            def spy(orig=orig):
                v = orig()
                mixed_extras.append(v)
                return v
            srv.sim._mixed_step_extra_ms = spy
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
                prompt = f"mixed chaos {wid} {i} tail"
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
                            "fired", 0) >= 1 and len(results) > 20:
                        break
                await asyncio.sleep(0.3)
                stop.set()
                await asyncio.gather(*workers, return_exceptions=True)
        finally:
            mtext = gw.scheduler.metrics.render().decode()
            await _cleanup(runners + [gw_runner])

        assert inj.stats()["engine.step"]["fired"] >= 1
        assert any(s.dead for s in sims), "no sim died"
        # The mirror was live: at least one decode step ticked while a
        # prefill was in flight, i.e. the kill landed under genuinely
        # MIXED rounds, not a pure-decode fleet with inert knobs.
        assert any(v > 0.0 for v in mixed_extras), \
            "no mixed round observed (prefill never overlapped decode)"
        bad = [(p, s) for p, s, *_ in results if s != 200]
        assert not bad, f"client-visible failures: {bad[:5]}"
        breaks = [p for p, _s, _t, _m, done in results if not done]
        assert not breaks, f"{len(breaks)} stream break(s): {breaks[:3]}"
        for prompt, _s, text, metas, _d in results:
            assert verify_continuity(metas, expect_total=max_tokens) \
                == [], prompt
            assert text == _sim_text(sims[0], prompt, max_tokens), \
                f"token sequence diverged for {prompt!r}"
        assert _metric_value(
            mtext, "llmd_tpu:stream_resume_total") >= 1.0
        assert _metric_value(
            mtext, 'llmd_tpu:stream_resume_total{outcome="failed"}') \
            == 0.0

    asyncio.run(asyncio.wait_for(run(), timeout=120))
