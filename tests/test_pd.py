"""PD disaggregation: KV transfer transport, connector, sidecar e2e.

The contract under test is the reference's TPUConnector flow
(README.tpu.md:182-189): a producer engine prefills and pins KV, the
consumer engine pulls the blocks over TCP before decoding, and the final
tokens are identical to a single aggregated engine.
"""

import asyncio
import socket
import threading
import time

import pytest
import requests

from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.request import Request, RequestState
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.transfer import KVConnectorConfig, TpuConnector
from llm_d_tpu.transfer import transport


ENGINE_KW = dict(model="tiny", block_size=4, num_blocks=64, max_num_seqs=8,
                 max_num_batched_tokens=64, min_token_bucket=16,
                 min_seq_bucket=4)


def greedy_req(rid, prompt, n=8, **kw):
    return Request(request_id=rid, prompt_token_ids=list(prompt),
                   sampling=SamplingParams(temperature=0.0, max_tokens=n,
                                           ignore_eos=True), **kw)


# ---------------------------------------------------------------------------
# transport layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("server_cls,fetch,release", [
    (transport.PyTransferServer, transport.py_fetch, transport.py_release),
    (transport.NativeTransferServer, transport.native_fetch,
     transport.native_release),
])
def test_transport_roundtrip(server_cls, fetch, release):
    server = server_cls("127.0.0.1", 0)
    try:
        blob = bytes(range(256)) * 1000
        server.register("req-1", blob)
        assert fetch("127.0.0.1", server.port, "req-1") == blob
        with pytest.raises(transport.TransferNotFound):
            fetch("127.0.0.1", server.port, "missing")
        assert release("127.0.0.1", server.port, "req-1")
        # Release removed the blob and queued the notification.
        with pytest.raises(transport.TransferNotFound):
            fetch("127.0.0.1", server.port, "req-1")
        deadline = time.time() + 5
        released = []
        while time.time() < deadline and not released:
            released = server.drain_released()
        assert released == ["req-1"]
    finally:
        server.close()


def test_native_and_python_interoperate():
    """Python client against native server and vice versa (same protocol)."""
    native = transport.NativeTransferServer("127.0.0.1", 0)
    try:
        native.register("x", b"abc" * 10)
        assert transport.py_fetch("127.0.0.1", native.port, "x") == b"abc" * 10
        assert transport.py_release("127.0.0.1", native.port, "x")
    finally:
        native.close()
    pysrv = transport.PyTransferServer("127.0.0.1", 0)
    try:
        pysrv.register("y", b"def" * 10)
        assert transport.native_fetch("127.0.0.1", pysrv.port, "y") == b"def" * 10
        assert transport.native_release("127.0.0.1", pysrv.port, "y")
    finally:
        pysrv.close()


# ---------------------------------------------------------------------------
# engine-level disaggregation
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def baseline_engine():
    return EngineCore(EngineConfig(**ENGINE_KW))


def _drive(engine, until, max_steps=2000):
    outs = []
    for _ in range(max_steps):
        outs.extend(engine.step())
        if until():
            return outs
        if not engine.scheduler.has_work():
            time.sleep(0.002)   # waiting on async transfer machinery
    raise AssertionError("condition not reached")


def test_pd_tokens_identical_to_single_engine(baseline_engine):
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]  # 10 tokens: partial last block
    n_out = 6
    expected = baseline_engine.generate(
        [greedy_req("base", prompt, n_out)])["base"]

    producer = EngineCore(EngineConfig(**ENGINE_KW),
                          params=baseline_engine.params)
    producer.kv_connector = TpuConnector(
        KVConnectorConfig(kv_role="kv_producer", host="127.0.0.1"))
    consumer = EngineCore(EngineConfig(**ENGINE_KW),
                          params=baseline_engine.params)
    consumer.kv_connector = TpuConnector(
        KVConnectorConfig(kv_role="kv_consumer"))
    try:
        # Step 1: remote prefill on the producer.
        preq = greedy_req("pd-1", prompt, 1, do_remote_decode=True)
        producer.add_request(preq)
        _drive(producer,
               lambda: preq.state == RequestState.FINISHED_REMOTE_PREFILL)
        params = preq.kv_transfer_params
        assert params is not None
        assert params["remote_port"] == producer.kv_connector.port
        assert params["remote_block_ids"] == preq.block_ids
        assert "pd-1" in producer.pinned_transfers

        # Step 2: decode on the consumer with the transfer params.
        dreq = greedy_req("pd-1", prompt, n_out, do_remote_prefill=True,
                          kv_transfer_params=params)
        out = consumer.generate([dreq])
        assert out["pd-1"] == expected

        # The consumer's pull released the producer's pinned blocks.
        _drive(producer, lambda: "pd-1" not in producer.pinned_transfers)
        assert producer.kv_manager.usage == 0.0
        # Transfer time was observed on the consumer.
        hist = consumer.metrics.kv_transfer_time.collect() \
            if hasattr(consumer.metrics.kv_transfer_time, "collect") else None
        # (prometheus child objects don't expose collect; render instead)
        text = consumer.metrics.render().decode()
        assert 'llmd_tpu:kv_transfer_seconds_count{model_name="tiny"} 1.0' \
            in text
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()


def test_pd_block_aligned_prompt(baseline_engine):
    """Prompt length an exact multiple of block_size (boundary case)."""
    prompt = [7, 8, 9, 10, 11, 12, 13, 14]  # 8 = 2 full blocks of 4
    expected = baseline_engine.generate(
        [greedy_req("base8", prompt, 4)])["base8"]
    producer = EngineCore(EngineConfig(**ENGINE_KW),
                          params=baseline_engine.params)
    producer.kv_connector = TpuConnector(
        KVConnectorConfig(kv_role="kv_producer"))
    consumer = EngineCore(EngineConfig(**ENGINE_KW),
                          params=baseline_engine.params)
    consumer.kv_connector = TpuConnector(
        KVConnectorConfig(kv_role="kv_consumer"))
    try:
        preq = greedy_req("pd-8", prompt, 1, do_remote_decode=True)
        producer.add_request(preq)
        _drive(producer,
               lambda: preq.state == RequestState.FINISHED_REMOTE_PREFILL)
        dreq = greedy_req("pd-8", prompt, 4, do_remote_prefill=True,
                          kv_transfer_params=preq.kv_transfer_params)
        assert consumer.generate([dreq])["pd-8"] == expected
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()


def test_missing_connector_fails_loudly(baseline_engine):
    """kv_transfer_params with no connector must NOT silently local-prefill."""
    engine = EngineCore(EngineConfig(**ENGINE_KW),
                        params=baseline_engine.params)
    req = greedy_req("orphan", [1, 2, 3], 4, do_remote_prefill=True,
                     kv_transfer_params={"remote_host": "h", "remote_port": 1,
                                         "uuid": "orphan"})
    engine.add_request(req)
    outs = engine.step()
    assert [o for o in outs if o.request_id == "orphan" and o.finished
            and o.finish_reason == "abort"]
    assert req.state == RequestState.FINISHED_ABORTED
    assert not engine.has_work()


def test_kv_load_failure_policy_fail(baseline_engine):
    """Unreachable producer + policy=fail -> request aborts, engine lives."""
    consumer = EngineCore(EngineConfig(**ENGINE_KW),
                          params=baseline_engine.params)
    consumer.kv_connector = TpuConnector(KVConnectorConfig(
        kv_role="kv_consumer", kv_load_failure_policy="fail",
        timeout_ms=2000))
    try:
        dead_port = socket.socket()
        dead_port.bind(("127.0.0.1", 0))
        port = dead_port.getsockname()[1]
        dead_port.close()   # nothing listens here now
        req = greedy_req("doomed", [1, 2, 3], 4, do_remote_prefill=True,
                         kv_transfer_params={"remote_host": "127.0.0.1",
                                             "remote_port": port,
                                             "uuid": "doomed"})
        consumer.add_request(req)
        outs = _drive(consumer, lambda: req.state.finished)
        assert [o for o in outs if o.request_id == "doomed"
                and o.finish_reason == "abort"]
        assert not consumer.scheduler.has_work()
    finally:
        consumer.kv_connector.close()


def test_kv_load_failure_policy_recompute(baseline_engine):
    """Unreachable producer + policy=recompute -> falls back to local prefill."""
    prompt = [5, 4, 3, 2, 1]
    expected = baseline_engine.generate(
        [greedy_req("b", prompt, 4)])["b"]
    consumer = EngineCore(EngineConfig(**ENGINE_KW),
                          params=baseline_engine.params)
    consumer.kv_connector = TpuConnector(KVConnectorConfig(
        kv_role="kv_consumer", kv_load_failure_policy="recompute",
        timeout_ms=2000))
    try:
        req = greedy_req("fallback", prompt, 4, do_remote_prefill=True,
                         kv_transfer_params={"remote_host": "127.0.0.1",
                                             "remote_port": 9,
                                             "uuid": "fallback"})
        out = consumer.generate([req])
        assert out["fallback"] == expected
    finally:
        consumer.kv_connector.close()


def _foreign_blob(engine, nb, segments):
    """A structurally valid wire-version-2 blob for ``nb`` blocks of
    ``engine``'s geometry whose buffer segments are ``segments``:
    ``[(row width, numpy dtype), ...]``, zero-filled — what a producer with
    another cache would ship."""
    import numpy as np
    from llm_d_tpu.transfer.connector import (
        _BUF_HEADER, _HEADER, _MAGIC, _WIRE_VERSION)
    L = engine.model_config.num_layers
    bs = engine.config.block_size
    parts = [_HEADER.pack(_MAGIC, _WIRE_VERSION, L, bs, len(segments), nb)]
    for width, dtype in segments:
        parts.append(_BUF_HEADER.pack(width, transport.wire_dtype_code(dtype)))
        parts.append(np.zeros((L, nb * bs, width), dtype).tobytes())
    return b"".join(parts)


@pytest.mark.parametrize("producer_cache,error", [
    # A peer of an older build with the int8 cache: int8 rows and an f32
    # scale plane per buffer, sorted by name as the wire sorts them.
    ("int8+scales", "ships another buffer set"),
    # The consumer's own buffer set and widths under another dtype code.
    ("int8", "producer shipped int8 but the local cache is bfloat16"),
    ("f32", "producer shipped float32 but the local cache is bfloat16"),
])
def test_consumer_refuses_foreign_cache_dtype_and_recomputes(
        baseline_engine, caplog, producer_cache, error):
    """The wire's dtype codes are checked, never reinterpreted: a slab
    whose segments are not this cache's bf16 k and v is refused with the
    mismatch error, and under policy=recompute the request is served by a
    local prefill at parity."""
    import numpy as np
    from llm_d_tpu.transfer.connector import _scatter_blocks
    prompt = [5, 4, 3, 2, 1, 7, 7]
    expected = baseline_engine.generate([greedy_req("b", prompt, 4)])["b"]
    w = baseline_engine.kv_cache["k"].shape[-1]
    segments = {
        "int8+scales": [(w, np.int8), (1, np.float32)] * 2,
        "int8": [(w, np.int8)] * 2,
        "f32": [(w, np.float32)] * 2,
    }[producer_cache]
    blob = _foreign_blob(baseline_engine, 2, segments)
    with pytest.raises(ValueError, match=error):
        _scatter_blocks(baseline_engine, [1, 2], blob)

    server = transport.PyTransferServer("127.0.0.1", 0)
    server.register("foreign", blob)
    consumer = EngineCore(EngineConfig(**ENGINE_KW),
                          params=baseline_engine.params)
    consumer.kv_connector = TpuConnector(KVConnectorConfig(
        kv_role="kv_consumer", kv_load_failure_policy="recompute",
        timeout_ms=2000))
    try:
        req = greedy_req("foreign", prompt, 4, do_remote_prefill=True,
                         kv_transfer_params={
                             "remote_host": "127.0.0.1",
                             "remote_port": server.port, "uuid": "foreign",
                             "remote_block_ids": [1, 2]})
        with caplog.at_level("WARNING", logger="llm_d_tpu.transfer.connector"):
            out = consumer.generate([req])
        assert out["foreign"] == expected
        assert any("bad slab" in r.getMessage() and error in r.getMessage()
                   for r in caplog.records), caplog.text
    finally:
        consumer.kv_connector.close()
        server.close()


def test_producer_pin_timeout_releases_blocks(baseline_engine):
    """A consumer that never pulls must not leak the producer's cache."""
    producer = EngineCore(EngineConfig(**ENGINE_KW),
                          params=baseline_engine.params)
    producer.kv_connector = TpuConnector(KVConnectorConfig(
        kv_role="kv_producer", pin_timeout_s=0.2))
    try:
        preq = greedy_req("ghost", [1, 2, 3, 4, 5], 1, do_remote_decode=True)
        producer.add_request(preq)
        _drive(producer,
               lambda: preq.state == RequestState.FINISHED_REMOTE_PREFILL)
        assert "ghost" in producer.pinned_transfers
        deadline = time.time() + 5
        while time.time() < deadline and "ghost" in producer.pinned_transfers:
            producer.step()
            time.sleep(0.02)
        assert "ghost" not in producer.pinned_transfers
        assert producer.kv_manager.usage == 0.0
    finally:
        producer.kv_connector.close()


# ---------------------------------------------------------------------------
# sidecar e2e over real HTTP: prefill server + decode server + sidecar
# ---------------------------------------------------------------------------

def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _start_app(app, port):
    from aiohttp import web
    started = threading.Event()

    def run():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", port)
        loop.run_until_complete(site.start())
        started.set()
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(timeout=60)


@pytest.fixture(scope="module")
def pd_stack(baseline_engine):
    """prefill server + decode server (consumer connector) + sidecar."""
    from llm_d_tpu.server.openai import build_server
    from llm_d_tpu.sidecar.proxy import RoutingSidecar

    ports = {k: _free_port() for k in ("prefill", "decode", "sidecar")}

    prefill_engine = EngineCore(EngineConfig(**ENGINE_KW),
                                params=baseline_engine.params)
    prefill_engine.kv_connector = TpuConnector(
        KVConnectorConfig(kv_role="kv_producer", host="127.0.0.1"))
    prefill_server = build_server(EngineConfig(**ENGINE_KW),
                                  engine=prefill_engine)

    decode_engine = EngineCore(EngineConfig(**ENGINE_KW),
                               params=baseline_engine.params)
    decode_engine.kv_connector = TpuConnector(
        KVConnectorConfig(kv_role="kv_consumer"))
    decode_server = build_server(EngineConfig(**ENGINE_KW),
                                 engine=decode_engine)

    sidecar = RoutingSidecar(f"http://127.0.0.1:{ports['decode']}",
                             static_prefiller=f"127.0.0.1:{ports['prefill']}")

    _start_app(prefill_server.build_app(), ports["prefill"])
    _start_app(decode_server.build_app(), ports["decode"])
    _start_app(sidecar.build_app(), ports["sidecar"])

    url = f"http://127.0.0.1:{ports['sidecar']}"
    for _ in range(200):
        try:
            if requests.get(url + "/v1/models", timeout=5).status_code == 200:
                break
        except requests.ConnectionError:
            pass
        time.sleep(0.1)
    return url


def test_sidecar_pd_completion(pd_stack, baseline_engine):
    prompt_ids = [11, 22, 33, 44, 55, 66]
    base = baseline_engine.generate(
        [greedy_req("side-base", prompt_ids, 5)])["side-base"]
    r = requests.post(pd_stack + "/v1/completions", json={
        "model": "tiny", "prompt": prompt_ids, "max_tokens": 5,
        "temperature": 0.0, "ignore_eos": True}, timeout=120)
    assert r.status_code == 200, r.text
    body = r.json()
    # The sidecar path produced the same tokens as the single engine
    # (completion text is the decoded ids; compare via usage + determinism).
    assert body["usage"]["completion_tokens"] == 5
    from llm_d_tpu.utils.tokenizer import get_tokenizer
    tok = get_tokenizer(None)
    assert body["choices"][0]["text"] == tok.decode(base)


def test_sidecar_passthrough_probes(pd_stack):
    assert requests.get(pd_stack + "/health", timeout=10).status_code == 200
    r = requests.get(pd_stack + "/metrics", timeout=10)
    assert r.status_code == 200
    assert "vllm:kv_cache_usage_perc" in r.text


# ---------------------------------------------------------------------------
# PD x DP: per-rank connectors (the reference's flagship shape is PD at
# DP=16 — wide-ep decode.yaml:73-96)
# ---------------------------------------------------------------------------

def test_pd_dp2_consumer_group(baseline_engine):
    """Producer -> dp=2 consumer group: every rank owns its own transfer
    server; pulled requests decode to token parity on whichever rank the
    dispatcher picked."""
    import jax
    from llm_d_tpu.engine.dp_group import DPEngineGroup

    prompts = {
        "pdda": [3, 1, 4, 1, 5, 9, 2, 6],
        "pddb": [2, 7, 1, 8, 2, 8],
        "pddc": [1, 6, 1, 8, 0, 3, 3, 9, 8, 8],
        "pddd": [5, 5, 5, 5],
    }
    n_out = 5
    expected = baseline_engine.generate(
        [greedy_req(f"base-{r}", p, n_out) for r, p in prompts.items()])

    producer = EngineCore(EngineConfig(**ENGINE_KW),
                          params=baseline_engine.params)
    producer.kv_connector = TpuConnector(
        KVConnectorConfig(kv_role="kv_producer", host="127.0.0.1"))
    group = DPEngineGroup(
        EngineConfig(**ENGINE_KW, allow_device_subset=True), dp_size=2,
        params=baseline_engine.params, devices=jax.devices()[:2])
    group.set_kv_connectors(KVConnectorConfig(kv_role="kv_consumer"))
    try:
        # Per-rank servers exist only on producer-role connectors; consumer
        # ranks still get their own pull pumps.
        assert len(group.kv_connectors) == 2
        assert all(c is not None for c in group.kv_connectors)
        assert group.kv_connectors[0] is not group.kv_connectors[1]

        # Remote prefill each request on the producer, then hand the
        # transfer params to the dp group (least-loaded dispatch spreads
        # the four requests over both ranks).
        dreqs = {}
        for rid, prompt in prompts.items():
            preq = greedy_req(f"p-{rid}", prompt, 1, do_remote_decode=True)
            producer.add_request(preq)
            _drive(producer, lambda preq=preq:
                   preq.state == RequestState.FINISHED_REMOTE_PREFILL)
            dreq = greedy_req(rid, prompt, n_out, do_remote_prefill=True,
                              kv_transfer_params=preq.kv_transfer_params)
            dreqs[rid] = dreq
            group.add_request(dreq)

        # Both ranks took a share (4 requests, least-loaded round-robins).
        share = [group._rank_of[rid] for rid in prompts]
        assert set(share) == {0, 1}, share

        deadline = time.time() + 60
        while time.time() < deadline and group.has_work():
            group.step()
            time.sleep(0.001)
        assert not group.has_work()

        # Token parity with the aggregated single engine, per request.
        for rid in prompts:
            assert list(dreqs[rid].output_token_ids) \
                == expected[f"base-{rid}"], rid
        # Producer pins all released (each rank's pull freed its blocks).
        _drive(producer, lambda: not producer.pinned_transfers)
        assert producer.kv_manager.usage == 0.0
    finally:
        producer.kv_connector.close()
        group.close_kv_connectors()
