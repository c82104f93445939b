"""OpenAI server contract: probes, metric names, completions, streaming.

Runs the real aiohttp app (tiny model on CPU) in a background thread and
talks to it over real HTTP — the same surface Envoy/EPP would see.
"""

import json
import socket
import threading
import time

import pytest
import requests

from llm_d_tpu.engine.engine import EngineConfig
from llm_d_tpu.server.openai import build_server


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(scope="module")
def server_url():
    import asyncio
    from aiohttp import web

    port = free_port()
    cfg = EngineConfig(model="tiny", block_size=4, num_blocks=64,
                       max_num_seqs=8, max_num_batched_tokens=64,
                       min_token_bucket=16, min_seq_bucket=4)
    server = build_server(cfg)
    started = threading.Event()

    def run():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.build_app())
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", port)
        loop.run_until_complete(site.start())
        started.set()
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(timeout=30)
    url = f"http://127.0.0.1:{port}"
    # three-probe contract: wait for readiness via /v1/models
    for _ in range(100):
        try:
            if requests.get(url + "/v1/models", timeout=5).status_code == 200:
                break
        except requests.ConnectionError:
            pass
        time.sleep(0.1)
    return url


def test_probes(server_url):
    assert requests.get(server_url + "/health").status_code == 200
    r = requests.get(server_url + "/v1/models")
    assert r.status_code == 200
    assert r.json()["data"][0]["id"] == "tiny"
    assert requests.get(server_url + "/version").status_code == 200


def test_metric_names(server_url):
    text = requests.get(server_url + "/metrics").text
    for name in ["vllm:kv_cache_usage_perc", "vllm:num_requests_waiting",
                 "vllm:num_requests_running", "vllm:time_to_first_token_seconds",
                 "vllm:prefix_cache_queries", "vllm:generation_tokens"]:
        assert name in text, f"missing metric {name}"


def test_completion(server_url):
    r = requests.post(server_url + "/v1/completions", json={
        "model": "tiny", "prompt": "hello", "max_tokens": 4,
        "temperature": 0.0, "ignore_eos": True})
    assert r.status_code == 200
    body = r.json()
    assert body["usage"]["completion_tokens"] == 4
    assert body["choices"][0]["finish_reason"] == "length"


def test_completion_token_ids_prompt(server_url):
    r = requests.post(server_url + "/v1/completions", json={
        "model": "tiny", "prompt": [1, 2, 3, 4], "max_tokens": 3,
        "temperature": 0.0, "ignore_eos": True})
    assert r.status_code == 200
    assert r.json()["usage"]["prompt_tokens"] == 4


def test_streaming(server_url):
    r = requests.post(server_url + "/v1/completions", json={
        "model": "tiny", "prompt": "stream me", "max_tokens": 4,
        "temperature": 0.0, "ignore_eos": True, "stream": True}, stream=True)
    assert r.status_code == 200
    events = []
    for line in r.iter_lines():
        if line.startswith(b"data: "):
            payload = line[len(b"data: "):]
            if payload == b"[DONE]":
                events.append("DONE")
            else:
                events.append(json.loads(payload))
    assert events[-1] == "DONE"
    assert len(events) == 5          # 4 tokens + DONE
    assert events[-2]["choices"][0]["finish_reason"] == "length"


def test_streamed_text_is_the_whole_answers_text(server_url):
    """The frames' text deltas, which the server decodes a frame's new
    tokens at a time, add up to the text of the same answer not streamed."""
    body = {"model": "tiny", "prompt": "stream me", "max_tokens": 24,
            "temperature": 0.0, "ignore_eos": True}
    whole = requests.post(server_url + "/v1/completions", json=body).json()
    r = requests.post(server_url + "/v1/completions",
                      json=dict(body, stream=True), stream=True)
    frames = [json.loads(line[len(b"data: "):]) for line in r.iter_lines()
              if line.startswith(b"data: ") and b"[DONE]" not in line]
    assert sum(len(f["llmd"]["tok"]) for f in frames) == 24
    assert "".join(f["choices"][0]["text"] for f in frames) \
        == whole["choices"][0]["text"]


@pytest.mark.parametrize("kind", ["bytes", "multibyte", "specials", "whole"])
def test_stream_decoder_is_the_full_decode(kind):
    """``stream_decoder`` over a list that grows, a call a frame: the full
    decode's text every time, also where a multi-byte character is split
    between frames, where ids past the bytes are dropped, where several
    tokens arrive at once, and for the tokenizer that decodes the whole list."""
    import random

    from llm_d_tpu.utils.tokenizer import ByteTokenizer, HFTokenizer
    tok = ByteTokenizer()
    rng = random.Random(7)
    if kind == "multibyte":
        ids = list("añ€𝄞 z".encode("utf-8")) * 5
    elif kind == "specials":
        ids = [rng.choice([65, 66, 256, 257, 258, 70000]) for _ in range(60)]
    else:
        ids = [rng.randrange(256) for _ in range(80)]
    if kind == "whole":
        class Inner:
            def decode(self, ids, skip_special_tokens):
                return tok.decode(ids)
        hf = HFTokenizer.__new__(HFTokenizer)
        hf._tok = Inner()
        decode = hf.stream_decoder()
    else:
        decode = tok.stream_decoder()
    seen, at = [], 0
    while at < len(ids):
        at += rng.choice([1, 1, 1, 2, 5])
        seen = ids[:at]
        assert decode(seen) == tok.decode(seen)
    assert decode(seen) == tok.decode(ids)      # a frame with nothing new


def test_chat_completion(server_url):
    r = requests.post(server_url + "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 3, "temperature": 0.0, "ignore_eos": True})
    assert r.status_code == 200
    body = r.json()
    assert body["object"] == "chat.completion"
    assert "content" in body["choices"][0]["message"]


def test_concurrent_load_and_metrics_progress(server_url):
    def fire():
        requests.post(server_url + "/v1/completions", json={
            "model": "tiny", "prompt": "load", "max_tokens": 8,
            "temperature": 0.0, "ignore_eos": True})
    threads = [threading.Thread(target=fire) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    text = requests.get(server_url + "/metrics").text
    for line in text.splitlines():
        if line.startswith("vllm:generation_tokens_total"):
            assert float(line.rsplit(" ", 1)[1]) >= 8 * 8
            break
    else:
        pytest.fail("generation_tokens metric missing")
