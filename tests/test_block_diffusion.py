"""Generation by diffusion over blocks (``tiny-sdar``: blocks of 4, four
denoising passes and a commit pass a block) through the scheduler, the
paged cache and the classic step path, against the plain reference's own
generation loop (``benchmarks/references/sdar_moe.py``).

The engines here are float32 with a float32 KV cache (the served cache is
bf16, which moves logprobs by hundredths and flips near-ties of a random
tiny model): the engine and the reference then agree to 1e-4 and on every
token, so a wrong slot, mask or key shows as a different token.
"""

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "benchmarks"))

import references.sdar_moe as ref  # noqa: E402
from llm_d_tpu.engine.engine import EngineConfig, EngineCore  # noqa: E402
from llm_d_tpu.engine.kv_cache import KVCacheManager  # noqa: E402
from llm_d_tpu.engine.request import Request  # noqa: E402
from llm_d_tpu.engine.scheduler import Scheduler  # noqa: E402
from llm_d_tpu.models.config import (  # noqa: E402
    DIFFUSION_REMASKING, ModelConfig, get_config)
from llm_d_tpu.ops import sampling  # noqa: E402
from llm_d_tpu.ops.sampling import SamplingParams  # noqa: E402

B = 4
LOGPROB_TOL = 1e-4      # float32 engine against the float32 reference
# A threshold a tiny random model's confidences straddle, so that
# low_confidence_dynamic takes both of its branches.
THRESHOLD = 0.02


def model(strategy="sequential", **kw):
    return dataclasses.replace(get_config("tiny-sdar"), **{
        "dtype": "float32", "diffusion_remasking": strategy,
        "diffusion_confidence_threshold": THRESHOLD, **kw})


def engine(strategy="sequential", model_kw=None, **kw):
    cfg = dict(model_config=model(strategy, **(model_kw or {})),
               num_blocks=64, max_num_batched_tokens=64, max_num_seqs=8)
    cfg.update(kw)
    eng = EngineCore(EngineConfig(**cfg))
    eng.kv_cache = {k: v.astype(jnp.float32)
                    for k, v in eng.kv_cache.items()}
    return eng


def request(rid, prompt, max_tokens, **kw):
    return Request(request_id=rid, prompt_token_ids=list(prompt),
                   sampling=SamplingParams(
                       temperature=0.0, max_tokens=max_tokens,
                       ignore_eos=kw.pop("ignore_eos", True), logprobs=0,
                       **kw))


def run(eng, reqs, on_step=None):
    """Step the engine dry; {request id: (ids, logprobs, outputs)}."""
    for r in reqs:
        eng.add_request(r)
    got = {r.request_id: ([], [], []) for r in reqs}
    for _ in range(2000):
        if not eng.has_work():
            break
        for o in eng.step():
            ids, lps, outs = got[o.request_id]
            ids.extend(o.new_token_ids)
            lps.extend(o.logprobs or [])
            outs.append(o)
        if on_step is not None:
            on_step(eng)
    assert not eng.has_work()
    return got


def prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 500, size=n).tolist() for n in lens]


def agrees(eng, prompt, max_tokens, ids, lps, eos=None):
    want, want_lps = ref.generate(eng.params, eng.model_config, prompt,
                                  max_tokens, eos=eos)
    assert ids == want
    np.testing.assert_allclose(lps, want_lps, atol=LOGPROB_TOL)


# ---- the engine against the reference's generation loop ----

@pytest.fixture(scope="module", params=DIFFUSION_REMASKING)
def strategy_engine(request):
    return engine(request.param)


@pytest.mark.parametrize("n,max_tokens", [
    (8, 10),     # n % B == 0, an answer that ends in mid-block
    (9, 7),      # n % B == 1: one revealed slot opens the first block
    (11, 5),     # n % B == B - 1
    (3, 6),      # a prompt shorter than a block: nothing to prefill
    (40, 9)])
def test_engine_matches_the_reference(strategy_engine, n, max_tokens):
    eng = strategy_engine
    prompt = prompts(n, [n])[0]
    ids, lps, outs = run(eng, [request(f"r{n}", prompt, max_tokens)])[
        f"r{n}"]
    assert len(ids) == max_tokens and outs[-1].finish_reason == "length"
    assert all(0 <= len(o.new_token_ids) <= B for o in outs)
    agrees(eng, prompt, max_tokens, ids, lps)


def test_confidence_strategies_reveal_out_of_order():
    """Under the confidence rules a pass may reveal a slot behind a masked
    one: it waits, and a later step hands over several tokens at once."""
    sizes = set()
    for strategy in DIFFUSION_REMASKING[1:]:
        eng = engine(strategy)
        for i, p in enumerate(prompts(3, [8, 12, 16, 20])):
            _, _, outs = run(eng, [request(f"{strategy}{i}", p, 12)])[
                f"{strategy}{i}"]
            sizes |= {len(o.new_token_ids) for o in outs}
    assert max(sizes) > 1


def test_a_chunked_prompt_and_a_batch_equal_each_alone():
    """A prompt chunked over several steps (a budget of 16 tokens a step)
    beside rows that denoise, and a batch of requests, give what each
    request gives alone."""
    lens = [50, 9, 8, 23]
    ps = prompts(11, lens)
    eng = engine(max_num_batched_tokens=16)
    chunks = []

    def note(e):
        chunks.append(e.scheduler.last_schedule_stats["prefill_tokens"])

    got = run(eng, [request(f"b{i}", p, 9) for i, p in enumerate(ps)], note)
    assert sum(1 for c in chunks if c) > 4       # the prompts took several
    alone = engine()
    for i, p in enumerate(ps):
        ids, lps, _ = got[f"b{i}"]
        agrees(alone, p, 9, ids, lps)


def test_prefix_cache_hit_gives_the_same_tokens():
    eng = engine()
    shared = prompts(5, [70])[0]
    first = run(eng, [request("a", shared + [7, 8, 9], 6)])["a"]
    hits0 = eng.metrics.prefix_cache_hits._value.get()
    again = run(eng, [request("b", shared + [7, 8, 9], 6)])["b"]
    assert again[0] == first[0]
    np.testing.assert_allclose(again[1], first[1], atol=LOGPROB_TOL)
    # Two whole pages of 32 were committed by the first request.
    assert eng.metrics.prefix_cache_hits._value.get() - hits0 == 64


def test_pages_written_by_commit_passes_serve_a_later_prompt():
    """34 of the 64 cached tokens the second prompt hits were written by
    commit passes, not by prefill: same tokens as the reference gives."""
    eng = engine()
    prompt = prompts(6, [30])[0]
    answer = run(eng, [request("a", prompt, 40)])["a"][0]
    hits0 = eng.metrics.prefix_cache_hits._value.get()
    longer = prompt + answer[:38]
    ids, lps, _ = run(eng, [request("b", longer, 5)])["b"]
    assert eng.metrics.prefix_cache_hits._value.get() - hits0 == 64
    agrees(eng, longer, 5, ids, lps)


def test_commit_written_pages_are_cached_only_once_committed():
    """Only committed tokens are hashed: a page fills with revealed tokens
    before the block that completes it commits."""
    eng = engine()
    prompt = prompts(9, [30])[0]              # 7 whole blocks + 2 slots
    req = request("p", prompt, 8)
    eng.add_request(req)
    cached = []
    while eng.has_work():
        eng.step()
        cached.append((req.num_tokens, req.num_computed_tokens,
                       len(eng.kv_manager._hash_of)))
    # the page of 32 is full of known tokens at num_tokens >= 32, and joins
    # the cache only when num_computed_tokens reaches 32
    assert any(nt >= 32 and nc < 32 and h == 0 for nt, nc, h in cached)
    assert all((nc >= 32) == (h == 1) for _, nc, h in cached)


def test_preemption_in_mid_block_gives_the_same_tokens():
    """A pool of 5 pages under two requests that grow past it: the later
    one is preempted in mid-block and resumes from its committed tokens
    plus the revealed prefix of its block."""
    ps = prompts(21, [40, 46])
    eng = engine(num_blocks=6, enable_prefix_caching=False)
    seen_mid_block = []

    def note(e):
        for r in e.scheduler.waiting:
            if r.num_preemptions and r.num_tokens % B:
                seen_mid_block.append(r.request_id)

    got = run(eng, [request(f"p{i}", p, 60) for i, p in enumerate(ps)], note)
    assert eng.scheduler.num_preemptions >= 1 and seen_mid_block
    alone = engine()
    for i, p in enumerate(ps):
        ids, lps, _ = got[f"p{i}"]
        agrees(alone, p, 60, ids, lps)


@pytest.mark.parametrize("strategy", DIFFUSION_REMASKING)
def test_eos_inside_a_block_ends_the_answer(strategy):
    eng = engine(strategy)
    prompt = prompts(2, [10])[0]
    free, _, _ = run(eng, [request("free", prompt, 12)])["free"]
    # The token the free run put at answer position 5 (a block's slot 3)
    # becomes the EOS: the answer ends there, whatever lies revealed behind.
    eos = free[5]
    cut = free.index(eos) + 1
    eng.eos_token_id = eos
    ids, lps, outs = run(eng, [request("eos", prompt, 12,
                                       ignore_eos=False)])["eos"]
    assert ids == free[:cut] and outs[-1].finish_reason == "stop"
    agrees(eng, prompt, 12, ids, lps, eos=eos)
    eng.eos_token_id = None


def test_seeded_sampling_is_reproducible():
    eng = engine()
    prompt = prompts(4, [9])[0]

    def sampled(rid):
        r = Request(request_id=rid, prompt_token_ids=list(prompt),
                    sampling=SamplingParams(temperature=0.8, max_tokens=7,
                                            seed=123, ignore_eos=True))
        return run(eng, [r])[rid][0]

    a, b = sampled("s1"), sampled("s2")
    assert a == b and len(a) == 7
    assert a != run(eng, [request("g", prompt, 7)])["g"][0]


# ---- the scheduler's invariants ----

def test_scheduler_invariants():
    eng = engine(max_num_batched_tokens=24)
    reqs = [request(f"s{i}", p, 10)
            for i, p in enumerate(prompts(8, [50, 9, 3, 21]))]
    for r in reqs:
        eng.add_request(r)
    bs = eng.config.block_size
    kinds = set()
    for _ in range(500):
        if not eng.has_work():
            break
        before = {r.request_id: r.num_computed_tokens for r in reqs}
        sched = eng.scheduler.schedule()
        rows = 0
        for sr in sched.scheduled:
            r, n = sr.request, sr.num_new_tokens
            assert n % B == 0 and r.num_computed_tokens % B == 0
            # KV is allocated to the end of the chunk or block
            assert len(r.block_ids) * bs >= r.num_computed_tokens + n
            if sr.denoise:
                assert n == B and r.num_computed_tokens == r.num_tokens // B * B
            else:
                # a chunk stops at the last whole block of known tokens
                assert r.num_computed_tokens + n <= r.num_tokens // B * B
            rows += sr.denoise or sr.commit
            kinds.add("denoise" if sr.denoise else
                      "commit" if sr.commit else "prefill")
        assert sched.decode_tokens == rows * B
        assert sched.decode_tokens + sched.prefill_tokens \
            == sched.total_tokens <= 24
        # put the pass back and let the engine run it
        for sr in sched.scheduled:
            assert sr.request.num_computed_tokens \
                == before[sr.request.request_id]
        packed, layout, scheduled, rows_ = eng._build_batch(sched)
        assert layout.Q >= B and layout.R == B
        ids, lps, eng.kv_cache, _, _, top, eng._rng = eng._step_fn(
            eng.params, eng.kv_cache, packed, eng._rng, layout)
        eng._retire_block_rows(scheduled, rows_, np.asarray(ids),
                               np.asarray(lps), None, 0.0, [])
        for sr in scheduled:
            moved = sr.request.num_computed_tokens \
                - before[sr.request.request_id]
            # only chunks and commits advance the computed tokens
            assert moved == (0 if sr.denoise else sr.num_new_tokens)
    assert kinds == {"denoise", "commit", "prefill"}
    assert all(len(r.output_token_ids) == 10 for r in reqs)


def test_plain_scheduler_asks_for_blocks():
    kv = KVCacheManager(16, 32)
    s = Scheduler(kv, max_num_seqs=4, max_num_batched_tokens=10,
                  block_length=B)
    r = request("x", range(1, 23), 5)           # 22 tokens: 5 blocks + 2
    s.add_request(r)
    out = s.schedule()
    # a budget of 10 funds two whole blocks
    assert [(x.num_new_tokens, x.denoise) for x in out.scheduled] \
        == [(8, False)]
    r.num_computed_tokens = 8
    assert s.schedule().scheduled[0].num_new_tokens == 8
    r.num_computed_tokens = 16
    assert s.schedule().scheduled[0].num_new_tokens == 4   # stops at 20
    r.num_computed_tokens = 20
    out = s.schedule()
    assert (out.scheduled[0].denoise, out.decode_tokens,
            out.prefill_tokens) == (True, B, 0)
    assert len(r.block_ids) * 32 >= 24
    r.output_token_ids += [5, 6]                # the block is complete
    out = s.schedule()
    assert (out.scheduled[0].commit, out.scheduled[0].denoise,
            out.decode_tokens) == (True, False, B)


@pytest.mark.parametrize("kw,what", [
    (dict(num_scheduler_steps=4), "multistep"),
    (dict(spec_k=2), "spec_decode"),
    (dict(spec_k=2, num_scheduler_steps=4), "multistep"),
    (dict(block_size=30), "block_size")])
def test_other_step_paths_are_refused_at_start_up(kw, what):
    with pytest.raises(ValueError, match=what):
        EngineCore(EngineConfig(model_config=model(), num_blocks=16, **kw))


def test_config_validation():
    for bad in (dict(diffusion_steps=5), dict(diffusion_remasking="random"),
                dict(mask_token_id=512), dict(kv_lora_rank=8)):
        with pytest.raises(ValueError):
            model(**bad)
    c = model(diffusion_steps=3)
    assert [c.diffusion_quota(s) for s in range(3)] == [2, 1, 1]


def test_transfers_and_resumes_are_rejected():
    eng = engine()
    r = request("pd", [1, 2, 3, 4, 5], 4)
    r.resume_offset = 2
    eng.add_request(r)
    out = eng.step()
    assert out[0].finished and out[0].finish_reason == "abort"


def test_step_shapes_cover_what_a_diffusion_engine_reaches():
    eng = engine(max_num_batched_tokens=64)
    shapes = set(eng.step_shapes())
    assert shapes and all(Q >= B and Q > 1 for _, _, Q in shapes)
    seen = set()
    real = eng._build_batch

    def spy(sched):
        out = real(sched)
        seen.add((out[1].T, out[1].S, out[1].Q))
        return out

    eng._build_batch = spy
    run(eng, [request(f"q{i}", p, 7)
              for i, p in enumerate(prompts(1, [60, 3, 17, 33, 8, 9, 41]))])
    assert seen and seen <= shapes
    # an autoregressive engine's list is what it was
    ar = EngineCore(EngineConfig(model="tiny", num_blocks=16,
                                 max_num_batched_tokens=64, max_num_seqs=8))
    assert (16, 8, 1) in ar.step_shapes()


def test_span_attributes_and_counters():
    from llm_d_tpu.utils import tracing
    eng = engine()
    r = request("t", prompts(6, [13])[0], 8)
    r.trace_ctx = tracing.get_tracer("test").start_span("req").ctx()
    run(eng, [r])
    steps = [s for s in eng.tracer.snapshot() if s["name"] == "engine.step"]
    a = [s["attrs"] for s in steps]
    assert all({"denoise_rows", "commit_rows", "denoise_slots",
                "denoise_revealed", "kv_read_tokens", "attn_q_real",
                "attn_q_slots"} <= set(x) for x in a)
    assert sum(x["denoise_revealed"] for x in a) == 8
    # 13 tokens: 3 blocks prefilled, a slot of the 4th known; 8 answers fill
    # it (3), the 5th (4) and one slot of the 6th: two commits
    assert sum(x["commit_rows"] for x in a) == 2
    assert sum(x["denoise_rows"] for x in a) == 8
    assert all(x["denoise_slots"]
               == B * (x["denoise_rows"] + x["commit_rows"]) for x in a)
    assert all(x["decode_tokens"] == x["denoise_slots"] for x in a)
    # block visibility: a pass over block b reads (b + 1) * B keys a query
    first = next(x for x in a if x["denoise_rows"])
    L = eng.model_config.num_layers
    assert first["kv_read_tokens"] == L * B * 16
    m = eng.metrics
    assert m.diffusion_revealed_tokens._value.get() == 8
    text = m.render().decode() if hasattr(m, "render") else ""
    if text:
        assert 'diffusion_block_passes_total{kind="commit"' in text \
            or "diffusion_block_passes_total" in text


# ---- the reveal rule on the device against its numpy rendering ----

@pytest.mark.parametrize("strategy", DIFFUSION_REMASKING)
def test_reveal_against_numpy(strategy):
    rng = np.random.default_rng(0)
    S, V = 64, 32
    logits = rng.normal(size=(S * B, V)).astype(np.float32) * 3
    # ties: rows 0-7 have equal logits in every slot, so equal confidences
    logits[:8 * B] = logits[0]
    masked = rng.random((S, B)) < 0.7
    masked[:4] = True
    quota = rng.integers(0, B + 1, size=S).astype(np.int32)
    quota[:8] = [1, 2, 3, 4, 1, 2, 3, 4]
    x0 = logits.argmax(-1).astype(np.int32)
    lp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits)))
    own = lp[np.arange(S * B), x0].reshape(S, B)
    threshold = float(np.median(np.exp(own)))
    ids, lps = jax.jit(sampling.reveal, static_argnums=(4, 5))(
        jnp.asarray(logits), jnp.asarray(x0), jnp.asarray(masked),
        jnp.asarray(quota), strategy, threshold)
    ids = np.asarray(ids)
    np.testing.assert_allclose(np.asarray(lps), own, atol=1e-5)
    took_threshold_branch = 0
    for s in range(S):
        want = ref.reveal_rule(np.exp(own[s]), masked[s], int(quota[s]),
                               strategy, threshold) if quota[s] else \
            np.zeros(B, bool)
        assert ((ids[s] >= 0) == want).all(), (s, ids[s], want)
        assert (ids[s][want] == x0.reshape(S, B)[s][want]).all()
        took_threshold_branch += want.sum() > quota[s]
    assert (took_threshold_branch > 0) == (
        strategy == "low_confidence_dynamic")


def test_block_mask_is_not_the_causal_mask():
    """The same tokens through a model with block length 0 (causal) differ:
    the visibility limit reaches the kernels' operands."""
    from llm_d_tpu.ops.attention import with_block_visibility
    batch = {"positions": jnp.asarray([0, 1, 5, 8, 11])}
    assert "vis_limit" not in with_block_visibility(batch, 0)
    np.testing.assert_array_equal(
        with_block_visibility(batch, B)["vis_limit"], [3, 3, 7, 11, 11])
    assert isinstance(ModelConfig().diffusion_block_length, int)


# ---- over HTTP: llmd-serve's own config path ------------------------------

def test_served_over_http_streamed_and_whole():
    """``build_arg_parser -> engine_config_from_args -> EngineCore ->
    build_server`` with a block-diffusion preset: SSE frames carry a step's
    revealed prefix (0 tokens: no frame; several: one frame), ``usage``,
    ``logprobs`` and ``max_tokens`` count tokens, not steps."""
    import asyncio
    import json
    import socket
    import threading

    import requests
    from aiohttp import web

    from llm_d_tpu.server.openai import (
        build_arg_parser, build_server, engine_config_from_args)
    args = build_arg_parser().parse_args(
        ["--model", "tiny-sdar", "--num-blocks", "64", "--max-num-seqs", "8",
         "--max-num-batched-tokens", "64"])
    cfg = dataclasses.replace(
        engine_config_from_args(args),
        model_config=model("low_confidence_static"))
    server = build_server(cfg, args.tokenizer)
    assert server.engine.block_length == B
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    started = threading.Event()

    def serve():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.build_app())
        loop.run_until_complete(runner.setup())
        loop.run_until_complete(
            web.TCPSite(runner, "127.0.0.1", port).start())
        started.set()
        loop.run_forever()

    threading.Thread(target=serve, daemon=True).start()
    assert started.wait(timeout=30)
    url = f"http://127.0.0.1:{port}/v1/completions"
    frames_of = []
    for i, prompt in enumerate(prompts(12, [9, 16, 21, 30])):
        body = {"model": "tiny-sdar", "prompt": prompt, "max_tokens": 11,
                "temperature": 0.0, "ignore_eos": True}
        whole = requests.post(url, json=dict(body, logprobs=0),
                              timeout=120).json()
        assert whole["usage"]["completion_tokens"] == 11
        assert whole["usage"]["prompt_tokens"] == len(prompt)
        lps = whole["choices"][0]["logprobs"]["token_logprobs"]
        assert len(lps) == 11 and all(x <= 1e-6 for x in lps)
        assert whole["choices"][0]["finish_reason"] == "length"
        ids, done = [], False
        with requests.post(url, json=dict(body, stream=True), stream=True,
                           timeout=120) as r:
            for line in r.iter_lines():
                if not line.startswith(b"data: "):
                    continue
                if line[6:] == b"[DONE]":
                    done = True
                    break
                meta = json.loads(line[6:])["llmd"]
                # (the offset is read when the frame is written: the engine
                # may have stepped on by then, as for any model)
                assert meta["off"] >= len(ids)
                frames_of.append(len(meta["tok"]))
                ids.extend(meta["tok"])
        assert done and len(ids) == 11
        # greedy: the streamed answer is the whole one
        again = requests.post(url, json=dict(body, logprobs=0),
                              timeout=120).json()
        assert again["choices"][0]["text"] == whole["choices"][0]["text"]
    assert max(frames_of) > 1 and min(frames_of) >= 1
    text = requests.get(f"http://127.0.0.1:{port}/metrics", timeout=30).text
    assert 'llmd_tpu:diffusion_block_passes_total{kind="denoise"' in text
    assert 'llmd_tpu:diffusion_block_passes_total{kind="commit"' in text
    assert "llmd_tpu:diffusion_revealed_tokens_total" in text
