"""One host-to-device copy and one program launch per classic engine step.

What this pins:

  - the step's 14 batch arrays travel as ONE int32 buffer
    (``engine/packed_batch.py``) and come out of it, inside the program,
    bit for bit as the 14 separate arrays the engine used to copy:
    non-zero defaults (``qtok_idx`` sentinel ``T``, ``seeds`` -1, ``top_p``
    1.0) and the float bit patterns included, in a decode bucket, a mixed
    bucket and a stacked ``dp = 2`` batch;
  - the layout, not the buffer's length, picks the program;
  - the RNG key is split inside the program with the values the host-side
    split gave: the key stream and the sampled tokens are the parent's;
  - ``engine.step`` carries ``h2d_copies == 1`` and ``launches == 1`` on
    the classic path, and that is what ``jax.device_put`` and
    ``jax.random.split`` see;
  - the per-layer metric ``loop_h2d_copies`` reads the first.

All CPU, tier-1 safe.
"""

import json
import pathlib
import sys

import jax
import numpy as np
import pytest

from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.packed_batch import BatchLayout
from llm_d_tpu.engine.request import Request
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.parallel.mesh import MeshConfig
from llm_d_tpu.utils import tracing

REPO = pathlib.Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmarks"
sys.path.insert(0, str(BENCH))

from readers import span_stat  # noqa: E402

ENGINE_KW = dict(model="tiny", block_size=4, num_blocks=64, max_num_seqs=8,
                 max_num_batched_tokens=64, min_token_bucket=16,
                 min_seq_bucket=4)
STACKED = dict(mesh=MeshConfig(dp=2, sp=1, tp=1), allow_device_subset=True)
CTX = tracing.TraceContext("a" * 32, "b" * 16, True)


@pytest.fixture(autouse=True)
def _tracing_on(monkeypatch):
    monkeypatch.delenv("LLMD_TRACE", raising=False)
    monkeypatch.delenv("LLMD_TRACE_SAMPLE", raising=False)
    tracing.reset()
    yield
    tracing.reset()


def _engine(**kw):
    return EngineCore(EngineConfig(**{**ENGINE_KW, **kw}))


def _req(rid, prompt, n=8, **sampling):
    r = Request(request_id=rid, prompt_token_ids=list(prompt),
                sampling=SamplingParams(max_tokens=n, ignore_eos=True,
                                        **{"temperature": 0.0, **sampling}))
    r.trace_ctx = CTX
    return r


def _separate_arrays(T, S, Q, B):
    """The 14 arrays as the engine allocated them before they were packed
    (the parent commit's ``_empty_batch_np``, word for word)."""
    return dict(
        token_ids=np.zeros(T, np.int32),
        positions=np.zeros(T, np.int32),
        token_seq_ids=np.zeros(T, np.int32),
        token_qpos=np.zeros(T, np.int32),
        slot_mapping=np.zeros(T, np.int32),
        block_tables=np.zeros((S, B), np.int32),
        seq_lens=np.zeros(S, np.int32),
        sample_idx=np.zeros(S, np.int32),
        qtok_idx=np.full((S, Q), T, np.int32),
        temperature=np.zeros(S, np.float32),
        top_k=np.zeros(S, np.int32),
        top_p=np.ones(S, np.float32),
        seeds=np.full(S, -1, np.int32),
        gen_idx=np.zeros(S, np.int32))


def _fill_row_by_row(eng, arrs, scheduled, block_offset=0):
    """``EngineCore._fill_batch`` as it was before the arrays were filled a
    field at a time (the parent commit's loop, word for word)."""
    bs = eng.config.block_size
    t = 0
    for s, sr in enumerate(scheduled):
        req, n = sr.request, sr.num_new_tokens
        start = req.num_computed_tokens
        toks = req.all_token_ids[start:start + n]
        arrs["token_ids"][t:t + n] = toks
        pos_arr = np.arange(start, start + n)
        arrs["positions"][t:t + n] = pos_arr
        arrs["token_seq_ids"][t:t + n] = s
        blocks = np.asarray(req.block_ids, np.int32) - block_offset
        arrs["slot_mapping"][t:t + n] = \
            blocks[pos_arr // bs] * bs + pos_arr % bs
        arrs["token_qpos"][t:t + n] = np.arange(n)
        arrs["qtok_idx"][s, :n] = np.arange(t, t + n)
        nb = len(req.block_ids)
        arrs["block_tables"][s, :nb] = blocks
        arrs["seq_lens"][s] = start + n
        arrs["sample_idx"][s] = t + n - 1
        sp = req.sampling
        arrs["temperature"][s] = sp.temperature
        arrs["top_k"][s] = sp.top_k
        arrs["top_p"][s] = sp.top_p
        if sp.seed is not None:
            arrs["seeds"][s] = int(sp.seed) & 0x7FFFFFFF
        arrs["gen_idx"][s] = len(req.output_token_ids)
        t += n


def _bits(x):
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


def _same(got, want, ordered=False):
    # (a dict that came out of a jitted function has its keys sorted)
    assert (list if ordered else sorted)(got) == \
        (list if ordered else sorted)(want)
    for k in want:
        g, w = np.asarray(got[k]), want[k]
        assert g.dtype == w.dtype and g.shape == w.shape, k
        assert np.array_equal(_bits(g), _bits(w)), k


# Sampling parameters whose float32 bit patterns are not round numbers, a
# seed that needs the int32 mask, and rows left at their defaults.
MIXED_SAMPLING = [dict(temperature=0.7, top_p=0.9, top_k=5, seed=123),
                  dict(temperature=1.3, top_p=0.333, seed=2 ** 40 + 17),
                  dict(), dict(temperature=0.05, top_k=2)]


@pytest.mark.parametrize("case", ["decode", "mixed", "stacked"])
def test_program_unpacks_what_the_engine_used_to_copy(case, devices):
    eng = _engine(**(STACKED if case == "stacked" else {}))
    for i, sp in enumerate(MIXED_SAMPLING[:3] + [{}] * 2):
        eng.add_request(_req(f"r{i}", [1 + i, 2, 3, 4, 5, 6][:4 + i % 3],
                             n=12, **sp))
    for _ in range(3):
        eng.step()                       # prefills done: five decode rows
    if case != "decode":                 # a prompt joins the decodes: Q > 1
        eng.add_request(_req("late", range(30, 49), **MIXED_SAMPLING[3]))
    sched = eng.scheduler.schedule()
    packed, layout, scheduled, rows = eng._build_batch(sched)

    T, S, Q, B = layout.T, layout.S, layout.Q, layout.B
    assert (Q == 1) == (case == "decode") and B == eng.max_blocks_per_seq
    assert layout.dp == (2 if case == "stacked" else 1)
    assert packed.dtype == np.int32 and packed.shape == layout.shape
    if case == "stacked":
        per = eng._split_by_shard(sched.scheduled)
        assert all(per) and scheduled == per[0] + per[1]
        shards = []
        for r, shard in enumerate(per):
            arrs = _separate_arrays(T, S, Q, B)
            _fill_row_by_row(
                eng, arrs, shard,
                block_offset=r * eng.kv_manager.blocks_per_region)
            shards.append(arrs)
        want = {k: np.stack([a[k] for a in shards]) for k in shards[0]}
        assert packed.sharding.is_equivalent_to(eng._dp_sharded, 2)
    else:
        want = _separate_arrays(T, S, Q, B)
        _fill_row_by_row(eng, want, sched.scheduled)
        assert list(rows) == list(range(len(scheduled)))
    # The defaults that are not zero are there to be seen in padded rows.
    n_rows = len(scheduled) if case != "stacked" else max(map(len, per))
    assert n_rows < S and (want["seeds"][..., -1] == -1).all()
    assert (want["top_p"][..., -1] == 1.0).all()
    assert (want["qtok_idx"][..., -1, :] == T).all()
    assert np.float32(0.7).view(np.int32) in _bits(want["temperature"])
    assert np.float32(0.333).view(np.int32) in _bits(want["top_p"])

    got = jax.jit(layout.unpack)(packed)
    _same(got, want)
    assert got["temperature"].dtype == got["top_p"].dtype == np.float32


@pytest.mark.parametrize("bucket", [(16, 4, 1, 16), (64, 8, 32, 16)])
def test_empty_batch_keeps_its_signature_and_defaults(bucket):
    eng = _engine()
    arrs = eng._empty_batch_np(*bucket)
    _same(arrs, _separate_arrays(*bucket), ordered=True)
    # Views of ONE buffer: filling them fills what is copied.
    root = arrs["token_ids"]
    while root.base is not None:
        root = root.base
    assert all(np.shares_memory(a, root) for a in arrs.values())
    assert root.shape == BatchLayout(*bucket).shape
    # The dict form of the step program still takes it (tools lower it).
    lowered = eng._build_step_fn().lower(
        eng.params, eng.kv_cache, jax.tree.map(np.asarray, arrs), eng._rng)
    assert "step_body" in lowered.as_text()[:400]


def test_layouts_of_one_length_reach_two_programs(monkeypatch):
    a, b = BatchLayout(16, 8, 1, 7), BatchLayout(16, 4, 16, 7)
    assert a.shape == b.shape == (200,) and a != b and hash(a) != hash(b)
    traced = []
    real = BatchLayout.unpack
    monkeypatch.setattr(BatchLayout, "unpack", lambda self, packed: (
        traced.append(self), real(self, packed))[1])
    eng = _engine()
    fn = eng._build_step_fn(packed=True)
    rows = []
    for layout in (a, b, a, b):
        buf = jax.device_put(layout.new_buffer(), eng._replicated)
        out = fn(eng.params, eng.kv_cache, buf, eng._rng, *eng._fed, layout)
        eng.kv_cache, eng._rng, eng._fed = out[2], out[-1], (out[0],)
        rows.append((out[0].shape, out[1].shape))
    # The ids come back in the fed operand's shape whatever the bucket
    # (any step's ids feed any bucket's program); the rest by sequence row.
    M = eng.config.max_num_seqs
    assert rows == [((M,), (8,)), ((M,), (4,))] * 2
    assert traced == [a, b]           # one program a layout, made once
    with pytest.raises(ValueError, match="packed batch"):
        real(a, np.zeros(199, np.int32))


# The parent commit (host-side split: ``self._rng, step_key =
# jax.random.split(self._rng)`` before every step), engine seed 7, the three
# requests below at temperature 1.0 without a seed.
RECORDED = {"a": [322, 464, 131, 464, 131, 99, 33, 479, 5, 403, 12, 157],
            "b": [268, 145, 39, 467, 72, 410, 280, 179, 436],
            "c": [4, 468, 375, 129, 45, 362]}
RECORDED_STEPS, RECORDED_KEY = 12, [1882804955, 1173222465]


def _sampled(eng):
    return eng.generate([
        _req(r, p, n=n, temperature=1.0)
        for r, p, n in (("a", [1, 2, 3, 4, 5], 12), ("b", [9, 8, 7], 9),
                        ("c", list(range(20, 60)), 6))])


def test_key_stream_and_sampled_tokens_are_the_host_splits():
    eng = _engine(seed=7)
    out = _sampled(eng)
    assert {k: list(v) for k, v in out.items()} == RECORDED
    assert eng.step_count == RECORDED_STEPS
    key = jax.random.PRNGKey(7)
    for _ in range(eng.step_count):
        key, _ = jax.random.split(key)
    assert np.asarray(eng._rng).tolist() == np.asarray(key).tolist() \
        == RECORDED_KEY
    assert isinstance(eng._rng, jax.Array)        # never fetched

    # The same engine driven as the parent drove it: the host splits the
    # key, the dict-form program takes 14 arrays and a ready step key.
    host = _engine(seed=7)
    body = host._build_step_fn()

    def parent_step(params, kv_cache, packed, rng, prev_ids, layout):
        rng, step_key = jax.random.split(rng)
        batch = jax.tree.map(np.asarray, layout.unpack(packed))
        # No slot is full here, so no step runs ahead and no row names a
        # token of the previous step: the parent's program serves as it is.
        assert (batch["token_ids"] >= 0).all()
        ids, *rest = body(params, kv_cache, batch, step_key)
        return (np.pad(ids, (0, len(prev_ids) - len(ids))), *rest, rng)

    host._step_fn = parent_step
    assert _sampled(host) == out


@pytest.mark.parametrize("mode", ["single", "stacked"])
def test_one_copy_and_one_launch_a_step(mode, monkeypatch, devices):
    eng = _engine(**(STACKED if mode == "stacked" else {}))
    puts, host_splits = [], []
    real_put, real_split = jax.device_put, jax.random.split

    def put(x, *a, **kw):
        puts.append(len(jax.tree.leaves(x)))
        return real_put(x, *a, **kw)

    def split(key, *a, **kw):
        host_splits.append(isinstance(key, jax.core.Tracer))
        return real_split(key, *a, **kw)

    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(jax.random, "split", split)
    per_step = []
    for i in range(3):
        eng.add_request(_req(f"r{i}", [1 + i, 2, 3, 4, 5], n=6,
                             temperature=float(i)))
    while eng.has_work():
        before = len(puts)
        eng.step()
        per_step.append(puts[before:])
    steps = [s["attrs"] for s in eng.tracer.snapshot()
             if s["name"] == "engine.step"]
    assert len(steps) == len(per_step) >= 6
    assert all(a["h2d_copies"] == 1 and a["launches"] == 1 for a in steps)
    assert all(p == [1] for p in per_step), per_step   # one call, one leaf
    # No split on the host, and none traced into a bucket's program either:
    # the engine lowered it once, when it was built (its lowering cost a
    # tenth of a second a program on the chip's host: setup_s).
    assert host_splits == []
    # One program a bucket: the key that comes back is the key that went in.
    assert eng._step_fn._cache_size() == len(
        {(a["kind"] == "decode") for a in steps}) == 2


def test_loop_h2d_copies_metric_reads_the_span():
    with open(BENCH / "layer_metrics" / "loop_h2d_copies.json") as f:
        m = json.load(f)
    with open(REPO / "BENCHMARK.json") as f:
        (entry,) = [e for e in json.load(f)["per_layer"]
                    if e["name"] == "loop_h2d_copies"]
    assert {k: m[k] for k in entry} == entry and "workloads" not in entry
    assert (m["reader"], m["unit"], m["better"], m["layer"], m["moves"]) == (
        "span_stat", "copies", "lower", "engine step loop", "ttft_p50_ms")

    def span(**attrs):
        return {"name": "engine.step", "ts": 0.0, "dur": 0.01, "attrs": attrs}

    spans = [span(h2d_copies=1, launches=1), span(h2d_copies=1),
             span(h2d_copies=14), span(build_ms=2.0)]
    assert span_stat.read({"spans": spans}, **m["args"]) == 1.0
    # The parent's spans carry no such attribute: nothing read, no raise.
    assert span_stat.read({"spans": spans[3:]}, **m["args"]) is None
    eng = _engine()
    eng.generate([_req("a", [1, 2, 3, 4, 5], n=4)])
    assert span_stat.read({"spans": eng.tracer.snapshot()}, **m["args"]) == 1


# ---------------------------------------------------------------------------
# tokens fed on the device (the classic path one step ahead, engine.py)
# ---------------------------------------------------------------------------

def test_one_program_a_bucket_fed_or_not():
    """A decode step whose rows name tokens of the previous step's ids
    (``-(row + 1)``) samples what the same step given the ids does, from
    the SAME program; padding and real tokens pass through as they are."""
    from llm_d_tpu.engine.packed_batch import feed_tokens
    prev = np.asarray([17, 4, 250, 99, 3, 0, 0, 0], np.int32)
    tok = np.asarray([5, -1, -3, 0, -4, 7], np.int32)
    got = jax.jit(feed_tokens)({"token_ids": tok, "x": tok}, prev)
    assert np.asarray(got["token_ids"]).tolist() == [5, 17, 250, 0, 99, 7]
    assert np.array_equal(got["x"], tok)             # nothing else touched

    def step(named: bool):
        eng = _engine(seed=3)
        eng.generate([_req("a", [1, 2, 3, 4, 5], n=3),
                      _req("b", [9, 8, 7], n=3)])      # a cache worth reading
        layout = BatchLayout(16, 4, 1, eng.max_blocks_per_seq)
        buf = layout.new_buffer()
        v = layout.views(buf)
        v["token_ids"][:2] = [-2, -1] if named else [4, 17]
        v["positions"][:2] = [3, 5]
        v["token_seq_ids"][:2] = [0, 1]
        v["slot_mapping"][:2] = [4 + 3, 8 + 1]
        v["block_tables"][0, :1], v["block_tables"][1, :2] = [1], [1, 2]
        v["seq_lens"][:2] = [4, 6]
        v["sample_idx"][:2] = [0, 1]
        v["qtok_idx"][:2, 0] = [0, 1]
        before = eng._step_fn._cache_size()
        ids, lps, *_ = eng._step_fn(
            eng.params, eng.kv_cache, jax.device_put(buf, eng._replicated),
            eng._rng, jax.device_put(prev, eng._replicated), layout)
        return (np.asarray(ids), np.asarray(lps),
                eng._step_fn._cache_size() - before)

    ids_n, lps_n, compiled_n = step(named=True)
    ids_r, lps_r, compiled_r = step(named=False)
    assert ids_n.shape == (8,) and lps_n.shape == (4,)
    assert np.array_equal(ids_n, ids_r) and np.array_equal(lps_n, lps_r)
    # The bucket (16, 4, 1) was compiled by ``generate``'s own decode steps:
    # neither call made a program.
    assert compiled_n == compiled_r == 0


def test_block_diffusion_program_is_the_parent_s():
    """A block-diffusion engine's step (R > 1) takes no fed operand: its
    served program lowers to the text of the parent commit's construction,
    written out here (the key's split called from the one exported module,
    the dict-form body over the unpacked buffer)."""
    import functools

    import jax.numpy as jnp
    eng = EngineCore(EngineConfig(
        model="tiny-sdar", block_size=16, num_blocks=64, max_num_seqs=8,
        max_num_batched_tokens=64))
    assert eng.block_length == 4 and eng._fed == () and not eng._runs_ahead
    body = eng._build_step_fn().__wrapped__
    split = jax.export.export(
        jax.jit(jax.random.split),
        platforms=(eng.mesh.devices.flat[0].platform,))(
            jax.ShapeDtypeStruct((2,), jnp.uint32))

    @functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(1,))
    def step_fn(params, kv_cache, buffer, rng, layout):
        rng, step_key = split.call(rng)
        return (*body(params, kv_cache, layout.unpack(buffer), step_key),
                rng)

    for bucket in ((16, 4, 16), (64, 8, 32)):
        layout = BatchLayout(*bucket, eng.max_blocks_per_seq, R=4)
        args = (eng.params, eng.kv_cache, layout.new_buffer(), eng._rng,
                layout)
        served = eng._step_fn.lower(*args)
        assert served.as_text() == step_fn.lower(*args).as_text()
        # ids come back [S, B] as the retire of a block-diffusion step
        # reads them, not padded to the slots.
        assert served.out_info[0].shape == (bucket[1], 4)
    # And the stacked autoregressive program takes none either.
    stacked = _engine(**STACKED)
    assert stacked._fed == () and not stacked._runs_ahead
