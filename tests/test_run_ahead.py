"""The classic step path one step ahead: where every sequence slot is taken
the engine composes and launches step N+1 before it fetches step N's tokens
(``EngineCore._may_run_ahead`` / ``_launch`` / ``_retire``).

What this pins, on small engines on the CPU:

  - the same requests through an engine whose slots are all taken (runs
    ahead) and one with slots to spare (never does) give the same tokens,
    logprobs and finish reasons: greedy, seeded sampling, top logprobs, a
    chunked prefill whose last chunk feeds the first decode row on the
    device, prefix-cache hits;
  - a stop the host cannot foresee (EOS, a stop string, an abort, a
    deadline) wastes exactly the row already launched, emits nothing after
    the stop and frees the blocks once; a stop by length wastes nothing and
    the step after it is composed in today's order;
  - the gate is off where the pool has no head-room (no row in flight is
    preempted), and on the other step paths;
  - the prefix cache never hashes a placeholder;
  - ``run_ahead`` / ``wasted_rows`` on the span, both counters on /metrics,
    the ``run_ahead_share`` reader (one ``engine.step`` a step, extents
    that do not overlap, phases that add up: tests/test_step_phases.py; the
    fed program: tests/test_packed_batch.py).
"""

import json
import pathlib
import sys
import time

import numpy as np
import pytest

from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.kv_cache import KVCacheManager
from llm_d_tpu.engine.request import Request, RequestState
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.utils import tracing

REPO = pathlib.Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmarks"
sys.path.insert(0, str(BENCH))

from readers import span_ratio  # noqa: E402

KW = dict(model="tiny", block_size=4, num_blocks=256,
          max_num_batched_tokens=32, min_token_bucket=16, min_seq_bucket=4)
CTX = tracing.TraceContext("a" * 32, "b" * 16, True)


@pytest.fixture(autouse=True)
def _tracing_on(monkeypatch):
    monkeypatch.delenv("LLMD_TRACE", raising=False)
    monkeypatch.delenv("LLMD_TRACE_SAMPLE", raising=False)
    tracing.reset()
    yield
    tracing.reset()


def _engine(slots, **kw):
    tracing.reset()     # the engine takes its tracer at construction
    return EngineCore(EngineConfig(**{**KW, "max_num_seqs": slots, **kw}))


def _prompt(i, n):
    return [(37 * i + 11 * j) % 250 + 1 for j in range(n)]


def _req(rid, prompt, n=8, ignore_eos=True, **sampling):
    sampling.setdefault("temperature", 0.0)
    r = Request(request_id=rid, prompt_token_ids=list(prompt),
                sampling=SamplingParams(max_tokens=n, ignore_eos=ignore_eos,
                                        **sampling))
    r.trace_ctx = CTX
    return r


def _steps(eng):
    return [s for s in eng.tracer.snapshot() if s["name"] == "engine.step"]


def _run(eng, reqs, each_step=None, max_steps=2000):
    """Step to the end; per request (tokens, logprobs, top logprobs, finish
    reason), and every output in the order it was emitted."""
    for r in reqs:
        eng.add_request(r)
    got = {r.request_id: ([], [], [], []) for r in reqs}
    emitted = []
    for i in range(max_steps):
        if not eng.has_work():
            break
        if each_step is not None:
            each_step(eng, i)
        for out in eng.step():
            emitted.append(out)
            toks, lps, tops, fin = got[out.request_id]
            assert not fin, f"{out.request_id}: output after its finish"
            toks += out.new_token_ids
            lps += out.logprobs or []
            tops += out.top_logprobs or []
            if out.finished:
                fin.append(out.finish_reason)
    assert not eng.has_work()
    return got, emitted


def _same(a, b):
    """Two runs' results: the same tokens, finish reasons and alternatives,
    logprobs to rounding (the two engines run other batch buckets)."""
    assert a.keys() == b.keys()
    for rid in a:
        assert a[rid][0] == b[rid][0], rid
        assert a[rid][3] == b[rid][3], rid
        np.testing.assert_allclose(a[rid][1], b[rid][1], atol=2e-3)
        assert [sorted(t) for t in a[rid][2]] \
            == [sorted(t) for t in b[rid][2]], rid


def _free_pool(eng):
    """Every block is back, none twice (a double free would raise in
    ``_release`` or show up as a duplicate here)."""
    km = eng.kv_manager
    ids = list(km._free[0]) + list(km._evictor[0])
    assert len(ids) == len(set(ids)) == km.num_blocks - 1
    assert not km._ref


# ---------------------------------------------------------------------------
# the same streams with and without running ahead
# ---------------------------------------------------------------------------

def _mix(kind):
    if kind == "greedy":
        return [_req(f"r{i}", _prompt(i, 5 + 3 * i), n=6 + 2 * i)
                for i in range(6)]
    if kind == "seeded":
        return [_req(f"r{i}", _prompt(i, 4 + 2 * i), n=7 + i,
                     temperature=0.9, top_k=40, top_p=0.95, seed=1000 + i,
                     logprobs=0)
                for i in range(6)]
    if kind == "top_logprobs":
        return [_req(f"r{i}", _prompt(i, 6 + i), n=5 + i,
                     logprobs=(2 if i % 2 else 0))
                for i in range(5)]
    if kind == "chunked":
        # 32 tokens a step: the long prompts take several chunks, and the
        # last chunk's sampled token is the next step's input, on the device.
        return [_req(f"r{i}", _prompt(i, (70, 9, 45, 7, 90, 5)[i]),
                     n=5 + 2 * i) for i in range(6)]
    if kind == "prefix":
        shared = _prompt(99, 24)        # six whole blocks of four
        return [_req(f"r{i}", shared + _prompt(i, 3 + i), n=6 + i)
                for i in range(7)]
    raise AssertionError(kind)


@pytest.mark.parametrize("kind", ["greedy", "seeded", "top_logprobs",
                                  "chunked", "prefix"])
def test_same_streams_with_slots_taken_and_to_spare(kind):
    full, spare = _engine(4), _engine(16)
    a, _ = _run(full, _mix(kind))
    b, _ = _run(spare, _mix(kind))
    _same(a, b)
    ahead = [s["attrs"]["run_ahead"] for s in _steps(full)]
    assert sum(ahead) >= 4, ahead
    assert not any(s["attrs"]["run_ahead"] for s in _steps(spare))
    assert all(s["attrs"]["wasted_rows"] == 0
               for s in _steps(full) + _steps(spare))
    # One program a bucket, fed or not: the steps that ran ahead compiled
    # nothing the others had not.
    buckets = {(s["attrs"]["kind"] == "decode", s["attrs"]["n_seqs"] > 4)
               for s in _steps(full)}
    fns = [full._step_fn] + ([full._step_fn_top] if kind == "top_logprobs"
                             else [])
    assert sum(fn._cache_size() for fn in fns) <= 2 * len(buckets) + 2
    assert full.metrics.run_ahead_steps._value.get() == sum(ahead)
    if kind == "prefix":
        hits = [r.metrics.prefix_cache_hits._value.get()
                for r in (full, spare)]
        assert min(hits) >= 24
    _free_pool(full)
    _free_pool(spare)


def test_chunked_prefill_last_chunk_feeds_first_decode_row():
    """The step after a prompt's last chunk runs ahead: its decode row's
    input is the token that chunk is still sampling, named by its row."""
    eng = _engine(2)
    seen = []
    real = eng._build_batch

    def spy(sched):
        out = real(sched)
        seen.append([list(sr.request.all_token_ids[
            sr.request.num_computed_tokens:
            sr.request.num_computed_tokens + sr.num_new_tokens])
            for sr in sched.scheduled])
        return out

    eng._build_batch = spy
    a, _ = _run(eng, [_req("long", _prompt(1, 70), n=4),
                      _req("short", _prompt(2, 6), n=9)])
    b, _ = _run(_engine(8), [_req("long", _prompt(1, 70), n=4),
                             _req("short", _prompt(2, 6), n=9)])
    _same(a, b)
    fed = [[t for row in step for t in row if t < 0] for step in seen]
    assert any(fed), "no step took a token from the device"
    # A fed entry names a row of the previous step: -(row + 1).
    assert all(-2 <= t <= -1 for step in fed for t in step)
    kinds = [(s["attrs"]["kind"], s["attrs"]["run_ahead"])
             for s in _steps(eng)]
    # Some step that ran ahead followed a step with prefill tokens.
    assert any(k0 != "decode" and ahead1
               for (k0, _), (_, ahead1) in zip(kinds, kinds[1:])), kinds


def test_a_first_token_leads_the_step_s_outputs():
    """The server writes a step's frames one after the other: the frame a
    new request waits for goes first, whatever its row."""
    eng = _engine(4)
    for i in range(3):
        eng.add_request(_req(f"old{i}", _prompt(i, 5), n=20))
    for _ in range(3):
        eng.step()
    eng.add_request(_req("new", _prompt(9, 7), n=5))
    outs = eng.step()
    while not any(o.request_id == "new" for o in outs):
        outs = eng.step()
    assert len(outs) == 4 and outs[0].request_id == "new"
    assert {o.request_id for o in outs[1:]} == {"old0", "old1", "old2"}


# ---------------------------------------------------------------------------
# stops
# ---------------------------------------------------------------------------

class _Tok:
    """A tokenizer for stop strings: every id prints as <id>."""

    def decode(self, ids):
        return "".join(f"<{i}>" for i in ids)


def _stop_setup(how):
    """Four requests on four slots; ``r1`` is made to stop in mid-answer on
    a token of its own greedy stream."""
    base, _ = _run(_engine(16), [
        _req(f"r{i}", _prompt(i, 5 + i), n=14) for i in range(4)])
    stream = base["r1"][0]
    k = next(i for i in range(3, 12) if stream[i] not in stream[:i])
    stopper = stream[k]

    def reqs():
        out = [_req(f"r{i}", _prompt(i, 5 + i), n=14) for i in range(4)]
        if how == "eos":
            out[1] = _req("r1", _prompt(1, 6), n=14, ignore_eos=False)
        else:
            out[1] = _req("r1", _prompt(1, 6), n=14, stop=[f"<{stopper}>"])
        return out

    def arm(eng):
        if how == "eos":
            eng.eos_token_id = stopper
        else:
            eng.tokenizer = _Tok()
        return eng

    return base, k, reqs, arm


@pytest.mark.parametrize("how", ["eos", "stop_string"])
def test_stop_in_mid_flight_wastes_exactly_one_row(how):
    base, k, reqs, arm = _stop_setup(how)
    full, spare = arm(_engine(4)), arm(_engine(16))
    a, emitted = _run(full, reqs())
    b, _ = _run(spare, reqs())
    _same(a, b)
    assert a["r1"][0] == base["r1"][0][:k + 1] and a["r1"][3] == ["stop"]
    assert all(a[f"r{i}"][0] == base[f"r{i}"][0] for i in (0, 2, 3))
    # Nothing after the stop (``_run`` asserts it output by output too).
    last = max(i for i, o in enumerate(emitted) if o.request_id == "r1")
    assert emitted[last].finished
    steps = [s["attrs"] for s in _steps(full)]
    assert sum(a_["wasted_rows"] for a_ in steps) == 1
    (at,) = [i for i, a_ in enumerate(steps) if a_["wasted_rows"]]
    assert steps[at]["run_ahead"] == 1 and steps[at]["n_seqs"] == 4
    # The step after the wasted one is composed in today's order: the slot
    # came free unforeseen, and nothing was in flight to run ahead of.
    assert steps[at + 1]["run_ahead"] == 0 and steps[at + 1]["n_seqs"] == 3
    assert full.metrics.run_ahead_wasted_rows._value.get() == 1
    assert spare.metrics.run_ahead_wasted_rows._value.get() == 0
    assert not any(a_["wasted_rows"] or a_["run_ahead"]
                   for a_ in (s["attrs"] for s in _steps(spare)))
    _free_pool(full)


def test_stop_by_length_wastes_nothing_and_next_step_is_in_order():
    full = _engine(4)
    reqs = [_req(f"r{i}", _prompt(i, 5 + i), n=(5, 9, 9, 9)[i])
            for i in range(4)]
    a, _ = _run(full, reqs)
    assert [len(a[f"r{i}"][0]) for i in range(4)] == [5, 9, 9, 9]
    assert all(a[f"r{i}"][3] == ["length"] for i in range(4))
    steps = [s["attrs"] for s in _steps(full)]
    assert sum(a_["wasted_rows"] for a_ in steps) == 0
    assert full.metrics.run_ahead_wasted_rows._value.get() == 0
    ahead = [a_["run_ahead"] for a_ in steps]
    rows = [a_["n_seqs"] for a_ in steps]
    # One prefill step, then decode steps: r0's fifth token comes with the
    # fifth step, which the gate saw coming: steps 1-3 ran ahead, step 4
    # (r0's last) too, the step behind it did not, and with a slot free
    # none does after.
    assert rows == [4] * 5 + [3] * 4
    assert ahead == [0, 1, 1, 1, 1, 0, 0, 0, 0]
    _free_pool(full)


def test_max_model_len_is_a_length_the_gate_foresees():
    import dataclasses

    from llm_d_tpu.models.config import get_config
    short = dataclasses.replace(get_config("tiny"), max_model_len=24)
    results = []
    for slots in (2, 8):
        eng = _engine(slots, model_config=short)
        got, _ = _run(eng, [_req("a", _prompt(1, 10), n=50),
                            _req("b", _prompt(2, 6), n=50)])
        results.append(got)
        assert len(got["a"][0]) == 24 - 10 and len(got["b"][0]) == 24 - 6
        assert all(s["attrs"]["wasted_rows"] == 0 for s in _steps(eng))
        if slots == 2:
            assert sum(s["attrs"]["run_ahead"] for s in _steps(eng)) >= 8
    _same(*results)


def test_abort_while_a_step_is_in_flight():
    full = _engine(4)
    reqs = [_req(f"r{i}", _prompt(i, 5 + i), n=12) for i in range(4)]

    def abort(eng, i):
        if i == 4:
            assert eng._ahead is not None       # a step is on the device
            assert any(sr.request.request_id == "r2"
                       for sr in eng._ahead.scheduled)
            eng.abort_request("r2")

    a, emitted = _run(full, reqs, each_step=abort)
    b, _ = _run(_engine(16), [_req(f"r{i}", _prompt(i, 5 + i), n=12)
                              for i in range(4)])
    for rid in ("r0", "r1", "r3"):
        assert a[rid][0] == b[rid][0] and a[rid][3] == ["length"]
    # Tokens of the steps retired before the abort, none after it, and no
    # finish frame (the engine emits none for an abort).
    assert a["r2"][0] == b["r2"][0][:len(a["r2"][0])] and not a["r2"][3]
    assert 2 <= len(a["r2"][0]) <= 4
    assert reqs[2].state is RequestState.FINISHED_ABORTED
    assert not reqs[2].inflight_token_ids and not reqs[2].block_ids
    steps = [s["attrs"] for s in _steps(full)]
    assert sum(a_["wasted_rows"] for a_ in steps) == 1
    _free_pool(full)


def test_deadline_in_flight_drops_the_row():
    full = _engine(2)
    reqs = [_req("a", _prompt(1, 6), n=10), _req("b", _prompt(2, 7), n=10)]

    def expire(eng, i):
        if i == 3:
            reqs[1].deadline = time.monotonic() - 1.0

    got, emitted = _run(full, reqs, each_step=expire)
    assert got["b"][3] == ["deadline"] and len(got["b"][0]) < 10
    assert got["a"][3] == ["length"] and len(got["a"][0]) == 10
    ref, _ = _run(_engine(8), [_req("a", _prompt(1, 6), n=10)])
    assert got["a"][0] == ref["a"][0]
    _free_pool(full)


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------

def test_pool_without_head_room_turns_the_gate_off():
    """Four rows whose next tokens need more blocks than the pool has left:
    composing ahead could only preempt a row in flight, so the gate fails,
    the step in flight retires, and the scheduler preempts in today's
    order, with nothing in flight."""
    tight = _engine(4, num_blocks=14)
    reqs = [_req(f"r{i}", _prompt(i, 6 + i), n=10) for i in range(4)]
    in_flight_at_preemption = []
    real = tight.scheduler._preempt_for

    def spy(needy, preempted_now, scheduled_ids):
        in_flight_at_preemption.append(
            [r.request_id for r in tight.scheduler.running
             if r.inflight_token_ids])
        return real(needy, preempted_now, scheduled_ids)

    tight.scheduler._preempt_for = spy
    got, _ = _run(tight, reqs)
    ref, _ = _run(_engine(16), [_req(f"r{i}", _prompt(i, 6 + i), n=10)
                                for i in range(4)])
    for rid in got:        # a preempted row recomputes to the same tokens
        assert got[rid][0] == ref[rid][0] and got[rid][3] == ["length"]
    assert tight.scheduler.num_preemptions >= 1
    assert in_flight_at_preemption \
        and not any(in_flight_at_preemption), in_flight_at_preemption
    steps = [s["attrs"] for s in _steps(tight)]
    full_rows = [a["run_ahead"] for a in steps if a["n_seqs"] == 4]
    assert full_rows[0] == 0 and sum(full_rows) >= 2
    # The step that preempted held three rows and was composed in order.
    first_short = next(a for a in steps if a["n_seqs"] == 3)
    assert first_short["run_ahead"] == 0
    assert sum(a["wasted_rows"] for a in steps) == 0
    _free_pool(tight)


def test_gate_needs_head_room_for_every_running_row():
    eng = _engine(2, num_blocks=64)
    eng.add_request(_req("a", _prompt(1, 7), n=20))
    eng.add_request(_req("b", _prompt(2, 7), n=20))
    # The prompts' step retires; the first decode step (position 7, the
    # second block's last slot) is in flight behind it.
    eng.step()
    assert eng._ahead is not None
    assert [(r.num_computed_tokens, len(r.block_ids), r.inflight_token_ids)
            for r in eng.scheduler.running] == [(8, 2, [-1]), (8, 2, [-2])]
    # The step after it needs a new block a row.
    free = eng.kv_manager._free[0]
    spare = [free.pop() for _ in range(len(free))]
    for left, want in ((0, False), (1, False), (2, True), (3, True)):
        free.extend(spare[:left])
        assert eng._may_run_ahead() is want, left
        for _ in range(left):
            free.pop()
    free.extend(spare)
    # A row the step in flight finishes by length frees a slot: gate off.
    assert eng._may_run_ahead()
    row = eng.scheduler.running[0]
    row.sampling = SamplingParams(temperature=0.0, max_tokens=2,
                                  ignore_eos=True)
    assert not eng._may_run_ahead()
    row.sampling = SamplingParams(temperature=0.0, max_tokens=20,
                                  ignore_eos=True)
    assert eng._may_run_ahead()
    # A free slot: gate off whatever the pool holds.
    eng.scheduler.max_num_seqs = 3
    assert not eng._may_run_ahead()


@pytest.mark.parametrize("kw", [dict(num_scheduler_steps=4), dict(spec_k=2),
                                dict(enable_eplb=True, model="tiny-moe"),
                                dict(kv_offload_blocks=8),
                                dict(model="tiny-sdar", block_size=16)],
                         ids=["multistep", "spec", "eplb", "host_tier",
                              "block_diffusion"])
def test_other_engines_never_run_ahead(kw):
    eng = _engine(2, **kw)
    assert eng._runs_ahead is False
    got, _ = _run(eng, [_req("a", _prompt(1, 6), n=6),
                        _req("b", _prompt(2, 9), n=6)])
    assert all(len(got[r][0]) == 6 for r in got)
    assert eng._ahead is None
    assert not any(s["attrs"]["run_ahead"] for s in _steps(eng))
    assert eng.metrics.run_ahead_steps._value.get() == 0


def test_has_work_while_a_step_is_in_flight():
    eng = _engine(2)
    reqs = [_req("a", _prompt(1, 6), n=6), _req("b", _prompt(2, 7), n=6)]
    for r in reqs:
        eng.add_request(r)
    eng.step()
    eng.step()
    assert eng._ahead is not None
    for r in reqs:
        eng.abort_request(r.request_id)
    # The scheduler holds nothing, the device still holds a step.
    assert not eng.scheduler.has_work() and eng.has_work()
    assert eng.step() == []
    assert not eng.has_work() and eng._ahead is None
    assert _steps(eng)[-1]["attrs"]["wasted_rows"] == 2
    _free_pool(eng)


# ---------------------------------------------------------------------------
# the prefix cache sees confirmed tokens only
# ---------------------------------------------------------------------------

def test_cache_full_blocks_never_hashes_a_placeholder():
    km = KVCacheManager(32, 4)
    req = Request("r", [1, 2, 3, 4, 5, 6], SamplingParams(max_tokens=9))
    km.allocate(req, 8)
    req.output_token_ids.append(7)
    req.inflight_token_ids.append(-1)       # would complete the second block
    req.num_computed_tokens = 8
    assert req.num_tokens == 8 and req.all_token_ids[-1] == -1
    km.cache_full_blocks(req)
    assert len(km.request_block_hashes(req)) == 1       # [1, 2, 3, 4] only
    assert set(km._hash_of) == {req.block_ids[0]}
    req.inflight_token_ids.pop(0)
    req.output_token_ids.append(8)
    km.cache_full_blocks(req)
    assert set(km._hash_of) == set(req.block_ids[:2])
    # The same chain a request that never held a placeholder gets.
    twin = Request("t", [1, 2, 3, 4, 5, 6], SamplingParams(max_tokens=9))
    twin.output_token_ids += [7, 8]
    assert km.request_block_hashes(twin) == km.request_block_hashes(req)


def test_engine_hashes_only_confirmed_tokens():
    eng = _engine(2)
    seen = []
    real = eng.kv_manager.request_block_hashes

    def spy(request):
        seen.append((list(request.inflight_token_ids),
                     list(request.output_token_ids)))
        return real(request)

    eng.kv_manager.request_block_hashes = spy
    _run(eng, [_req("a", _prompt(1, 6), n=12), _req("b", _prompt(2, 7), n=12)])
    assert any(inflight for inflight, _ in seen)   # hashed with one in flight
    assert all(t >= 0 for _, out in seen for t in out)
    stored = []
    eng2 = _engine(2)
    eng2.kv_manager.on_block_stored.append(lambda h, b: stored.append(h))
    reqs = [_req("a", _prompt(1, 6), n=12), _req("b", _prompt(2, 7), n=12)]
    _run(eng2, reqs)
    plain = KVCacheManager(64, 4)
    want = {h for r in reqs for h in plain.request_block_hashes(r)}
    assert stored and set(stored) <= want


# ---------------------------------------------------------------------------
# the span, the counters, the reader
# ---------------------------------------------------------------------------

def test_counters_on_metrics_and_the_reader():
    _, _, reqs, arm = _stop_setup("eos")
    eng = arm(_engine(4))
    _run(eng, reqs())
    text = eng.metrics.render().decode()
    steps = [s["attrs"] for s in _steps(eng)]
    n_ahead = sum(a["run_ahead"] for a in steps)
    assert n_ahead >= 4
    assert f'llmd_tpu:run_ahead_steps_total{{model_name="tiny"}} ' \
        f'{float(n_ahead)}' in text
    assert 'llmd_tpu:run_ahead_wasted_rows_total{model_name="tiny"} 1.0' \
        in text
    with open(BENCH / "layer_metrics" / "run_ahead_share.json") as f:
        m = json.load(f)
    with open(REPO / "BENCHMARK.json") as f:
        (entry,) = [e for e in json.load(f)["per_layer"]
                    if e["name"] == "run_ahead_share"]
    assert {k: m[k] for k in entry if k != "workloads"} \
        == {k: v for k, v in entry.items() if k != "workloads"}
    assert entry["workloads"] == ["kanana2.batch"]
    assert (m["reader"], m["unit"], m["better"], m["layer"], m["moves"]) == (
        "span_ratio", "%", "higher", "engine step loop", "out_tok_s")
    share = span_ratio.read({"spans": eng.tracer.snapshot()}, **m["args"])
    assert share == pytest.approx(100.0 * n_ahead / len(steps))
    # The parent's spans carry no ``run_ahead``: nothing read, no raise.
    bare = [{"name": "engine.step", "ts": 0.0, "dur": 0.01,
             "attrs": {"rounds": 1, "decode_tokens": 4}}]
    assert span_ratio.read({"spans": bare}, **m["args"]) is None


# ---------------------------------------------------------------------------
# behind the async front-end: the server's thread reads confirmed tokens
# ---------------------------------------------------------------------------

def test_streams_through_the_async_engine_on_a_full_engine():
    """Four streams on four slots through ``AsyncEngine``, one client gone
    in mid-answer (an abort that lands while a step is in flight): the
    others get the tokens an engine with slots to spare gives, and whatever
    the event loop reads in ``output_token_ids`` is a confirmed token."""
    import asyncio

    from llm_d_tpu.engine.async_engine import AsyncEngine

    def reqs():
        return [_req(f"r{i}", _prompt(i, 5 + i), n=24) for i in range(4)]

    ref, _ = _run(_engine(16), reqs())
    eng = _engine(4)
    live = reqs()
    seen_negative = []

    async def one(ae, r, quit_after=None):
        toks = []
        async for out in ae.generate(r):
            # What the server does with every frame: read the whole list.
            seen_negative.extend(t for t in list(r.output_token_ids)
                                 if t < 0)
            toks += out.new_token_ids
            if quit_after is not None and len(toks) >= quit_after:
                break               # the generator's finally aborts
        return toks

    async def run():
        ae = AsyncEngine(eng)
        await ae.start()
        try:
            return await asyncio.wait_for(asyncio.gather(
                one(ae, live[0]), one(ae, live[1]),
                one(ae, live[2], quit_after=7), one(ae, live[3])),
                timeout=120)
        finally:
            ae.stop()

    toks = asyncio.run(run())
    assert not seen_negative
    for i in (0, 1, 3):
        assert toks[i] == ref[f"r{i}"][0]
    assert toks[2] == ref["r2"][0][:len(toks[2])] and len(toks[2]) >= 7
    assert all(t >= 0 for r in live for t in r.output_token_ids)
    steps = [s["attrs"] for s in _steps(eng)]
    assert sum(a["run_ahead"] for a in steps) >= 4
    assert not eng.has_work()
    _free_pool(eng)

