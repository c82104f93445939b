"""Phase times of the engine loop on the ``engine.step`` span, the
``engine.emit`` span of the event-loop hand-over, and the benchmark readers
that turn them into per-layer metrics.

What this pins:

  - all four step paths (classic, fused mixed round, multistep, fused
    multistep) write ``engine.step`` through one helper: one attribute set,
    one extent (the read before the RNG split -> tokens fetched), so
    ``dispatch_ms + fetch_ms`` is the span's duration and batch assembly is
    in ``build_ms``, never in the duration; a classic step launched while
    its predecessor was on the device (``run_ahead``) starts where that
    one's tokens were fetched: still one span a step, extents that do not
    overlap, and the identity holds for the steps composed in order;
  - the phases are contiguous: they add up to the loop iteration;
  - a wait for lack of work is ``starved_ms``, a wait with work pending
    ``between_ms``;
  - ``AsyncEngine`` records one ``engine.emit`` span per step that produced
    outputs;
  - none of it adds a host sync (the JIT pass walks the helper);
  - ``span_ratio`` and ``idle_under`` on hand-made input, and ``idle_under``
    on a trace recorded on a v5e, where its shares add up to the idle share
    ``tracereduce.reduce_trace`` reads from the same file.

All CPU, tier-1 safe.
"""

import ast
import asyncio
import json
import pathlib
import statistics
import sys
import time

import pytest

from llm_d_tpu.analysis.core import Context, run_passes
from llm_d_tpu.analysis.passes.jit_hygiene import JitHygienePass
from llm_d_tpu.engine import engine as engine_mod
from llm_d_tpu.engine.async_engine import AsyncEngine
from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.request import Request
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.utils import tracing

REPO = pathlib.Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmarks"
sys.path.insert(0, str(BENCH))

from readers import idle_under, span_ratio, span_stat  # noqa: E402
import tracereduce  # noqa: E402

ENGINE_KW = dict(model="tiny", block_size=4, num_blocks=64, max_num_seqs=8,
                 max_num_batched_tokens=64, min_token_bucket=16,
                 min_seq_bucket=4)
PATHS = {
    "classic": {},
    "fused": dict(spec_k=4),
    "multistep": dict(num_scheduler_steps=4),
    "fused_multistep": dict(spec_k=4, num_scheduler_steps=4),
}
PHASES = ("schedule_ms", "build_ms", "dispatch_ms", "fetch_ms", "post_ms")
GAPS = ("between_ms", "starved_ms")
SHAPE = {"step", "kind", "n_seqs", "prefill_tokens", "decode_tokens",
         "fused", "rounds"}
CTX = tracing.TraceContext("a" * 32, "b" * 16, True)


@pytest.fixture(autouse=True)
def _tracing_on(monkeypatch):
    monkeypatch.delenv("LLMD_TRACE", raising=False)
    monkeypatch.delenv("LLMD_TRACE_SAMPLE", raising=False)
    tracing.reset()
    yield
    tracing.reset()


def _req(rid, prompt, n=10, traced=True):
    r = Request(request_id=rid, prompt_token_ids=list(prompt),
                sampling=SamplingParams(temperature=0.0, max_tokens=n,
                                        ignore_eos=True))
    if traced:
        r.trace_ctx = CTX
    return r


def _engine(**kw):
    # After tracing.reset(): the engine takes its tracer at construction.
    return EngineCore(EngineConfig(**ENGINE_KW, **kw))


def _steps(eng):
    return [s for s in eng.tracer.snapshot() if s["name"] == "engine.step"]


def _wall_ms(attrs):
    return sum(attrs.get(k, 0.0) for k in PHASES + GAPS)


# ---------------------------------------------------------------------------
# the span: one attribute set, one extent, phases that add up
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", list(PATHS))
def test_every_path_writes_one_attribute_set_and_extent(path):
    eng = _engine(**PATHS[path])
    out = eng.generate([_req("a", [1, 2, 3, 4, 5], n=12),
                        _req("b", [9, 8, 7], n=9)])
    assert len(out["a"]) == 12 and len(out["b"]) == 9
    steps = _steps(eng)
    assert len(steps) >= 3
    for s in steps:
        a = s["attrs"]
        assert SHAPE <= set(a), (path, sorted(a))
        assert all(k in a and a[k] >= 0 for k in PHASES), (path, a)
        # One extent on every path: dispatch -> fetched.
        assert abs(a["dispatch_ms"] + a["fetch_ms"] - s["dur"] * 1e3) < 1.0
        assert a["kind"] == ("decode" if a["prefill_tokens"] == 0 else
                             "prefill" if a["decode_tokens"] == 0
                             else "mixed")
    # The synchronous loop never sleeps: every gap is between_ms, and the
    # thread's CPU time is known from the second span on.
    assert all("starved_ms" not in s["attrs"] for s in steps)
    assert all(s["attrs"]["between_ms"] >= 0 for s in steps[1:])
    assert all(0 <= s["attrs"]["host_cpu_ms"] for s in steps[1:])
    rounds = {s["attrs"]["rounds"] for s in steps}
    assert (max(rounds) == 4) == ("multistep" in path), rounds
    assert all(s["attrs"]["fused"] == (path != "classic") or
               s["attrs"]["rounds"] == 1 for s in steps)
    assert all(("accepted" in s["attrs"]) == ("fused" in path)
               for s in steps)
    # The classic step (every step of the classic path, and the prefill
    # steps of the multistep path) counts its copies and launches: one of
    # each.
    classic = [s["attrs"] for s in steps
               if not s["attrs"]["fused"] and s["attrs"]["rounds"] == 1]
    assert (len(classic) == len(steps)) == (path == "classic")
    assert bool(classic) == ("fused" not in path)
    assert all(a["h2d_copies"] == 1 and a["launches"] == 1 for a in classic)
    assert all(("h2d_copies" in s["attrs"]) == (s["attrs"] in classic)
               and ("launches" in s["attrs"]) == (s["attrs"] in classic)
               for s in steps)


@pytest.mark.parametrize("path", ["classic", "fused"])
def test_build_delay_is_in_build_ms_not_in_the_span(monkeypatch, path):
    eng = _engine(**PATHS[path])
    name = "_build_batch" if path == "classic" else "_build_fused_batch"
    real = getattr(eng, name)
    built_at = []

    def slow(*a, **kw):
        time.sleep(0.03)
        out = real(*a, **kw)
        built_at.append(time.time())
        return out

    monkeypatch.setattr(eng, name, slow)
    eng.generate([_req("a", [1, 2, 3, 4, 5], n=4)])
    steps = _steps(eng)
    assert len(steps) == len(built_at) >= 3
    for s, t_built in zip(steps, built_at):
        a = s["attrs"]
        assert a["build_ms"] >= 30.0
        assert s["ts"] >= t_built - 1e-3          # starts after the build
        assert abs(a["dispatch_ms"] + a["fetch_ms"] - s["dur"] * 1e3) < 1.0


@pytest.mark.parametrize("path", list(PATHS))
def test_phases_add_up_to_the_iteration(path):
    eng = _engine(**PATHS[path])
    eng.generate([_req("a", [1, 2, 3, 4, 5], n=16)])
    steps = _steps(eng)
    assert len(steps) >= 3
    off = []
    for prev, cur in zip(steps, steps[1:]):
        p, c = prev["attrs"], cur["attrs"]
        # start to start: the previous span, its post, then everything
        # this iteration booked before its own dispatch.
        want = (prev["dur"] * 1e3 + p["post_ms"] + _wall_ms(c)
                - c["dispatch_ms"] - c["fetch_ms"] - c["post_ms"])
        off.append(abs((cur["ts"] - prev["ts"]) * 1e3 - want))
    # (a span's start is two clock reads away from the phase clock's: a
    # preemption between them shifts one start, not the sum)
    assert statistics.median(off) < 0.5 and max(off) < 50.0, off


def test_pipelined_blocks_keep_the_extent_and_book_what_they_know():
    eng = _engine(num_scheduler_steps=4, async_scheduling=True)
    eng.generate([_req("a", [1, 2, 3, 4, 5], n=24)])
    steps = _steps(eng)
    piped = [s for s in steps if s["attrs"]["rounds"] == 4]
    assert len(piped) >= 3
    for s in piped:
        a = s["attrs"]
        assert a["fetch_ms"] >= 0 and a["post_ms"] >= 0
        # dispatched in an earlier iteration than the one that fetched it
        assert s["dur"] * 1e3 >= a["fetch_ms"]


def test_idle_wait_is_starved_not_between():
    eng = _engine()
    eng.generate([_req("warm", [1, 2, 3], n=3)])
    assert not eng.has_work()
    eng.tracer.clear()
    time.sleep(0.05)                       # the loop asleep: nothing to do
    eng.add_request(_req("a", [1, 2, 3, 4], n=4))
    eng.step()
    time.sleep(0.03)                       # work pending: a slow hand-over
    while eng.has_work():
        eng.step()
    steps = _steps(eng)
    first, second = steps[0]["attrs"], steps[1]["attrs"]
    assert first["starved_ms"] >= 50.0 and "between_ms" not in first
    assert second["between_ms"] >= 30.0 and "starved_ms" not in second
    assert all("starved_ms" not in s["attrs"] for s in steps[1:])


def test_an_iteration_without_a_fetch_rides_the_next_span(monkeypatch):
    eng = _engine()
    real = eng.scheduler.schedule

    def slow():
        time.sleep(0.02)
        return real()

    monkeypatch.setattr(eng.scheduler, "schedule", slow)
    assert eng.step() == [] and _steps(eng) == []    # nothing to schedule
    eng.add_request(_req("a", [1, 2, 3, 4], n=2))
    eng.step()
    (first,) = _steps(eng)
    # Both iterations' scheduling, and the gap between them as starved.
    assert first["attrs"]["schedule_ms"] >= 40.0
    assert "starved_ms" in first["attrs"]
    assert "between_ms" not in first["attrs"]


def test_untraced_requests_write_no_step_span_and_tracing_off_nothing(
        monkeypatch):
    eng = _engine()
    eng.generate([_req("plain", [1, 2, 3], n=4, traced=False)])
    assert _steps(eng) == []
    monkeypatch.setenv("LLMD_TRACE", "0")
    eng.generate([_req("off", [1, 2, 3], n=4)])
    assert eng.tracer.snapshot() == []


# ---------------------------------------------------------------------------
# engine.emit: the hand-over to the event loop
# ---------------------------------------------------------------------------

def _serve(eng, reqs):
    produced = []
    real = eng.step

    def counting():
        outs = real()
        if outs:
            produced.append(eng.step_count)
        return outs

    eng.step = counting

    async def run():
        ae = AsyncEngine(eng)
        await ae.start()
        try:
            async def one(r):
                return [t async for o in ae.generate(r)
                        for t in o.new_token_ids]
            return await asyncio.wait_for(
                asyncio.gather(*(one(r) for r in reqs)), timeout=120)
        finally:
            ae.stop()

    return asyncio.run(run()), produced


def test_async_engine_records_one_emit_span_per_step_with_outputs():
    eng = _engine()
    eng.generate([_req("warm", [1, 2, 3], n=2)])
    eng.tracer.clear()
    toks, produced = _serve(eng, [_req("a", [1, 2, 3, 4, 5], n=8),
                                  _req("b", [7, 7, 7], n=5)])
    assert [len(t) for t in toks] == [8, 5]
    emits = [s for s in eng.tracer.snapshot() if s["name"] == "engine.emit"]
    assert [s["attrs"]["step"] for s in emits] == produced
    assert all(s["attrs"]["n_outputs"] >= 1 and s["dur"] >= 0 for s in emits)
    assert sum(s["attrs"]["n_outputs"] for s in emits) == 13
    # The loop had nothing to do until the first request came: starved
    # time, not time between steps.
    first = _steps(eng)[0]["attrs"]
    assert "starved_ms" in first and "between_ms" not in first


def test_async_engine_tracing_off_records_no_emit(monkeypatch):
    monkeypatch.setenv("LLMD_TRACE", "0")
    eng = _engine()
    toks, produced = _serve(eng, [_req("a", [1, 2, 3], n=4)])
    assert len(toks[0]) == 4 and produced
    assert eng.tracer.snapshot() == []


# ---------------------------------------------------------------------------
# no new host sync
# ---------------------------------------------------------------------------

def test_jit_guard_walks_the_step_span_helper():
    ctx = Context(REPO)
    p = JitHygienePass()
    reach = p._step_reachable(ctx.source(
        "llm_d_tpu/engine/engine.py").tree)
    assert {"step", "_step", "_note_step", "_run_fused", "_ms_retire",
            "_fms_retire"} <= set(reach)
    findings, suppressed, _ = run_passes(ctx, [p])
    assert findings == [] and suppressed >= 4    # the four documented syncs
    # The clock itself touches no device value at all.
    src = (REPO / "llm_d_tpu/engine/step_clock.py").read_text()
    called = {n.func.attr for n in ast.walk(ast.parse(src))
              if isinstance(n, ast.Call) and isinstance(n.func,
                                                        ast.Attribute)}
    assert not called & {"device_get", "item", "block_until_ready",
                         "asarray", "array"}
    assert len([ln for ln in open(engine_mod.__file__)
                if '"engine.step"' in ln and "record_span" not in ln
                and "check(" not in ln]) == 1      # written in ONE place


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------

def _span(name, dur, **attrs):
    return {"name": name, "ts": 0.0, "dur": dur, "attrs": attrs}


SPANS = [
    _span("engine.step", 0.010, between_ms=2.0, schedule_ms=1.0,
          build_ms=1.0, dispatch_ms=4.0, fetch_ms=6.0, post_ms=2.0,
          host_cpu_ms=8.0),
    _span("engine.step", 0.020, between_ms=4.0, schedule_ms=1.0,
          build_ms=3.0, dispatch_ms=2.0, fetch_ms=18.0, post_ms=2.0,
          starved_ms=50.0, host_cpu_ms=6.0),
    _span("engine.step", 0.030, schedule_ms=9.0, dispatch_ms=1.0),
    _span("engine.emit", 0.002, n_outputs=3, step=1),
    _span("engine.emit", 0.004, n_outputs=1, step=2),
    _span("engine.queue", 0.5),
]


def test_span_ratio_on_hand_made_spans():
    # Only spans that carry the numerator count: (8 + 6) / (10 + 12).
    got = span_ratio.read(
        {"spans": SPANS}, "engine.step", "host_cpu_ms",
        ["between_ms", "schedule_ms", "build_ms", "dispatch_ms", "post_ms"])
    assert got == pytest.approx(100 * 14 / 22)
    assert span_ratio.read({"spans": SPANS}, "engine.step", "fetch_ms",
                           ["dur_ms"]) == pytest.approx(100 * 24 / 30)
    assert span_ratio.read({"spans": SPANS}, "engine.step", "missing",
                           ["dur_ms"]) is None
    assert span_ratio.read({"spans": []}, "engine.step", "host_cpu_ms",
                           ["dur_ms"]) is None


def _new_metrics():
    with open(REPO / "BENCHMARK.json") as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    return [n for n in names if n.startswith(
        ("loop_ms.", "loop_cpu_share", "emit_lag", "device_idle_under."))]


HAND = {"loop_ms.between": 3.0, "loop_ms.post": 2.0,
        "loop_ms.schedule": 1.0, "loop_ms.build": 2.0,
        "loop_ms.dispatch": 3.0, "loop_ms.fetch": 12.0,
        "loop_cpu_share": 100 * 14 / 22, "emit_lag_p95_ms": 4.0,
        "device_idle_under.schedule": None,
        "device_idle_under.build": None,
        "device_idle_under.dispatch": None,
        "device_idle_under.post": None, "device_idle_under.other": None}


@pytest.mark.parametrize("name", sorted(HAND))
def test_new_layer_metric_reads_hand_made_evidence(name):
    """Each new metric's file names a reader and args that read the spans
    above; on a program without the attributes (the parent commit) or a
    run without a device trace, it reads nothing and does not raise."""
    assert sorted(HAND) == sorted(_new_metrics())
    with open(BENCH / "layer_metrics" / f"{name}.json") as f:
        m = json.load(f)
    assert m["moves"] == "ttft_p50_ms"
    reader = {"span_stat": span_stat, "span_ratio": span_ratio,
              "idle_under": idle_under}[m["reader"]]
    ctx = {"spans": SPANS[:2] + SPANS[3:], "trace": None}
    got = reader.read(ctx, **m["args"])
    assert got == (None if HAND[name] is None
                   else pytest.approx(HAND[name]))
    bare = {"spans": [_span("engine.step", 0.01, prefill_tokens=0)],
            "trace": None}
    assert reader.read(bare, **m["args"]) is None


def test_idle_under_attribution_on_hand_made_intervals():
    ops = [(0, 10), (5, 12), (20, 30), (50, 60)]
    phases = [(8, 14, "schedule"), (14, 22, "build"), (30, 45, "post"),
              (45, 47, "fetch")]
    got = idle_under.attribute([ops], phases, 100)
    assert got == pytest.approx({"schedule": 2.0, "build": 6.0,
                                 "post": 15.0, "fetch": 2.0, "none": 43.0})
    assert sum(got.values()) == pytest.approx(100 - 32)
    # Two chips: each chip's idle time, averaged; a chip that ran for less
    # of the span is idle for the rest under no phase.
    two = idle_under.attribute([ops, [(0, 30)]], phases, 100)
    assert sum(two.values()) == pytest.approx(100 - (32 + 30) / 2)
    assert two["post"] == pytest.approx(7.5)


def test_idle_under_on_a_trace_recorded_on_the_chip():
    path = str(BENCH / "testdata" / "v5e_phases.xplane.pb")
    assert pathlib.Path(path).stat().st_size < 2 * 1024 * 1024
    got = idle_under.shares(path)
    tr = tracereduce.reduce_trace(path)
    assert got is not None and tr is not None
    idle = 100 * (1 - tr["busy_s"] / tr["window_s"])
    assert abs(sum(got.values()) - idle) < 1.0
    assert set(idle_under.NAMED) <= set(got)
    assert all(v >= 0 for v in got.values())
    names = "\n".join(tracereduce.describe(path))
    assert "/host:CPU" in names and "/device:TPU:0 | XLA Ops" in names
    # The trace the benchmark came with carries no annotation: the reader
    # returns nothing, as it does on a program without them.
    assert idle_under.shares(
        str(BENCH / "testdata" / "v5e_slice.xplane.pb")) is None


# ---------------------------------------------------------------------------
# the classic path one step ahead: still one span a step, one extent each
# ---------------------------------------------------------------------------

def _full_engine(slots):
    return EngineCore(EngineConfig(**{**ENGINE_KW, "max_num_seqs": slots}))


def _prompt(i, n):
    return [(37 * i + 11 * j) % 250 + 1 for j in range(n)]


def test_one_span_a_step_extents_apart_phases_add_up():
    eng = _full_engine(4)
    t_in = time.monotonic()
    eng.generate([_req(f"r{i}", _prompt(i, 5 + i), n=12)
                  for i in range(4)])
    wall = (time.monotonic() - t_in) * 1e3
    steps = _steps(eng)
    assert [s["attrs"]["step"] for s in steps] == list(
        range(1, len(steps) + 1)) and len(steps) == eng.step_count == 12
    ahead = [s["attrs"]["run_ahead"] for s in steps]
    assert ahead == [0] + [1] * 11
    # Extents do not overlap: a step that ran ahead starts where its
    # predecessor's tokens were fetched.
    for prev, cur in zip(steps, steps[1:]):
        assert cur["ts"] >= prev["ts"] + prev["dur"] - 2e-4, (prev, cur)
    # The phases of the iterations add up to the run, launches included.
    total = sum(s["attrs"].get(k, 0.0) for s in steps
                for k in PHASES + GAPS)
    assert 0.85 * wall <= total <= wall + 1.0, (total, wall)
    for prev, s in zip(steps, steps[1:]):
        a = s["attrs"]
        assert all(a.get(k, 0.0) >= 0 for k in PHASES)
        assert a["run_ahead"] == 1
        # dispatch_ms + fetch_ms belong to two steps here, so the identity
        # of a step composed in order does not hold; the extent is the pace:
        # from the predecessor's fetch (its post, the hand-over) to this
        # step's own, through the launch of the step behind it.
        pace = prev["attrs"]["post_ms"] + sum(
            a.get(k, 0.0) for k in ("between_ms", "schedule_ms", "build_ms",
                                    "dispatch_ms", "fetch_ms"))
        assert abs(s["dur"] * 1e3 - pace) < 0.5, (s, pace)
    # One copy and one launch an iteration in steady state; the iteration
    # that enters the pipeline makes two of each, the last one none.
    copies = [s["attrs"].get("h2d_copies", 0) for s in steps]
    assert copies == [2] + [1] * 10 + [0]
    assert [s["attrs"].get("launches", 0) for s in steps] == copies


def test_in_order_steps_keep_the_span_identity():
    eng = _full_engine(8)
    eng.generate([_req(f"r{i}", _prompt(i, 5 + i), n=6) for i in range(3)])
    for s in _steps(eng):
        a = s["attrs"]
        assert a["run_ahead"] == 0 and a["wasted_rows"] == 0
        assert abs(a["dispatch_ms"] + a["fetch_ms"] - s["dur"] * 1e3) < 0.5
