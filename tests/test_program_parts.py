"""Device time by part of the step program (PR 36).

What this pins, on small engines on the CPU and on traces recorded on the
chip:

  - every operation the five families' step programs hold lies under one
    ``llmd.<part>`` scope (llm_d_tpu/ops/parts.py), a Q == 1 program's
    attention under ``attn.decode`` and a wider one's under ``attn.prefill``,
    and the program's vocabulary is the one ``readers/device_parts.py``
    groups;
  - ``moe_experts_touched`` is the number of distinct routed experts the
    step's REAL rows select, layer by layer, the same with and without
    run-ahead, fetched in the step's one fetch, and rides the span and the
    ``llmd.post`` annotation beside ``moe_experts_held`` / ``moe_pairs``;
  - ``xplanemeta`` reads what TensorFlow's parser reads, and the new
    readers' shares are the PART's: moving its work to another operation
    name leaves them as they are.
"""

import functools
import json
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.packed_batch import BatchLayout
from llm_d_tpu.engine.request import Request
from llm_d_tpu.ops import parts
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.utils import tracing

REPO = pathlib.Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmarks"
sys.path.insert(0, str(BENCH))

import modelcfg  # noqa: E402
import partwork  # noqa: E402
import tracereduce  # noqa: E402
import xplanemeta  # noqa: E402
from readers import device_parts, part_roofline, span_ratio  # noqa: E402

# The five configurations' families at their tiny presets.
FAMILIES = {"qwen3moe": "tiny-moe", "kanana2": "tiny-mla",
            "trinity": "tiny-swa-moe", "sdar": "tiny-sdar",
            "falcon": "tiny-ssm"}
# (T, S, Q) of a pure-decode and of a mixed step program; a block-diffusion
# row always brings a whole block, so that family has no Q == 1 program.
KINDS = {"decode": (8, 8, 1), "mixed": (32, 8, 16)}
PROGRAMS = [(f, k) for f in FAMILIES for k in KINDS
            if (f, k) != ("sdar", "decode")]
SCOPES = {parts.PREFIX + p for p in parts.PARTS}
CTX = tracing.TraceContext("a" * 32, "b" * 16, True)
PARTS_TRACE = BENCH / "testdata" / "v5e_parts.xplane.pb"
OLD_TRACES = ["v5e_slice.xplane.pb", "v5e_phases.xplane.pb"]
V5E = modelcfg.load_json("peaks.json")["TPU v5 lite"]


@pytest.fixture(autouse=True)
def _tracing_on(monkeypatch):
    monkeypatch.delenv("LLMD_TRACE", raising=False)
    monkeypatch.delenv("LLMD_TRACE_SAMPLE", raising=False)
    tracing.reset()
    yield
    tracing.reset()


def _engine(preset, **kw):
    tracing.reset()     # the engine takes its tracer at construction
    return EngineCore(EngineConfig(**{**dict(
        model=preset, block_size=16, num_blocks=64, max_num_seqs=8,
        max_num_batched_tokens=64), **kw}))


# ---------------------------------------------------------------------------
# the scopes, from the compiled programs' op_names
# ---------------------------------------------------------------------------

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z\-]+)\(.*'
    r'metadata=\{[^}]*op_name="([^"]*)"')
# What ``lax.scan`` itself lowers to around a layer body (the loop, the
# slices of the stacked weights, the stacking of the per-layer outputs):
# the scope around the scan names it, nothing inside the body can.
_SCAN = re.compile(
    r"^jit\(step_fn\)/llmd\.scan/while(/(body|cond)(/(closed_call|"
    r"dynamic_slice|dynamic_update_slice|squeeze|add|lt|"
    r"broadcast_in_dim))?)?$")


@functools.lru_cache(maxsize=None)
def _program(family, kind):
    """(opcode, op_name) of every instruction of the served step program
    that carries a JAX name stack, from the compiled HLO's metadata (where
    an inlined call's operations carry their callers' names)."""
    eng = _engine(FAMILIES[family])
    T, S, Q = KINDS[kind]
    layout = BatchLayout(T, S, Q, eng.max_blocks_per_seq,
                         R=eng.block_length or 1, state=eng._has_state)
    text = eng._step_fn.lower(
        eng.params, eng.kv_cache, layout.new_buffer(), eng._rng, *eng._fed,
        layout).compile().as_text()
    ops = [m.groups() for m in map(_INSTRUCTION.match, text.splitlines())
           if m]
    return [(op, name) for op, name in ops
            if name.startswith("jit(step_fn)")]


def _scopes(name):
    return re.findall(r"llmd\.[a-z_.]+", name)


@pytest.mark.parametrize("family,kind", PROGRAMS)
def test_every_operation_lies_in_one_part(family, kind):
    ops = _program(family, kind)
    assert len(ops) > 300
    bare = sorted({(op, name) for op, name in ops if not _scopes(name)})
    assert not bare, bare[:10]
    for _, name in ops:
        found = _scopes(name)
        assert set(found) <= SCOPES, name
        if found == ["llmd.scan"]:      # the scan's own operations only
            assert _SCAN.match(name), name
        # One scope an operation inside the layer scan (the innermost
        # counts); the list of scan pieces, derived inside the mixer, is
        # the one nesting.
        inner = found[1:] if found[0] == "llmd.scan" else found
        assert len(set(inner)) <= 1 or inner[-2:] == [
            "llmd.ssm.state", "llmd.tiles"], name


@pytest.mark.parametrize("family,kind", PROGRAMS)
def test_heavy_operations_are_scoped(family, kind):
    """Every dot, gather, scatter, cache write, sort and reduction, by
    opcode: each lies under exactly one scope."""
    heavy = [(op, name) for op, name in _program(family, kind)
             if op in ("dot", "convolution", "gather", "scatter", "sort",
                       "dynamic-update-slice", "reduce", "custom-call")
             and not _SCAN.match(name)]
    assert sum(op == "dot" for op, _ in heavy) >= 5

    def one(name):
        inner = [s for s in _scopes(name) if s != "llmd.scan"]
        return len(set(inner)) == 1 or inner[-1:] == ["llmd.tiles"]

    assert all(one(name) for _, name in heavy), [
        (op, name) for op, name in heavy if not one(name)][:5]
    # The output head's dot and the reductions over the vocabulary.
    assert any(op == "dot" and "llmd.head" in name for op, name in heavy)
    assert any(op == "reduce" and "llmd.sample" in name
               for op, name in heavy)


@pytest.mark.parametrize("family,kind", PROGRAMS)
def test_decode_and_mixed_attention_are_told_apart(family, kind):
    found = {s for _, name in _program(family, kind) for s in _scopes(name)}
    here, other = ("llmd.attn.decode", "llmd.attn.prefill")[
        ::1 if kind == "decode" else -1]
    assert here in found and other not in found
    want = {"llmd.embed", "llmd.tiles", "llmd.attn.proj", "llmd.mlp",
            "llmd.scan", "llmd.head", "llmd.sample"}
    if family == "falcon":
        want |= {"llmd.ssm.proj", "llmd.ssm.state"}
    else:
        want |= {"llmd.router", "llmd.experts"}
    if family in ("kanana2", "trinity"):
        want.add("llmd.shared")
    assert want <= found, want - found


def test_the_vocabulary_is_the_one_the_reader_groups():
    grouped = [s for scopes in device_parts.GROUPS.values() for s in scopes]
    assert len(grouped) == len(set(grouped))
    # llmd.attn.index (PR 39), llmd.attn.cross and llmd.gmu (PR 41) are in
    # no group of the accepted reader: their shares are read by
    # readers/scope_share.py (device_part_share.index / .cross / .gmu).
    # llmd.lin.state and llmd.lin.proj (PR 49) neither: device_part_share
    # .linear reads the former by its scope, the reader's table prints both.
    assert set(grouped) - {device_parts.UNSCOPED} == SCOPES - {
        "llmd.attn.index", "llmd.attn.cross", "llmd.gmu", "llmd.lin.state",
        "llmd.lin.proj"}
    assert device_parts.scope_of(
        "jit(step_fn)/while/body/closed_call/llmd.ssm.state/llmd.tiles/"
        "cumsum:") == "llmd.tiles"
    assert device_parts.scope_of(
        "jit(step_fn)/llmd.attn.proj/dot_general:") == "llmd.attn.proj"
    assert device_parts.scope_of("jit(step_fn)/while:") == "unscoped"
    assert device_parts.scope_of(None) == "unscoped"
    with pytest.raises(ValueError, match="unknown part"):
        parts.part("attn")


# ---------------------------------------------------------------------------
# the experts a step touches
# ---------------------------------------------------------------------------

def _batch(rows, T, S, Q, block_size, B):
    """The step's batch as ``_fill_batch`` lays it out, for ``rows`` of
    (tokens already computed, new tokens); padded to the buckets."""
    b = {"token_ids": np.zeros(T, np.int32),
         "positions": np.zeros(T, np.int32),
         "token_seq_ids": np.zeros(T, np.int32),
         "token_qpos": np.zeros(T, np.int32),
         "slot_mapping": np.zeros(T, np.int32),
         "block_tables": np.zeros((S, B), np.int32),
         "seq_lens": np.zeros(S, np.int32),
         "sample_idx": np.zeros(S, np.int32),
         "qtok_idx": np.full((S, Q), T, np.int32)}
    t = 0
    for s, (start, n) in enumerate(rows):
        b["block_tables"][s] = 1 + s * B + np.arange(B)
        for q in range(n):
            pos = start + q
            b["token_ids"][t] = (31 * s + 7 * pos) % 250 + 1
            b["positions"][t], b["token_seq_ids"][t] = pos, s
            b["token_qpos"][t], b["qtok_idx"][s, q] = q, t
            b["slot_mapping"][t] = (b["block_tables"][s, pos // block_size]
                                    * block_size + pos % block_size)
            t += 1
        b["seq_lens"][s], b["sample_idx"][s] = start + n, t - 1
    return {k: jnp.asarray(v) for k, v in b.items()}, t


TOUCH_CASES = {
    # 5 real rows of a bucket of 16: the 11 padded rows select experts too
    # (the zero embedding's favourites) and are not counted.
    "padded_rows": ([(0, 5)], 16, 4, 16),
    # One row: exactly k experts a layer.
    "one_row": ([(9, 1)], 8, 4, 1),
    # A prompt chunk beside three decode rows.
    "mixed": ([(0, 12), (20, 1), (7, 1), (33, 1)], 32, 4, 16),
    # A full decode bucket: nothing padded.
    "full_decode": ([(3 + 2 * i, 1) for i in range(8)], 8, 8, 1),
}


@pytest.mark.parametrize("family", ["qwen3moe", "kanana2", "trinity"])
@pytest.mark.parametrize("case", TOUCH_CASES)
def test_touched_is_the_distinct_experts_of_the_real_rows(family, case):
    eng = _engine(FAMILIES[family])
    c = eng.model_config
    rows, T, S, Q = TOUCH_CASES[case]
    batch, real = _batch(rows, T, S, Q, eng.config.block_size,
                         eng.max_blocks_per_seq)
    _, _, routed, touched = jax.jit(
        lambda p, kv, b: eng.model.forward(
            p, kv, b, c, eng.config.block_size, eng.config.attn_backend,
            mesh=eng.mesh, collect_routed=True, count_touched=True))(
        eng.params, eng.kv_cache, batch)
    routed = np.asarray(routed)                         # [Lm, T, k]
    want = sum(len(np.unique(layer[:real])) for layer in routed)
    assert int(touched) == want
    moe_layers = c.num_layers - c.first_dense_layers
    assert routed.shape == (moe_layers, T, c.num_experts_per_tok)
    if case == "one_row":
        assert want == moe_layers * c.num_experts_per_tok
    if real < T:
        # The padded rows would have been counted.
        assert want <= sum(len(np.unique(layer)) for layer in routed)
    assert moe_layers * c.num_experts_per_tok <= want \
        <= moe_layers * c.num_experts


def _req(rid, n_prompt, n=8):
    r = Request(request_id=rid,
                prompt_token_ids=[(37 * n_prompt + 11 * j) % 250 + 1
                                  for j in range(n_prompt)],
                sampling=SamplingParams(temperature=0.0, max_tokens=n,
                                        ignore_eos=True))
    r.trace_ctx = CTX
    return r


def _steps(eng):
    return [s for s in eng.tracer.snapshot() if s["name"] == "engine.step"]


def _serve(slots, marks=None):
    eng = _engine("tiny-moe", max_num_seqs=slots, block_size=4,
                  num_blocks=256, max_num_batched_tokens=32,
                  min_token_bucket=16, min_seq_bucket=4)
    if marks is not None:
        real = eng._clock.mark
        eng._clock.mark = lambda phase, **kw: (
            marks.append((phase, kw)), real(phase, **kw))[1]
    eng.generate([_req(f"r{i}", 5 + 3 * i, n=10) for i in range(4)])
    return eng, _steps(eng)


@pytest.mark.parametrize("what", ["same_count", "span_attributes",
                                  "one_fetch", "annotations"])
def test_the_count_rides_the_step_with_and_without_run_ahead(what):
    marks = []
    ahead, a_steps = _serve(4, marks)       # every slot taken: runs ahead
    plain, p_steps = _serve(8)              # slots to spare: never does
    assert sum(s["attrs"]["run_ahead"] for s in a_steps) >= 8
    assert not any(s["attrs"]["run_ahead"] for s in p_steps)
    c = ahead.model_config
    layers = c.num_layers - c.first_dense_layers
    if what == "same_count":
        # The same steps in the same order, whichever engine composed them.
        def key(steps):
            return [(s["attrs"]["prefill_tokens"], s["attrs"]["decode_tokens"],
                     s["attrs"]["moe_experts_touched"]) for s in steps]
        assert key(a_steps) == key(p_steps)
    elif what == "span_attributes":
        for s in a_steps + p_steps:
            a = s["attrs"]
            tokens = a["prefill_tokens"] + a["decode_tokens"]
            assert a["moe_experts_held"] == layers * c.num_experts
            assert a["moe_pairs"] == tokens * c.num_experts_per_tok * layers
            assert layers * c.num_experts_per_tok \
                <= a["moe_experts_touched"] \
                <= min(a["moe_experts_held"], a["moe_pairs"])
        assert span_ratio.read(
            {"spans": a_steps}, "engine.step", "moe_experts_touched",
            ["moe_experts_held"]) == pytest.approx(100.0 * sum(
                s["attrs"]["moe_experts_touched"] for s in a_steps)
                / (len(a_steps) * layers * c.num_experts))
    elif what == "one_fetch":
        # Still one copy and one launch a step (an iteration that enters
        # the pipeline makes two of each, the last one none).
        for steps in (a_steps, p_steps):
            for what in ("h2d_copies", "launches"):
                n = [s["attrs"].get(what, 0) for s in steps]
                assert sum(n) == len(steps) and max(n) <= 2, (what, n)
        assert {s["attrs"].get("launches", 0) for s in p_steps} == {1}
    else:
        post = [kw for phase, kw in marks if phase == "post"]
        dispatch = [kw for phase, kw in marks if phase == "dispatch"]
        assert len(post) == len(dispatch) == len(a_steps)
        assert [kw["moe_experts_touched"] for kw in post] == [
            s["attrs"]["moe_experts_touched"] for s in a_steps]
        assert all(set(kw) == {"moe_experts_touched", "moe_experts_held",
                               "moe_pairs", "moe_one_pass_pairs"}
                   for kw in post)
        assert all(kw["sample_rows"] in (4, 8) and "prefill_tokens" in kw
                   and "kv_read_tokens" in kw for kw in dispatch)


@pytest.mark.parametrize("bucket,int8_kernels,served", [
    (16, True, False), (512, True, False), (1024, True, True),
    (2048, True, True), (2048, False, False)])
def test_one_pass_pairs_follow_the_token_bucket(bucket, int8_kernels, served):
    """``moe_one_pass_pairs`` is ``moe_pairs`` where the step's program has
    more than ``ROUTED_INT8_MAX_T`` token rows and the int8 kernels serve the
    engine (never on the CPU), else 0: known from the bucket, so no step
    program changes for it; ``moe_one_pass_share`` reads it through
    ``span_ratio``, and nothing from a program without it."""
    eng = _engine("tiny-moe")
    assert eng._int8_expert_kernels is False          # bf16 experts, a CPU
    eng._int8_expert_kernels = int8_kernels
    c = eng.model_config
    got = eng._moe_counts(100, 7, bucket)
    pairs = 100 * c.num_experts_per_tok * (c.num_layers
                                           - c.first_dense_layers)
    assert got["moe_pairs"] == pairs
    assert got["moe_one_pass_pairs"] == (pairs if served else 0)
    metric = json.loads((BENCH / "layer_metrics"
                         / "moe_one_pass_share.json").read_text())
    assert metric["reader"] == "span_ratio"
    spans = [{"name": "engine.step", "dur": 0.01, "attrs": dict(got)},
             {"name": "engine.step", "dur": 0.01,
              "attrs": eng._moe_counts(28, 7, 16)}]
    share = span_ratio.read({"spans": spans}, **metric["args"])
    assert share == pytest.approx(
        100.0 * (pairs if served else 0) / (pairs * 1.28))
    for s in spans:
        del s["attrs"]["moe_one_pass_pairs"]          # the parent's spans
    assert span_ratio.read({"spans": spans}, **metric["args"]) is None


@pytest.mark.parametrize("preset", ["tiny", "tiny-ssm"])
def test_a_stack_without_experts_carries_no_count(preset):
    marks = []
    eng = _engine(preset)
    real = eng._clock.mark
    eng._clock.mark = lambda phase, **kw: (
        marks.append((phase, kw)), real(phase, **kw))[1]
    eng.generate([_req("a", 6, n=4), _req("b", 9, n=4)])
    steps = _steps(eng)
    assert steps and not any("moe_experts_touched" in s["attrs"]
                             or "moe_pairs" in s["attrs"] for s in steps)
    assert all(kw == {} for phase, kw in marks if phase == "post")
    assert span_ratio.read({"spans": steps}, "engine.step",
                           "moe_experts_touched",
                           ["moe_experts_held"]) is None


def test_stub_components_are_gone():
    assert "stub_components" not in {
        f.name for f in EngineConfig.__dataclass_fields__.values()}
    for path in (REPO / "llm_d_tpu").rglob("*.py"):
        assert "stub_components" not in path.read_text(), path


# ---------------------------------------------------------------------------
# the readers, on traces recorded on the chip
# ---------------------------------------------------------------------------

def _profile_events(path):
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            if line.name == tracereduce.OPS_LINE:
                out[plane.name] = [(ev.name, ev.start_ns, ev.duration_ns)
                                   for ev in line.events]
    return out


@pytest.mark.parametrize("name", OLD_TRACES + [PARTS_TRACE.name])
def test_xplanemeta_reads_the_events_profiledata_reads(name):
    path = BENCH / "testdata" / name
    planes = {p["name"]: p for p in xplanemeta.read(
        str(path), (tracereduce.OPS_LINE,))}
    seen = _profile_events(path)
    assert seen and set(seen) == {
        n for n, p in planes.items() if p["lines"]}
    for plane, events in seen.items():
        mine = planes[plane]["lines"][tracereduce.OPS_LINE]
        ops = planes[plane]["ops"]
        assert len(mine) == len(events) > 100
        for (start, end, key), (ev_name, start_ns, dur_ns) in zip(
                mine, events):
            assert ops[key]["name"] == ev_name
            assert abs(start / 1e3 - start_ns) <= 1.0
            assert abs((end - start) / 1e3 - dur_ns) <= 1.0
    table = xplanemeta.op_table(str(path))["/device:TPU:0"]
    named = [s for s in table.values() if s.get("tf_op")]
    assert len(named) > 50
    assert all(isinstance(s.get("flops", 0), int)
               and isinstance(s["tf_op"], str)
               and isinstance(s.get("hlo_category", ""), str)
               for s in named)
    assert any(s.get("source", "").startswith("/") for s in named)


def test_xplanemeta_against_a_hand_made_space(tmp_path):
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    def field(number, payload):
        if isinstance(payload, int):
            return varint(number << 3) + varint(payload)
        return varint(number << 3 | 2) + varint(len(payload)) + payload

    def entry(key, value):
        return field(1, key) + field(2, value)

    stat_meta = [field(5, entry(i, field(1, i) + field(2, name.encode())))
                 for i, name in ((1, "tf_op"), (2, "flops"),
                                 (3, "hlo_category"), (4, "fusion"),
                                 (5, "bytes_accessed"))]
    stats = (field(5, field(1, 1) + field(5, b"jit(f)/llmd.head/dot:"))
             + field(5, field(1, 2) + field(4, 2**64 - 3))
             + field(5, field(1, 3) + field(7, 4))
             + field(5, field(1, 5) + field(3, 77)))
    event_meta = field(4, entry(9, field(1, 9) + field(2, b"%dot.1 = x")
                                + stats))
    line = field(3, field(2, b"XLA Ops") + field(3, 5)
                 + field(4, field(1, 9) + field(2, 1500) + field(3, 250)))
    plane = field(1, field(2, b"/device:TPU:0") + line + event_meta
                  + b"".join(stat_meta))
    path = tmp_path / "space.pb"
    path.write_bytes(plane)
    (got,) = xplanemeta.read(str(path), ("XLA Ops",))
    assert got["lines"] == {"XLA Ops": [(6500, 6750, 9)]}
    assert got["ops"] == {9: {
        "name": "%dot.1 = x", "tf_op": "jit(f)/llmd.head/dot:",
        "flops": -3, "hlo_category": "fusion", "bytes_accessed": 77}}
    assert xplanemeta.op_table(str(path)) == {"/device:TPU:0": {
        "%dot.1 = x": {"tf_op": "jit(f)/llmd.head/dot:", "flops": -3,
                       "hlo_category": "fusion", "bytes_accessed": 77}}}
    assert device_parts.by_scope(str(path)) == {"llmd.head": 250e-12}
    # A recorder drops the plane of HLO protos; the rest is untouched.
    protos = field(1, field(2, b"/host:metadata") + field(6, b"x" * 99))
    both = protos + plane + field(4, b"host-name")
    kept = xplanemeta.without_planes(both, ("/host:metadata",))
    assert kept == plane + field(4, b"host-name")
    assert xplanemeta.without_planes(both, ()) == both


@pytest.mark.parametrize("name", OLD_TRACES)
def test_a_program_without_scopes_reads_nothing(name):
    path = str(BENCH / "testdata" / name)
    assert set(device_parts.by_scope(path)) == {"unscoped"}
    assert all(device_parts.scoped_seconds(path, scopes) is None
               for scopes in device_parts.GROUPS.values())
    conf = modelcfg.load_config("kanana-2-30b-a3b")
    assert all(part_roofline.share(path, part, ["llmd.experts"], conf, V5E)
               is None for part in partwork.COUNTS)


def _new_metrics():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    new = [m for m in bench["per_layer"] if m["name"].startswith(
        ("device_part_share.", "moe_", "mla_"))]
    # Appended together (PR 36); later PRs append after them.
    first = bench["per_layer"].index(new[0])
    new = new[:13]          # PR 39 appended three more of these prefixes
    assert new == bench["per_layer"][first:first + 13]
    return new


@pytest.mark.parametrize("entry", _new_metrics(), ids=lambda m: m["name"])
def test_new_metric_files_agree_and_read_nothing_on_a_rehearsal(entry):
    d = json.loads(
        (BENCH / "layer_metrics" / (entry["name"] + ".json")).read_text())
    assert all(d[k] == entry[k] for k in (
        "name", "unit", "better", "source", "layer", "moves"))
    assert d["unit"] == "%" and len(d["what"]) > 100
    cells = {w["name"] for w in json.loads(
        (REPO / "BENCHMARK.json").read_text())["workloads"]}
    assert set(entry.get("workloads", cells)) <= cells
    import importlib
    reader = importlib.import_module("readers." + d["reader"])
    # A CPU rehearsal has no device plane, a parent's spans no count.
    bare = {"spans": [{"name": "engine.step", "dur": 0.01,
                       "attrs": {"prefill_tokens": 0}}], "trace": None}
    assert reader.read(bare, **d["args"]) is None
    if d["reader"] == "part_roofline":
        assert d["args"]["part"] in partwork.COUNTS
        assert set(d["args"]["scopes"]) <= SCOPES
        fields = modelcfg.model_config_fields(
            modelcfg.load_config(d["args"]["config"]))
        assert fields["num_experts"] == 128 and fields["hidden_size"] == 2048
    if d["reader"] == "device_parts":
        assert d["args"]["group"] in device_parts.GROUPS


def test_partwork_counts_necessary_work_only():
    conf = modelcfg.load_config("kanana-2-30b-a3b")
    # hidden 2048, experts of 768: three int8 matrices and their scales.
    assert partwork.expert_bytes(conf) == 3 * 2048 * 768 + 4 * (
        2 * 768 + 2048)
    peaks = {"hbm_bytes_per_s": 1e9, "bf16_flops": 1e12}
    stream = partwork.experts(
        conf, {"moe_experts_touched": 10, "moe_pairs": 1}, peaks)
    assert stream == pytest.approx(10 * partwork.expert_bytes(conf) / 1e9)
    dots = partwork.experts(
        conf, {"moe_experts_touched": 1, "moe_pairs": 10**6}, peaks)
    assert dots == pytest.approx(10**6 * 6 * 2048 * 768 / 1e12)
    assert partwork.mla_decode(
        conf, {"decode_kv_read_tokens": 1000}, peaks) == pytest.approx(
            1000 * 576 * 2 / 1e9)
    assert partwork.mla_prefill(
        conf, {"prefill_kv_read_tokens": 1000}, peaks) == pytest.approx(
            1000 * 32 * (2 * 576 + 2 * 512) / 1e12)


needs_parts_trace = pytest.mark.skipif(
    not PARTS_TRACE.exists(), reason="recorded on the chip")


@needs_parts_trace
def test_groups_add_up_and_pallas_kernels_land_in_their_parts():
    path = str(PARTS_TRACE)
    times = device_parts.by_scope(path)
    busy = tracereduce.reduce_trace(path)["busy_s"]
    assert sum(times.values()) == pytest.approx(busy, rel=1e-3)
    assert set(times) - {"unscoped"} <= SCOPES
    shares = {g: device_parts.scoped_seconds(path, scopes)
              for g, scopes in device_parts.GROUPS.items()}
    assert shares["state"] == 0.0       # no mixer in the recorded stack
    assert 100.0 * sum(shares.values()) / busy == pytest.approx(100.0,
                                                                abs=0.1)
    # What no scope reaches: copies the compiler adds on its own (0.1-0.4 %
    # of a cell's busy time, PERF.md section 5; more of this small stack's).
    assert times.get("unscoped", 0.0) < 0.05 * busy
    assert times.get("llmd.scan", 0.0) > 0.0
    for wanted in ("llmd.experts", "llmd.attn.decode", "llmd.attn.prefill",
                   "llmd.attn.proj", "llmd.router", "llmd.shared",
                   "llmd.mlp", "llmd.head", "llmd.sample", "llmd.embed"):
        assert times.get(wanted, 0.0) > 0.0, wanted
    # A Pallas kernel is named by its source file: each lands in the part
    # that file serves.
    home = {"moe_int8.py": "llmd.experts", "moe_routed.py": "llmd.experts",
            "moe_routed_stream.py": "llmd.experts",
            "mla_attention.py": "llmd.attn.decode",
            "mla_prefill.py": "llmd.attn.prefill"}
    kernels = 0
    for _, ops in device_parts.self_times(path):
        for rec in ops.values():
            source = rec.get("source", "")
            if "/ops/pallas/" in source:
                kernels += 1
                file = source.rsplit("/", 1)[1].split(":")[0]
                assert device_parts.scope_of(rec["tf_op"]) == home[file], rec
    assert kernels >= 3
    text = "\n".join(device_parts.table(path))
    assert "NOT IN ANY GROUP" not in text and "llmd.experts" in text


@needs_parts_trace
@pytest.mark.parametrize("part,scopes", [
    ("experts", ["llmd.experts"]), ("mla_decode", ["llmd.attn.decode"]),
    ("mla_prefill", ["llmd.attn.prefill"])])
def test_a_share_is_the_parts_whatever_operation_serves_it(
        part, scopes, monkeypatch):
    """Move the part's work to another (fake) operation name: the share is
    the same, and no reader holds a kernel's name."""
    path = str(PARTS_TRACE)
    # The recorded stack is deepseek-v3-bench cut down, not a benchmark
    # configuration: its geometry, in a configuration file's form.
    conf = {"name": "recorded", "model_config_map": {
        k: k for k in ("hidden_size", "moe_intermediate_size",
                       "kv_lora_rank", "qk_rope_head_dim", "num_heads")},
        "hidden_size": 2048, "moe_intermediate_size": 512,
        "kv_lora_rank": 512, "qk_rope_head_dim": 64, "num_heads": 16}
    before = part_roofline.share(path, part, scopes, conf, V5E)
    assert before is not None and 0.0 < before < 100.0
    real = xplanemeta.read

    def renamed(p, lines=()):
        planes = real(p, lines)
        for plane in planes:
            for key, rec in plane["ops"].items():
                rec["name"] = f"%fake_kernel.{key} = moved()"
        return planes

    monkeypatch.setattr(xplanemeta, "read", renamed)
    device_parts.self_times.cache_clear()
    try:
        assert part_roofline.share(path, part, scopes, conf, V5E) == before
    finally:
        device_parts.self_times.cache_clear()
    for reader in ("part_roofline.py", "device_parts.py"):
        source = (BENCH / "readers" / reader).read_text()
        for kernel in ("dense_moe", "routed_moe", "streamed_moe",
                       "mla_paged", "mla_flash", "paged_attention",
                       "flash_prefill", "ssm_decode", "ssm_chunk"):
            assert kernel not in source, (reader, kernel)


@needs_parts_trace
def test_held_stream_share_counts_touched_experts_once_over_the_parts_time(
        monkeypatch):
    """``moe_held_hbm_share`` (readers/held_stream.py): the touched experts'
    bf16 bytes, each once, over the time under the scopes plus the
    operations of the given names; nothing on a trace without the count,
    the names counted wherever they lie."""
    from readers import held_stream
    path = str(PARTS_TRACE)
    conf = {"name": "recorded", "model_config_map": {
        k: k for k in ("hidden_size", "moe_intermediate_size")},
        "hidden_size": 2048, "moe_intermediate_size": 512}
    touched = part_roofline.annotation_counts(path)["moe_experts_touched"]
    seconds = device_parts.scoped_seconds(path, ("llmd.experts",))
    got = held_stream.share(path, ("llmd.experts",), ("ragged-dot",), conf,
                            V5E)
    want = 100.0 * touched * 3 * 2048 * 512 * 2 / V5E["hbm_bytes_per_s"] \
        / seconds
    assert got == pytest.approx(want) and got > 0
    # a name counts beside the scopes: every operation under another scope
    # renamed ragged-dot-none lowers the share by that scope's time
    real = xplanemeta.read

    def renamed(p, lines=()):
        planes = real(p, lines)
        for plane in planes:
            for rec in plane["ops"].values():
                if device_parts.scope_of(rec.get("tf_op")) == "llmd.mlp":
                    rec["name"] = "%ragged-dot-none.7 = custom-call()"
        return planes

    monkeypatch.setattr(xplanemeta, "read", renamed)
    device_parts.self_times.cache_clear()
    try:
        more = device_parts.scoped_seconds(path, ("llmd.experts", "llmd.mlp"))
        assert held_stream.share(
            path, ("llmd.experts",), ("ragged-dot",), conf, V5E
        ) == pytest.approx(want * seconds / more)
    finally:
        device_parts.self_times.cache_clear()
    for old in OLD_TRACES:          # no annotation carries the count
        assert held_stream.share(str(BENCH / "testdata" / old),
                                 ("llmd.experts",), ("ragged-dot",), conf,
                                 V5E) is None
    assert held_stream.read({"trace": None}, ["llmd.experts"],
                            ["ragged-dot"], "dots3-note-prev") is None
