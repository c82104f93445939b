"""Quantized EP/TP collective contract (LLMD_COLLECTIVE_DTYPE), end to end.

The claim under test is the ISSUE-8 acceptance set: the int8 exchange
wire (per-row symmetric int8 payloads + f32 scale vectors riding sibling
exchanges — parallel/quant_collectives.py) matches the bf16 wire within
2% rel-RMS PER COLLECTIVE (dispatch and combine bounded separately), the
scale plane lands exactly aligned with its payload rows under skewed
routing and chunking (byte-exact round trip on exactly-representable
rows), the EQuARX-style quantized allreduce matches ``lax.psum`` on both
the flattened EP axes and a single TP axis, the accuracy harness holds
its documented bounds on REAL routed traces (the gate behind ``auto``),
the env knob rejects unsupported dtypes by falling back, and the engine
exports the wire-byte accounting.  Everything runs on CPU: the dense
``all_to_all`` fallback ships the identical quantized payloads the TPU
ragged path does (quantization happens before the exchange, per row, so
both branches deliver the same bytes), and the int8 EXPERT kernel rides
along in interpret mode to prove the quantized wire feeds the streamed
kernel path unchanged.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from llm_d_tpu.models.config import ModelConfig
from llm_d_tpu.ops import collective_accuracy as acc
from llm_d_tpu.ops import moe as moe_ops
from llm_d_tpu.ops.quant import dequantize, quantize_int8
from llm_d_tpu.parallel.mesh import AXIS_EP, MeshConfig, make_mesh
from llm_d_tpu.parallel.quant_collectives import (
    a2a_row_bytes,
    dequantize_rows,
    ep_a2a_bytes_per_token,
    quantize_rows,
    quantized_psum,
    resolve_collective_dtype,
)


@pytest.fixture(scope="module")
def mesh(devices):
    return make_mesh(MeshConfig(dp=4, sp=1, tp=2), devices)


def _case(seed, T, E, H=32, I=16, k=2):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, H)), jnp.bfloat16)
    router = jnp.asarray(rng.standard_normal((H, E)), jnp.float32)
    w_gate = jnp.asarray(rng.standard_normal((E, H, I)) * 0.2, jnp.bfloat16)
    w_up = jnp.asarray(rng.standard_normal((E, H, I)) * 0.2, jnp.bfloat16)
    w_down = jnp.asarray(rng.standard_normal((E, I, H)) * 0.2, jnp.bfloat16)
    cfg = ModelConfig(name="cq-test", num_experts=E, num_experts_per_tok=k,
                      moe_renormalize=True)
    weights, idx = moe_ops.route(
        jnp.dot(x.astype(jnp.float32), router), cfg)
    return x, weights, idx, w_gate, w_up, w_down


def _rel_rms(a, b, ref):
    a, b, ref = (np.asarray(v, np.float32) for v in (a, b, ref))
    return float(np.sqrt(np.mean((a - b) ** 2))
                 / max(np.sqrt(np.mean(ref ** 2)), 1e-12))


# ---------------------------------------------------------------------------
# Wire-mode parity (the 2% rel-RMS per-collective acceptance bound)
# ---------------------------------------------------------------------------

def test_int8_wire_parity_per_collective(mesh, under_jit):
    """Each collective's quantization error, isolated by differencing
    wire modes against the SAME routing, is bounded at 2% rel-RMS of the
    oracle output — the acceptance bound, asserted on the op itself."""
    x, w, idx, wg, wu, wd = _case(7, 32, 16)
    oracle = under_jit(moe_ops.expert_ffn, x, w, idx, wg, wu, wd, mesh=mesh,
                                dispatch="psum")
    outs = {mode: under_jit(moe_ops.expert_ffn_a2a,
        x, w, idx, wg, wu, wd, mesh, collective_dtype=mode)
        for mode in ("bf16", "int8-dispatch", "int8")}
    # Dispatch collective: int8 outbound vs bf16 outbound, same combine.
    assert _rel_rms(outs["int8-dispatch"], outs["bf16"], oracle) <= 2e-2
    # Combine collective: int8 return vs bf16 return, same dispatch.
    assert _rel_rms(outs["int8"], outs["int8-dispatch"], oracle) <= 2e-2
    # And the full int8 wire still lands on the oracle.
    np.testing.assert_allclose(np.asarray(outs["int8"], np.float32),
                               np.asarray(oracle, np.float32),
                               atol=6e-2, rtol=6e-2)


def test_bf16_combine_downcast_parity(mesh, under_jit):
    """The round-10 quick win: the bf16 baseline combine no longer ships
    f32 rows.  Parity vs the psum oracle pins the downcast's tolerance —
    one bf16 rounding of the expert output, inside the pre-existing
    dispatch tolerance."""
    x, w, idx, wg, wu, wd = _case(11, 16, 8)
    oracle = under_jit(moe_ops.expert_ffn, x, w, idx, wg, wu, wd, mesh=mesh,
                                dispatch="psum")
    a2a = under_jit(moe_ops.expert_ffn_a2a, x, w, idx, wg, wu, wd, mesh,
                                 collective_dtype="bf16")
    np.testing.assert_allclose(np.asarray(a2a, np.float32),
                               np.asarray(oracle, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_int8_wire_feeds_streamed_kernel_interpret(mesh, under_jit):
    """Quantized wire + quantized EXPERTS together: the dequantized
    arrival rows feed the chunk-streamed int8 kernel (interpret mode)
    exactly like bf16 arrivals do — the wide-EP serving configuration,
    end to end on CPU."""
    key = jax.random.PRNGKey(3)
    T, E, H, I, k = 32, 16, 64, 32, 2
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    idx = jax.random.randint(ks[1], (T, k), 0, E)
    w = jnp.abs(jax.random.normal(ks[2], (T, k), jnp.float32)) * 0.3
    stack = lambda a: jnp.stack([jnp.zeros_like(a), a])
    quant = {"layer": jnp.int32(1)}
    deq = []
    wkeys = jax.random.split(ks[3], 3)
    for (name, shape), kk in zip(
            (("w_gate", (E, H, I)), ("w_up", (E, H, I)),
             ("w_down", (E, I, H))), wkeys):
        q, s = quantize_int8(
            jax.random.normal(kk, shape, jnp.float32) * 0.05)
        quant[f"{name}_q"], quant[f"{name}_s"] = stack(q), stack(s)
        deq.append(dequantize(q, s))
    got = under_jit(moe_ops.expert_ffn_a2a, x, w, idx, None, None, None, mesh,
                                 quant=quant, interpret=True,
                                 collective_dtype="int8")
    want = under_jit(moe_ops.expert_ffn_a2a, x, w, idx, *deq, mesh,
                                  collective_dtype="bf16")
    scale = float(jnp.max(jnp.abs(np.asarray(want, np.float32)))) + 1e-9
    np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                               np.asarray(want, np.float32) / scale,
                               atol=2e-2)


# ---------------------------------------------------------------------------
# Scale-plane exchange correctness (dense fallback; the ragged TPU branch
# consumes the SAME offset/size arrays by construction — XLA:CPU has no
# ragged_all_to_all to execute, so the dense path carries the contract)
# ---------------------------------------------------------------------------

def _exact_rows(rng, T, H):
    """Rows whose int8 round trip is EXACT: amax = 127/64 makes the
    per-row scale exactly 1/64 (an IEEE-exact division), and every entry
    m/64 with |m| <= 127 survives quantize->dequantize bit-for-bit (and
    is bf16-representable).  Any scale-plane misalignment — a scale
    landing on the wrong row under skew, chunking, or region offsets —
    then shows up as a hard numeric difference, not as noise."""
    m = rng.integers(-127, 128, (T, H)).astype(np.float32)
    m[:, 0] = 127.0                     # pin amax -> scale = 1/64 exactly
    return jnp.asarray(m / 64.0, jnp.bfloat16)


def test_scale_plane_alignment_byte_exact_under_skew(mesh, under_jit):
    """Dispatch-only quantization on exactly-representable rows must equal
    the bf16 wire BIT-FOR-BIT, under worst-case routing skew (every token
    to one shard's experts) and multi-chunk dispatch — the scale plane
    provably rides the same offsets as its payload rows."""
    rng = np.random.default_rng(5)
    T, E, H, k = 32, 16, 64, 2
    x = _exact_rows(rng, T, H)
    wg = jnp.asarray(rng.standard_normal((E, H, 16)) * 0.2, jnp.bfloat16)
    wu = jnp.asarray(rng.standard_normal((E, H, 16)) * 0.2, jnp.bfloat16)
    wd = jnp.asarray(rng.standard_normal((E, 16, H)) * 0.2, jnp.bfloat16)
    cases = {
        "skewed": jnp.tile(jnp.asarray([[0, 1]], jnp.int32), (T, 1)),
        "random": jnp.asarray(rng.integers(0, E, (T, k)), jnp.int32),
    }
    for name, idx in cases.items():
        w = jnp.abs(jnp.asarray(rng.standard_normal((T, k)),
                                jnp.float32)) * 0.5
        for chunk in (None, 2):
            a = under_jit(moe_ops.expert_ffn_a2a, x, w, idx, wg, wu, wd, mesh,
                                       chunk_tokens=chunk,
                                       collective_dtype="bf16")
            b = under_jit(moe_ops.expert_ffn_a2a, x, w, idx, wg, wu, wd, mesh,
                                       chunk_tokens=chunk,
                                       collective_dtype="int8-dispatch")
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                err_msg=f"case={name} chunk={chunk}")


def test_quantize_rows_round_trip_shapes():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((6, 32)), jnp.float32)
    q, s = quantize_rows(x)
    assert q.shape == (6, 32) and q.dtype == jnp.int8
    assert s.shape == (6,) and s.dtype == jnp.float32
    back = dequantize_rows(q, s)
    assert float(jnp.max(jnp.abs(back - x))) <= float(
        jnp.max(jnp.abs(x))) / 127.0 + 1e-6


# ---------------------------------------------------------------------------
# Quantized allreduce (psum fallback / TP)
# ---------------------------------------------------------------------------

def test_quantized_psum_matches_psum_on_ep_axes(mesh, under_jit):
    """expert_ffn dispatch='psum' under the int8 wire == the exact psum
    oracle within the combine bound — the EQuARX allreduce swap is
    numerically invisible at the documented tolerance."""
    x, w, idx, wg, wu, wd = _case(13, 16, 16)
    exact = under_jit(moe_ops.expert_ffn, x, w, idx, wg, wu, wd, mesh=mesh,
                               dispatch="psum", collective_dtype="bf16")
    quant = under_jit(moe_ops.expert_ffn, x, w, idx, wg, wu, wd, mesh=mesh,
                               dispatch="psum", collective_dtype="int8")
    assert _rel_rms(quant, exact, exact) <= 2e-2
    np.testing.assert_allclose(np.asarray(quant, np.float32),
                               np.asarray(exact, np.float32),
                               atol=6e-2, rtol=6e-2)


def test_quantized_psum_single_tp_axis(mesh):
    """The helper reduces over ONE named axis too (the dense-TP
    allreduce shape): parity vs lax.psum over 'tp', including a row
    count that does not divide the shard count (internal padding)."""
    rng = np.random.default_rng(17)
    for T in (8, 9):
        xs = jnp.asarray(rng.standard_normal((2 * T, 16)), jnp.float32)

        def body(xl):
            return (quantized_psum(xl, "tp", 2),
                    jax.lax.psum(xl, "tp"))

        from jax.sharding import PartitionSpec as P
        got, want = jax.shard_map(
            body, mesh=mesh, in_specs=(P("tp"),), out_specs=(P(), P()),
            check_vma=False)(xs)
        assert _rel_rms(got, want, want) <= 2e-2
        assert got.shape == want.shape == (T, 16)


# ---------------------------------------------------------------------------
# Accuracy harness on real routed traces — the gate behind `auto`
# ---------------------------------------------------------------------------

def _traffic_engine():
    from llm_d_tpu.engine.engine import EngineConfig, EngineCore
    from llm_d_tpu.engine.request import Request
    from llm_d_tpu.ops.sampling import SamplingParams
    e = EngineCore(EngineConfig(
        model="tiny-moe", block_size=4, num_blocks=64, max_num_seqs=4,
        max_num_batched_tokens=64, min_token_bucket=16, min_seq_bucket=4))
    reqs = [Request(
        request_id=f"r{i}",
        prompt_token_ids=[(7 * i + 13 * j) % 500 + 1 for j in range(12)],
        sampling=SamplingParams(temperature=0.0, max_tokens=6,
                                ignore_eos=True)) for i in range(3)]
    out = e.generate(reqs)
    streams = [r.prompt_token_ids + out[r.request_id] for r in reqs]
    return e, streams


def test_collective_harness_bounds_on_real_trace():
    """Real routed traces (a tiny-moe engine's served sequences replayed
    through the model with trace capture) must hold the documented
    per-collective bounds — the measured gate that justifies `auto`
    resolving to the int8 wire on TPU."""
    e, streams = _traffic_engine()
    trace = acc.harvest_routed_trace(e, streams)
    assert trace["x"].shape[0] == 1          # tiny-moe: one MoE layer
    assert trace["x"].shape[1] >= 32         # traffic actually traced
    reports = acc.layer_reports(trace, e.params["moe_layers"])
    for rep in reports:
        assert rep["dispatch"]["rel_rms"] <= rep["dispatch"]["bound_rel_rms"], rep
        assert rep["combine"]["rel_rms"] <= rep["combine"]["bound_rel_rms"], rep
        assert rep["within_bounds"] is True
        assert rep["end_to_end"]["rel_rms"] <= (
            acc.DISPATCH_REL_BOUND + acc.COMBINE_REL_BOUND)


def test_auto_gating_follows_backend():
    """`auto` = int8 exactly where the harness gate applies (TPU, where
    the ICI is the scarce resource) and the exact bf16 wire elsewhere —
    the MLA-absorption-harness gating pattern."""
    assert resolve_collective_dtype("auto", backend="tpu") == "int8"
    assert resolve_collective_dtype("auto", backend="cpu") == "bf16"
    assert resolve_collective_dtype(None, backend="cpu") == "bf16"
    assert resolve_collective_dtype("int8", backend="cpu") == "int8"
    assert resolve_collective_dtype("bf16", backend="tpu") == "bf16"


# ---------------------------------------------------------------------------
# Env knob + byte accounting + engine metric
# ---------------------------------------------------------------------------

def test_env_knob_rejects_unsupported_dtype(monkeypatch):
    monkeypatch.setenv("LLMD_COLLECTIVE_DTYPE", "fp4")
    assert resolve_collective_dtype(backend="cpu") == "bf16"   # auto
    assert resolve_collective_dtype(backend="tpu") == "int8"   # auto
    monkeypatch.setenv("LLMD_COLLECTIVE_DTYPE", "int8")
    assert resolve_collective_dtype(backend="cpu") == "int8"
    monkeypatch.setenv("LLMD_COLLECTIVE_DTYPE", "bf16")
    assert resolve_collective_dtype(backend="tpu") == "bf16"
    with pytest.raises(ValueError):
        resolve_collective_dtype("int4")
    with pytest.raises(ValueError):
        a2a_row_bytes(64, "fp4")


def test_wire_byte_accounting_acceptance_ratio():
    """The acceptance arithmetic itself: int8 dispatch+combine bytes are
    <= 0.35x the f32-combine baseline at the paper model's hidden size,
    and the per-mode table is internally consistent."""
    H, k = 7168, 8
    base = ep_a2a_bytes_per_token(H, k, "f32-combine")
    int8 = ep_a2a_bytes_per_token(H, k, "int8")
    assert int8 / base <= 0.35, (int8, base)
    row = a2a_row_bytes(H, "int8")
    assert row["dispatch"] == H + 4 + 4      # payload + scale + index
    assert row["combine"] == H + 4
    assert ep_a2a_bytes_per_token(H, k, "bf16", layers=3) == \
        3 * k * (a2a_row_bytes(H, "bf16")["dispatch"] + 2 * H)


def test_engine_exports_collective_bytes(devices):
    """A multi-device MoE engine charges the exchange bytes per computed
    token to llmd_tpu:collective_bytes_total, labeled by collective and
    resolved wire dtype; a single-device engine ships none."""
    from llm_d_tpu.engine.engine import EngineConfig, EngineCore
    from llm_d_tpu.engine.request import Request
    from llm_d_tpu.ops.sampling import SamplingParams
    from llm_d_tpu.utils.metrics import parse_prometheus_text
    kw = dict(model="tiny-moe", block_size=4, num_blocks=64,
              max_num_seqs=4, max_num_batched_tokens=64,
              min_token_bucket=16, min_seq_bucket=4)
    e = EngineCore(EngineConfig(**kw, mesh=MeshConfig(tp=2),
                                allow_device_subset=True),
                   devices=devices[:2])
    assert e._collective_wire == "bf16"      # auto on CPU
    e.generate([Request(
        request_id="m", prompt_token_ids=list(range(1, 9)),
        sampling=SamplingParams(temperature=0.0, max_tokens=4,
                                ignore_eos=True))])
    parsed = parse_prometheus_text(e.metrics.render().decode())
    got = {k: v for k, v in parsed.items()
           if "collective_bytes" in k and "{" in k}
    assert any("dispatch" in k for k in got), parsed.keys()
    assert any("combine" in k for k in got), parsed.keys()
    # Consistency with the byte model: dispatch bytes = N computed
    # tokens x k x dispatch-row bytes (Lm = 1 on tiny-moe), and the
    # combine counter charges the same N tokens at combine-row bytes.
    row = a2a_row_bytes(e.model_config.hidden_size, "bf16")
    k_tok = e.model_config.num_experts_per_tok
    dispatch_val = [v for k, v in got.items() if "dispatch" in k][0]
    combine_val = [v for k, v in got.items() if "combine" in k][0]
    n_tok = dispatch_val / (k_tok * row["dispatch"])
    assert n_tok == int(n_tok) and n_tok >= 8, (dispatch_val, row)
    assert combine_val == n_tok * k_tok * row["combine"]

    single = EngineCore(EngineConfig(**kw), devices=[devices[0]])
    assert single._collective_wire is None


def test_psum_bytes_model():
    """The allreduce accounting model (charged when a non-power-of-two
    ep forces the psum fallback on every step — a mesh E % ep != 0
    cannot even build, the expert weights shard over the EP axes):
    k-independent, full-activation, both ring legs; int8 mode charges
    the quantized reduce-scatter + all-gather wire."""
    from llm_d_tpu.parallel.quant_collectives import psum_bytes_per_token
    H = 7168
    assert psum_bytes_per_token(H, "bf16") == 2 * 4 * H     # f32 psum
    assert psum_bytes_per_token(H, "int8") == 2 * (H + 4)
    # ~4x fewer wire bytes than the f32 psum (the quantized_psum claim).
    assert psum_bytes_per_token(H, "int8") \
        <= 0.26 * psum_bytes_per_token(H, "bf16")
    with pytest.raises(ValueError):
        psum_bytes_per_token(H, "fp4")
