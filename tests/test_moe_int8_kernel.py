"""Pallas int8 MoE kernel: interpret-mode parity vs the dequantized XLA
dense path (the kernel's math contract: raw-integer bf16 dots with the
per-output-column scale applied to the f32 output — numerically the same
weight-only-int8 scheme as ops.quant.dequantize, so the two paths must
agree to within bf16 dot noise)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.ops import moe as moe_ops
from llm_d_tpu.ops.pallas.moe_int8 import dense_moe_int8
from llm_d_tpu.ops.quant import dequantize, quantize_int8


@pytest.mark.parametrize("T,E,H,I", [(16, 8, 256, 128), (32, 4, 512, 256)])
def test_kernel_matches_dequantized_dense(T, E, H, I):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    wg_q, wg_s = quantize_int8(
        jax.random.normal(ks[1], (E, H, I), jnp.float32) * 0.05)
    wu_q, wu_s = quantize_int8(
        jax.random.normal(ks[2], (E, H, I), jnp.float32) * 0.05)
    wd_q, wd_s = quantize_int8(
        jax.random.normal(ks[3], (E, I, H), jnp.float32) * 0.05)
    comb = jnp.abs(jax.random.normal(ks[4], (T, E), jnp.float32)) * 0.2
    # Zero out most combine entries like real routing does.
    comb = jnp.where(comb > 0.15, comb, 0.0)

    g = dequantize(wg_q, wg_s)
    u = dequantize(wu_q, wu_s)
    d = dequantize(wd_q, wd_s)
    h = jnp.einsum("th,ehi->eti", x, g, preferred_element_type=jnp.float32)
    uu = jnp.einsum("th,ehi->eti", x, u, preferred_element_type=jnp.float32)
    a = (jax.nn.silu(h) * uu * comb.T[:, :, None]).astype(jnp.bfloat16)
    want = jnp.einsum("eti,eih->th", a, d,
                      preferred_element_type=jnp.float32)

    # Stacked layout (the engine passes whole [Lm, E, ...] stacks + a
    # layer index): duplicate the layer twice and address plane 1 to
    # exercise the scalar-prefetch indexing.
    stack = lambda a: jnp.stack([jnp.zeros_like(a), a])
    got = dense_moe_int8(x, comb, 1,
                         stack(wg_q), stack(wg_s), stack(wu_q),
                         stack(wu_s), stack(wd_q), stack(wd_s),
                         interpret=True)
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=6e-3)


def test_kernel_dispatch_wiring_matches_dequant_path():
    """Drives expert_ffn's ACTUAL kernel glue (_dense_int8_kernel_path:
    combine scatter + stacked call) in interpret mode against the
    _dequant_layer fallback — the backend gate hides this wiring from CPU
    CI otherwise."""

    key = jax.random.PRNGKey(1)
    T, E, H, I, k, Lm = 16, 8, 256, 128, 2, 2
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    weights = jnp.abs(jax.random.normal(ks[1], (T, k), jnp.float32))
    idx = jax.random.randint(ks[2], (T, k), 0, E)
    quant = {"layer": 1}
    for name, kk, shape in (("w_gate", ks[3], (Lm, E, H, I)),
                            ("w_up", ks[4], (Lm, E, H, I)),
                            ("w_down", ks[5], (Lm, E, I, H))):
        q, s = quantize_int8(
            jax.random.normal(kk, shape, jnp.float32) * 0.05)
        quant[f"{name}_q"], quant[f"{name}_s"] = q, s

    got = moe_ops._dense_int8_kernel_path(x, weights, idx, quant,
                                          interpret=True)
    w_gate, w_up, w_down = moe_ops._dequant_layer(quant)
    want = moe_ops._dense_expert_ffn(x, weights, idx, w_gate, w_up, w_down)
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    np.testing.assert_allclose(np.asarray(got).astype(np.float32) / scale,
                               np.asarray(want).astype(np.float32) / scale,
                               atol=1e-2)


def test_engine_int8_uses_kernel_only_on_tpu():
    """On CPU the engine's int8 path must fall back to the XLA dequant
    dense path (the kernel is TPU-only); generation stays correct."""
    from llm_d_tpu.engine.engine import EngineConfig, EngineCore
    from llm_d_tpu.engine.request import Request
    from llm_d_tpu.ops.sampling import SamplingParams

    def req(rid):
        return Request(request_id=rid, prompt_token_ids=[1, 2, 3, 4, 5, 6],
                       sampling=SamplingParams(temperature=0.0, max_tokens=4,
                                               ignore_eos=True))

    base = EngineCore(EngineConfig(
        model="tiny-moe", block_size=4, num_blocks=32, max_num_seqs=4,
        max_num_batched_tokens=64, min_token_bucket=16, min_seq_bucket=4))
    q = EngineCore(EngineConfig(
        model="tiny-moe", block_size=4, num_blocks=32, max_num_seqs=4,
        max_num_batched_tokens=64, min_token_bucket=16, min_seq_bucket=4,
        quantization="int8"))
    want = base.generate([req("a")])["a"]
    got = q.generate([req("b")])["b"]
    # int8 weight noise may flip late tokens; the first ones must agree.
    assert got[:2] == want[:2]


def _rand_quant(key, E, H, I, Lm=2, plane=1):
    """Stacked int8 payloads addressing plane 1 (exercises the
    scalar-prefetch layer indexing) + the dequantized plane for oracles."""
    ks = jax.random.split(key, 3)
    stack = lambda a: jnp.stack([jnp.zeros_like(a), a])
    quant = {"layer": jnp.int32(plane)}
    deq = {}
    for name, kk, shape in (("w_gate", ks[0], (E, H, I)),
                            ("w_up", ks[1], (E, H, I)),
                            ("w_down", ks[2], (E, I, H))):
        q, s = quantize_int8(
            jax.random.normal(kk, shape, jnp.float32) * 0.05)
        quant[f"{name}_q"], quant[f"{name}_s"] = stack(q), stack(s)
        deq[name] = dequantize(q, s)
    return quant, (deq["w_gate"], deq["w_up"], deq["w_down"])


def _eplb_physical(quant, idx):
    """A physical layout of 4 logical experts: expert 1 gets a replica in
    slot 4, expert 3 in slot 5 (E_phys = 6), replica weights copies of the
    logical ones.  Returns the physical payloads and the physical ids of
    ``idx`` (``to_physical_experts``)."""
    replica_table = jnp.asarray(
        [[0, 0], [1, 4], [2, 2], [3, 5]], jnp.int32)
    num_replicas = jnp.asarray([1, 2, 1, 2], jnp.int32)
    phys_of = jnp.asarray([0, 1, 2, 3, 1, 3])
    quant_phys = dict(quant)
    for name in ("w_gate", "w_up", "w_down"):
        for suf in ("_q", "_s"):
            quant_phys[name + suf] = quant[name + suf][:, phys_of]
    phys_idx = moe_ops.to_physical_experts(idx, replica_table, num_replicas)
    assert int(phys_idx.max()) >= 4  # replicas actually exercised
    return quant_phys, phys_idx


def _assert_routed_matches_oracle(x, w, idx, quant, deq, rt=None):
    got = moe_ops._routed_int8_kernel_path(
        x, w, idx, quant, row_tile=rt, interpret=True)
    want = moe_ops._local_expert_ffn(x, w, idx, *deq, jnp.int32(0))
    scale = float(jnp.max(jnp.abs(np.asarray(want)))) + 1e-9
    np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                               np.asarray(want, np.float32) / scale,
                               atol=8e-3)


@pytest.mark.parametrize("T,E,H,I,k,rt", [
    (16, 8, 256, 128, 2, 8),     # tiny decode batch
    (36, 8, 256, 128, 2, 16),    # T not a multiple of the bf16 sublane (16)
    (64, 4, 512, 256, 4, 32),    # multi-tile groups
    (48, 16, 256, 128, 8, 16),   # S = T*k >> E: every expert multi-row
])
def test_routed_kernel_matches_dequant_oracle(T, E, H, I, k, rt):
    """Fused-routing kernel (in-kernel one-hot gather/combine) == routed
    dequant oracle, through the ACTUAL glue (_routed_int8_kernel_path:
    counting sort, slot arithmetic, tile_expert map) in interpret mode.
    The routed-only math must equal the XLA dense-combine reference."""
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    idx = jax.random.randint(ks[1], (T, k), 0, E)
    w = jnp.abs(jax.random.normal(ks[2], (T, k), jnp.float32)) * 0.3
    quant, deq = _rand_quant(ks[3], E, H, I)
    _assert_routed_matches_oracle(x, w, idx, quant, deq, rt=rt)


def test_routed_kernel_empty_expert_groups():
    """Routing concentrated on 3 of 16 experts: the 13 empty groups get
    ZERO tiles (their weights are never addressed) and the output still
    matches the oracle — the empty-group skip the EPLB-sharded and
    small-batch layouts rely on."""

    key = jax.random.PRNGKey(13)
    T, E, H, I, k = 32, 16, 256, 128, 2
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    hot = jnp.asarray([1, 7, 12], jnp.int32)
    idx = hot[jax.random.randint(ks[1], (T, k), 0, 3)]
    w = jnp.abs(jax.random.normal(ks[2], (T, k), jnp.float32)) * 0.3
    quant, deq = _rand_quant(ks[3], E, H, I)
    _assert_routed_matches_oracle(x, w, idx, quant, deq, rt=16)
    # The tile map must reference only populated experts: with 3 hot
    # experts and rt=16, every active tile belongs to {1, 7, 12}, and
    # the inactive trailing tiles REPEAT the last active tile's expert
    # (same weight index map -> Pallas skips their DMA; a clamp to E-1
    # would stream an unused expert's weights).
    rt, S = 16, T * k
    _, _, _, tile_e, num_tiles = moe_ops._sorted_tile_layout(
        idx.reshape(S), w.reshape(S), k, E, rt)
    nt = int(num_tiles)
    active = np.asarray(tile_e[:nt])
    assert set(active.tolist()) == {1, 7, 12}
    assert np.all(np.asarray(tile_e[nt:]) == active[-1])


def test_routed_kernel_duplicate_routes_accumulate():
    """A token routed to the SAME expert in two slots contributes the sum
    of both combine weights (the transposed one-hot merges duplicates)."""
    key = jax.random.PRNGKey(17)
    T, E, H, I = 16, 4, 256, 128
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    idx = jnp.stack([jnp.full((T,), 2, jnp.int32),
                     jnp.full((T,), 2, jnp.int32)], axis=1)
    w = jnp.abs(jax.random.normal(ks[1], (T, 2), jnp.float32)) * 0.3
    quant, deq = _rand_quant(ks[2], E, H, I)
    _assert_routed_matches_oracle(x, w, idx, quant, deq, rt=8)


def test_routed_kernel_eplb_physical_layout():
    """Routed kernel under an EPLB replica table: logical ids map to
    physical slots (to_physical_experts), replicas carry the SAME weights,
    and the kernel over the physical layout matches the logical oracle."""

    key = jax.random.PRNGKey(19)
    T, E_log, H, I, k = 24, 4, 256, 128, 2
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    idx = jax.random.randint(ks[1], (T, k), 0, E_log)
    w = jnp.abs(jax.random.normal(ks[2], (T, k), jnp.float32)) * 0.3
    quant, deq = _rand_quant(ks[3], E_log, H, I)

    quant_phys, phys_idx = _eplb_physical(quant, idx)

    got = moe_ops._routed_int8_kernel_path(
        x, w, phys_idx, quant_phys, row_tile=8, interpret=True)
    want = moe_ops._local_expert_ffn(x, w, idx, *deq, jnp.int32(0))
    scale = float(jnp.max(jnp.abs(np.asarray(want)))) + 1e-9
    np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                               np.asarray(want, np.float32) / scale,
                               atol=8e-3)


def _assert_streamed_matches_oracle(x, w, idx, quant, deq,
                                    chunk_t=None, rt=None):
    got = moe_ops._streamed_int8_kernel_path(
        x, w, idx, quant, chunk_t=chunk_t, row_tile=rt, interpret=True)
    want = moe_ops._local_expert_ffn(x, w, idx, *deq, jnp.int32(0))
    scale = float(jnp.max(jnp.abs(np.asarray(want)))) + 1e-9
    np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                               np.asarray(want, np.float32) / scale,
                               atol=8e-3)


@pytest.mark.parametrize("T,chunk_t,E,H,I,k,rt", [
    (32, 16, 8, 256, 128, 2, 8),    # T an exact chunk multiple
    (17, 16, 8, 256, 128, 2, 8),    # T = chunk + 1 (padded final chunk)
    (15, 16, 8, 256, 128, 2, 8),    # T = chunk - 1 (single padded chunk)
    (8, 64, 8, 256, 128, 2, 8),     # T < chunk (degenerates to routed)
    (48, 16, 16, 256, 128, 8, 16),  # k=8: S_c >> chunk, multi-row groups
])
def test_streamed_kernel_matches_dequant_oracle(T, chunk_t, E, H, I, k, rt):
    """Chunk-streamed kernel (per-chunk counting sort + in-kernel one-hot
    gather/combine over streamed x chunks) == routed dequant oracle,
    through the ACTUAL glue (_streamed_int8_kernel_path: chunk padding,
    vmapped per-chunk layouts, flattened tile metadata) in interpret
    mode, across every chunk-boundary shape class."""
    key = jax.random.PRNGKey(23)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    idx = jax.random.randint(ks[1], (T, k), 0, E)
    w = jnp.abs(jax.random.normal(ks[2], (T, k), jnp.float32)) * 0.3
    quant, deq = _rand_quant(ks[3], E, H, I)
    _assert_streamed_matches_oracle(x, w, idx, quant, deq,
                                    chunk_t=chunk_t, rt=rt)


def test_streamed_kernel_empty_experts_within_chunk():
    """Routing concentrated on 3 of 16 experts: every CHUNK's tile map
    references only populated experts (zero tiles for empty groups —
    their weights are never streamed for that chunk) and trailing
    inactive tiles repeat the last active expert so their weight DMA is
    skipped.  Output still matches the oracle."""

    key = jax.random.PRNGKey(29)
    T, chunk_t, E, H, I, k, rt = 32, 16, 16, 256, 128, 2, 16
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    hot = jnp.asarray([1, 7, 12], jnp.int32)
    idx = hot[jax.random.randint(ks[1], (T, k), 0, 3)]
    w = jnp.abs(jax.random.normal(ks[2], (T, k), jnp.float32)) * 0.3
    quant, deq = _rand_quant(ks[3], E, H, I)
    _assert_streamed_matches_oracle(x, w, idx, quant, deq,
                                    chunk_t=chunk_t, rt=rt)
    S_c = chunk_t * k
    for c in range(T // chunk_t):
        sl = idx.reshape(-1)[c * S_c:(c + 1) * S_c]
        wl = w.reshape(-1)[c * S_c:(c + 1) * S_c]
        _, _, _, tile_e, num_tiles = moe_ops._sorted_tile_layout(
            sl, wl, k, E, rt)
        nt = int(num_tiles)
        active = np.asarray(tile_e[:nt])
        assert set(active.tolist()) <= {1, 7, 12}, c
        assert np.all(np.asarray(tile_e[nt:]) == active[-1]), c


def test_streamed_kernel_duplicate_routes_across_chunk_boundaries():
    """Duplicate routes both WITHIN a token (both k slots -> expert 2)
    and ACROSS chunks (every chunk routes to the same expert, whose
    weights re-stream per chunk): contributions accumulate exactly in
    the chunk-resident f32 output blocks."""
    key = jax.random.PRNGKey(31)
    T, chunk_t, E, H, I = 48, 16, 4, 256, 128
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    idx = jnp.stack([jnp.full((T,), 2, jnp.int32),
                     jnp.full((T,), 2, jnp.int32)], axis=1)
    w = jnp.abs(jax.random.normal(ks[1], (T, 2), jnp.float32)) * 0.3
    quant, deq = _rand_quant(ks[2], E, H, I)
    _assert_streamed_matches_oracle(x, w, idx, quant, deq,
                                    chunk_t=chunk_t, rt=8)


def test_streamed_kernel_eplb_physical_layout():
    """Streamed kernel under an EPLB replica table (mirrors the routed
    kernel's test): logical ids map to physical slots, replicas carry
    the same weights, and the chunked physical layout matches the
    logical oracle."""

    key = jax.random.PRNGKey(37)
    T, chunk_t, E_log, H, I, k = 40, 16, 4, 256, 128, 2
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    idx = jax.random.randint(ks[1], (T, k), 0, E_log)
    w = jnp.abs(jax.random.normal(ks[2], (T, k), jnp.float32)) * 0.3
    quant, deq = _rand_quant(ks[3], E_log, H, I)

    quant_phys, phys_idx = _eplb_physical(quant, idx)

    got = moe_ops._streamed_int8_kernel_path(
        x, w, phys_idx, quant_phys, chunk_t=chunk_t, row_tile=8,
        interpret=True)
    want = moe_ops._local_expert_ffn(x, w, idx, *deq, jnp.int32(0))
    scale = float(jnp.max(jnp.abs(np.asarray(want)))) + 1e-9
    np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                               np.asarray(want, np.float32) / scale,
                               atol=8e-3)


def test_streamed_a2a_matches_dequant_a2a(devices, under_jit):
    """Wide-EP per-chunk GEMM through the streamed int8 kernel
    (expert_ffn_a2a with quant payloads sharded over the expert dim)
    == the bf16 dequant a2a path — the prefill-regime win carries to
    EP without changing the exchange wire layout."""
    from llm_d_tpu.ops.quant import dequantize
    from llm_d_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=4, sp=1, tp=2), devices)
    key = jax.random.PRNGKey(41)
    T, E, H, I, k = 32, 16, 64, 32, 2
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    idx = jax.random.randint(ks[1], (T, k), 0, E)
    w = jnp.abs(jax.random.normal(ks[2], (T, k), jnp.float32)) * 0.3
    quant, deq = _rand_quant(ks[3], E, H, I)

    got = under_jit(moe_ops.expert_ffn_a2a, x, w, idx, None, None, None, mesh,
                    quant=quant, interpret=True)
    want = under_jit(moe_ops.expert_ffn_a2a, x, w, idx, *deq, mesh)
    scale = float(jnp.max(jnp.abs(np.asarray(want, np.float32)))) + 1e-9
    np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                               np.asarray(want, np.float32) / scale,
                               atol=1e-2)


def _assert_one_pass_matches_oracle(x, w, idx, quant, deq, rt=None):
    got = moe_ops._one_pass_int8_kernel_path(
        x, w, idx, quant, row_tile=rt, interpret=True)
    want = moe_ops._local_expert_ffn(x, w, idx, *deq, jnp.int32(0))
    assert got.shape == x.shape and got.dtype == x.dtype
    scale = float(jnp.max(jnp.abs(np.asarray(want)))) + 1e-9
    np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                               np.asarray(want, np.float32) / scale,
                               atol=8e-3)


def _routed_case(key, T, E, H, I, k, distinct=True):
    """Rows, routing as ``route()`` gives it (top-k of seeded router
    logits: k distinct experts a token) and stacked int8 experts."""
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    logits = jax.random.normal(ks[1], (T, E), jnp.float32)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    quant, deq = _rand_quant(ks[2], E, H, I)
    return x, w, idx.astype(jnp.int32), quant, deq


@pytest.mark.parametrize("T,E,H,I,k,rt", [
    # The four geometries' rows an expert against the tile, at small
    # widths: trinity-mini / qwen3 (128 experts, top-8: the mean run is one
    # tile), kanana (top-6: three quarters of one), mellum2 (64 experts:
    # two tiles), and the 1,024 bucket's half tile.
    (64, 16, 256, 128, 8, 32),     # S / E = 32 = rt
    (64, 16, 256, 128, 6, 32),     # S / E = 24: top-6
    (64, 8, 384, 128, 8, 32),      # S / E = 64 = 2 rt; hidden 3 lane tiles
    (32, 16, 256, 128, 8, 32),     # S / E = 16 = rt / 2
    (37, 8, 256, 128, 2, 16),      # T no multiple of anything
    (48, 16, 256, 128, 8, None),   # the tile the path's own rule gives
], ids=["mean-1-tile", "top6", "mean-2-tiles-H384", "half-tile", "T37",
        "rule"])
def test_one_pass_kernel_matches_dequant_oracle(T, E, H, I, k, rt):
    """One-pass kernel == routed dequant oracle through the ACTUAL glue
    (_one_pass_int8_kernel_path: the layout over the whole step, the row
    gather into it, the kernel, the gather back and the k-sum) in
    interpret mode."""
    x, w, idx, quant, deq = _routed_case(jax.random.PRNGKey(43), T, E, H, I,
                                         k)
    _assert_one_pass_matches_oracle(x, w, idx, quant, deq, rt=rt)


def _layout_reference(idx, w, E, rt):
    """The layout by a plain stable sort on the host."""
    idx, w = np.asarray(idx), np.asarray(w)
    T, k = idx.shape
    counts = np.bincount(idx.reshape(-1), minlength=E)
    tiles = -(-counts // rt)
    base = (np.cumsum(tiles) - tiles) * rt
    NT = -(-T * k // rt) + E
    pos = np.zeros((T, k), np.int64)
    tok_pad = np.zeros(NT * rt, np.int64)
    wslot = np.zeros(NT * rt, np.float32)
    seen = np.zeros(E, np.int64)
    for t in range(T):
        for j in range(k):
            e = idx[t, j]
            pos[t, j] = base[e] + seen[e]
            tok_pad[pos[t, j]], wslot[pos[t, j]] = t, w[t, j]
            seen[e] += 1
    nt = int(tiles.sum())
    tile_expert = np.repeat(np.arange(E), tiles)
    tile_expert = np.concatenate(
        [tile_expert, np.full(NT - nt, tile_expert[-1])])
    return pos, tok_pad, wslot, tile_expert, nt


@pytest.mark.parametrize("T,E,k,rt,seed", [
    (64, 16, 8, 32, 0), (600, 16, 4, 32, 1), (1100, 8, 2, 64, 2),
    (5, 4, 3, 16, 3)])
def test_one_pass_layout_is_the_stable_sort(T, E, k, rt, seed):
    """``_one_pass_layout`` (per-token counts, a triangular product for the
    ranks, one scatter) gives what a stable sort by expert gives: slots in
    token order within an expert's run, runs padded to the tile, in expert
    order; more rows than one block of the triangular product holds, and
    duplicate choices within a token (random ids, not top-k)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    idx = jax.random.randint(ks[0], (T, k), 0, E)
    w = jax.random.uniform(ks[1], (T, k), jnp.float32)
    got = jax.jit(moe_ops._one_pass_layout, static_argnums=(2, 3))(
        idx, w, E, rt)
    want = _layout_reference(idx, w, E, rt)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), r)


def test_one_pass_kernel_empty_experts_get_no_tile():
    """Routing concentrated on 3 of 16 experts: only they have tiles (the
    others' matrices are not read), the idle tiles repeat the last one's
    expert, and the output matches the oracle."""
    key = jax.random.PRNGKey(47)
    T, E, H, I, k, rt = 32, 16, 256, 128, 2, 16
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    hot = jnp.asarray([1, 7, 12], jnp.int32)
    idx = hot[jax.random.randint(ks[1], (T, k), 0, 3)]
    w = jnp.abs(jax.random.normal(ks[2], (T, k), jnp.float32)) * 0.3
    quant, deq = _rand_quant(ks[3], E, H, I)
    _assert_one_pass_matches_oracle(x, w, idx, quant, deq, rt=rt)
    _, _, _, tile_e, num_tiles = moe_ops._one_pass_layout(idx, w, E, rt)
    nt = int(num_tiles)
    active = np.asarray(tile_e[:nt])
    assert set(active.tolist()) == {1, 7, 12}
    assert np.all(np.diff(active) >= 0)
    assert np.all(np.asarray(tile_e[nt:]) == active[-1])


def test_one_pass_kernel_duplicate_routes_accumulate():
    """A token routed to the SAME expert in both slots: two rows of the
    expert's run, both summed into the token."""
    key = jax.random.PRNGKey(53)
    T, E, H, I = 24, 4, 256, 128
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    idx = jnp.full((T, 2), 2, jnp.int32)
    w = jnp.abs(jax.random.normal(ks[1], (T, 2), jnp.float32)) * 0.3
    quant, deq = _rand_quant(ks[2], E, H, I)
    _assert_one_pass_matches_oracle(x, w, idx, quant, deq, rt=16)


def test_one_pass_kernel_padded_rows_of_the_token_bucket():
    """A token bucket's padded rows (zero rows that all choose the same
    experts, as a zero embedding does) change no real row's result."""
    T, real, E, H, I, k = 64, 41, 16, 256, 128, 4
    x, w, idx, quant, deq = _routed_case(jax.random.PRNGKey(59), T, E, H, I,
                                         k)
    pad = jnp.arange(T)[:, None] >= real
    x = jnp.where(pad, 0, x)
    idx = jnp.where(pad, idx[real], idx)
    got = moe_ops._one_pass_int8_kernel_path(x, w, idx, quant, row_tile=16,
                                             interpret=True)
    alone = moe_ops._one_pass_int8_kernel_path(
        x[:real], w[:real], idx[:real], quant, row_tile=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[:real], np.float32),
                                  np.asarray(alone, np.float32))
    assert not np.any(np.asarray(got[real:], np.float32))


@pytest.mark.parametrize("rows", [32, 33], ids=["one-tile", "one-row-over"])
def test_one_pass_kernel_run_of_one_tile_and_one_row_over(rows):
    """An expert whose run is exactly one tile, and one row over (a second
    tile of one real row, multiplied from the matrices the first tile
    cast)."""
    T, E, H, I, rt = 48, 4, 256, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(61), 3)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    first = jnp.where(jnp.arange(T) < rows, 1, 3)
    idx = jnp.stack([first, jnp.zeros((T,), jnp.int32)], axis=1).astype(
        jnp.int32)
    w = jnp.abs(jax.random.normal(ks[1], (T, 2), jnp.float32)) * 0.3
    quant, deq = _rand_quant(ks[2], E, H, I)
    _, _, _, tile_e, num_tiles = moe_ops._one_pass_layout(idx, w, E, rt)
    assert np.asarray(tile_e[:int(num_tiles)]).tolist().count(1) == \
        -(-rows // rt)
    _assert_one_pass_matches_oracle(x, w, idx, quant, deq, rt=rt)


def test_one_pass_kernel_eplb_physical_layout():
    """One-pass kernel under an EPLB replica table (mirrors the routed
    kernel's test): replicas carry the same weights, the layout over the
    physical ids matches the logical oracle."""
    key = jax.random.PRNGKey(67)
    T, E_log, H, I, k = 40, 4, 256, 128, 2
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    idx = jax.random.randint(ks[1], (T, k), 0, E_log)
    w = jnp.abs(jax.random.normal(ks[2], (T, k), jnp.float32)) * 0.3
    quant, deq = _rand_quant(ks[3], E_log, H, I)

    quant_phys, phys_idx = _eplb_physical(quant, idx)

    got = moe_ops._one_pass_int8_kernel_path(
        x, w, phys_idx, quant_phys, row_tile=16, interpret=True)
    want = moe_ops._local_expert_ffn(x, w, idx, *deq, jnp.int32(0))
    scale = float(jnp.max(jnp.abs(np.asarray(want)))) + 1e-9
    np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                               np.asarray(want, np.float32) / scale,
                               atol=8e-3)


def test_one_pass_rounds_where_the_streamed_kernel_does():
    """Same mathematics as the streamed kernel the path replaced: bf16
    after ``silu(h) * u * weight`` and after the down projection, f32
    elsewhere.  The two differ only in the order of the f32 k-sum, which
    the last rounding to bf16 shows in an ulp here and there."""
    x, w, idx, quant, _ = _routed_case(jax.random.PRNGKey(71), 64, 16, 256,
                                       128, 4)
    got = moe_ops._one_pass_int8_kernel_path(x, w, idx, quant, row_tile=16,
                                             interpret=True)
    was = moe_ops._streamed_int8_kernel_path(x, w, idx, quant, chunk_t=32,
                                             row_tile=8, interpret=True)
    got, was = np.asarray(got, np.float32), np.asarray(was, np.float32)
    ulp = np.maximum(np.abs(was), 2.0 ** -126) * 2.0 ** -7
    assert np.all(np.abs(got - was) <= ulp)
    assert np.mean(got != was) < 0.05


@pytest.mark.parametrize("T,k,E,want", [
    (2048, 8, 128, 128),   # trinity-mini, qwen3, sdar: 128 rows an expert
    (1024, 8, 128, 64),    # ... their 1,024 bucket: 64
    (2048, 6, 128, 128),   # kanana-2: 96
    (1024, 6, 128, 64),    # 48
    (2048, 8, 64, 128),    # mellum2: 256
    (1024, 8, 64, 128),    # 128
    (8192, 8, 128, 128),
])
def test_one_pass_row_tile_by_rows_an_expert(T, k, E, want):
    """The MXU's height from 96 rows an expert up, half below: the rule of
    the one-pass path alone (``_routed_row_tile`` keeps its own for the
    kernels of 512 rows and fewer, whose programs did not change)."""
    assert moe_ops._one_pass_row_tile(T * k, E) == want
    assert moe_ops._routed_row_tile(512 * 8, 128) == 32
    assert moe_ops._routed_row_tile(512 * 8, 32) == 64


# Selectors that PR 44 deleted: what they held must no longer reach the
# choice (each value would have moved it).
_DELETED_SELECTORS = {
    "LLMD_MOE_DENSE_KERNEL_MAX_T": "4",
    "LLMD_MOE_GROUPED_MIN_T": "100",
    "LLMD_MOE_PREFILL_KERNEL": "grouped",
    "LLMD_MOE_DENSE_MAX_T": "1",
    "LLMD_MOE_ROUTED_ROW_TILE": "8",
    "LLMD_MOE_PREFILL_CHUNK_T": "128",
}


@pytest.fixture
def kernel_calls(monkeypatch):
    """The three int8 kernels replaced by recorders of (kernel, row tile,
    chunk) under a pretended TPU backend, so that ``expert_ffn`` and the
    real glue paths run on the CPU; the deleted selectors set."""
    from llm_d_tpu.ops.pallas import (
        moe_int8, moe_one_pass, moe_routed, moe_routed_stream)
    calls = []

    def recorder(name):
        def kernel(x, *args, row_tile=None, chunk_t=None, **kw):
            calls.append((name, row_tile, chunk_t))
            return jnp.zeros(x.shape, jnp.float32)
        return kernel

    monkeypatch.setattr(moe_int8, "dense_moe_int8", recorder("dense"))
    monkeypatch.setattr(moe_routed, "routed_moe_int8", recorder("routed"))
    monkeypatch.setattr(moe_routed_stream, "streamed_moe_int8",
                        recorder("streamed"))
    monkeypatch.setattr(moe_one_pass, "one_pass_moe_int8",
                        recorder("one_pass"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name, value in _DELETED_SELECTORS.items():
        monkeypatch.setenv(name, value)
    return calls


@pytest.mark.parametrize("T,want", [
    (8, ("dense", None, None)),
    (moe_ops.DENSE_INT8_MAX_T, ("dense", None, None)),
    (moe_ops.DENSE_INT8_MAX_T + 1, ("routed", 32, None)),
    (moe_ops.ROUTED_INT8_MAX_T, ("routed", 64, None)),
    (moe_ops.ROUTED_INT8_MAX_T + 1, ("one_pass", 128, None)),
    (1024, ("one_pass", 128, None)),
    (2048, ("one_pass", 128, None)),
    (8192, ("one_pass", 128, None)),
], ids=lambda v: v[0] if isinstance(v, tuple) else f"T{v}")
def test_int8_kernel_choice_by_token_count(kernel_calls, T, want):
    """Which int8 kernel serves a step, with what row tile, is a function
    of its shapes alone: dense up to DENSE_INT8_MAX_T tokens, routed up to
    ROUTED_INT8_MAX_T, one pass over the weights above (the streamed kernel
    is the a2a exchange's only); the row tile by the mean rows an expert.
    No ``LLMD_MOE_*`` variable but ``LLMD_MOE_DISPATCH`` has a say."""
    E, H, k = 4, 8, 2
    quant, _ = _rand_quant(jax.random.PRNGKey(0), E, H, 8)
    idx = jnp.arange(T * k, dtype=jnp.int32).reshape(T, k) % E
    out = moe_ops.expert_ffn(jnp.ones((T, H), jnp.bfloat16),
                             jnp.ones((T, k), jnp.float32), idx,
                             None, None, None, quant=quant)
    assert out.shape == (T, H)
    assert kernel_calls == [want]
    assert moe_ops.int8_kernel(T) == want[0]
