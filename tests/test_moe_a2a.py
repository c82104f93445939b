"""Sparse EP all-to-all dispatch: parity vs the psum oracle + comm-volume
proof (no full-activation all-reduce per MoE layer).

Reference role: DeepEP's dispatch/combine kernels + VLLM_MOE_DP_CHUNK_SIZE
chunking (wide-ep decode.yaml:108-118,131-132).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.models.config import get_config
from llm_d_tpu.ops import moe as moe_ops
from llm_d_tpu.parallel.mesh import MeshConfig, make_mesh


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
    return x, router, w_gate, w_up, w_down


def _route(x, router, cfg):
    return moe_ops.route(
        jnp.dot(x.astype(jnp.float32), router), cfg)


@pytest.mark.parametrize("T,E", [(16, 8), (32, 16), (16, 64)])
@pytest.mark.slow
def test_a2a_matches_psum_oracle(mesh, T, E):
    from llm_d_tpu.models.config import ModelConfig
    cfg = ModelConfig(name="a2a-test", num_experts=E, num_experts_per_tok=2,
                      moe_renormalize=True)
    x, router, w_gate, w_up, w_down = _case(hash((T, E)) % 2**32, T, E)
    weights, idx = _route(x, router, cfg)

    psum = moe_ops.expert_ffn(x, weights, idx, w_gate, w_up, w_down,
                              mesh=mesh, dispatch="psum")
    a2a = moe_ops.expert_ffn(x, weights, idx, w_gate, w_up, w_down,
                             mesh=mesh, dispatch="a2a")
    single = moe_ops.expert_ffn(x, weights, idx, w_gate, w_up, w_down)

    np.testing.assert_allclose(np.asarray(a2a, np.float32),
                               np.asarray(psum, np.float32),
                               atol=3e-2, rtol=3e-2)
    np.testing.assert_allclose(np.asarray(a2a, np.float32),
                               np.asarray(single, np.float32),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.slow
def test_a2a_chunked_dispatch_matches(mesh):
    """VLLM_MOE_DP_CHUNK_SIZE analogue: chunked == unchunked."""
    from llm_d_tpu.models.config import ModelConfig
    cfg = ModelConfig(name="a2a-test", num_experts=16, num_experts_per_tok=2,
                      moe_renormalize=True)
    T = 64   # 8 tokens/shard
    x, router, w_gate, w_up, w_down = _case(11, T, 16)
    weights, idx = _route(x, router, cfg)
    full = moe_ops.expert_ffn_a2a(x, weights, idx, w_gate, w_up, w_down,
                                  mesh, chunk_tokens=8)
    chunked = moe_ops.expert_ffn_a2a(x, weights, idx, w_gate, w_up, w_down,
                                     mesh, chunk_tokens=2)
    np.testing.assert_allclose(np.asarray(chunked, np.float32),
                               np.asarray(full, np.float32),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.slow
def test_a2a_skewed_routing(mesh):
    """All tokens routed to ONE shard's experts (worst-case imbalance):
    the fixed-region capacity must absorb it without drops."""
    from llm_d_tpu.models.config import ModelConfig
    cfg = ModelConfig(name="a2a-test", num_experts=16, num_experts_per_tok=2,
                      moe_renormalize=True)
    T, E = 16, 16
    x, _, w_gate, w_up, w_down = _case(5, T, E)
    # Force every token to experts 0 and 1 (both on shard 0).
    idx = jnp.tile(jnp.asarray([[0, 1]], jnp.int32), (T, 1))
    weights = jnp.full((T, 2), 0.5, jnp.float32)
    a2a = moe_ops.expert_ffn(x, weights, idx, w_gate, w_up, w_down,
                             mesh=mesh, dispatch="a2a")
    psum = moe_ops.expert_ffn(x, weights, idx, w_gate, w_up, w_down,
                              mesh=mesh, dispatch="psum")
    np.testing.assert_allclose(np.asarray(a2a, np.float32),
                               np.asarray(psum, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_a2a_has_no_full_allreduce(mesh):
    """The comm-volume proof: the compiled a2a path contains NO all-reduce
    (dispatch moves rows point-to-point; combine is one bf16 all-gather),
    while the psum oracle does all-reduce the full [T, H] f32 activations."""
    from llm_d_tpu.models.config import ModelConfig
    cfg = ModelConfig(name="a2a-test", num_experts=16, num_experts_per_tok=2,
                      moe_renormalize=True)
    T, E = 16, 16
    x, router, w_gate, w_up, w_down = _case(9, T, E)
    weights, idx = _route(x, router, cfg)

    def run(dispatch):
        return jax.jit(
            lambda *a: moe_ops.expert_ffn(*a, mesh=mesh, dispatch=dispatch)
        ).lower(x, weights, idx, w_gate, w_up, w_down).compile()

    a2a_hlo = run("a2a").as_text()
    psum_hlo = run("psum").as_text()
    assert "all-reduce" not in a2a_hlo
    assert "all-to-all" in a2a_hlo
    assert "all-reduce" in psum_hlo


def test_a2a_in_moe_model_forward(mesh):
    """Dispatch wired through the model: full MoE forward parity
    a2a vs psum on the 8-device mesh (deepseek-style tiny config)."""
    import os
    from llm_d_tpu.models import moe as moe_model
    from llm_d_tpu.models.config import get_config

    cfg = get_config("tiny-moe")
    params = moe_model.init_params(cfg, jax.random.PRNGKey(0))
    T, S = 16, 8
    rng = np.random.default_rng(2)
    bs = 4
    num_blocks = 16
    batch = dict(
        token_ids=jnp.asarray(rng.integers(0, cfg.vocab_size, T), jnp.int32),
        positions=jnp.zeros(T, jnp.int32),
        token_seq_ids=jnp.asarray(np.arange(T) % S, jnp.int32),
        token_qpos=jnp.zeros(T, jnp.int32),
        slot_mapping=jnp.asarray(np.arange(T) + bs, jnp.int32),
        block_tables=jnp.asarray(
            np.tile(np.arange(1, 6), (S, 1)), jnp.int32),
        seq_lens=jnp.ones(S, jnp.int32),
        sample_idx=jnp.asarray(np.arange(S), jnp.int32),
        qtok_idx=jnp.asarray(np.arange(S)[:, None], jnp.int32),
        token_qpos2=None,
    )
    batch.pop("token_qpos2")
    kv = {k: jnp.zeros((cfg.num_layers, num_blocks * bs,
                        cfg.num_kv_heads * cfg.head_dim_), jnp.bfloat16)
          for k in ("k", "v")}

    outs = {}
    for dispatch in ("psum", "a2a"):
        os.environ["LLMD_MOE_DISPATCH"] = dispatch
        try:
            hidden, _ = moe_model.forward(
                params, {k: v.copy() for k, v in kv.items()}, batch, cfg,
                block_size=bs, attn_backend="reference", mesh=mesh)
            outs[dispatch] = np.asarray(hidden, np.float32)
        finally:
            del os.environ["LLMD_MOE_DISPATCH"]
    np.testing.assert_allclose(outs["a2a"], outs["psum"],
                               atol=5e-2, rtol=5e-2)


def test_a2a_matches_psum_oracle_fast(mesh, under_jit):
    """GATING-TIER parity representative (advisor r4): one tiny a2a-vs-psum
    case so a dispatch-math regression cannot merge green; the full sweep
    stays in the slow tier."""
    from llm_d_tpu.models.config import ModelConfig
    cfg = ModelConfig(name="a2a-fast", num_experts=8, num_experts_per_tok=2,
                      moe_renormalize=True)
    x, router, w_gate, w_up, w_down = _case(99, 16, 8)
    weights, idx = _route(x, router, cfg)
    psum = under_jit(moe_ops.expert_ffn, x, weights, idx, w_gate, w_up, w_down,
                     mesh=mesh, dispatch="psum")
    a2a = under_jit(moe_ops.expert_ffn, x, weights, idx, w_gate, w_up, w_down,
                    mesh=mesh, dispatch="a2a")
    np.testing.assert_allclose(np.asarray(a2a, np.float32),
                               np.asarray(psum, np.float32),
                               atol=3e-2, rtol=3e-2)
