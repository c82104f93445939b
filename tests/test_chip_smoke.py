"""chip_smoke.py rehearsed on the CPU (on-chip-measurement guide §2.1) and
the compile-cache helper every entry point shares.

The phase functions are driven with ``tiny`` / ``tiny-mla`` at toy sizes:
the XLA attention / dequantize paths serve (no Pallas, no interpret mode),
so this finds wrong paths, arguments and control flow — nothing about the
Mosaic-compiled kernels, which only the chip run can show.
"""

import importlib.util
import json
import pathlib

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TINY = dict(min_token_bucket=16, min_seq_bucket=4)


def test_server_phase_tiny(smoke):
    info = smoke.server_phase(
        "tiny", ["--block-size", "8", "--num-blocks", "256",
                 "--max-num-seqs", "8", "--max-num-batched-tokens", "64"],
        prompt_lens=[150, 5, 12, 30, 7, 21], max_tokens=8, seed=0,
        expect_kernels=False, cfg_overrides=TINY,
        parity_lens=(9, 20), parity_ks=(0, 2, 5))
    assert info["programs"] >= 2
    assert info["custom_calls"] == {"decode": 0, "prefill": 0}
    assert info["attn_parity"] <= smoke.ATTN_LOGPROB_TOL


def test_moe_phase_tiny_mla(smoke):
    info = smoke.moe_phase(
        "tiny-mla", n_seqs=12, prompt_len=6, max_tokens=10, seed=0,
        expect_kernels=False,
        cfg_overrides=dict(TINY, block_size=8, num_blocks=128,
                           max_num_seqs=16, max_num_batched_tokens=64,
                           num_scheduler_steps=4),
        small_wave=2, n_parity=2, parity_ks=(0, 2, 5), op_sizes=(4, 24))
    assert info["moe_parity"] <= smoke.MOE_LOGPROB_TOL_MAX
    assert info["moe_op_parity"] <= smoke.MOE_OP_REL_RMS_TOL


def test_window_phase_interpreted(smoke):
    """The kernel interpreted at 8 heads x 128 (tiles of 128 slots, key
    blocks of 256 keys = 32 pages of 8)."""
    info = smoke.window_phase(
        8, 128, 128, 257, [(600, 150), (9, 9), (256, 1), (257, 1)], seed=0,
        block_size=8, table_blocks=128, interpret=True)
    assert info["window_rel_diff"] <= smoke.WINDOW_KERNEL_REL_TOL
    assert info["window_decode_vs_prefill"] <= 2e-6


def test_hybrid_phase_interpreted(smoke):
    """The Mamba-1 kernels and the one-query read interpreted at 256
    channels x 8 states, pieces of 16, 8 paired heads over 2 of 128."""
    info = smoke.hybrid_phase(
        256, 8, 16, 8, 2, 128, [(90, 40), (33, 33), (70, 1), (1, 1)],
        seed=0, block_size=16, table_blocks=8, interpret=True)
    assert info["ssm1_y_rel_diff"] <= 1e-5
    assert info["ssm1_state_rel_diff"] <= 1e-5
    assert info["cross_read_rel_diff"] <= smoke.WINDOW_KERNEL_REL_TOL


def test_held_phase_interpreted(smoke):
    """The held experts' kernels interpreted at hidden 1,024 x width 256,
    4 of 16 experts, tiles of 16 rows and blocks of 128 columns, 40 and 16
    tokens."""
    info = smoke.held_phase(1024, 256, (8, 4), 16, 4, (40, 16), seed=0,
                            interpret=True, row_tile=16, block=128)
    assert set(info) == {"held_rel_diff_T40", "held_rel_diff_T16"}
    assert max(info.values()) <= smoke.HELD_KERNEL_REL_TOL


def test_index_phase_interpreted(smoke, monkeypatch):
    """The indexer's kernel interpreted at 4 heads of 16, top-k 40, key
    blocks of 32 keys = 4 pages of 8 and index blocks of two: a chunk that
    crosses the top-k, decode rows around it, a short prompt."""
    from llm_d_tpu.ops.pallas import dsa_index, mla_masked
    monkeypatch.setattr(mla_masked, "KEY_BLOCK", 32)
    monkeypatch.setattr(dsa_index, "INDEX_BLOCK", 64)
    info = smoke.index_phase(
        4, 16, 40, [(150, 130), (39, 1), (40, 1), (41, 1), (97, 1), (9, 9)],
        seed=0, block_size=8, table_blocks=32, n_check=130, interpret=True)
    assert info["index_worst_margin"] <= smoke.INDEX_MARGIN_REL_TOL
    assert info["index_decode_vs_prefill"] == 0


def test_sharded_phase_tiny_on_virtual_devices(smoke, devices):
    """The --chips 4 phase on four of the suite's virtual CPU devices."""
    info = smoke.sharded_phase(
        "tiny", ["--tensor-parallel-size", "2", "--block-size", "8",
                 "--num-blocks", "128", "--max-num-seqs", "8",
                 "--max-num-batched-tokens", "64", "--allow-device-subset"],
        ["all-reduce"], seed=0, prompt_lens=(9, 20, 40),
        parity_ks=(0, 2, 5), cfg_overrides=TINY)
    assert "all-reduce" in info["collectives"]
    info = smoke.sharded_phase(
        "tiny-mla", ["--data-parallel-size", "4", "--block-size", "8",
                     "--num-blocks", "128", "--max-num-seqs", "8",
                     "--max-num-batched-tokens", "64",
                     "--allow-device-subset"],
        ["all-to-all"], seed=0, quantization="int8",
        prompt_lens=(9, 20, 40, 12), parity_ks=(0, 2, 5),
        cfg_overrides=TINY, op_sizes=(8, 64))
    assert info["parity"] <= smoke.SHARDED_LOGPROB_TOL
    assert info["moe_op_parity"] <= smoke.MOE_A2A_OP_REL_RMS_TOL


def test_main_without_tpu_fails_with_ok_false(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    rc = smoke.main([])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    doc = json.loads(last)
    assert rc != 0 and doc["ok"] is False
    assert set(doc["device"]) == {"platform", "kind", "count"}
    assert doc["device"]["platform"] == "cpu"


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_helper(monkeypatch, env_set):
    from llm_d_tpu.utils import compile_cache as cc
    updates = {}
    monkeypatch.setattr(cc.jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    if env_set:
        monkeypatch.setenv(cc.ENV_VAR, "/somewhere/else")
        assert cc.configure_compile_cache("/flag/dir") == "/somewhere/else"
        # JAX reads the variable itself: no directory is set in code.
        assert "jax_compilation_cache_dir" not in updates
    else:
        monkeypatch.delenv(cc.ENV_VAR, raising=False)
        want = str(REPO / ".jax_cache")
        assert cc.configure_compile_cache() == want
        assert updates["jax_compilation_cache_dir"] == want
        # An operator's flag is honoured when the variable is unset.
        assert cc.configure_compile_cache("/flag/dir") == "/flag/dir"
