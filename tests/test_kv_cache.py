"""KV block allocator + prefix cache semantics, and the pool's size.

Every benchmark cell passes ``--kv-cache-hbm-gb``, so the pool a cell
serves with is what ``derive_num_blocks`` makes of its budget, layout and
depth: the counts here are PERF.md section 4's.  An engine handed a budget
sizes its pool from it and not from ``num_blocks``.
"""

import pathlib
import sys

import jax.numpy as jnp
import pytest

from llm_d_tpu.engine.engine import EngineConfig, EngineCore, derive_num_blocks
from llm_d_tpu.engine.kv_cache import KVCacheManager
from llm_d_tpu.engine.request import Request
from llm_d_tpu.models import get_model
from llm_d_tpu.models.config import ModelConfig
from llm_d_tpu.ops.sampling import SamplingParams
from llm_d_tpu.server.openai import build_arg_parser, engine_config_from_args

sys.path.insert(
    0, str(pathlib.Path(__file__).resolve().parent.parent / "benchmarks"))

import modelcfg  # noqa: E402


def mk_req(rid, tokens):
    return Request(request_id=rid, prompt_token_ids=list(tokens),
                   sampling=SamplingParams())


def test_allocate_and_free():
    kv = KVCacheManager(num_blocks=9, block_size=4)   # 8 usable
    r = mk_req("a", range(10))
    got = kv.allocate(r, 10)
    assert len(got) == 3 and 0 not in got
    assert kv.num_free_blocks == 5
    kv.free(r)
    assert kv.num_free_blocks == 8


def test_prefix_reuse_between_requests():
    kv = KVCacheManager(num_blocks=17, block_size=4)
    r1 = mk_req("r1", range(12))
    kv.allocate(r1, 12)
    r1.num_computed_tokens = 12
    kv.cache_full_blocks(r1)
    b1 = list(r1.block_ids)
    kv.free(r1)

    # Same 12-token prompt: blocks 0,1 reusable; block 2 holds the last
    # token's block but the final token must be recomputed -> only 2 blocks.
    r2 = mk_req("r2", range(12))
    blocks, n = kv.find_cached_prefix(r2)
    assert n == 8 and blocks == b1[:2]
    got = kv.allocate(r2, 12, reuse_blocks=blocks)
    assert got[:2] == b1[:2]

    # Diverging prompt reuses only the shared prefix.
    r3 = mk_req("r3", list(range(8)) + [99, 98, 97, 96])
    blocks3, n3 = kv.find_cached_prefix(r3)
    assert n3 == 8 == len(blocks3) * 4


def test_lru_eviction_and_events():
    kv = KVCacheManager(num_blocks=5, block_size=2)   # 4 usable
    stored, removed = [], []
    kv.on_block_stored.append(lambda h, b: stored.append(b))
    kv.on_block_removed.append(lambda h, b: removed.append(b))

    r1 = mk_req("r1", range(4))
    kv.allocate(r1, 4)
    r1.num_computed_tokens = 4
    kv.cache_full_blocks(r1)
    assert len(stored) == 2
    kv.free(r1)
    assert kv.num_free_blocks == 4      # cached blocks still count as free

    # Fill the pool with an unrelated request: cached blocks get evicted LRU.
    r2 = mk_req("r2", range(100, 108))
    got = kv.allocate(r2, 8)
    assert len(got) == 4
    assert len(removed) == 2            # both cached blocks evicted
    assert kv.eviction_count == 2


def test_refcount_shared_blocks():
    kv = KVCacheManager(num_blocks=9, block_size=4)
    r1 = mk_req("r1", range(8))
    kv.allocate(r1, 8)
    r1.num_computed_tokens = 8
    kv.cache_full_blocks(r1)
    # r2 shares the first block while r1 still holds it.
    r2 = mk_req("r2", list(range(4)) + [50, 51, 52, 53])
    blocks, n = kv.find_cached_prefix(r2)
    assert n == 4
    kv.allocate(r2, 8, reuse_blocks=blocks)
    assert r2.block_ids[0] == r1.block_ids[0]
    kv.free(r1)
    # Shared block must survive r1's free (still referenced by r2).
    free_before = kv.num_free_blocks
    r3 = mk_req("r3", list(range(4)))
    blocks3, n3 = kv.find_cached_prefix(r3)
    assert n3 == 0 or blocks3[0] == r2.block_ids[0]


def test_allocation_failure():
    kv = KVCacheManager(num_blocks=4, block_size=4, enable_prefix_caching=False)
    r1 = mk_req("r1", range(12))
    assert kv.allocate(r1, 12) is not None
    r2 = mk_req("r2", range(4))
    assert kv.allocate(r2, 4) is None   # exhausted
    kv.free(r1)
    assert kv.allocate(r2, 4) is not None


# ---- the pool sized from an HBM budget -----------------------------------

@pytest.mark.parametrize("name,blocks,row_bytes", [
    ("qwen3-30b-a3b", 10240, 2 * 4 * 128 * 2),      # k + v, 4 KV heads x 128
    ("kanana-2-30b-a3b", 14563, 640 * 2),           # one latent row of 640
    ("trinity-mini", 10240, 2 * 4 * 128 * 2),
])
def test_configuration_pool_from_its_serve_args(name, blocks, row_bytes):
    conf = modelcfg.load_config(name)
    mc = ModelConfig(**modelcfg.model_config_fields(conf))
    cfg = engine_config_from_args(build_arg_parser().parse_args(
        ["--model", name, *modelcfg.serve_args(conf)]))
    assert (cfg.kv_cache_hbm_bytes, cfg.block_size) == (5 << 30, 32)
    layout = get_model(mc).kv_cache_layout(mc)
    assert sum(layout.values()) * 2 == row_bytes
    got = derive_num_blocks(cfg.kv_cache_hbm_bytes, layout, mc.num_layers,
                            cfg.block_size)
    assert got == blocks
    # The most whole blocks the budget holds: one more would not fit.
    block_bytes = mc.num_layers * cfg.block_size * row_bytes
    assert got * block_bytes <= cfg.kv_cache_hbm_bytes < (got + 1) * block_bytes


@pytest.mark.parametrize("model", ["tiny", "tiny-mla"])
def test_engine_sizes_its_pool_from_the_budget(model):
    budget = 1 << 20
    engine = EngineCore(EngineConfig(
        model=model, block_size=4, num_blocks=7, max_num_seqs=4,
        max_num_batched_tokens=64, min_token_bucket=16, min_seq_bucket=4,
        kv_cache_hbm_bytes=budget))
    c = engine.model_config
    want = derive_num_blocks(
        budget, engine.model.kv_cache_layout(c), c.num_layers, 4)
    assert want > 7
    assert engine.config.num_blocks == engine.kv_manager.num_blocks == want
    held = 0
    for buf in engine.kv_cache.values():
        assert buf.dtype == jnp.bfloat16
        assert buf.shape[:2] == (c.num_layers, want * 4)
        held += buf.nbytes
    # The pool fills the budget to within one block.
    assert budget - held // want < held <= budget
