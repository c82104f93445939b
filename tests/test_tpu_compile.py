"""The v5e compiler's verdict on every Pallas kernel the TPU branches select.

The TPU compiler is installed without a chip: it compiles for a DESCRIBED
``v5e:2x2`` topology (on-chip-measurement guide §2.3).  Interpret-mode
parity (the other kernel tests) says nothing about what Mosaic accepts —
BlockSpec tiling, DMA slice alignment and the VMEM plan are only checked
here.  Each case compiles ONE kernel at real widths in about two seconds;
nothing runs, so nothing here is a measurement.

Rules this file follows (the guide's): the topology is described inside a
module-scoped fixture (never at import / in skipif / parametrize /
conftest, not autouse), everything built from it is built in the test,
the persistent compile cache is off around the compiles (a described-
device executable cannot be read back), and all cases live in this one
file so one xdist worker holds libtpu.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from llm_d_tpu.ops import moe as moe_ops

BS = 32                    # default engine block size
SLOTS = 256 * BS           # KV pool rows per layer in these cases


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---- case builders: each returns (fn, [ShapeDtypeStruct, ...]) ----------

def _dense_decode(H, KVH, D, S=64, B=64, L=16, window=False):
    """``window``: the traced per-layer window operand of a mixed stack."""
    from llm_d_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_update as kern)
    F = KVH * D

    def fn(q, kn, vn, kc, vc, bt, sl, layer):
        kw = {"window": layer + 2048} if window else {}
        return kern(q, kn, vn, kc, vc, bt, sl, block_size=BS,
                    num_kv_heads=KVH, layer=layer, **kw)

    return fn, [_sds((S, H, D), jnp.bfloat16), _sds((S, F), jnp.bfloat16),
                _sds((S, F), jnp.bfloat16), _sds((L, SLOTS, F), jnp.bfloat16),
                _sds((L, SLOTS, F), jnp.bfloat16), _sds((S, B), jnp.int32),
                _sds((S,), jnp.int32), _sds((), jnp.int32)]


def _dense_prefill(H, KVH, D, S=8, Q=256, B=64, L=16, window=False):
    from llm_d_tpu.ops.pallas.flash_prefill import flash_prefill_paged as kern
    F = KVH * D

    def fn(qs, qp, kc, vc, bt, sl, layer):
        kw = {"window": layer + 2048} if window else {}
        return kern(qs, qp, kc, vc, bt, sl, block_size=BS,
                    num_kv_heads=KVH, layer=layer, **kw)

    return fn, [_sds((S, Q, H, D), jnp.bfloat16), _sds((S, Q), jnp.int32),
                _sds((L, SLOTS, F), jnp.bfloat16),
                _sds((L, SLOTS, F), jnp.bfloat16), _sds((S, B), jnp.int32),
                _sds((S,), jnp.int32), _sds((), jnp.int32)]


def _mla_decode(H, F=640, S=64, B=64, L=16):
    from llm_d_tpu.ops.pallas.mla_attention import (
        mla_paged_decode_update as kern)

    def fn(q, row, kc, bt, sl, layer):
        return kern(q, row, kc, bt, sl, block_size=BS, scale=0.07,
                    layer=layer)

    return fn, [_sds((S, H, F), jnp.bfloat16), _sds((S, F), jnp.bfloat16),
                _sds((L, SLOTS, F), jnp.bfloat16), _sds((S, B), jnp.int32),
                _sds((S,), jnp.int32), _sds((), jnp.int32)]


def _mla_prefill(H, F=640, S=8, Q=256, B=64, L=16):
    from llm_d_tpu.ops.pallas.mla_prefill import mla_flash_prefill as kern

    def fn(qs, qp, kc, bt, sl, layer):
        return kern(qs, qp, kc, bt, sl, block_size=BS, scale=0.07,
                    layer=layer)

    return fn, [_sds((S, Q, H, F), jnp.bfloat16), _sds((S, Q), jnp.int32),
                _sds((L, SLOTS, F), jnp.bfloat16), _sds((S, B), jnp.int32),
                _sds((S,), jnp.int32), _sds((), jnp.int32)]


def _mla_masked(T, S, Q, H=128, F=640, R=512, B=1024, L=3):
    """``mla_masked_attention`` over the tile list a step of T tokens in S
    rows brings at ``ops.sparse_mla.SELECT_Q_TILE`` slots a tile."""
    from llm_d_tpu.ops.attention import num_query_tiles
    from llm_d_tpu.ops.pallas.mla_masked import (
        KEY_BLOCK, mla_masked_attention as kern)
    from llm_d_tpu.ops.sparse_mla import SELECT_Q_TILE
    qt = min(SELECT_Q_TILE, Q)
    NT = num_query_tiles(T, S, qt)

    def fn(q, bias, ts, live, first, kc, bt, layer):
        return kern(q, bias, ts, live, first, kc, bt, layer, block_size=BS,
                    scale=0.07, value_width=R)

    return fn, [_sds((NT, qt, H, F), jnp.bfloat16),
                _sds((NT, B * BS // KEY_BLOCK, qt, KEY_BLOCK), jnp.float32),
                _sds((NT,), jnp.int32), _sds((NT,), jnp.int32),
                _sds((NT,), jnp.int32),
                _sds((L, SLOTS, F), jnp.bfloat16), _sds((S, B), jnp.int32),
                _sds((), jnp.int32)]


def _mla_window(T, S, Q, H=64, F=1152, R=1024, window=513, B=1024, L=3):
    """``attend_window`` through the kernel (the window's bias in XLA, the
    walk from each tile's first visible key block in Mosaic) for a step of
    T tokens in S rows, at the tile height the geometry picks."""
    from llm_d_tpu.ops import sparse_mla

    def fn(q, kc, bt, sl, pos, seq, qpos, qtok, layer):
        batch = dict(block_tables=bt, seq_lens=sl, positions=pos,
                     token_seq_ids=seq, token_qpos=qpos, qtok_idx=qtok)
        return sparse_mla.attend_window(q, kc, batch, window, BS, layer,
                                        0.07, R, kernel=True)

    return fn, [_sds((T, H, F), jnp.bfloat16),
                _sds((L, SLOTS, F), jnp.bfloat16), _sds((S, B), jnp.int32),
                _sds((S,), jnp.int32), _sds((T,), jnp.int32),
                _sds((T,), jnp.int32), _sds((T,), jnp.int32),
                _sds((S, Q), jnp.int32), _sds((), jnp.int32)]


def _dsa_index(T, S, Q, Hi=64, Di=128, topk=2048, B=1024, L=3):
    """``sparse_mla.index_bias``: the row keys' gather in XLA, the walk, the
    threshold and the bias in Mosaic (ops/pallas/dsa_index.py), for a step
    of T tokens in S rows at dots3-note-prev's indexer (64 heads of 128,
    the 2,048 best of a table of 32,768 positions)."""
    from llm_d_tpu.ops import sparse_mla

    def fn(q, w, kc, bt, sl, pos, seq, qpos, qtok, layer):
        batch = dict(block_tables=bt, seq_lens=sl, positions=pos,
                     token_seq_ids=seq, token_qpos=qpos, qtok_idx=qtok)
        return sparse_mla.index_bias(q, w, kc, batch, BS, layer, topk)

    return fn, [_sds((T, Hi, Di), jnp.bfloat16), _sds((T, Hi), jnp.float32),
                _sds((L, SLOTS, Di), jnp.bfloat16), _sds((S, B), jnp.int32),
                _sds((S,), jnp.int32), _sds((T,), jnp.int32),
                _sds((T,), jnp.int32), _sds((T,), jnp.int32),
                _sds((S, Q), jnp.int32), _sds((), jnp.int32)]


def _ssm_update(H=32, P=128, N=256, G=2, S=64, L=6):
    """The state-space mixer's one-token update, in place on the state pool
    of ``S`` slots and the trash slot (falcon-h1-34b's geometry)."""
    from llm_d_tpu.ops.pallas.ssm_update import ssm_decode_update as fn
    fn.hlo_name = "ssm_decode_update"
    return fn, [_sds((S, H, P), jnp.float32), _sds((S, H), jnp.float32),
                _sds((S, G, N), jnp.bfloat16), _sds((S, G, N), jnp.bfloat16),
                _sds((L, S + 1, H, N, P), jnp.float32), _sds((), jnp.int32),
                _sds((S,), jnp.int32), _sds((S,), jnp.bool_)]


def _ssm_scan(T, S, H=32, P=128, N=256, G=2, L=6, c=128, slots=65):
    """The chunked scan over the pieces of a step of ``T`` tokens in ``S``
    rows (ceil(T / c) + S of them)."""
    from llm_d_tpu.ops.pallas.ssm_scan import ssm_chunk_scan as fn
    fn.hlo_name = "ssm_chunk_scan"
    NT = -(-T // c) + S
    return fn, [_sds((NT, c, H, P), jnp.bfloat16),
                _sds((NT, c, G, N), jnp.bfloat16),
                _sds((NT, c, G, N), jnp.bfloat16),
                _sds((NT, c, H), jnp.float32),
                _sds((L, slots, H, N, P), jnp.float32), _sds((), jnp.int32),
                _sds((NT,), jnp.int32), _sds((NT,), jnp.bool_),
                _sds((NT,), jnp.bool_), _sds((NT,), jnp.bool_)]


def _delta_update(H=32, K=128, V=128, S=16, L=6):
    """A linear-attention layer's one-token delta-rule update, in place on
    the state pool of ``S`` slots and the trash slot (ling-3.0-flash-vl's
    geometry)."""
    from llm_d_tpu.ops.pallas.delta_update import delta_decode_update as fn
    fn.hlo_name = "delta_decode_update"
    return fn, [_sds((S, H, K), jnp.float32), _sds((S, H, K), jnp.float32),
                _sds((S, H, V), jnp.float32), _sds((S, H, K), jnp.float32),
                _sds((S, H), jnp.float32),
                _sds((L, S + 1, H, K, V), jnp.float32), _sds((), jnp.int32),
                _sds((S,), jnp.int32), _sds((S,), jnp.bool_)]


def _delta_scan(T, S, H=32, K=128, V=128, L=6, c=64, slots=17):
    """The walk over the pieces of a step of ``T`` tokens in ``S`` rows
    (ceil(T / c) + S of them), their terms computed by XLA."""
    from llm_d_tpu.ops.pallas.delta_scan import delta_chunk_scan as fn
    fn.hlo_name = "delta_chunk_scan"
    NT = -(-T // c) + S
    return fn, [_sds((NT, H, c, K), jnp.float32),
                _sds((NT, H, c, V), jnp.float32),
                _sds((NT, H, c, c), jnp.float32),
                _sds((NT, H, c, K), jnp.float32),
                _sds((NT, H, c, K), jnp.float32), _sds((NT, H, K), jnp.float32),
                _sds((L, slots, H, K, V), jnp.float32), _sds((), jnp.int32),
                _sds((NT,), jnp.int32), _sds((NT,), jnp.bool_),
                _sds((NT,), jnp.bool_), _sds((NT,), jnp.bool_)]


def _ssm1_update(S=8, inner=5120, N=16, L=9, slots=9):
    from llm_d_tpu.ops.pallas.ssm1_scan import ssm1_decode_update as fn
    fn.hlo_name = "ssm1_decode_update"
    return fn, [_sds((S, inner), jnp.float32), _sds((S, inner), jnp.float32),
                _sds((N, inner), jnp.float32), _sds((S, N), jnp.bfloat16),
                _sds((S, N), jnp.bfloat16),
                _sds((L, slots, N, inner), jnp.float32), _sds((), jnp.int32),
                _sds((S,), jnp.int32), _sds((S,), jnp.bool_)]


def _ssm1_scan(T, S, inner=5120, N=16, c=128, L=9, slots=9):
    """The Mamba-1 selective scan over the pieces of a step of ``T`` tokens
    in ``S`` rows."""
    from llm_d_tpu.ops.pallas.ssm1_scan import ssm1_chunk_scan as fn
    fn.hlo_name = "ssm1_chunk_scan"
    NT = -(-T // c) + S
    return fn, [_sds((NT, c, inner), jnp.float32),
                _sds((NT, c, inner), jnp.float32),
                _sds((N, inner), jnp.float32), _sds((NT, c, N), jnp.bfloat16),
                _sds((NT, c, N), jnp.bfloat16),
                _sds((L, slots, N, inner), jnp.float32), _sds((), jnp.int32),
                _sds((NT,), jnp.int32), _sds((NT,), jnp.bool_),
                _sds((NT,), jnp.bool_), _sds((NT,), jnp.bool_)]


def _paged_read(H, KVH, D, S=8, B=1024, L=9):
    from llm_d_tpu.ops.pallas.paged_attention import paged_attention_read
    F = KVH * D

    def fn(q, kc, vc, bt, sl, layer):
        return paged_attention_read(q, kc, vc, bt, sl, block_size=BS,
                                    num_kv_heads=KVH, scale=0.125,
                                    layer=layer)

    fn.hlo_name = "paged_attention_read"
    return fn, [_sds((S, H, D), jnp.bfloat16),
                _sds((L, SLOTS, F), jnp.bfloat16),
                _sds((L, SLOTS, F), jnp.bfloat16), _sds((S, B), jnp.int32),
                _sds((S,), jnp.int32), _sds((), jnp.int32)]


def _prefill_tiles(H, KVH, D, T, S, Q, B=64, L=16, window=False,
                   mla=False):
    """Either prefill kernel over a step's query TILE LIST, as the step
    programs call it: ``ceil(T / Qt) + S`` tiles of the ``Qt`` slots the
    kernel family picks for (Q, H, F).  ``fn.hlo_name``: the name the
    benchmark's readers find the kernel by in a trace."""
    from llm_d_tpu.ops import attention as A
    from llm_d_tpu.ops.pallas.flash_prefill import flash_prefill_paged
    from llm_d_tpu.ops.pallas.mla_prefill import mla_flash_prefill
    F = KVH * D
    qt = A.prefill_q_tile(Q, H, F, mla)
    NT = A.num_query_tiles(T, S, qt)

    def fn(qs, qp, ts, kc, vc, bt, sl, layer):
        if mla:
            return mla_flash_prefill(qs, qp, kc, bt, sl, block_size=BS,
                                     scale=0.07, layer=layer, tile_seq=ts)
        kw = {"window": layer + 2048} if window else {}
        return flash_prefill_paged(qs, qp, kc, vc, bt, sl, block_size=BS,
                                   num_kv_heads=KVH, layer=layer,
                                   tile_seq=ts, **kw)

    fn.hlo_name = "mla_flash_prefill" if mla else "flash_prefill_paged"
    return fn, [_sds((NT, qt, H, D), jnp.bfloat16), _sds((NT, qt), jnp.int32),
                _sds((NT,), jnp.int32),
                _sds((L, SLOTS, F), jnp.bfloat16),
                _sds((L, SLOTS, F), jnp.bfloat16),
                _sds((S, B), jnp.int32), _sds((S,), jnp.int32),
                _sds((), jnp.int32)]


def _moe(path, T, H=2048, I=512, E=64, k=8, Lm=15):
    """One of ops/moe.py's ``_*_int8_kernel_path`` wrappers — the exact
    glue the TPU branch of ``expert_ffn`` calls (the branch itself asks
    ``jax.default_backend()`` and would take its CPU side here)."""
    wrapper = getattr(moe_ops, f"_{path}_int8_kernel_path")

    def fn(x, w, idx, layer, gq, gs, uq, us, dq, ds):
        quant = dict(w_gate_q=gq, w_gate_s=gs, w_up_q=uq, w_up_s=us,
                     w_down_q=dq, w_down_s=ds, layer=layer)
        return wrapper(x, w, idx, quant)

    args = [_sds((T, H), jnp.bfloat16), _sds((T, k), jnp.float32),
            _sds((T, k), jnp.int32), _sds((), jnp.int32),
            _sds((Lm, E, H, I), jnp.int8), _sds((Lm, E, 1, I), jnp.float32),
            _sds((Lm, E, H, I), jnp.int8), _sds((Lm, E, 1, I), jnp.float32),
            _sds((Lm, E, I, H), jnp.int8), _sds((Lm, E, 1, H), jnp.float32)]
    return fn, args


def _moe_held(T, H=5120, I=1536, E=32, k=8, L=5):
    """``ops.moe._held_expert_ffn``'s kernels (ops/pallas/moe_held.py) at
    dots3-note-prev's widths: 32 of 256 bf16 experts of 5120 x 1536, the
    layers' stacks read in place by plane.  Hidden 5120 compiles in no other
    kernel of the family; the row tile and the block of the expert width
    are the module's rule, the same for every T."""
    from llm_d_tpu.ops.pallas import moe_held

    def fn(x, w, idx, wg, wu, wd, plane):
        assert moe_held.ineligible_reason(x, wg) is None
        return moe_held.held_expert_ffn(x, w, idx, wg, wu, wd, 96, plane)

    return fn, [_sds((T, H), jnp.bfloat16), _sds((T, k), jnp.float32),
                _sds((T, k), jnp.int32), _sds((L, E, H, I), jnp.bfloat16),
                _sds((L, E, H, I), jnp.bfloat16),
                _sds((L, E, I, H), jnp.bfloat16), _sds((), jnp.int32)]


CASES = [
    # bf16 dense attention (every non-MLA model).
    pytest.param(functools.partial(_dense_decode, 32, 8, 64),
                 id="paged_decode-bf16-llama3-1b"),
    pytest.param(functools.partial(_dense_decode, 32, 8, 128, L=32),
                 id="paged_decode-bf16-llama3-8b"),
    pytest.param(functools.partial(_dense_prefill, 32, 8, 64),
                 id="flash_prefill-bf16-llama3-1b"),
    # One tp shard of llama3-1b under --tensor-parallel-size 4 (F = 128):
    # the prefill q-tile plan once let a 4096-row tile through here and the
    # compiler refused it (16.17 MB of scoped VMEM).
    pytest.param(functools.partial(_dense_decode, 8, 2, 64),
                 id="paged_decode-bf16-llama3-1b-tp4"),
    pytest.param(functools.partial(_dense_prefill, 8, 2, 64, Q=1024),
                 id="flash_prefill-bf16-llama3-1b-tp4"),
    pytest.param(functools.partial(_dense_prefill, 32, 8, 64, Q=1024),
                 id="flash_prefill-bf16-llama3-1b-Q1024"),
    # MLA, bf16 latent (F = 512 + 64 padded to 640).
    pytest.param(functools.partial(_mla_decode, 16),
                 id="mla_decode-bf16-16h"),
    pytest.param(functools.partial(_mla_decode, 128),
                 id="mla_decode-bf16-128h"),
    # ... over key blocks of several pages, at the block and the group
    # the shapes pick: a tp-4 shard's 8 heads, kanana-2-30b-a3b's 32, and
    # every head count at the smallest sequence bucket.
    pytest.param(functools.partial(_mla_decode, 8),
                 id="mla_decode-bf16-8h"),
    pytest.param(functools.partial(_mla_decode, 32, L=9),
                 id="mla_decode-bf16-32h-kanana"),
    pytest.param(functools.partial(_mla_decode, 8, S=8),
                 id="mla_decode-bf16-8h-S8"),
    pytest.param(functools.partial(_mla_decode, 16, S=8),
                 id="mla_decode-bf16-16h-S8"),
    pytest.param(functools.partial(_mla_decode, 32, S=8, L=9),
                 id="mla_decode-bf16-32h-kanana-S8"),
    pytest.param(functools.partial(_mla_decode, 128, S=8),
                 id="mla_decode-bf16-128h-S8"),
    pytest.param(functools.partial(_mla_prefill, 16),
                 id="mla_prefill-bf16-16h"),
    pytest.param(functools.partial(_mla_prefill, 128, Q=64),
                 id="mla_prefill-bf16-128h"),
    # int8 expert kernels at deepseek-v3-bench widths (H=2048, I=512).
    pytest.param(functools.partial(_moe, "dense", 8),
                 id="dense_moe_int8-T8"),
    pytest.param(functools.partial(_moe, "dense", 8, I=1024),
                 id="dense_moe_int8-T8-I1024"),
    pytest.param(functools.partial(_moe, "routed", 128),
                 id="routed_moe_int8-T128"),
    pytest.param(functools.partial(_moe, "routed", 512),
                 id="routed_moe_int8-T512"),
    pytest.param(functools.partial(_moe, "streamed", 2048),
                 id="streamed_moe_int8-T2048"),
    # The a2a path's arrival buffer at EP=4 (4 shards x 1024 tokens x k=8
    # rows, 16 local experts, k=1): from 32768 rows the compiler enforced
    # its 16 MB default scope on the kernel's 18 MB of blocks.
    pytest.param(functools.partial(_moe, "streamed", 32768, E=16, k=1),
                 id="streamed_moe_int8-a2a-EP4-T32768"),
    # A mixed sliding-window / full stack at 32 / 4 x 128 heads, the window
    # a traced scalar, contexts to 32768 (B = 1024 pages), 8 layers: the
    # sequence and query buckets its long-document cell reaches.
    pytest.param(functools.partial(_dense_decode, 32, 4, 128, B=1024, L=8,
                                   window=True),
                 id="paged_decode-bf16-window-S64"),
    pytest.param(functools.partial(_dense_decode, 32, 4, 128, S=8, B=1024,
                                   L=8, window=True),
                 id="paged_decode-bf16-window-S8"),
    pytest.param(functools.partial(_dense_prefill, 32, 4, 128, S=64, Q=2048,
                                   B=1024, L=8, window=True),
                 id="flash_prefill-bf16-window-S64-Q2048"),
    pytest.param(functools.partial(_dense_prefill, 32, 4, 128, S=8, Q=128,
                                   B=1024, L=8, window=True),
                 id="flash_prefill-bf16-window-S8-Q128"),
    # The int8 expert kernels a served step program holds at expert width
    # 1024 (128 experts, top-8, 6 MoE layers): dense to 64 tokens, routed
    # to 512, streamed above (ops/moe.py).
    pytest.param(functools.partial(_moe, "dense", 64, I=1024, E=128, Lm=6),
                 id="dense_moe_int8-T64-I1024-E128"),
    pytest.param(functools.partial(_moe, "routed", 128, I=1024, E=128, Lm=6),
                 id="routed_moe_int8-T128-I1024-E128"),
    pytest.param(functools.partial(_moe, "routed", 512, I=1024, E=128, Lm=6),
                 id="routed_moe_int8-T512-I1024-E128"),
    pytest.param(functools.partial(_moe, "streamed", 1024, I=1024, E=128,
                                   Lm=6),
                 id="streamed_moe_int8-T1024-I1024-E128"),
    pytest.param(functools.partial(_moe, "streamed", 2048, I=1024, E=128,
                                   Lm=6),
                 id="streamed_moe_int8-T2048-I1024-E128"),
    # ... at 64 experts of [2304, 896] (hidden 2304 = 18 lane tiles, width
    # 896 = 7: neither a power of two; top-8, 12 MoE layers), every kernel
    # at the ends of its token range: the slabs are 1.97 MiB and the kernels
    # are chosen by the step's token count alone, so there is no geometry
    # rule to extend (ROADMAP B9).
    pytest.param(functools.partial(_moe, "dense", 16, H=2304, I=896, Lm=12),
                 id="dense_moe_int8-T16-H2304-I896-E64"),
    pytest.param(functools.partial(_moe, "dense", 64, H=2304, I=896, Lm=12),
                 id="dense_moe_int8-T64-H2304-I896-E64"),
    pytest.param(functools.partial(_moe, "routed", 128, H=2304, I=896, Lm=12),
                 id="routed_moe_int8-T128-H2304-I896-E64"),
    pytest.param(functools.partial(_moe, "routed", 512, H=2304, I=896, Lm=12),
                 id="routed_moe_int8-T512-H2304-I896-E64"),
    pytest.param(functools.partial(_moe, "streamed", 1024, H=2304, I=896,
                                   Lm=12),
                 id="streamed_moe_int8-T1024-H2304-I896-E64"),
    pytest.param(functools.partial(_moe, "streamed", 2048, H=2304, I=896,
                                   Lm=12),
                 id="streamed_moe_int8-T2048-H2304-I896-E64"),
    # ... and the attention kernels over a cache in groups by layer kind:
    # ONE plane, contexts to 36864 (B = 1152 pages), 32 sequence rows.
    pytest.param(functools.partial(_dense_decode, 32, 4, 128, S=32, B=1152,
                                   L=1, window=True),
                 id="paged_decode-bf16-window-one-plane-S32"),
    pytest.param(functools.partial(_dense_prefill, 32, 4, 128, S=32, Q=2048,
                                   B=1152, L=1, window=True),
                 id="flash_prefill-bf16-window-one-plane-S32-Q2048"),
    # ... and at expert width 768 (qwen3-30b-a3b and sdar-30b-a3b: 128
    # experts, top-8, 8 MoE layers; kanana-2-30b-a3b: top-6), the token
    # counts that the ledger's breakdown names in their cells.
    pytest.param(functools.partial(_moe, "dense", 16, I=768, E=128, Lm=8),
                 id="dense_moe_int8-T16-I768-E128"),
    pytest.param(functools.partial(_moe, "dense", 64, I=768, E=128, Lm=8),
                 id="dense_moe_int8-T64-I768-E128"),
    pytest.param(functools.partial(_moe, "routed", 256, I=768, E=128, Lm=8),
                 id="routed_moe_int8-T256-I768-E128"),
    pytest.param(functools.partial(_moe, "routed", 512, I=768, E=128, Lm=8),
                 id="routed_moe_int8-T512-I768-E128"),
    pytest.param(functools.partial(_moe, "streamed", 1024, I=768, E=128,
                                   Lm=8),
                 id="streamed_moe_int8-T1024-I768-E128"),
    pytest.param(functools.partial(_moe, "streamed", 2048, I=768, E=128,
                                   Lm=8),
                 id="streamed_moe_int8-T2048-I768-E128"),
    pytest.param(functools.partial(_moe, "routed", 256, I=768, E=128, k=6,
                                   Lm=8),
                 id="routed_moe_int8-T256-I768-E128-top6-kanana"),
    # The one-pass kernel, which serves every single-device step above 512
    # rows since PR 48 (the streamed kernel's cases above are the a2a
    # exchange's body at those widths), at the four published geometries and
    # both token buckets it meets: tiles of 128 rows, the bf16 matrices of an
    # expert in a 12.6 MB scratch at width 1024.
    *[pytest.param(functools.partial(_moe, "one_pass", T, **kw),
                   id=f"one_pass_moe_int8-T{T}-{name}")
      for name, kw in (
          ("I1024-E128-trinity", dict(I=1024, E=128, Lm=6)),
          ("I768-E128-qwen3", dict(I=768, E=128, Lm=8)),
          ("I768-E128-top6-kanana", dict(I=768, E=128, k=6, Lm=8)),
          ("H2304-I896-E64-mellum2", dict(H=2304, I=896, Lm=12)))
      for T in (1024, 2048)],
    # The query tile list at the shapes the benchmark's cells serve (token
    # bucket / sequence bucket): kanana-2-30b-a3b's MLA latent row (320
    # tiles of 4 slots; 192 of 16 for a 2,048-token chunk), trinity-mini's
    # windowed layers at 32768 context (72 tiles of 32), qwen3-30b-a3b at a
    # full sequence bucket (128 of 32), and a tp shard's 8 heads (TP = 4:
    # 128 tiles of 16).
    pytest.param(functools.partial(_prefill_tiles, 32, 1, 640, T=1024, S=64,
                                   Q=512, L=9, mla=True),
                 id="mla_prefill-tiles-kanana-T1024-S64"),
    pytest.param(functools.partial(_prefill_tiles, 32, 1, 640, T=2048, S=64,
                                   Q=2048, L=9, mla=True),
                 id="mla_prefill-tiles-kanana-T2048-S64"),
    pytest.param(functools.partial(_prefill_tiles, 32, 4, 128, T=2048, S=8,
                                   Q=2048, B=1024, L=8, window=True),
                 id="flash_prefill-tiles-trinity-window-T2048-S8"),
    pytest.param(functools.partial(_prefill_tiles, 32, 4, 128, T=2048, S=64,
                                   Q=2048, B=128, L=8),
                 id="flash_prefill-tiles-qwen3-T2048-S64"),
    pytest.param(functools.partial(_prefill_tiles, 8, 1, 640, T=1024, S=64,
                                   Q=512, L=9, mla=True),
                 id="mla_prefill-tiles-tp4-T1024-S64"),
    # The GQA kernel's key blocks (several pages a step of the inner loop,
    # one KV-head group a dot where the head size is whole lane tiles):
    # trinity-mini's full layers, one of four tp shards of the cells'
    # geometry and of llama3-8b's (8 / 1 and 8 / 2 x 128, F 128 / 256),
    # llama3-1b's zero-expanded 32 / 8 x 64 at its largest tile, and the
    # small query bucket of --spec-k 7 (8 slots a row).
    pytest.param(functools.partial(_prefill_tiles, 32, 4, 128, T=2048, S=8,
                                   Q=2048, B=1024, L=8),
                 id="flash_prefill-tiles-trinity-full-T2048-S8"),
    pytest.param(functools.partial(_prefill_tiles, 8, 1, 128, T=2048, S=64,
                                   Q=2048, B=128, L=8),
                 id="flash_prefill-tiles-tp4-8x1x128-T2048-S64"),
    pytest.param(functools.partial(_prefill_tiles, 8, 2, 128, T=1024, S=16,
                                   Q=1024, B=256, L=32),
                 id="flash_prefill-tiles-tp4-8x2x128-T1024-S16"),
    pytest.param(functools.partial(_prefill_tiles, 32, 8, 64, T=2048, S=8,
                                   Q=2048, B=256),
                 id="flash_prefill-tiles-llama3-1b-T2048-S8"),
    pytest.param(functools.partial(_prefill_tiles, 32, 4, 128, T=64, S=8,
                                   Q=8, B=1024, L=8, window=True),
                 id="flash_prefill-tiles-trinity-window-Q8"),
    pytest.param(functools.partial(_prefill_tiles, 32, 8, 64, T=64, S=8,
                                   Q=8, B=256),
                 id="flash_prefill-tiles-llama3-1b-Q8"),
    # falcon-h1-34b: the state-space mixer's two kernels over the state
    # pool (32 heads x 256 x 128 float32 a slot and layer), and the GQA
    # kernels at a query group of 5 (20 heads over 4 KV heads: 60 fused
    # rows a group in a prefill tile, no multiple of 8).
    pytest.param(_ssm_update, id="ssm_decode_update-falcon-h1-S64"),
    pytest.param(functools.partial(_ssm_scan, T=2048, S=64),
                 id="ssm_chunk_scan-falcon-h1-T2048-S64"),
    pytest.param(functools.partial(_ssm_scan, T=16, S=8),
                 id="ssm_chunk_scan-falcon-h1-T16-S8"),
    pytest.param(functools.partial(_dense_decode, 20, 4, 128, L=6),
                 id="dense_decode-falcon-h1-20x4x128"),
    # ling-3.0-flash-vl: the linear-attention layers' two kernels over the
    # state pool (32 heads x 128 x 128 float32 a slot and layer).
    pytest.param(_delta_update, id="delta_decode_update-ling3-S16"),
    pytest.param(functools.partial(_delta_scan, T=2048, S=16),
                 id="delta_chunk_scan-ling3-T2048-S16"),
    pytest.param(functools.partial(_delta_scan, T=16, S=8),
                 id="delta_chunk_scan-ling3-T16-S8"),
    pytest.param(functools.partial(_prefill_tiles, 20, 4, 128, T=2048, S=64,
                                   Q=512, B=1024, L=6),
                 id="flash_prefill-tiles-falcon-h1-T2048-S64"),
    pytest.param(functools.partial(_prefill_tiles, 20, 4, 128, T=64, S=8,
                                   Q=16, B=1024, L=6),
                 id="flash_prefill-tiles-falcon-h1-Q16"),
    # dots3-note-prev's full layers: attention dense under the selection
    # as a mask, 128 heads over the 640-wide latent row, a 2,048-token
    # mixed step (tiles of 8 slots) and a pure-decode step (tiles of one).
    pytest.param(functools.partial(_mla_masked, T=2048, S=16, Q=2048),
                 id="mla_masked-dots3-T2048-S16"),
    pytest.param(functools.partial(_mla_masked, T=16, S=16, Q=1),
                 id="mla_masked-dots3-decode-S16"),
    # Its sliding layers: the same kernel's windowed walk, 64 heads over
    # the 1,152-wide row with values of 1,024 and a window of 513 (tiles of
    # 16 slots over 3-4 key blocks; a decode row one slot over 3).
    pytest.param(functools.partial(_mla_window, T=2048, S=16, Q=2048),
                 id="mla_masked-dots3-window-T2048-S16"),
    pytest.param(functools.partial(_mla_window, T=16, S=16, Q=1),
                 id="mla_masked-dots3-window-decode-S16"),
    # Its full layers' indexer: a tile's scores, threshold and bias in one
    # kernel, tiles of 8 slots and tiles of one padded to 8.
    pytest.param(functools.partial(_dsa_index, T=2048, S=16, Q=2048),
                 id="dsa_index-dots3-T2048-S16"),
    pytest.param(functools.partial(_dsa_index, T=16, S=16, Q=1),
                 id="dsa_index-dots3-decode-S16"),
    # Its held bf16 experts: the grouped kernel over tiles of held rows and
    # the combine, a 2,048-token mixed step and a pure-decode step.
    pytest.param(functools.partial(_moe_held, T=2048),
                 id="moe_held-dots3-T2048"),
    pytest.param(functools.partial(_moe_held, T=16),
                 id="moe_held-dots3-decode-T16"),
    # phi4-mini-flash: the Mamba-1 kernels over the state pool (16 states x
    # 5,120 channels float32 a slot and layer), the GQA kernels with heads
    # in pairs (40 over 10 of 128, rows of 1,280, window 512), and the
    # one-query read of the shared plane.
    pytest.param(_ssm1_update, id="ssm1_decode_update-phi4flash-S8"),
    pytest.param(functools.partial(_ssm1_scan, T=2048, S=8),
                 id="ssm1_chunk_scan-phi4flash-T2048-S8"),
    pytest.param(functools.partial(_ssm1_scan, T=16, S=1),
                 id="ssm1_chunk_scan-phi4flash-T16-S1"),
    pytest.param(functools.partial(_paged_read, 40, 10, 128),
                 id="paged_attention_read-phi4flash-S8"),
    pytest.param(functools.partial(_paged_read, 40, 10, 128, S=1),
                 id="paged_attention_read-phi4flash-S1"),
    pytest.param(functools.partial(_dense_decode, 40, 10, 128, S=8, B=1024,
                                   L=9, window=True),
                 id="paged_decode-phi4flash-window-S8"),
    pytest.param(functools.partial(_prefill_tiles, 40, 10, 128, T=2048, S=8,
                                   Q=2048, B=1024, L=9, window=True),
                 id="flash_prefill-tiles-phi4flash-window-T2048-S8"),
    pytest.param(functools.partial(_prefill_tiles, 40, 10, 128, T=64, S=8,
                                   Q=16, B=1024, L=9, window=True),
                 id="flash_prefill-tiles-phi4flash-window-Q16"),
]


@pytest.mark.parametrize("build", CASES)
def test_kernel_compiles_for_v5e(build, one_chip, no_compile_cache):
    fn, args = build()
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in args]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"%{getattr(fn, 'hlo_name', '')}" in text
