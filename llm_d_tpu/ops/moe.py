"""MoE ops: routing, grouped expert GEMM, expert-parallel dispatch.

TPU-native counterpart of the reference's DeepEP (expert all-to-all) +
DeepGEMM (grouped GEMM) CUDA stack (reference: docker/Dockerfile.cuda:51-56,
wide-ep decode.yaml:76-132).  Design:

  - Routing (incl. DeepSeek group-limited top-k) is a few tiny matmuls and
    sorts — computed replicated on every device; only expert FFNs shard.
  - Grouped GEMM: tokens are sorted by expert id and fed to
    ``jax.lax.ragged_dot`` — one MXU-friendly kernel over all local experts
    instead of a Python loop (the DeepGEMM role).  The int8 path has its
    own three kernels on one device (dense streaming / fused-routing
    routed / one pass over rows sorted by expert), chosen by a step's
    token count alone: see ``DENSE_INT8_MAX_T`` and ``ops.pallas``; the
    a2a exchange's arrival chunks have a fourth (chunk-streamed).
  - Expert parallelism: experts shard over the *flattened* (dp, sp, tp) mesh
    axes ("TPxDP in attention, EP in MoE layers", decode.yaml:76,87).  Two
    dispatch strategies:

      * ``a2a`` (default multi-device): the DeepEP role.  Tokens are split
        over the EP shards; each (token, choice) row travels ONLY to the
        shard owning its expert via ``jax.lax.ragged_all_to_all`` over ICI,
        the grouped GEMM runs on received rows, and results return by the
        reverse exchange — no full-activation all-reduce per MoE layer.
        Dispatch is chunked (``LLMD_MOE_DP_CHUNK_SIZE``, the
        ``VLLM_MOE_DP_CHUNK_SIZE`` analogue, decode.yaml:108-118) to bound
        the exchange buffers.  XLA:CPU has no ragged-all-to-all, so tests
        run the same fixed-region layout through a dense ``all_to_all``
        (identical math, padded comm volume).  The exchange WIRE is
        dtype-selectable (``LLMD_COLLECTIVE_DTYPE``, the EQuARX trade):
        int8 mode ships per-row-quantized payloads both ways with f32
        scale vectors as sibling exchanges; bf16 mode ships bf16 both
        ways (the combine return was f32 before round 10 — the baseline
        accounting in parallel/quant_collectives.py keeps that number).

      * ``psum`` (oracle / fallback): each shard computes all T tokens
        against its local experts and partial outputs all-reduce.  Kept as
        the correctness oracle and for shapes the a2a path can't split.
        Under the int8 wire mode the all-reduce runs quantized too
        (``quantized_psum``).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from llm_d_tpu.models.config import ModelConfig
from llm_d_tpu.parallel.mesh import AXIS_EP
from llm_d_tpu.parallel.quant_collectives import (
    dequantize_rows, quantize_rows, quantized_psum,
    resolve_collective_dtype)


def route(
    router_logits: jax.Array,      # [T, E] f32
    config: ModelConfig,
    e_bias: Optional[jax.Array] = None,   # [E] sigmoid-selection bias
) -> Tuple[jax.Array, jax.Array]:  # (weights [T, k] f32, idx [T, k] i32)
    """Top-k expert selection with optional DeepSeek group-limited routing.

    Scoring follows ``config.scoring_func``: ``softmax`` (Mixtral / Qwen-MoE)
    or ``sigmoid`` (DeepSeek-V3/R1), where ``e_score_correction_bias`` is
    added for group/expert *selection only* and combine weights come from the
    un-biased sigmoid scores.

    With ``n_group > 0`` the expert set is partitioned into groups; only the
    ``topk_group`` groups with the highest (sum of top-2 member scores) stay
    eligible — the device-locality trick DeepSeek-V3 uses so each token's
    experts land on few nodes (reference wide-EP deploys DeepSeek-R1 with
    this scheme; decode.yaml:76-132).
    """
    c = config
    T, E = router_logits.shape
    k = c.num_experts_per_tok
    logits = router_logits.astype(jnp.float32)
    if c.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores + (e_bias.astype(jnp.float32)[None, :]
                           if e_bias is not None else 0.0)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        choice = scores

    if c.n_group > 0:
        g = c.n_group
        gs = choice.reshape(T, g, E // g)
        # Group score: sum of each group's top-2 expert scores (V3 scheme).
        top2 = jax.lax.top_k(gs, min(2, E // g))[0].sum(-1)     # [T, g]
        _, keep = jax.lax.top_k(top2, c.topk_group)             # [T, topk_group]
        mask = jnp.zeros((T, g), bool).at[
            jnp.arange(T)[:, None], keep].set(True)
        choice = jnp.where(
            jnp.repeat(mask, E // g, axis=1), choice, -jnp.inf)

    _, idx = jax.lax.top_k(choice, k)                           # [T, k]
    weights = jnp.take_along_axis(scores, idx, axis=1)
    if c.moe_renormalize:
        weights = weights / jnp.maximum(
            weights.sum(-1, keepdims=True), 1e-20)
    weights = weights * c.routed_scaling_factor
    return weights.astype(jnp.float32), idx.astype(jnp.int32)


def _swiglu_grouped(xs, w_gate, w_up, w_down, group_sizes):
    """SwiGLU through three grouped GEMMs (per-expert weights)."""
    h = jax.lax.ragged_dot(xs, w_gate, group_sizes,
                           preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(xs, w_up, group_sizes,
                           preferred_element_type=jnp.float32)
    a = (jax.nn.silu(h) * u).astype(xs.dtype)
    return jax.lax.ragged_dot(a, w_down, group_sizes,
                              preferred_element_type=jnp.float32)


def _local_expert_ffn(
    x: jax.Array,          # [T, H] all tokens (replicated per shard)
    weights: jax.Array,    # [T, k] combine weights
    idx: jax.Array,        # [T, k] global expert ids
    w_gate: jax.Array,     # [E_loc, H, I]
    w_up: jax.Array,
    w_down: jax.Array,     # [E_loc, I, H]
    e0: jax.Array,         # scalar: first global expert id on this shard
) -> jax.Array:            # [T, H] partial output (only local experts)
    """Sorted grouped-GEMM over this shard's experts; non-local slots are
    routed to a trailing zero-weight trash group (static shapes, no drops)."""
    T, H = x.shape
    k = idx.shape[1]
    E_loc = w_gate.shape[0]
    S = T * k

    flat = idx.reshape(S)
    lid = flat - e0
    is_local = (lid >= 0) & (lid < E_loc)
    sort_key = jnp.where(is_local, lid, E_loc)
    order, inv, key_counts = _stable_argsort_bounded(sort_key, E_loc + 1)
    tok = order // k
    xs = x[tok]                                                 # [S, H]
    group_sizes = key_counts                # [E_loc+1], last = trash group

    zpad = jnp.zeros((1,) + w_gate.shape[1:], w_gate.dtype)
    y = _swiglu_grouped(
        xs,
        jnp.concatenate([w_gate, zpad]),
        jnp.concatenate([w_up, zpad]),
        jnp.concatenate([w_down, jnp.zeros((1,) + w_down.shape[1:],
                                           w_down.dtype)]),
        group_sizes)                                            # [S, H] f32

    wslot = (weights.reshape(S)[order]
             * is_local[order].astype(jnp.float32))[:, None]
    return _unsort_combine(y * wslot, inv, T, k)


def _held_expert_ffn(
    x: jax.Array,          # [T, H]
    weights: jax.Array,    # [T, k] combine weights
    idx: jax.Array,        # [T, k] expert ids over the router's width
    w_gate: jax.Array,     # [E_held, H, I] experts e0 .. e0 + E_held - 1,
    w_up: jax.Array,       # or with ``plane`` the layers' stacks
    w_down: jax.Array,     # [L, E_held, ...]
    e0: int,
    plane: Optional[jax.Array] = None,
) -> jax.Array:            # [T, H] the held experts' part of the sum
    """One rank's share of the routed experts on one device: the slots
    routed to held experts (at 8 slots a token over 8 ranks, 1 of 8).  On
    the TPU, bf16 rows and experts of whole lane tiles go through
    ``ops/pallas/moe_held.py``: tiles of held rows only, each touched
    expert's matrices read once, no row of a slot held elsewhere moved.
    Below, the XLA form (the CPU, other geometries, the kernels' reference):
    the grouped product is handed all the sorted slots, the rest lie past
    the last group.  Why not that form with fewer rows: what it costs goes
    by the experts' bytes (on the v5e, one layer's call at 2,048 tokens, 32
    of 256 experts of 5120 x 1536: 10.0 ms, 8.8 with a quarter of the slots).

    ``plane``: the weights are whole stacks over layers, read in place (a
    layer's slice handed to either form would be a copy of its experts a
    step, 1.4 GB a layer; the grouped product takes every layer's experts
    as groups of ONE product, all empty but this layer's)."""
    from llm_d_tpu.ops.pallas import moe_held
    if jax.default_backend() == "tpu" \
            and moe_held.ineligible_reason(x, w_gate) is None:
        return moe_held.held_expert_ffn(
            x, weights, idx, w_gate, w_up, w_down, e0, plane)
    T = x.shape[0]
    k = idx.shape[1]
    E_held = w_gate.shape[-3]
    S = T * k
    lid = idx.reshape(S) - e0
    is_held = (lid >= 0) & (lid < E_held)
    order, inv, counts = _stable_argsort_bounded(
        jnp.where(is_held, lid, E_held), E_held + 1)
    group_sizes = counts[:E_held]
    if plane is not None:
        w_gate, w_up, w_down = (w.reshape((-1,) + w.shape[2:])
                                for w in (w_gate, w_up, w_down))
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((w_gate.shape[0],), group_sizes.dtype), group_sizes,
            (plane * E_held,))
    y = _swiglu_grouped(x[order // k], w_gate, w_up, w_down,
                        group_sizes)                              # [S, H] f32
    y = jnp.where(is_held[order][:, None],
                  y * weights.reshape(S)[order][:, None], 0.0)
    return _unsort_combine(y, inv, T, k)


def _unsort_combine(y: jax.Array, inv: jax.Array, T: int,
                    k: int) -> jax.Array:
    """Per-token combine WITHOUT a [T, H] scatter-add (XLA lowers big row
    scatters to serialized updates on TPU): un-sort by ONE fast row gather
    through the sort's inverse permutation ``inv`` (slot ``s`` lies at
    sorted row ``inv[s]``), then a [T, k, H] reshape-sum.  ``y`` rows are
    already combine-weighted, in the sorted layout."""
    # f32 AFTER the gather (bf16 rows move at half the bytes); the k-sum
    # accumulates in f32 either way.
    contrib = y[inv].astype(jnp.float32)      # [S, H] in flat (t, k) order
    return contrib.reshape(T, k, -1).sum(axis=1)


def _dense_expert_ffn(
    x: jax.Array,          # [T, H]
    weights: jax.Array,    # [T, k] combine weights
    idx: jax.Array,        # [T, k] expert ids
    w_gate: jax.Array,     # [E, H, I]
    w_up: jax.Array,
    w_down: jax.Array,     # [E, I, H]
) -> jax.Array:            # [T, H] f32
    """All-experts batched GEMM with masked combine — the decode path.

    Rationale (measured on v5e): decode batches are tiny, so the MoE FFN is
    HBM-bound on expert weights with ~100x MXU headroom.  ``ragged_dot``
    with E groups of ~T*k/E rows streams weights at ~260 GB/s here (tile
    padding + per-group pipeline bubbles); one batched einsum over ALL
    experts streams at ~700 GB/s — 2.7x faster despite computing E/k times
    the FLOPs — and stays ahead through T=512.  The combine weight is
    pre-scaled onto the activations so unrouted (token, expert) pairs
    contribute exactly zero; int8 weights dequantize inside the einsum
    operand read (no materialized bf16 copy).
    """
    T = x.shape[0]
    E = w_gate.shape[0]
    comb = _combine_matrix(T, E, idx, weights)               # [T, E]
    h = jnp.einsum("th,ehi->eti", x, w_gate,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("th,ehi->eti", x, w_up,
                   preferred_element_type=jnp.float32)
    a = (jax.nn.silu(h) * u * comb.T[:, :, None]).astype(x.dtype)
    return jnp.einsum("eti,eih->th", a, w_down,
                      preferred_element_type=jnp.float32)


# One device, bf16 or dequantized weights: up to this many tokens take the
# all-experts batched product (``_dense_expert_ffn`` says why), more take the
# sorted grouped product.
DENSE_DISPATCH_MAX_T = 512

# int8 experts on the TPU: which of the three kernels serves a step is a
# function of its token count T alone.  A step's prefill-chunk and decode
# rows are one T: each layer's expert weights stream once for both.
#
#   T <= DENSE_INT8_MAX_T    ``dense_moe_int8``, every expert against every
#     row.  So few rows are bound by the weights' bytes: the extra products
#     ride under the stream, and the routed kernel's padding of each
#     expert's rows to a tile (up to E * row_tile / 2 rows) is at its
#     relative worst.
#   T <= ROUTED_INT8_MAX_T   ``routed_moe_int8``, T * k rows: the whole batch
#     stays in VMEM, gather and combine are one-hot products inside the
#     kernel.  512 rows is where that residency ends (x, the f32 output
#     block and the double-buffered weight slabs share VMEM).
#   above                    ``one_pass_moe_int8``: the whole step's rows
#     sorted by expert in HBM, every expert's matrices read ONCE
#     (``_one_pass_int8_kernel_path``).
#
# Until PR 48 the steps above 512 rows went through ``streamed_moe_int8``,
# the routed body over token-order chunks of PREFILL_CHUNK_T rows: a
# 2,048-token step was four chunks and each streamed every expert it touched
# again.  Its cost model was written at H 2048, I 512, E 64 (201 MB a layer),
# which no cell runs.  At the published widths (T 2,048, v5e: 819 GB/s,
# 197 TFLOP/s):
#
#   configuration                    int8 experts  4 passes  1 pass   the real    rows an expert:
#                                    a layer                          pairs' dots a chunk / the step
#   trinity-mini (128 x 1024)        805 MB        3.9 ms    0.98 ms  1.05 ms     32 / 128
#   qwen3-/sdar-30b-a3b (128 x 768)  604 MB        2.95 ms   0.74 ms  0.79 ms     32 / 128
#   kanana-2-30b-a3b (same, top-6)   604 MB        2.95 ms   0.74 ms  0.59 ms     24 / 96
#   mellum2-12b-a2.5b (64 x 896,     397 MB        1.94 ms   0.48 ms  1.03 ms     64 / 256
#     hidden 2304)
#
# and the kernel read 5.5 ms a layer in ``trinity-mini.docqa`` (ledger, PR
# 47): the re-streaming at 71 % of the HBM rate, in tiles of the 32 rows a
# CHUNK gives an expert.  The streamed kernel stays for the a2a exchange's
# arrival chunks (``_a2a_moe_chunk``: k = 1 rows in arrival order, a chunk
# the resident unit), which no cell runs.
DENSE_INT8_MAX_T = 64
ROUTED_INT8_MAX_T = 512

# Rows a chunk of the streamed kernel (the a2a exchange's only, see above).
# A shard's weights are read once a chunk (rows / chunk passes) while the
# one-hot gather and combine cost 2 * chunk / (3 * I) of the expert FLOPs;
# 512 is what VMEM holds on the v5e beside the f32 accumulator and the
# double-buffered weight tiles.
PREFILL_CHUNK_T = 512


def int8_kernels_serve(dispatch: str = "auto") -> bool:
    """Whether a single-device ``expert_ffn`` hands int8 experts to the
    kernels: on the TPU, unless a dispatch is asked for by name (the
    dequantized paths, the kernels' reference)."""
    if dispatch == "auto":
        dispatch = os.environ.get("LLMD_MOE_DISPATCH", "auto")
    return jax.default_backend() == "tpu" and dispatch == "auto"


def int8_kernel(T: int) -> str:
    """The int8 expert kernel that serves a single-device step of ``T``
    token rows on the TPU: ``dense``, ``routed`` or ``one_pass``.  The one
    rule ``expert_ffn`` dispatches by and the engine counts by."""
    if T <= DENSE_INT8_MAX_T:
        return "dense"
    return "routed" if T <= ROUTED_INT8_MAX_T else "one_pass"


def _routed_row_tile(slots: int, E: int) -> int:
    """Rows a tile of the routed and streamed kernels, from the mean rows
    an expert: small tiles bound each expert's padding (the only waste
    left), larger ones feed the MXU better once the groups fill them."""
    return 32 if slots < E * 96 else 64


def _one_pass_row_tile(slots: int, E: int) -> int:
    """Rows a tile of the one-pass kernel, from the mean rows an expert: the
    MXU's height from 96 rows an expert up, half of it below (a mean run of
    48-64 rows in tiles of 128 is a third to a half padding).  One layer's
    call alone on the v5e, ms at tiles of 64 / 128 / 256 rows (my chip runs,
    PR 48; rows an expert = T k / E):
      trinity-mini  T 2048 (128)  3.43 / 3.23 / 3.73   T 1024 (64)   2.15 / 2.25 / 3.56
      qwen3, sdar   T 2048 (128)  2.86 / 2.74 / 3.12   T 1024 (64)   1.81 / 1.90 / 2.95
      kanana-2      T 2048 (96)   2.60 / 2.06 / 3.03   T 1024 (48)   1.27 / 1.79 / 2.84
      mellum2       T 2048 (256)  2.69 / 2.65 / 2.87   T 1024 (128)  1.62 / 1.77 / 2.05
    (the streamed path: 7.05, 5.78, 4.52, 5.66 at 2,048 rows; 3.60, 2.95, 2.31,
    2.85 at 1,024: no boundary above ROUTED_INT8_MAX_T where it wins)."""
    return 128 if slots >= E * 96 else 64


def _env_int(name: str, default: int) -> int:
    """Integer environment option; a malformed value falls back to the
    default (``llm_d_tpu.utils.config.env_int``) instead of failing the
    serving path at trace time."""
    from llm_d_tpu.utils.config import env_int
    return env_int(name, default)


def _sorted_tile_layout(flat: jax.Array, weights_flat: jax.Array,
                        k: int, E: int, rt: int):
    """Counting-sort tile layout shared by the routed and streamed int8
    kernel paths: rows sorted by expert, each group padded to a ``rt``
    multiple, one expert per tile.

    Returns ``(tok_s, slot, wslot_pad, tile_expert, num_tiles)``:
    ``tok_s[s]`` is sorted element s's token, ``slot[s]`` its position in the
    padded layout (static worst case ``S_pad = ceil(S/rt)*rt + E*rt`` —
    METADATA length only, no [_, H] rows); ``wslot_pad`` carries the
    combine weight per padded slot (0 = pad); ``tile_expert`` maps each
    of the ``S_pad // rt`` static tiles to its expert, with inactive
    trailing tiles REPEATING the last active tile's expert so their
    weight-block index map repeats and Pallas skips the DMA (clamping to
    E-1 instead would stream one unused expert whenever E-1 is empty);
    ``num_tiles`` counts the populated tiles.  Empty experts get zero
    tiles — their weights are never streamed."""
    S = flat.shape[0]
    order, _, counts = _stable_argsort_bounded(flat, E)
    eid_s = flat[order]
    tok_s = (order // k).astype(jnp.int32)
    padded = -(-counts // rt) * rt
    offs = _excl_cumsum(padded)
    rank = jnp.arange(S, dtype=jnp.int32) - _excl_cumsum(counts)[eid_s]
    slot = offs[eid_s] + rank
    S_pad = -(-S // rt) * rt + E * rt
    NT = S_pad // rt
    wslot_pad = jnp.zeros((S_pad,), jnp.float32).at[slot].set(
        weights_flat[order])
    num_tiles = padded.sum() // rt                 # >= 1: S >= 1 always
    bounds = jnp.cumsum(padded)
    starts = jnp.minimum(jnp.arange(NT, dtype=jnp.int32),
                         num_tiles - 1) * rt
    tile_expert = jnp.minimum(
        jnp.searchsorted(bounds, starts, side="right"),
        E - 1).astype(jnp.int32)
    return tok_s, slot, wslot_pad, tile_expert, num_tiles


def _routed_int8_kernel_path(x, weights, idx, quant: dict,
                             row_tile: Optional[int] = None,
                             interpret: bool = False):
    """Metadata-only glue for the fused-routing kernel (decode regime).

    No activation row moves here: the counting sort plus O(S) int32 slot
    arithmetic produce the scalar-prefetch routing tables and the kernel
    does the gather / combine itself (ops/pallas/moe_routed.py)."""
    from llm_d_tpu.ops.pallas.moe_routed import routed_moe_int8
    T, H = x.shape
    k = idx.shape[1]
    E = quant["w_gate_q"].shape[1]
    S = T * k
    rt = row_tile or _routed_row_tile(S, E)
    tok_s, slot, wslot_pad, tile_expert, num_tiles = _sorted_tile_layout(
        idx.reshape(S), weights.reshape(S), k, E, rt)
    S_pad = wslot_pad.shape[0]
    NT = S_pad // rt
    # Pad slots keep token 0 with zero combine weight: they select a real
    # row in the kernel's one-hot but contribute exactly nothing.
    tok_pad = jnp.zeros((S_pad,), jnp.int32).at[slot].set(tok_s)
    # bf16 sublane alignment for the resident x / output blocks.
    Tp = -(-T // 16) * 16
    x_p = x.astype(jnp.bfloat16)
    if Tp != T:
        x_p = jnp.pad(x_p, ((0, Tp - T), (0, 0)))
    out = routed_moe_int8(
        x_p, tok_pad[:, None], tok_pad.reshape(NT, rt), wslot_pad[:, None],
        tile_expert, num_tiles, quant["layer"],
        quant["w_gate_q"], quant["w_gate_s"],
        quant["w_up_q"], quant["w_up_s"],
        quant["w_down_q"], quant["w_down_s"],
        row_tile=rt, interpret=interpret)
    return out[:T].astype(x.dtype)


def _streamed_int8_kernel_path(x, weights, idx, quant: dict,
                               chunk_t: Optional[int] = None,
                               row_tile: Optional[int] = None,
                               out_dtype=None,
                               interpret: bool = False):
    """Metadata-only glue for the chunk-streamed kernel (prefill regime).

    Like ``_routed_int8_kernel_path`` no activation row moves here — but
    the counting sort runs PER token-order CHUNK (vmapped), so the
    kernel can stream ``x`` chunk by chunk instead of holding it
    VMEM-resident whole.  Routing metadata stays O(S) int32; no
    ``[S_pad, H]`` layout is ever materialized in HBM
    (ops/pallas/moe_routed_stream.py)."""
    from llm_d_tpu.ops.pallas.moe_routed_stream import streamed_moe_int8
    T, H = x.shape
    k = idx.shape[1]
    E = quant["w_gate_q"].shape[1]
    if chunk_t is None:
        chunk_t = PREFILL_CHUNK_T
    # bf16 sublane alignment; never a taller chunk than the (aligned)
    # batch itself — small batches degenerate to a single chunk.
    chunk_t = max(16, min(-(-chunk_t // 16) * 16, -(-T // 16) * 16))
    C = -(-T // chunk_t)
    Tp = C * chunk_t
    S_c = chunk_t * k
    rt = row_tile or _routed_row_tile(S_c, E)     # per-chunk group sizes
    x_p = x.astype(jnp.bfloat16)
    if Tp != T:
        # Pad tokens route to expert 0 with ZERO combine weight: they
        # occupy sorted slots in the last chunk but contribute nothing
        # (their x rows are zero too).
        x_p = jnp.pad(x_p, ((0, Tp - T), (0, 0)))
        idx = jnp.pad(idx, ((0, Tp - T), (0, 0)))
        weights = jnp.pad(weights, ((0, Tp - T), (0, 0)))

    def chunk_layout(flat, wf):
        # Chunk-local layout: tok ids are 0..chunk_t-1 within the chunk.
        tok_s, slot, wslot_pad, tile_expert, num_tiles = \
            _sorted_tile_layout(flat, wf, k, E, rt)
        tok_pad = jnp.zeros((wslot_pad.shape[0],), jnp.int32).at[slot].set(
            tok_s)
        return tok_pad, wslot_pad, tile_expert, num_tiles

    tok_pad, wslot_pad, tile_expert, num_tiles = jax.vmap(chunk_layout)(
        idx.reshape(C, S_c), weights.reshape(C, S_c))      # [C, ...]
    out = streamed_moe_int8(
        x_p, tok_pad.reshape(-1, 1), tok_pad.reshape(-1, rt),
        wslot_pad.reshape(-1, 1), tile_expert.reshape(-1),
        num_tiles.astype(jnp.int32), quant["layer"],
        quant["w_gate_q"], quant["w_gate_s"],
        quant["w_up_q"], quant["w_up_s"],
        quant["w_down_q"], quant["w_down_s"],
        chunk_t=chunk_t, row_tile=rt, interpret=interpret)
    # out_dtype lets combine-in-f32 callers (the a2a exchange) skip a
    # lossy bf16 round trip of the kernel's native f32 accumulator.
    return out[:T].astype(out_dtype or x.dtype)


def _excl_prefix_rows(m: jax.Array, block: int = 512) -> jax.Array:
    """Exclusive prefix sums down the rows of ``m`` [T, E] (small counts),
    int32: blocks of rows against a strict lower triangle on the MXU
    (integers in bf16, f32 sums: exact) and the blocks' totals before
    them.  XLA's own cumsum over a long axis is a tree of passes."""
    T, E = m.shape
    B = min(block, T)
    nb = -(-T // B)
    mb = jnp.pad(m, ((0, nb * B - T), (0, 0))).astype(
        jnp.bfloat16).reshape(nb, B, E)
    r = jnp.arange(B, dtype=jnp.int32)
    tri = (r[:, None] > r[None, :]).astype(jnp.bfloat16)
    within = jnp.einsum("ij,bje->bie", tri, mb,
                        preferred_element_type=jnp.float32)
    totals = jnp.sum(mb, axis=1, dtype=jnp.float32)            # [nb, E]
    before = jnp.cumsum(totals, axis=0) - totals
    return (within + before[:, None, :]).astype(jnp.int32).reshape(
        nb * B, E)[:T]


def _one_pass_layout(idx: jax.Array, weights: jax.Array, E: int, rt: int):
    """The one-pass kernel's layout over ALL of a step's slots ``idx``
    [T, k]: each expert's slots, in token order, a run padded to ``rt``,
    one expert a tile, in expert order.

    Returns ``(pos, tok_pad, wslot_pad, tile_expert, num_tiles)``: ``pos``
    [T, k] the padded slot of each (token, choice); ``tok_pad`` / ``wslot_pad``
    [NT * rt] the token and the combine weight of each padded slot (token 0
    at weight 0 = pad), NT = ceil(S / rt) + E the static worst case;
    ``tile_expert`` [NT], a tile past ``num_tiles`` repeating the last one's
    expert (its blocks are not fetched again).

    No sort: a slot's rank in its expert's run is the slots of that expert
    in the tokens before it (``_excl_prefix_rows`` of the per-token counts)
    plus the token's own earlier choices of it, all compares and sums over
    [T, k, E]; the one scatter writes the inverse."""
    T, k = idx.shape
    S = T * k
    assert k <= 256, k          # the per-token counts ride in bf16
    NT = -(-S // rt) + E
    hit = idx[:, :, None] == jnp.arange(E, dtype=idx.dtype)     # [T, k, E]
    per_tok = jnp.sum(hit, axis=1, dtype=jnp.int32)             # [T, E]
    prior = _excl_prefix_rows(per_tok)                          # [T, E]
    counts = prior[-1] + per_tok[-1]
    tiles = jax.lax.div(counts + (rt - 1), rt)
    base = _excl_cumsum(tiles) * rt
    j = jnp.arange(k, dtype=jnp.int32)
    earlier = jnp.sum(
        (idx[:, :, None] == idx[:, None, :]) & (j[None, :] < j[:, None]),
        axis=-1, dtype=jnp.int32)                               # [T, k]
    pos = jnp.sum(jnp.where(hit, (base + prior)[:, None, :], 0),
                  axis=-1) + earlier
    inverse = jnp.zeros((NT * rt, 2), jnp.int32).at[pos.reshape(S)].set(
        jnp.stack([jnp.arange(S, dtype=jnp.int32) // k,
                   jax.lax.bitcast_convert_type(
                       weights.reshape(S).astype(jnp.float32), jnp.int32)],
                  axis=1),
        unique_indices=True, mode="promise_in_bounds")
    num_tiles = tiles.sum().astype(jnp.int32)      # >= 1: S >= 1 always
    tile = jnp.minimum(jnp.arange(NT, dtype=jnp.int32), num_tiles - 1)
    tile_expert = jnp.minimum(
        jnp.sum(jnp.cumsum(tiles)[None, :] <= tile[:, None], axis=1),
        E - 1).astype(jnp.int32)
    return (pos, inverse[:, 0],
            jax.lax.bitcast_convert_type(inverse[:, 1], jnp.float32),
            tile_expert, num_tiles)


def _one_pass_int8_kernel_path(x, weights, idx, quant: dict,
                               row_tile: Optional[int] = None,
                               interpret: bool = False):
    """Glue for the one-pass kernel (a prefill chunk's step on one device):
    the layout over the whole step, the rows gathered into it by their
    token ids, and after the kernel each token's k results gathered back
    and summed in f32 (ops/pallas/moe_one_pass.py)."""
    from llm_d_tpu.ops.pallas.moe_one_pass import one_pass_moe_int8
    T, H = x.shape
    k = idx.shape[1]
    E = quant["w_gate_q"].shape[1]
    rt = row_tile or _one_pass_row_tile(T * k, E)
    pos, tok_pad, wslot_pad, tile_expert, num_tiles = _one_pass_layout(
        idx, weights, E, rt)
    y = one_pass_moe_int8(
        x.astype(jnp.bfloat16).at[tok_pad].get(mode="promise_in_bounds"),
        wslot_pad[:, None], tile_expert, num_tiles, quant["layer"],
        quant["w_gate_q"], quant["w_gate_s"],
        quant["w_up_q"], quant["w_up_s"],
        quant["w_down_q"], quant["w_down_s"],
        row_tile=rt, interpret=interpret)
    out = jnp.sum(y.at[pos].get(mode="promise_in_bounds"), axis=1,
                  dtype=jnp.float32)
    return out.astype(x.dtype)


def _dense_int8_kernel_path(x, weights, idx, quant: dict,
                            interpret: bool = False):
    """Glue for the Pallas streaming kernel: combine-weight scatter + the
    stacked-payload call.  Factored out so CI can drive the exact wiring
    in interpret mode (the backend gate above never passes on CPU).
    ``quant`` must carry STACKED [Lm, E, ...] payloads and a "layer"
    plane index (the model's contract; see models/moe.py)."""
    from llm_d_tpu.ops.pallas.moe_int8 import dense_moe_int8
    T = x.shape[0]
    E = quant["w_gate_q"].shape[1]
    comb = _combine_matrix(T, E, idx, weights)
    out = dense_moe_int8(
        x.astype(jnp.bfloat16), comb, quant["layer"],
        quant["w_gate_q"], quant["w_gate_s"],
        quant["w_up_q"], quant["w_up_s"],
        quant["w_down_q"], quant["w_down_s"],
        interpret=interpret)
    return out.astype(x.dtype)


def _combine_matrix(T: int, E: int, idx: jax.Array,
                    weights: jax.Array) -> jax.Array:
    """[T, E] f32 combine weights (0 for unrouted pairs); duplicate
    (token, expert) routes accumulate.  The ONE implementation of the
    routing->combine contract shared by the dense XLA path, the Pallas
    int8 kernel glue, and the reference oracle."""
    return jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], idx].add(weights)


def _dequant_layer(quant: dict):
    """Materialized dequant for the non-kernel paths.  Stacked payloads
    ([Lm, E, ...] + "layer") are sliced to the layer plane first; the
    sliced int8 passes through ``optimization_barrier`` before the
    convert so XLA cannot commute ``convert(dynamic_slice(W))`` into
    ``dynamic_slice(convert(W))`` and hoist a full-stack bf16 copy out
    of the layer scan (2x the int8 model's weight footprint — the OOM
    class observed on v5e at deepseek-v3-bench scale)."""
    from llm_d_tpu.ops.quant import dequantize
    trip = []
    for name in ("w_gate", "w_up", "w_down"):
        q, s = quant[f"{name}_q"], quant[f"{name}_s"]
        if "layer" in quant:
            li = quant["layer"]
            q = jax.lax.optimization_barrier(
                jax.lax.dynamic_index_in_dim(q, li, 0, keepdims=False))
            s = jax.lax.dynamic_index_in_dim(s, li, 0, keepdims=False)
        trip.append(dequantize(q, s))
    return tuple(trip)


def _excl_cumsum(v: jax.Array) -> jax.Array:
    return jnp.concatenate([jnp.zeros(1, v.dtype), jnp.cumsum(v)[:-1]])


def _stable_argsort_bounded(
        keys: jax.Array, bound: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Stable argsort for integer keys in [0, bound) — counting sort from
    cheap primitives.

    ``jnp.argsort`` on TPU is a bitonic network: measured 4.3 ms for
    65536 int32 on v5e — at one sort per MoE layer that was ~65 ms of a
    ~440 ms prefill step.  This build (one-hot cumsum for stable ranks +
    a 1-D scatter) moves ~2*S*bound i32 bytes instead: ~0.4 ms at the
    same shape, identical output order.

    Returns (order, dest, counts): ``order`` is the argsort result,
    ``dest`` its inverse permutation (``dest[s]`` = where element s
    landed — callers need it anyway and rebuilding it is another
    scatter), ``counts`` the per-key histogram."""
    S = keys.shape[0]
    one_hot = (keys[:, None] == jnp.arange(bound, dtype=keys.dtype)[None, :])
    cum = jnp.cumsum(one_hot.astype(jnp.int32), axis=0)
    rank = cum[jnp.arange(S), keys] - 1                # stable within-key rank
    counts = cum[-1]                                   # totals: free from cum
    dest = _excl_cumsum(counts)[keys] + rank           # position in sorted order
    order = jnp.zeros((S,), jnp.int32).at[dest].set(
        jnp.arange(S, dtype=jnp.int32))
    return order, dest, counts


def _a2a_moe_chunk(
    x_c: jax.Array,        # [Tc, H] this shard's token chunk
    w_c: jax.Array,        # [Tc, k]
    idx_c: jax.Array,      # [Tc, k] global (physical) expert ids
    w_gate: Optional[jax.Array],   # [E_loc, H, I] local expert slice
    w_up: Optional[jax.Array],     #   (None when quant is given)
    w_down: Optional[jax.Array],
    ep: int,
    my_rank: jax.Array,
    ragged: bool,
    quant: Optional[dict] = None,  # local int8 payloads [Lm, E_loc, ...]
    interpret: bool = False,
    wire: str = "bf16",            # resolved collective wire mode
) -> jax.Array:            # [Tc, H] f32
    """One chunk of the sparse dispatch/compute/combine pipeline.

    Wire layout (both exchange primitives share it): the receive buffer has
    a fixed region of ``S = Tc*k`` rows per source shard; source ``s``'s
    rows land contiguously from offset ``s*S``.  ``ragged`` sends only the
    actual row counts (TPU, dynamic comm volume); the dense emulation ships
    the padded regions (CPU tests, identical math).

    With ``quant`` the per-chunk GEMM runs through the chunk-streamed
    int8 kernel on the received rows (arrival order, k=1 routing with
    validity as the combine weight) — no sort, no ragged_dot, no
    materialized dequant on the wide-EP path either.

    ``wire`` quantizes the exchanges themselves (the EQuARX trade,
    parallel/quant_collectives.py): ``int8`` ships per-row-quantized
    payloads BOTH ways with the f32 scale vector as a sibling exchange
    riding the exact same offsets as the payload (so ragged and dense
    fallback deliver identical rows); ``int8-dispatch`` quantizes only
    the outbound leg (the microbench A/B lever).  Arriving int8 rows are
    dequantized before the expert FFN — SwiGLU is nonlinear, so the row
    scale cannot ride into the combine weight the way a linear op would
    allow; the dequant is one VPU pass over rows this path materializes
    in bf16 anyway, and the wire still moved ~0.5x (dispatch) / ~0.25x
    (combine vs the old f32 return) the bytes.  Combine weights are
    applied at the origin AFTER dequantization, so wire error never
    compounds through the weighting.
    """
    Tc, H = x_c.shape
    k = idx_c.shape[1]
    E_loc = (quant["w_gate_q"].shape[1] if quant is not None
             else w_gate.shape[0])
    S = Tc * k
    quant_dispatch = wire in ("int8", "int8-dispatch")
    quant_combine = wire == "int8"

    flat = idx_c.reshape(S)
    dest = (flat // E_loc).astype(jnp.int32)
    order, _, send_counts = _stable_argsort_bounded(
        dest, ep)                                   # send order: by dest shard
    dest_s = dest[order]
    eloc_s = (flat % E_loc)[order].astype(jnp.int32)
    tok_s = order // k

    input_offsets = _excl_cumsum(send_counts)
    all_counts = jax.lax.all_gather(
        send_counts, AXIS_EP, tiled=False)          # [ep_src, ep_dst]
    recv_sizes = all_counts[:, my_rank]

    payload = x_c[tok_s]                            # [S, H]
    if quant_dispatch:
        # Per-row symmetric int8 + f32 scale vector (the KV-cache scale
        # machinery); the scale plane is a sibling exchange on the same
        # offsets, like the expert-id plane below.
        payload, payload_s = quantize_rows(payload)
    if ragged:
        output_offsets = (my_rank * S) * jnp.ones(ep, jnp.int32)
        recv_x = jax.lax.ragged_all_to_all(
            payload, jnp.zeros((ep * S, H), payload.dtype),
            input_offsets, send_counts, output_offsets, recv_sizes,
            axis_name=AXIS_EP)
        recv_e = jax.lax.ragged_all_to_all(
            eloc_s, jnp.zeros(ep * S, jnp.int32),
            input_offsets, send_counts, output_offsets, recv_sizes,
            axis_name=AXIS_EP)
        if quant_dispatch:
            recv_xs = jax.lax.ragged_all_to_all(
                payload_s, jnp.zeros(ep * S, jnp.float32),
                input_offsets, send_counts, output_offsets, recv_sizes,
                axis_name=AXIS_EP)
    else:
        within = jnp.arange(S, dtype=jnp.int32) - input_offsets[dest_s]
        pidx = dest_s * S + within
        recv_x = jax.lax.all_to_all(
            jnp.zeros((ep * S, H), payload.dtype).at[pidx].set(payload),
            AXIS_EP, split_axis=0, concat_axis=0, tiled=True)
        recv_e = jax.lax.all_to_all(
            jnp.zeros(ep * S, jnp.int32).at[pidx].set(eloc_s),
            AXIS_EP, split_axis=0, concat_axis=0, tiled=True)
        if quant_dispatch:
            recv_xs = jax.lax.all_to_all(
                jnp.zeros(ep * S, jnp.float32).at[pidx].set(payload_s),
                AXIS_EP, split_axis=0, concat_axis=0, tiled=True)
    if quant_dispatch:
        # Dequantize on arrival (see docstring); invalid region tails
        # carry zero scales and dequantize to exact zero rows.
        recv_x = dequantize_rows(recv_x, recv_xs, x_c.dtype)

    # Expert FFN over received rows (invalid region tails contribute 0).
    rows = ep * S
    region = jnp.arange(rows, dtype=jnp.int32) // S
    valid = (jnp.arange(rows, dtype=jnp.int32) % S) < recv_sizes[region]
    if quant is not None:
        # Chunk-streamed int8 kernel on the arrival-order rows: each row
        # is its own "token" (k=1) routed to its local expert, with the
        # validity mask as the combine weight — invalid tails select
        # expert 0 but multiply by 0.  Output lands in arrival order
        # directly; the un-sort scatter below disappears.
        y = _streamed_int8_kernel_path(
            recv_x, valid.astype(jnp.float32)[:, None],
            jnp.where(valid, recv_e, 0)[:, None], quant,
            out_dtype=jnp.float32, interpret=interpret)
    else:
        # Grouped GEMM (bf16): sort by expert, trash group for tails.
        e_key = jnp.where(valid, recv_e, E_loc)
        order2, _, _ = _stable_argsort_bounded(e_key, E_loc + 1)
        xs = recv_x[order2]
        counts_e = jnp.zeros(E_loc, jnp.int32).at[
            jnp.where(valid, recv_e, 0)].add(valid.astype(jnp.int32))
        group_sizes = jnp.concatenate([counts_e,
                                       (rows - counts_e.sum())[None]])
        zg = jnp.zeros((1,) + w_gate.shape[1:], w_gate.dtype)
        zd = jnp.zeros((1,) + w_down.shape[1:], w_down.dtype)
        y = _swiglu_grouped(
            xs, jnp.concatenate([w_gate, zg]), jnp.concatenate([w_up, zg]),
            jnp.concatenate([w_down, zd]), group_sizes)      # [rows, H] f32
        y = jnp.zeros((rows, H), jnp.float32).at[order2].set(
            y)                                               # arrival order

    # Combine: results travel back by the exact reverse exchange; weights
    # are applied at the origin (they never cross the wire).  The wire
    # never ships f32: int8 + scales in quantized mode, else a bf16
    # downcast — f32 accumulation (weighting + the k-sum scatter) happens
    # only AFTER arrival, so the baseline pays half the old return bytes
    # at one bf16 rounding of the expert output.
    if quant_combine:
        y_wire, y_s = quantize_rows(y)
    else:
        y_wire = y.astype(jnp.bfloat16)
    if ragged:
        # On this shard, rows to return to shard d sit at region d (d*S);
        # they must land at d's original send offsets toward us.
        excl_dst = jnp.cumsum(all_counts, axis=1) - all_counts
        ret = jax.lax.ragged_all_to_all(
            y_wire, jnp.zeros((S, H), y_wire.dtype),
            jnp.arange(ep, dtype=jnp.int32) * S, recv_sizes,
            excl_dst[:, my_rank], send_counts,
            axis_name=AXIS_EP)                               # [S, H]
        if quant_combine:
            ret_s = jax.lax.ragged_all_to_all(
                y_s, jnp.zeros(S, jnp.float32),
                jnp.arange(ep, dtype=jnp.int32) * S, recv_sizes,
                excl_dst[:, my_rank], send_counts,
                axis_name=AXIS_EP)
    else:
        ret_pad = jax.lax.all_to_all(
            y_wire, AXIS_EP, split_axis=0, concat_axis=0, tiled=True)
        ret = ret_pad[pidx]                                  # [S, H]
        if quant_combine:
            ret_s = jax.lax.all_to_all(
                y_s, AXIS_EP, split_axis=0, concat_axis=0, tiled=True
            )[pidx]
    if quant_combine:
        ret = dequantize_rows(ret, ret_s)                    # [S, H] f32
    else:
        ret = ret.astype(jnp.float32)

    contrib = ret * w_c.reshape(S)[order][:, None]
    return jnp.zeros((Tc, H), jnp.float32).at[tok_s].add(contrib)


def expert_ffn_a2a(
    x: jax.Array, weights: jax.Array, idx: jax.Array,
    w_gate: Optional[jax.Array], w_up: Optional[jax.Array],
    w_down: Optional[jax.Array],
    mesh: Mesh,
    chunk_tokens: Optional[int] = None,
    dbo_min_tokens: Optional[int] = None,
    quant: Optional[dict] = None,   # int8 payloads (w_* may be None then)
    interpret: bool = False,        # tests: run the int8 kernel interpreted
    collective_dtype: Optional[str] = None,  # None -> LLMD_COLLECTIVE_DTYPE
) -> jax.Array:
    """Sparse all-to-all EP dispatch (the DeepEP role; see module docstring).

    Tokens split over the EP shards (in_specs slice the replicated batch);
    each (token, choice) row visits only its expert's shard.  Requires
    ``T % ep == 0`` and ``E % ep == 0`` — callers fall back to ``psum``
    otherwise.  With ``quant`` the stacked int8 payloads shard over the
    expert dim and each shard's per-chunk GEMM runs the chunk-streamed
    kernel (``_a2a_moe_chunk``) — the prefill-regime win carries to
    wide EP.  ``collective_dtype`` selects the exchange wire format
    (bf16 / int8 / int8-dispatch; None resolves LLMD_COLLECTIVE_DTYPE —
    see parallel/quant_collectives.py).
    """
    wire = resolve_collective_dtype(collective_dtype)
    ep = mesh.devices.size
    E = quant["w_gate_q"].shape[1] if quant is not None else w_gate.shape[0]
    T = x.shape[0]
    assert T % ep == 0 and E % ep == 0
    T_loc = T // ep
    if chunk_tokens is None:
        chunk_tokens = _env_int("LLMD_MOE_DP_CHUNK_SIZE", 1024)
    # DBO (the reference's --enable-dbo, decode.yaml:78,98-99): when the
    # BATCH reaches the token threshold, force at least TWO dispatch chunks.
    # Chunks are data-independent, so XLA's async collectives overlap chunk
    # i+1's ragged all-to-all with chunk i's grouped GEMM — the dual-batch
    # compute/communication overlap, expressed as a schedule the compiler
    # already knows how to pipeline.  Evidence status (r4): the data
    # independence that overlap REQUIRES is asserted structurally from the
    # jaxpr (tests/test_dbo.py::test_dbo_chunks_are_data_independent —
    # chunk i+1's dispatch exchanges consume nothing derived from chunk i),
    # and chunk count + numerical parity are pinned; a timed A/B of the
    # overlap itself needs >= 2 real chips (`chip_smoke.py --chips 4` runs
    # this path on a four-chip host; it is not timed there).  The engine
    # threads the phase-specific threshold in (decode vs prefill); the env
    # vars are the standalone-op fallback.
    # None -> standalone env fallback; negative -> explicitly disabled (an
    # engine configured with enable_dbo=False must not inherit env state).
    if dbo_min_tokens is None \
            and os.environ.get("LLMD_MOE_DBO", "0") == "1":
        dbo_min_tokens = _env_int("LLMD_DBO_TOKEN_THRESHOLD", 32)
    if dbo_min_tokens is not None and dbo_min_tokens >= 0 \
            and T >= max(dbo_min_tokens, 2 * ep) and T_loc >= 2:
        chunk_tokens = min(chunk_tokens, T_loc // 2)
    chunk_tokens = max(1, min(chunk_tokens, T_loc))
    while T_loc % chunk_tokens:
        chunk_tokens -= 1
    n_chunks = T_loc // chunk_tokens
    ragged = jax.default_backend() == "tpu"
    sizes = [mesh.shape[a] for a in AXIS_EP]

    qkeys = ("w_gate_q", "w_gate_s", "w_up_q", "w_up_s",
             "w_down_q", "w_down_s")

    def shard_body(x, weights, idx, layer, *wargs):
        ep_rank = jnp.int32(0)
        for a, s in zip(AXIS_EP, sizes):
            ep_rank = ep_rank * s + jax.lax.axis_index(a)
        if quant is not None:
            w_gate = w_up = w_down = None
            q_loc = dict(zip(qkeys, wargs), layer=layer)
        else:
            w_gate, w_up, w_down = wargs
            q_loc = None
        outs = []
        for ci in range(n_chunks):
            sl = slice(ci * chunk_tokens, (ci + 1) * chunk_tokens)
            outs.append(_a2a_moe_chunk(
                x[sl], weights[sl], idx[sl], w_gate, w_up, w_down,
                ep, ep_rank, ragged, quant=q_loc, interpret=interpret,
                wire=wire))
        out = jnp.concatenate(outs) if n_chunks > 1 else outs[0]
        # Every shard needs the full hidden state back (attention and the
        # residual stream are replicated in-engine): one bf16 all-gather —
        # half the bytes of the f32 psum combine, and the dispatch above
        # moved only routed rows instead of everything.
        return jax.lax.all_gather(
            out.astype(x.dtype), AXIS_EP, axis=0, tiled=True)

    if quant is not None:
        # Stacked payloads shard over the expert dim; the layer plane
        # index rides along replicated (it is a traced scan carry).
        wargs = tuple(quant[k] for k in qkeys)
        wspecs = (P(None, AXIS_EP),) * len(qkeys)
        layer = jnp.asarray(quant["layer"], jnp.int32)
    else:
        wargs = (w_gate, w_up, w_down)
        wspecs = (P(AXIS_EP),) * 3
        layer = jnp.int32(0)
    return jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(AXIS_EP), P(AXIS_EP), P(AXIS_EP), P()) + wspecs,
        out_specs=P(),
        check_vma=False,
    )(x, weights, idx, layer, *wargs)


def expert_ffn(
    x: jax.Array,          # [T, H]
    weights: jax.Array,    # [T, k]
    idx: jax.Array,        # [T, k]
    w_gate: Optional[jax.Array],   # [E, H, I] (None when quant is given)
    w_up: Optional[jax.Array],
    w_down: Optional[jax.Array],   # [E, I, H]
    mesh: Optional[Mesh] = None,
    dispatch: str = "auto",   # auto | a2a | psum | dense | ragged
    dbo_min_tokens: Optional[int] = None,   # DBO: force >= 2 chunks at this T
    quant: Optional[dict] = None,   # int8 payloads {w_gate_q, w_gate_s, ...}
    collective_dtype: Optional[str] = None,  # None -> LLMD_COLLECTIVE_DTYPE
    held: Optional[Tuple[int, int]] = None,  # (first id, count): the share
                                             # of the experts the weights
                                             # hold; None = all of them
    held_plane: Optional[jax.Array] = None,  # the weights are stacks over
                                             # layers: this layer's plane
) -> jax.Array:            # [T, H] in x.dtype
    """Routed-expert FFN, expert-parallel over the flattened mesh.

    ``held`` (single device, bf16 weights): ``idx`` ranges over the
    router's whole width and the weights hold experts ``first id`` ..
    ``first id + count - 1`` only, one rank's share of a wider
    expert-parallel deployment; slots routed elsewhere add nothing and
    cost no expert FLOPs (``_held_expert_ffn``).

    Single-device: dense all-experts batched GEMM below
    ``DENSE_DISPATCH_MAX_T`` tokens (decode regime — see
    ``_dense_expert_ffn``), sorted grouped GEMM above it (prefill).
    Multi-device: sparse all-to-all dispatch by default
    (``LLMD_MOE_DISPATCH=psum`` forces the oracle path; see module
    docstring).  One call serves whatever population the engine batched:
    prefill-chunk AND decode/verify tokens together, so each layer's
    expert weights stream once for both (the thresholds see the combined T).

    ``quant`` carries int8 expert payloads END TO END: on the TPU
    single-device path they reach the Pallas kernel family (dense
    streaming / fused-routing routed / one-pass) WITHOUT a
    materialized dequant (XLA cannot fuse ``convert(int8)`` into a dot
    operand, and the int8+bf16 round trip costs ~2.5x the quantized
    bytes — see ops/pallas/moe_int8.py), and on the TPU a2a mesh path
    they shard over the expert dim and feed the chunk-streamed kernel
    per dispatch chunk; every other path dequantizes here, which is
    numerically identical to dequantizing in the model.
    """
    if held is not None:
        if quant is not None or not (mesh is None or mesh.devices.size == 1):
            raise ValueError(
                "a share of the experts is served on one device from bf16 "
                "weights: the int8 kernels and the mesh paths assume that "
                "every expert is held")
        return _held_expert_ffn(x, weights, idx, w_gate, w_up, w_down,
                                held[0], held_plane).astype(x.dtype)
    if mesh is None or mesh.devices.size == 1:
        if dispatch == "auto":
            dispatch = os.environ.get("LLMD_MOE_DISPATCH", "auto")
        if quant is not None and int8_kernels_serve(dispatch):
            # The three int8 kernels, by the step's token count (the
            # comment at DENSE_INT8_MAX_T says why each boundary); an
            # EXPLICIT dispatch gets the dequantized paths below, the
            # kernels' reference.
            path = {"dense": _dense_int8_kernel_path,
                    "routed": _routed_int8_kernel_path,
                    "one_pass": _one_pass_int8_kernel_path}
            return path[int8_kernel(x.shape[0])](x, weights, idx, quant)
        if dispatch == "auto":
            dispatch = ("dense" if x.shape[0] <= DENSE_DISPATCH_MAX_T
                        else "ragged")
        if quant is not None:
            w_gate, w_up, w_down = _dequant_layer(quant)
        if dispatch == "dense":
            out = _dense_expert_ffn(x, weights, idx, w_gate, w_up, w_down)
        else:
            out = _local_expert_ffn(
                x, weights, idx, w_gate, w_up, w_down, jnp.int32(0))
        return out.astype(x.dtype)
    E = (quant["w_gate_q"].shape[1] if quant is not None
         else w_gate.shape[0])
    ep = mesh.devices.size
    E_loc = E // ep
    if dispatch == "auto":
        dispatch = os.environ.get("LLMD_MOE_DISPATCH", "auto")
    if dispatch in ("dense", "ragged"):
        # Single-device-only modes must not silently run the psum oracle.
        raise ValueError(
            f"dispatch={dispatch!r} is single-device only; use 'a2a' or "
            f"'psum' on a {ep}-device mesh")
    if dispatch == "auto":
        dispatch = "a2a" if (x.shape[0] % ep == 0 and E % ep == 0) else "psum"
    if quant is not None and not (dispatch == "a2a"
                                  and jax.default_backend() == "tpu"):
        # Only the TPU a2a path consumes int8 payloads directly (the
        # per-chunk streamed kernel); everything else dequantizes here.
        w_gate, w_up, w_down = _dequant_layer(quant)
        quant = None
    if dispatch == "a2a":
        return expert_ffn_a2a(x, weights, idx, w_gate, w_up, w_down, mesh,
                              dbo_min_tokens=dbo_min_tokens, quant=quant,
                              collective_dtype=collective_dtype)

    sizes = [mesh.shape[a] for a in AXIS_EP]
    # The psum-oracle allreduce rides the same wire knob: int8 mode swaps
    # the full-activation f32 psum for the EQuARX-style quantized
    # allreduce (reduce-scatter + all-gather, both legs int8 + per-row
    # scales — parallel/quant_collectives.py).  "int8-dispatch" has no
    # meaning for a reduction and keeps the exact psum.
    psum_wire = resolve_collective_dtype(collective_dtype)

    def shard_body(x, weights, idx, w_gate, w_up, w_down):
        ep_rank = jnp.int32(0)
        for a, s in zip(AXIS_EP, sizes):
            ep_rank = ep_rank * s + jax.lax.axis_index(a)
        out = _local_expert_ffn(
            x, weights, idx, w_gate, w_up, w_down, ep_rank * E_loc)
        if psum_wire == "int8":
            return quantized_psum(out, AXIS_EP, ep)
        return jax.lax.psum(out, AXIS_EP)

    out = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), P(), P(), P(AXIS_EP), P(AXIS_EP), P(AXIS_EP)),
        out_specs=P(),
        check_vma=False,
    )(x, weights, idx, w_gate, w_up, w_down)
    return out.astype(x.dtype)


def experts_touched(idx: jax.Array, real: jax.Array,
                    num_experts: int) -> jax.Array:
    """How many DISTINCT experts the rows ``real`` [T] (bool: not padding
    of the token bucket) select in ``idx`` [T, k] (logical ids): what a
    stream of only the touched experts would read, an int32 scalar.  A
    compare against every expert id and one reduction, no scatter."""
    hit = (idx[:, :, None] == jnp.arange(num_experts, dtype=idx.dtype)) \
        & real[:, None, None]
    return jnp.sum(jnp.any(hit, axis=(0, 1)), dtype=jnp.int32)


def to_physical_experts(
    idx: jax.Array,            # [T, k] logical expert ids
    replica_table: jax.Array,  # [E, max_r] physical slots per logical expert
    num_replicas: jax.Array,   # [E]
    phase=0,                   # scalar round-robin offset (per layer)
) -> jax.Array:                # [T, k] physical expert ids
    """Map routed logical experts to EPLB physical replicas.

    Replica choice is round-robin over the (token, slot) index — load spreads
    across a hot expert's replicas without any cross-token coordination (the
    dispatch stays embarrassingly parallel).  ``phase`` offsets the
    round-robin per layer: with per-layer plans the replica counts differ
    between layers, and an unphased walk would hand every layer's replica 0
    the same leading tokens — the phase decorrelates that without touching
    the token->expert routing (replicas hold identical weights, so the
    choice is output-invariant).  Used with
    ``parallel.eplb.plan_placement`` + ``gather_physical``.
    """
    T, k = idx.shape
    slot = jnp.arange(T * k, dtype=jnp.int32).reshape(T, k) + phase
    r = slot % num_replicas[idx]
    return replica_table[idx, r]


def moe_ffn_reference(
    x: jax.Array,
    router_w: jax.Array,   # [H, E]
    w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
    config: ModelConfig,
    e_bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Dense-dispatch oracle: every expert computed for every token, combined
    with the routing weights.  O(T*E) FLOPs — tests only."""
    weights, idx = route(
        jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32)), config,
        e_bias=e_bias)
    T, k = idx.shape
    E = w_gate.shape[0]
    comb = _combine_matrix(T, E, idx, weights)
    xf = x.astype(jnp.float32)
    h = jnp.einsum("th,ehi->tei", xf, w_gate.astype(jnp.float32))
    u = jnp.einsum("th,ehi->tei", xf, w_up.astype(jnp.float32))
    y = jnp.einsum("tei,eih->teh", jax.nn.silu(h) * u,
                   w_down.astype(jnp.float32))
    return jnp.einsum("te,teh->th", comb, y).astype(x.dtype)
