"""Accuracy harness for the int8 EP collectives (per-collective bounds).

Quantizing the MoE exchange wire (parallel/quant_collectives.py) injects
error at TWO distinct points with different amplification paths, so each
is measured and bounded separately before ``LLMD_COLLECTIVE_DTYPE=auto``
may resolve to int8:

  1. **Dispatch** (rows quantized BEFORE the expert FFN): the per-row
     int8 error passes through three GEMMs and the SwiGLU nonlinearity —
     curvature can amplify it, and it lands in every expert output the
     row produces.
  2. **Combine** (expert outputs quantized on the return wire): the
     error enters AFTER the FFN and is only scaled by the combine
     weights (which never cross the wire — they apply at the origin
     post-dequant), so it averages across the k routed copies.

The harness measures both terms in isolation (and end-to-end) against
the bf16-dispatch / f32-combine reference on REAL routed traces — real
hidden rows and the real router's (weights, idx) harvested by replaying
a serving engine's actual token streams through the model with
``collect_moe_trace=True`` — so the bound the gate quotes is a measured
property of actual activation statistics, not of a synthetic N(0,1)
proxy.  ``tests/test_collective_quant.py`` asserts the bounds and fails
the merge gate when they drift.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from llm_d_tpu.parallel.quant_collectives import (
    dequantize_rows, quantize_rows)

# Documented (and test-gated) relative-RMS bounds for the int8 wire with
# one symmetric f32 scale per row (per-element error <= amax/254 of the
# row); both collectives land well inside these on real routed traces.
DISPATCH_REL_BOUND = 2e-2
COMBINE_REL_BOUND = 2e-2


def harvest_routed_trace(engine, token_streams: Sequence[Sequence[int]],
                         max_tokens: Optional[int] = None
                         ) -> Dict[str, np.ndarray]:
    """Real MoE dispatch operands from a serving engine's traffic.

    ``token_streams`` are the engine's ACTUAL served sequences (prompt +
    generated ids, e.g. ``req.prompt_token_ids + req.output_token_ids``
    after :meth:`EngineCore.generate`).  They replay through the model as
    one full prefill batch (reference attention, scratch bf16 cache) with
    ``collect_moe_trace=True``, capturing per MoE layer exactly what the
    EP dispatch ships: the rms-normed hidden rows and the router's
    combine weights / expert ids.

    Returns ``{"x": [Lm, T, H] f32, "weights": [Lm, T, k] f32,
    "idx": [Lm, T, k] i32}``."""
    c = engine.model_config
    bs = engine.config.block_size
    streams = [list(ts)[:c.max_model_len] for ts in token_streams if ts]
    if max_tokens is not None:
        kept, total = [], 0
        for ts in streams:
            if total >= max_tokens:
                break
            kept.append(ts[:max_tokens - total])
            total += len(kept[-1])
        streams = kept
    assert streams, "no token streams to replay"
    lens = [len(ts) for ts in streams]
    T, S, Q = sum(lens), len(streams), max(lens)
    B = max(-(-n // bs) for n in lens)

    batch = dict(
        token_ids=np.zeros(T, np.int32),
        positions=np.zeros(T, np.int32),
        token_seq_ids=np.zeros(T, np.int32),
        token_qpos=np.zeros(T, np.int32),
        slot_mapping=np.zeros(T, np.int32),
        block_tables=np.zeros((S, B), np.int32),
        seq_lens=np.asarray(lens, np.int32),
        sample_idx=np.zeros(S, np.int32),
        qtok_idx=np.full((S, Q), T, np.int32),   # T = padded-q sentinel
    )
    t, next_block = 0, 1                         # block 0 = trash block
    for s, ts in enumerate(streams):
        n = len(ts)
        pos = np.arange(n)
        blocks = np.arange(next_block, next_block + -(-n // bs))
        next_block += len(blocks)
        batch["token_ids"][t:t + n] = ts
        batch["positions"][t:t + n] = pos
        batch["token_seq_ids"][t:t + n] = s
        batch["token_qpos"][t:t + n] = pos
        batch["slot_mapping"][t:t + n] = blocks[pos // bs] * bs + pos % bs
        batch["block_tables"][s, :len(blocks)] = blocks
        batch["sample_idx"][s] = t + n - 1
        batch["qtok_idx"][s, :n] = np.arange(t, t + n)
        t += n

    from llm_d_tpu.models import moe as moe_model
    layout = moe_model.kv_cache_layout(c)
    kv = {k: jnp.zeros((c.num_layers, next_block * bs, w), jnp.bfloat16)
          for k, w in layout.items()}
    _, _, trace = moe_model.forward(
        engine.params, kv,
        {k: jnp.asarray(v) for k, v in batch.items()}, c,
        block_size=bs, attn_backend="reference", collect_moe_trace=True)
    return {
        "x": np.asarray(trace["x"], np.float32),
        "weights": np.asarray(trace["weights"], np.float32),
        "idx": np.asarray(trace["idx"], np.int32),
    }


def _rel_rms(err: np.ndarray, ref: np.ndarray) -> float:
    return float(np.sqrt(np.mean(err ** 2))
                 / max(np.sqrt(np.mean(ref ** 2)), 1e-12))


def _routed_ffn(xs: np.ndarray, e_flat: np.ndarray, w_gate: np.ndarray,
                w_up: np.ndarray, w_down: np.ndarray) -> np.ndarray:
    """f32 SwiGLU expert FFN per flat (token, choice) slot — the oracle
    the wire error is measured through (``xs`` [S, H], experts gathered
    per slot; small harness shapes only)."""
    g = np.einsum("sh,shi->si", xs, w_gate[e_flat])
    u = np.einsum("sh,shi->si", xs, w_up[e_flat])
    a = g / (1.0 + np.exp(-g)) * u                  # silu(g) * u
    return np.einsum("si,sih->sh", a, w_down[e_flat])


def collective_error_report(x: np.ndarray,          # [T, H] real rows
                            weights: np.ndarray,    # [T, k] combine weights
                            idx: np.ndarray,        # [T, k] expert ids
                            w_gate: jax.Array,      # [E, H, I]
                            w_up: jax.Array,
                            w_down: jax.Array) -> Dict:
    """Per-collective int8-vs-exact error over real routed rows.

    Reference: bf16 dispatch rows (the serve dtype), f32 expert FFN, f32
    combine return — the pre-round-10 wire.  Error is isolated per
    collective:

      - ``dispatch``:   rows int8-quantized on the outbound wire, return
                        exact (what ``int8-dispatch`` mode ships)
      - ``combine``:    rows exact, expert outputs int8-quantized on the
                        return wire
      - ``end_to_end``: both wires quantized (``int8`` mode)

    Returns nested ``max_abs`` / ``rel_rms`` dicts plus the tested
    bounds, for the docs table and the gate assertions."""
    T, k = idx.shape
    e_flat = idx.reshape(-1).astype(np.int64)
    wg = np.asarray(w_gate, np.float32)
    wu = np.asarray(w_up, np.float32)
    wd = np.asarray(w_down, np.float32)

    rows_bf = np.asarray(
        jnp.asarray(x).astype(jnp.bfloat16), np.float32)    # serve dtype
    q, s = quantize_rows(jnp.asarray(x, jnp.float32))
    rows_q8 = np.asarray(dequantize_rows(q, s))

    def combine(y_slots: np.ndarray) -> np.ndarray:          # [S, H] -> [T, H]
        return (y_slots.reshape(T, k, -1)
                * weights[..., None]).sum(axis=1)

    def quant_return(y_slots: np.ndarray) -> np.ndarray:
        yq, ys = quantize_rows(jnp.asarray(y_slots, jnp.float32))
        return np.asarray(dequantize_rows(yq, ys))

    y_ref = _routed_ffn(rows_bf[np.repeat(np.arange(T), k)], e_flat,
                        wg, wu, wd)
    y_disp = _routed_ffn(rows_q8[np.repeat(np.arange(T), k)], e_flat,
                         wg, wu, wd)
    out_ref = combine(y_ref)
    out_disp = combine(y_disp)                   # dispatch wire only
    out_comb = combine(quant_return(y_ref))      # combine wire only
    out_e2e = combine(quant_return(y_disp))      # both wires

    report = {
        "rows": int(T),
        "dispatch": {
            "max_abs": float(np.abs(out_disp - out_ref).max()),
            "rel_rms": _rel_rms(out_disp - out_ref, out_ref),
            "bound_rel_rms": DISPATCH_REL_BOUND,
        },
        "combine": {
            "max_abs": float(np.abs(out_comb - out_ref).max()),
            "rel_rms": _rel_rms(out_comb - out_ref, out_ref),
            "bound_rel_rms": COMBINE_REL_BOUND,
        },
        "end_to_end": {
            "max_abs": float(np.abs(out_e2e - out_ref).max()),
            "rel_rms": _rel_rms(out_e2e - out_ref, out_ref),
        },
    }
    report["within_bounds"] = bool(
        report["dispatch"]["rel_rms"] <= DISPATCH_REL_BOUND
        and report["combine"]["rel_rms"] <= COMBINE_REL_BOUND)
    return report


def layer_reports(trace: Dict[str, np.ndarray], params: Dict) -> List[Dict]:
    """Run :func:`collective_error_report` per MoE layer of a harvested
    trace against that layer's ACTUAL expert weights (``params`` is the
    engine's ``moe_layers`` group, stacked ``[Lm, E, ...]``; quantized
    payloads are dequantized first — the wire error is measured on the
    weights serving actually uses)."""
    if "w_gate" in params:
        wg_all, wu_all, wd_all = (params["w_gate"], params["w_up"],
                                  params["w_down"])
    else:
        from llm_d_tpu.ops.quant import dequantize
        wg_all, wu_all, wd_all = (
            dequantize(params[f"{n}_q"], params[f"{n}_s"], jnp.float32)
            for n in ("w_gate", "w_up", "w_down"))
    return [
        collective_error_report(
            trace["x"][li], trace["weights"][li], trace["idx"][li],
            wg_all[li], wu_all[li], wd_all[li])
        for li in range(trace["x"].shape[0])
    ]
