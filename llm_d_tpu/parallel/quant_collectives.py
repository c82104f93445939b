"""EQuARX-style quantized collectives for the wide-EP / TP path.

The expert weights are int8, but the *interconnect* moved full-width
activations: the EP dispatch shipped bf16 rows and the combine return
shipped f32 rows — 2-4x the ICI bytes the payload needs.
EQuARX (PAPERS.md) shows block-scaled int8 AllReduce at negligible
quality cost; this module is that trade expressed over JAX collectives:

  - :func:`quantize_rows` / :func:`dequantize_rows` — the per-row
    symmetric f32-scale wire format every quantized collective ships.
    The scale plane rides the SAME collective primitive as the payload
    (a sibling exchange), so ragged and dense fallbacks stay byte-wise
    identical in what they deliver per row.
  - :func:`quantized_psum` — an all-reduce with int8 wire bytes: the
    reduce-scatter half ships per-row-quantized chunks via
    ``all_to_all``, partial sums accumulate in f32 on the owning shard,
    and the all-gather half re-quantizes the reduced chunks.  Applied
    to the MoE psum-oracle dispatch mode and usable for any manual
    TP-style reduction (works over a single axis name or the flattened
    EP tuple).
  - byte accounting (:func:`a2a_row_bytes`,
    :func:`ep_a2a_bytes_per_token`) — the ONE place wire bytes per
    (token, choice) row are computed: the engine's
    ``llmd_tpu:collective_bytes_total`` accounting and the tests of the
    wire formats read it.

Mode selection rides ``LLMD_COLLECTIVE_DTYPE`` (``auto``/``bf16``/
``int8``): ``auto`` resolves to int8 on TPU — gated by the per-collective
accuracy harness (``ops.collective_accuracy``, asserted on real routed
traces in ``tests/test_collective_quant.py`` exactly like the MLA
absorption harness) — and to bf16 everywhere else, so CPU tests and
oracles default to the exact wire.  ``int8-dispatch`` (int8 dispatch,
bf16 combine) exists as a function-level A/B lever for the microbench;
it is deliberately not a valid env value.

Quantization error contract: one symmetric f32 scale per row bounds the
per-element error at ``amax/254`` of that row — dispatch error enters
BEFORE the expert FFN (amplified by the SwiGLU curvature), combine error
AFTER it (averaged by the combine weights), so the harness bounds the
two separately at 2% rel-RMS.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from llm_d_tpu.ops.quant import dequantize
from llm_d_tpu.utils.config import env_choice

# Engine/env-facing knob values (``auto`` follows the backend: int8 on
# TPU under the harness gate, bf16 elsewhere).
COLLECTIVE_DTYPES = ("auto", "bf16", "int8")
# Resolved wire modes (function-level; "int8-dispatch" is the
# dispatch-only A/B lever the microbench sweeps).
A2A_WIRE_MODES = ("bf16", "int8", "int8-dispatch")

# Every dispatched (token, choice) row also ships its local expert id
# (int32) — counted so the byte accounting matches the wire exactly.
DISPATCH_INDEX_BYTES = 4
# One symmetric f32 scale per quantized row (the sibling scale plane).
ROW_SCALE_BYTES = 4


def resolve_collective_dtype(explicit: Optional[str] = None,
                             backend: Optional[str] = None) -> str:
    """Resolve the MoE-collective wire mode to ``bf16``/``int8``(+\\
    ``int8-dispatch``).

    ``explicit`` (an engine/bench argument) wins over the env knob; an
    unknown explicit value is a programmer error and raises.  The env
    knob degrades to ``auto`` on invalid values (``env_choice``).
    ``auto`` -> int8 on TPU (the harness-gated default: the 2% rel-RMS
    per-collective bounds are asserted in CI on real routed traces),
    bf16 elsewhere (CPU tests and oracles keep the exact wire unless a
    test opts in)."""
    if explicit is not None:
        if explicit not in COLLECTIVE_DTYPES + ("int8-dispatch",):
            raise ValueError(
                f"collective_dtype={explicit!r}: expected one of "
                f"{COLLECTIVE_DTYPES + ('int8-dispatch',)}")
        mode = explicit
    else:
        mode = env_choice("LLMD_COLLECTIVE_DTYPE", "auto",
                          COLLECTIVE_DTYPES)
    if mode == "auto":
        backend = backend if backend is not None else jax.default_backend()
        mode = "int8" if backend == "tpu" else "bf16"
    return mode


def quantize_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``[..., N, H]`` rows -> (int8 payload, f32 scales ``[..., N]``).

    Symmetric per-row quantization (one scale covers the whole row), the
    scales a 1-D vector so they ride the same exchange primitives as the
    1-D index plane."""
    f32 = x.astype(jnp.float32)
    scales = jnp.maximum(jnp.max(jnp.abs(f32), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(f32 / scales[..., None]), -127, 127)
    return q.astype(jnp.int8), scales


def dequantize_rows(q: jax.Array, scales: jax.Array,
                    dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_rows` (scales ``[..., N]``)."""
    return dequantize(q, scales[..., None], dtype)


def quantized_psum(x: jax.Array, axis_name, num_shards: int,
                   out_dtype=None) -> jax.Array:
    """All-reduce ``x`` ``[T, H]`` over ``axis_name`` with int8 wire bytes.

    The EQuARX decomposition over JAX collectives — both wire phases ship
    int8 rows + f32 row scales instead of full-width activations:

      1. reduce-scatter phase: every shard quantizes its T rows per-row
         and an ``all_to_all`` delivers chunk ``i`` (T/num_shards rows)
         of every source to shard ``i``; the owning shard dequantizes
         and accumulates the partial sums in f32.
      2. all-gather phase: the reduced chunk is re-quantized and an
         ``all_gather`` of the int8 rows + scales rebuilds the full
         result on every shard.

    Wire bytes per shard ~= ``2 * (n-1)/n * T * (H + 4)`` vs
    ``2 * (n-1)/n * T * 4H`` for the f32 psum — a ~4x reduction.  Works
    over a single axis name or an axis tuple (the flattened EP axes),
    on CPU and TPU alike (``all_to_all``/``all_gather`` lower on both,
    so the fallback numerics ARE the TPU numerics).  Error: two
    quantization points, each bounded at amax/254 per row."""
    T, H = x.shape
    xf = x.astype(jnp.float32)
    if T % num_shards:
        # Divisibility gate for the chunked exchange: pad rows are exact
        # zeros (they quantize to zero codes) and are sliced off below.
        xf = jnp.pad(xf, ((0, -T % num_shards), (0, 0)))
    q, s = quantize_rows(xf)
    rq = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)
    rs = jax.lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)
    part = dequantize_rows(rq, rs).reshape(
        num_shards, -1, H).sum(axis=0)                 # [T'/n, H] f32
    gq, gs = quantize_rows(part)
    fq = jax.lax.all_gather(gq, axis_name, axis=0, tiled=True)
    fs = jax.lax.all_gather(gs, axis_name, axis=0, tiled=True)
    out = dequantize_rows(fq, fs)[:T]
    return out.astype(out_dtype or x.dtype)


def a2a_row_bytes(h: int, mode: str) -> Dict[str, int]:
    """Wire bytes ONE dispatched (token, choice) row costs, by phase.

    ``mode`` is a resolved wire mode, plus ``"f32-combine"`` — the
    pre-round-10 accounting (bf16 dispatch, f32 combine return) kept as
    the baseline the acceptance ratio is quoted against."""
    if mode == "int8":
        d, c = h + ROW_SCALE_BYTES, h + ROW_SCALE_BYTES
    elif mode == "int8-dispatch":
        d, c = h + ROW_SCALE_BYTES, 2 * h
    elif mode == "bf16":
        d, c = 2 * h, 2 * h
    elif mode == "f32-combine":
        d, c = 2 * h, 4 * h
    else:
        raise ValueError(f"unknown wire mode {mode!r}")
    return {"dispatch": d + DISPATCH_INDEX_BYTES, "combine": c}


def ep_a2a_bytes_per_token(h: int, k: int, mode: str,
                           layers: int = 1) -> int:
    """EP dispatch+combine wire bytes one token costs across ``layers``
    MoE layers (each of its ``k`` routed copies crosses twice)."""
    row = a2a_row_bytes(h, mode)
    return k * (row["dispatch"] + row["combine"]) * layers


def psum_bytes_per_token(h: int, mode: str) -> int:
    """Wire bytes one token's row costs in the psum-oracle allreduce
    (per MoE layer, per shard, ring-factor ``(n-1)/n ~= 1`` folded in):
    the quantized allreduce ships int8 rows + f32 scales on both the
    reduce-scatter and all-gather legs; the exact psum all-reduces the
    f32 partial output.  Independent of ``k`` — the psum path moves the
    full activation regardless of routing."""
    if mode == "int8":
        return 2 * (h + ROW_SCALE_BYTES)
    if mode in ("bf16", "int8-dispatch"):
        return 2 * 4 * h            # f32 allreduce, both ring passes
    raise ValueError(f"unknown wire mode {mode!r}")
