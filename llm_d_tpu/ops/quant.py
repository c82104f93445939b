"""Int8 weight quantization for MoE experts (the DeepGEMM role).

The reference runs DeepSeek's routed experts through FP8 grouped GEMMs
(``VLLM_USE_DEEP_GEMM=1``, decode.yaml:129-130; DeepGEMM pinned at
Dockerfile.cuda:53-54).  TPU translation: symmetric int8 weight-only
quantization with per-(expert, output-column) scales — expert weights are
the dominant HBM resident at wide-EP scale, and halving them doubles the
experts (or batch) a chip holds.  The grouped GEMM itself stays
``lax.ragged_dot`` in bf16 with the dequant fused into the operand read by
XLA; activations stay bf16 (weight-only keeps parity within quantization
noise, no calibration pass needed).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

# Keys holding expert-major arrays [L, E, ...] in moe_layers (quantized
# variants carry _q int8 payloads and _s scales).
EXPERT_WEIGHT_KEYS = ("w_gate", "w_up", "w_down")


def quantize_int8(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 over the contraction dim of ``[..., K, N]`` weights.

    Scales are per output column (finest grain that still lets the dequant
    fuse as a broadcast multiply): ``scale [..., 1, N]``."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


# Jitted: eagerly, the quantize chain materializes several full-size f32
# temporaries (4 GB each at bench scale) that OOM the chip; under jit the
# elementwise chain fuses into the int8 write.  The donating variant also
# retires the bf16 original at entry — only safe when the caller owns the
# buffers (engine-initialized params, not caller-provided ones).
_quantize_int8_jit = jax.jit(quantize_int8)
_quantize_int8_donate = jax.jit(quantize_int8, donate_argnums=(0,))


def dequantize(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def quantize_moe_experts(params: Dict[str, Any],
                         donate: bool = False) -> Dict[str, Any]:
    """Replace moe_layers expert weights with int8 payload + scale pairs.

    ``w_gate [L,E,H,I]`` -> ``w_gate_q`` int8 + ``w_gate_s`` f32 [L,E,1,I].
    The EP sharding rules match the ``w_gate``/``w_up``/``w_down`` prefixes,
    so the quantized tensors shard over experts exactly like the originals.
    ``donate=True`` frees each bf16 original as it converts (halves peak
    HBM) — callers must own the arrays (donated buffers are invalidated).
    """
    quantize = _quantize_int8_donate if donate else _quantize_int8_jit
    ml = dict(params["moe_layers"])
    for name in EXPERT_WEIGHT_KEYS:
        if name not in ml:
            continue
        q, s = quantize(ml.pop(name))
        ml[f"{name}_q"] = q
        ml[f"{name}_s"] = s
    out = dict(params)
    out["moe_layers"] = ml
    return out


