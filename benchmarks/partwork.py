"""The work a PART of the step program cannot avoid, for its share of a
peak of the chip (``readers/part_roofline.py``).

Counted from what the program says its steps asked of the part (the counts
on its ``llmd.dispatch`` and ``llmd.post`` annotations, engine/step_clock.py)
and the configuration's published geometry, as ``kernelwork.py`` and
``ssmwork.py`` count theirs.  The same whatever kernel serves the part, and
only necessary work: the experts a step's real rows touch (not every expert
held), real (token, expert) pairs (no padding of a tile), visible keys (no
page rounding, no lane padding of the latent row).  Padding and dead reads
then LOWER a share, and nothing counted here can push one past 100.

Each function returns the LEAST seconds the chip could take, given
``counts`` (sums over the slice's annotations) and ``peaks`` (one entry of
peaks.json).
"""

from __future__ import annotations

from typing import Any, Dict

import modelcfg

# The counts each part's work is made of (``part_roofline.annotation_counts``).
COUNTS = {"experts": ("moe_experts_touched", "moe_pairs"),
          "mla_decode": ("decode_kv_read_tokens",),
          "mla_prefill": ("prefill_kv_read_tokens",)}
EXPERT_WEIGHT_ITEMSIZE = 1      # int8 experts (--quantization int8)
SCALE_ITEMSIZE = 4              # one float32 scale an output column
CACHE_ITEMSIZE = 2              # the paged cache's one dtype: bf16


def expert_bytes(conf: Dict[str, Any]) -> int:
    """One routed expert as served: the gate, up and down matrices in int8
    and their per-output-column scales."""
    f = modelcfg.model_config_fields(conf)
    hidden, width = f["hidden_size"], f["moe_intermediate_size"]
    return (3 * hidden * width * EXPERT_WEIGHT_ITEMSIZE
            + (2 * width + hidden) * SCALE_ITEMSIZE)


def experts(conf: Dict[str, Any], counts: Dict[str, int],
            peaks: Dict[str, float]) -> float:
    """The routed experts: the larger of streaming every TOUCHED expert's
    weights once from HBM (``moe_experts_touched``: distinct experts the
    real rows select, summed over layers and steps) and the dots of the real
    (token, expert) pairs (``moe_pairs``; three matrices, a multiply and an
    add each) at the bf16 peak, the dtype the kernels' dots run in (int8
    weights are widened in VMEM)."""
    f = modelcfg.model_config_fields(conf)
    stream = counts["moe_experts_touched"] * expert_bytes(conf) \
        / peaks["hbm_bytes_per_s"]
    dots = counts["moe_pairs"] * 6.0 * f["hidden_size"] \
        * f["moe_intermediate_size"] / peaks["bf16_flops"]
    return max(stream, dots)


def _latent_row(conf: Dict[str, Any]) -> int:
    f = modelcfg.model_config_fields(conf)
    return f["kv_lora_rank"] + f["qk_rope_head_dim"]


def mla_decode(conf: Dict[str, Any], counts: Dict[str, int],
               peaks: Dict[str, float]) -> float:
    """Latent attention of the pure-decode steps: every visible key's latent
    row (``kv_lora_rank + qk_rope_head_dim`` values; the lane padding of the
    cache row is the program's, not necessary) read once from HBM."""
    return counts["decode_kv_read_tokens"] * _latent_row(conf) \
        * CACHE_ITEMSIZE / peaks["hbm_bytes_per_s"]


def mla_prefill(conf: Dict[str, Any], counts: Dict[str, int],
                peaks: Dict[str, float]) -> float:
    """Latent attention of the steps with prefill tokens: per visible
    (query, key) pair and head the score over the whole latent row and the
    value over its ``kv_lora_rank`` columns, a multiply and an add each."""
    f = modelcfg.model_config_fields(conf)
    return counts["prefill_kv_read_tokens"] * f["num_heads"] * (
        2.0 * _latent_row(conf) + 2.0 * f["kv_lora_rank"]) \
        / peaks["bf16_flops"]
