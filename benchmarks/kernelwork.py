"""The work an attention kernel cannot avoid, for its share of a peak.

Counted from what a step asks of the KV cache (the program's
``kv_read_tokens``: per row, attention layer and query, the keys the layer's
window lets the query see) and the configuration's published head geometry.
Only necessary work: visible keys, no padding of a kernel's grid, no page
rounding.  Padding and dead reads then LOWER a share, and nothing counted
here can push one past 100.
"""

from __future__ import annotations

from typing import Any, Dict


def kv_row_bytes(conf: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes of one token's keys and values in one layer's cache (grouped-
    query attention: KV heads x head size, keys and values)."""
    return 2 * conf["num_key_value_heads"] * conf["head_dim"] * itemsize


def decode_read_bytes(conf: Dict[str, Any], kv_read_tokens: int) -> float:
    """HBM bytes a decode step's attention must read: every visible key and
    value once (queries, outputs and the new row are thousands of times
    smaller and left out, which can only lower the share)."""
    return float(kv_read_tokens) * kv_row_bytes(conf)


def prefill_flops(conf: Dict[str, Any], kv_read_tokens: int) -> float:
    """Floating-point operations of the visible (query, key) pairs: q.k and
    p.v, a multiply and an add each, over every query head."""
    return 4.0 * conf["num_attention_heads"] * conf["head_dim"] \
        * float(kv_read_tokens)
