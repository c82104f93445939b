"""The work a linear-attention layer's two state kernels cannot avoid, for
their shares of a peak of the chip (``readers/lin_roofline.py``).

Counted from what a step asks of the state pool (the program's
``ssm_decode_rows``, ``ssm_prefill_rows``, ``ssm_prefill_tokens`` on its
``llmd.dispatch`` annotations: rows through the state pool, whatever the
update) and the configuration's published geometry.  Only necessary work:
real rows and real tokens, no padded row of a sequence bucket, no dead piece
of the scan's list, no padding of a piece to its tokens, and of the chunked
form only what the recurrence itself needs.  Padding then LOWERS a share,
and nothing counted here can push one past 100.
"""

from __future__ import annotations

from typing import Any, Dict

import modelcfg

STATE_ITEMSIZE = 4      # the pool's one dtype: float32
ACT_ITEMSIZE = 2        # bf16 activations


def geometry(conf: Dict[str, Any]):
    """(LINEAR layers, heads, key size, value size) of a configuration."""
    f = modelcfg.model_config_fields(conf)
    return (list(f["layer_types"]).count("linear_attention"),
            f["lin_num_heads"], f["lin_key_dim"], f["lin_value_dim"])


def state_bytes(conf: Dict[str, Any]) -> int:
    """One slot's recurrent state in one layer."""
    _, H, K, V = geometry(conf)
    return H * K * V * STATE_ITEMSIZE


def decode_state_bytes(conf: Dict[str, Any], rows: int) -> float:
    """HBM bytes the one-token update must move for ``rows`` rows in every
    LINEAR layer: each row's state read and written once (the token's own
    q, k, v, g and o are a thousand times smaller, and its convolution
    tail is XLA's, outside the kernel: both left out, which can only lower
    the share)."""
    return float(rows) * geometry(conf)[0] * 2 * state_bytes(conf)


def scan_flops(conf: Dict[str, Any], tokens: int) -> float:
    """Floating-point operations the recurrence needs for ``tokens`` real
    tokens in every LINEAR layer, whatever form computes it: per token and
    head the state read under the key (S^T k: 2 K V), the rank-one write
    (2 K V) and the read under the query (S^T q: 2 K V).  The chunked
    form's triangular solve and its (query, key) products inside a piece
    are its own choice and not counted."""
    L, H, K, V = geometry(conf)
    return float(tokens) * L * H * 6 * K * V


def scan_bytes(conf: Dict[str, Any], rows: int, tokens: int) -> float:
    """HBM bytes the chunked form must move in every LINEAR layer: each
    row's state written once (its read is not counted: a chunk from
    position 0 needs none), each token's q, k, g and v read and its o
    written, in the activations' dtype."""
    L, H, K, V = geometry(conf)
    return L * (float(rows) * state_bytes(conf)
                + float(tokens) * H * (3 * K + 2 * V) * ACT_ITEMSIZE)
