"""The work the state-space mixer's two device computations cannot avoid,
for their shares of a peak of the chip.

Counted from what a step asks of the state pool (the program's
``ssm_decode_rows``, ``ssm_prefill_rows``, ``ssm_prefill_tokens`` on its
``llmd.dispatch`` annotations) and the configuration's published geometry
(``mamba_*`` keys).  Only necessary work: real rows and real tokens, no
padded row of a sequence bucket, no dead piece of the scan's list, no
padding of a piece to its 128 tokens.  Padding then LOWERS a share, and
nothing counted here can push one past 100.
"""

from __future__ import annotations

from typing import Any, Dict

STATE_ITEMSIZE = 4      # the pool's one dtype: float32 (models/ssm.py)
ACT_ITEMSIZE = 2        # bf16 activations and convolution tails


def state_bytes(conf: Dict[str, Any]) -> int:
    """One slot's recurrent state in one layer."""
    return (conf["mamba_n_heads"] * conf["mamba_d_head"]
            * conf["mamba_d_state"] * STATE_ITEMSIZE)


def conv_tail_bytes(conf: Dict[str, Any]) -> int:
    """One slot's convolution tail in one layer: the last kernel - 1 inputs
    of x, B and C."""
    channels = conf["mamba_d_ssm"] \
        + 2 * conf["mamba_n_groups"] * conf["mamba_d_state"]
    return (conf["mamba_d_conv"] - 1) * channels * ACT_ITEMSIZE


def decode_state_bytes(conf: Dict[str, Any], rows: int) -> float:
    """HBM bytes the one-token update must move for ``rows`` rows in every
    layer: each row's state read and written once, and its convolution tail
    (the token's own x, B, C, dt and y are a thousand times smaller and
    left out, which can only lower the share)."""
    return float(rows) * conf["num_hidden_layers"] * (
        2 * state_bytes(conf) + conv_tail_bytes(conf))


def scan_flops(conf: Dict[str, Any], tokens: int) -> float:
    """Floating-point operations the chunked scan needs for ``tokens`` real
    tokens in every layer: per token and head the state's contribution to
    the output (C S: 2 N P) and the token's to the state (B^T x: 2 N P),
    and of the part inside a piece only the token's own (query, key) pair
    (scores 2 N a group, applied 2 P a head): a lower bound, since the
    annotations carry no chunk lengths."""
    H, P, N, G = (conf["mamba_n_heads"], conf["mamba_d_head"],
                  conf["mamba_d_state"], conf["mamba_n_groups"])
    return float(tokens) * conf["num_hidden_layers"] * (
        4 * H * N * P + 2 * G * N + 2 * H * P)


def scan_bytes(conf: Dict[str, Any], rows: int, tokens: int) -> float:
    """HBM bytes the chunked scan must move in every layer: each row's state
    written once (its read is not counted: a chunk from position 0 needs
    none), each token's x, B, C and dt read and its y written."""
    H, P, N, G = (conf["mamba_n_heads"], conf["mamba_d_head"],
                  conf["mamba_d_state"], conf["mamba_n_groups"])
    per_token = (H * P + 2 * G * N) * ACT_ITEMSIZE + H * 4 \
        + H * P * ACT_ITEMSIZE
    return conf["num_hidden_layers"] * (
        float(rows) * state_bytes(conf) + float(tokens) * per_token)
