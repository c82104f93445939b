"""The work a decoder-hybrid-decoder stack's own device computations cannot
avoid, for their shares of a peak of the chip
(``readers/hybrid_roofline.py``).

Counted from what the program says its steps asked (the counts on its
``llmd.dispatch`` annotations, engine/step_clock.py: ``ssm_decode_rows``,
``ssm_prefill_rows``, ``ssm_prefill_tokens``, ``xattn_read_tokens``) and
the configuration's geometry (its ``mamba_*`` keys, ``layer_types``, the
head counts), as ``ssmwork.py`` and ``partwork.py`` count theirs.  Only
necessary work: real rows and real tokens, no padded row of a sequence
bucket, no dead piece of the scan's list, no page rounding, no zero half of
a paired query.  Padding then LOWERS a share, and nothing counted here can
push one past 100.

Each bound returns the LEAST seconds the chip could take, given ``counts``
(sums over the slice's annotations) and ``peaks`` (one entry of peaks.json).
"""

from __future__ import annotations

from typing import Any, Dict

COUNTS = {"scan": ("ssm_prefill_rows", "ssm_prefill_tokens"),
          "decode": ("ssm_decode_rows",),
          "xattn": ("xattn_read_tokens",)}
STATE_ITEMSIZE = 4      # the pool's one dtype: float32 (models/ssm.py)
ACT_ITEMSIZE = 2        # bf16 activations, convolution tails and cache rows


def mamba_layers(conf: Dict[str, Any]) -> int:
    return conf["layer_types"].count("mamba")


def state_bytes(conf: Dict[str, Any]) -> int:
    """One slot's recurrent state in one layer: [d_state, d_inner]."""
    return conf["mamba_d_state"] * conf["mamba_d_inner"] * STATE_ITEMSIZE


def conv_tail_bytes(conf: Dict[str, Any]) -> int:
    """One slot's convolution tail in one layer: the last kernel - 1 inputs
    of the d_inner channels."""
    return (conf["mamba_d_conv"] - 1) * conf["mamba_d_inner"] * ACT_ITEMSIZE


def kv_token_bytes(conf: Dict[str, Any]) -> int:
    """One token's keys and values in one plane of the paged cache."""
    head = conf["hidden_size"] // conf["num_attention_heads"]
    return 2 * conf["num_key_value_heads"] * head * ACT_ITEMSIZE


def scan_bytes(conf: Dict[str, Any], rows: int, tokens: int) -> float:
    """HBM bytes the selective scan must move in every Mamba layer: each
    row's state written once (its read is not counted: a chunk from position
    0 needs none), each token's x read and y written, its B and C and the
    bottleneck dt is made from."""
    per_token = (2 * conf["mamba_d_inner"] + 2 * conf["mamba_d_state"]
                 + conf["mamba_dt_rank"]) * ACT_ITEMSIZE
    return mamba_layers(conf) * (float(rows) * state_bytes(conf)
                                 + float(tokens) * per_token)


def scan(conf, counts, peaks) -> float:
    """The selective scan of the slice's prompt chunks.  It has no MXU
    FLOPs (the decay is by channel and state: elementwise), so the HBM bound
    is the larger of the two always."""
    return scan_bytes(conf, counts["ssm_prefill_rows"],
                      counts["ssm_prefill_tokens"]) / peaks["hbm_bytes_per_s"]


def decode_state_bytes(conf: Dict[str, Any], rows: int) -> float:
    """HBM bytes the one-token update must move for ``rows`` rows in every
    Mamba layer: each row's state read and written once, and its
    convolution tail."""
    return float(rows) * mamba_layers(conf) * (
        2 * state_bytes(conf) + conv_tail_bytes(conf))


def decode(conf, counts, peaks) -> float:
    return decode_state_bytes(conf, counts["ssm_decode_rows"]) \
        / peaks["hbm_bytes_per_s"]


def xattn(conf, counts, peaks) -> float:
    """Every one-query attention over the shared plane: each visible key's
    and value's row read once a reading layer (a query a row: no reuse)."""
    return counts["xattn_read_tokens"] * kv_token_bytes(conf) \
        / peaks["hbm_bytes_per_s"]
