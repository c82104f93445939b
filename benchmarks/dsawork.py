"""The work learned key selection and the latent attention behind it cannot
avoid, for their shares of a peak of the chip (``readers/dsa_roofline.py``).

Counted from what the program says its steps asked (the counts on its
``llmd.dispatch`` annotations, engine/step_clock.py) and the configuration's
published geometry, as ``partwork.py`` counts its parts:

  index_pairs         (query, visible key) pairs of the FULL layers: what
                      the indexer scores
  kv_selected_tokens  of those, the pairs the full layers attend to:
                      min(visible, index_topk) a query
  kv_read_tokens      the selected pairs plus the SLIDING layers' windowed
                      pairs
  kv_held_tokens      cached tokens of the stepped rows x layers

Only necessary work: real pairs at the published heads and row widths, no
padding of a query tile or of a selection to ``index_topk`` columns, no lane
padding of a cache row, no key scored twice.  A masked dense implementation
then reads LOW, and nothing counted here can push a share past 100.

Each function returns the LEAST seconds the chip could take, given
``counts`` ({"decode": sums over the slice's pure-decode dispatches,
"prefill": over those with prefill tokens}) and ``peaks`` (one entry of
peaks.json).
"""

from __future__ import annotations

from typing import Any, Dict

COUNTS = ("index_pairs", "kv_selected_tokens", "kv_read_tokens",
          "kv_held_tokens")
CACHE_ITEMSIZE = 2              # the paged cache's one dtype: bf16
SLIDING = "sliding_attention"


def layers(conf: Dict[str, Any]):
    """(full layers, sliding layers) of the configuration as run."""
    kinds = conf["layer_types"]
    return len(kinds) - kinds.count(SLIDING), kinds.count(SLIDING)


def index(conf: Dict[str, Any], counts, peaks: Dict[str, float]) -> float:
    """The indexer: a dot of ``index_head_dim`` a pair and index head at the
    MXU's bf16 peak, or reading every cached index key of the stepped rows
    once a full layer and step from HBM where that takes longer."""
    pairs = sum(c["index_pairs"] for c in counts.values())
    held = sum(c["kv_held_tokens"] for c in counts.values())
    full, sliding = layers(conf)
    flops = pairs * conf["index_n_heads"] * conf["index_head_dim"] * 2.0
    key_bytes = (held * full / (full + sliding) * conf["index_head_dim"]
                 * CACHE_ITEMSIZE)
    return max(flops / peaks["bf16_flops"],
               key_bytes / peaks["hbm_bytes_per_s"])


def _pair_work(conf: Dict[str, Any], c: Dict[str, int]):
    """(FLOP, cache bytes) of one regime's attended pairs: per pair and
    head the score over the whole latent row and the value over its
    ``kv_lora_rank`` columns, a multiply and an add each
    (``partwork.mla_prefill``); per pair the latent row read once
    (``partwork.mla_decode``).  Each layer kind at its own heads and row."""
    selected = c["kv_selected_tokens"]
    windowed = c["kv_read_tokens"] - selected
    flops = bytes_ = 0.0
    for pairs, heads, rank, rope in (
            (selected, conf["num_attention_heads"], conf["kv_lora_rank"],
             conf["qk_rope_head_dim"]),
            (windowed, conf["swa_num_attention_heads"],
             conf["swa_kv_lora_rank"], conf["swa_qk_rope_head_dim"])):
        flops += pairs * heads * (2.0 * (rank + rope) + 2.0 * rank)
        bytes_ += pairs * (rank + rope) * CACHE_ITEMSIZE
    return flops, bytes_


def sparse_attention(conf: Dict[str, Any], counts,
                     peaks: Dict[str, float]) -> float:
    """Attention over the SELECTED pairs of the full layers and the windowed
    pairs of the sliding layers: a pure-decode step is held to the larger
    of its dots and of reading each attended row once (a query a row: no
    reuse), a step with prefill tokens to its dots (a chunk's queries share
    the rows they read)."""
    dec_flops, dec_bytes = _pair_work(conf, counts["decode"])
    pre_flops, _ = _pair_work(conf, counts["prefill"])
    return (max(dec_flops / peaks["bf16_flops"],
                dec_bytes / peaks["hbm_bytes_per_s"])
            + pre_flops / peaks["bf16_flops"])
