"""The parts of a step program: one name for every operation on the device.

Every operation the models' ``forward``, the output head and the sampling
body trace lies inside ``part(<name>)``, a ``jax.named_scope("llmd.<name>")``.
A scope is metadata of the lowered program (the operation's ``op_name``, which
the profiler's trace carries as ``tf_op``): it costs the device nothing and
there is no option to turn it off.  ``benchmarks/readers/device_parts.py``
groups the device time of a traced slice by the INNERMOST scope of each
operation, whatever kernel or fusion implements the part.

  embed         the token embedding (and the ids fed on the device)
  tiles         what is derived once a step program: the packed batch taken
                apart, block visibility, the query tile list, the scan's
                pieces
  attn.proj     q / k / v / o projections, rotary embedding, the attention
                block's own norms and gate, the norm that feeds it
  attn.decode   the cache write and the attention of a program whose query
  attn.prefill  width is 1 (pure decode), or wider (prefill, mixed): chosen
                at trace time, kernel and XLA glue or the chunked XLA path
  attn.index    a layer that selects its keys (ops/sparse_mla.py): the
                indexer's projections, the index key's norm and cache write,
                the scores against the cached index keys and the top-k
  attn.cross    one query a row over ANOTHER layer's cache plane (a stack
                whose last layers keep no keys of their own): the kernel or
                the chunked XLA path, nothing written
  gmu           a gated memory unit: its two projections and the gate on
                the memory an earlier layer's mixer left
  router        the router's dot, the top-k, the count of experts touched,
                and the norm that feeds the MoE block
  experts       ``ops.moe.expert_ffn``: every kernel of the family and its
                sort / gather / combine glue
  shared        shared experts
  mlp           the dense MLP with its norm, the residual additions, the
                MoE block's output norm
  ssm.proj      the mixer's in_proj, out_proj and gated norm
  ssm.state     the causal convolution with its tails, the state update or
                the chunked scan and the gathers around it
  lin.proj      a linear-attention layer's projections (q | k | v, decay,
                write strength, output gate), its output norm and o_proj
  lin.state     its causal convolution with the tails, the delta-rule
                update or the chunked form and the gathers around it
  scan          what ``lax.scan`` itself does around a layer body: the loop,
                the slices of the stacked weights that XLA materialises, the
                stacking of per-layer outputs (the body's own operations lie
                in their parts: the innermost scope counts)
  head          the final norm, the gather of the sampled rows, the logits
  sample        sampling, log-softmax, top log-probabilities, ``reveal``, the
                key's split
"""

from __future__ import annotations

import jax

PREFIX = "llmd."
PARTS = ("embed", "tiles", "attn.proj", "attn.decode", "attn.prefill",
         "attn.index", "attn.cross", "gmu", "router", "experts", "shared",
         "mlp", "ssm.proj", "ssm.state", "lin.proj", "lin.state", "scan", "head",
         "sample")


def part(name: str):
    """The scope of part ``name`` (one of ``PARTS``)."""
    if name not in PARTS:
        raise ValueError(f"unknown part {name!r}; one of {PARTS}")
    return jax.named_scope(PREFIX + name)


def attn_part(batch) -> str:
    """The attention part of the program that takes ``batch``: its query
    width is static under jit, and 1 holds exactly for pure-decode
    programs."""
    qtok_idx = batch.get("qtok_idx")
    decode = qtok_idx is not None and qtok_idx.shape[-1] == 1
    return "attn.decode" if decode else "attn.prefill"
