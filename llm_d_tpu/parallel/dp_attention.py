"""SPMD data-parallel attention: per-shard paged attention under shard_map.

The wide-EP serving regime ("TP×DP in attention, EP in MoE layers";
reference: guides/wide-ep-lws/manifests/modelserver/base/decode.yaml:76,87)
needs attention to be data-parallel over the mesh's ``dp`` axis while the
MoE FFN is expert-parallel over ALL axes.  On TPU the natural expression is
ONE jitted program over the full (dp, sp, tp) mesh in which:

  - the ragged batch and the paged KV cache carry a leading [dp] dim
    sharded ``P("dp")`` — each dp shard holds its own sequences' tokens and
    KV pages (the engine's region-partitioned ``KVCacheManager`` pins every
    request's blocks to one shard, so block tables are shard-local);
  - the attention block (q/k/v/o projections + paged attention + KV
    scatter) runs under a PARTIAL-MANUAL ``jax.shard_map``: manual over
    ``dp`` (each shard sees only its [T_l] tokens and [slots_l] cache
    plane — zero cross-shard attention traffic), while ``tp`` stays an
    AUTO axis inside, so the Megatron head sharding and its collectives
    are still XLA's job;
  - everything outside attention (norms, dense MLPs, router, MoE a2a,
    sampling) stays in auto mode on the stacked arrays.

This replaces the reference's N-independent-engine-ranks DP (NCCL groups +
per-rank schedulers) with a single SPMD program whose dp axis is just
another mesh dimension — expert weights shard 1/EP over every device
(``models.moe.sharding_rules``) and per-device KV capacity scales 1/dp.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P


from llm_d_tpu.ops.attention import ATTN_BATCH_KEYS  # per-shard when stacked

AttendLocal = Callable[..., Tuple[jax.Array, Tuple[jax.Array, ...]]]


def dp_attend(
    attend_local: AttendLocal,
    mesh: Mesh,
    lp,                       # layer params (auto-sharded over tp)
    hn: jax.Array,            # [dp, T_l, D] normed hidden, P("dp")
    caches: Tuple[jax.Array, ...],   # each [dp, L, slots_l, W], P("dp")
    batch: Dict[str, jax.Array],     # stacked batch, P("dp") per leaf
    li: jax.Array,            # layer index scalar
):
    """Run ``attend_local(lp, hn_1shard, caches_1shard, abatch_1shard, li)``
    per dp shard; returns (attn_out [dp, T_l, D], new caches).

    tp remains an auto axis inside the manual region (``axis_names={"dp"}``)
    — the projections' tp sharding and collectives are unchanged.  The
    Pallas kernels cannot live in a partially-manual region (Mosaic is
    never partitioned automatically): the attention ops nest a second
    shard_map over the remaining axes around the kernel call
    (``ops.attention.manual_over_mesh``), where they see exactly the
    per-shard local shapes they already handle on a single chip.
    """
    ab = {k: batch[k] for k in ATTN_BATCH_KEYS if k in batch}
    n_cache = len(caches)

    def body(lp, hn, caches, ab, li):
        # Leading dp dim is 1 inside the manual region: squeeze in, pad out.
        a, new_caches = attend_local(
            lp, hn[0], tuple(c[0] for c in caches),
            {k: v[0] for k, v in ab.items()}, li)
        return a[None], tuple(c[None] for c in new_caches)

    dp = P("dp")
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), dp, (dp,) * n_cache, {k: dp for k in ab}, P()),
        out_specs=(dp, (dp,) * n_cache),
        axis_names={"dp"}, check_vma=False,
    )(lp, hn, caches, ab, li)
