"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

The reference has NO sequence parallelism (SURVEY.md §2.3: long context is
handled by max-model-len bounds, PD splitting and prefix caching) — this is
a capability the TPU stack adds beyond parity, and why the mesh carries a
first-class ``sp`` axis (parallel/mesh.py).

Design (the standard ring-flash scheme, TPU-idiomatic):
  - The sequence shards over ``sp``: each device holds a [T/sp] slice of
    Q, K, V.
  - sp ring steps: every device runs the flash (online-softmax) recurrence
    of its local Q against the KV chunk currently resident, then passes
    the chunk to its ring neighbor with ``lax.ppermute`` over ICI.  After
    sp steps every Q row has attended to every KV row; peak memory per
    device stays O(T/sp).
  - Causal masking uses global positions (chunk origin = source rank);
    chunks entirely in a query's future are skipped via ``lax.cond`` so
    causal prefill does ~half the FLOPs, like single-device flash.

Compute/comm overlap note: XLA schedules the ppermute of step i+1's chunk
concurrently with step i's matmuls when latency hiding is enabled (the
collective is issued before the compute that doesn't depend on it) — the
DBO role for this path.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from llm_d_tpu.parallel.mesh import AXIS_SP, AXIS_TP

NEG_INF = -1e30


def _flash_block(q, k, v, q_pos, k_pos, scale, causal, carry):
    """One online-softmax accumulation of q against a (k, v) chunk."""
    m, l, acc = carry
    Tq, H, D = q.shape
    KVH = k.shape[1]
    G = H // KVH
    qf = q.astype(jnp.float32).reshape(Tq, KVH, G, D) * scale
    s = jnp.einsum("qkgd,skd->qkgs", qf, k.astype(jnp.float32))
    if causal:
        valid = k_pos[None, :] <= q_pos[:, None]          # [Tq, Tk]
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m_new = jnp.maximum(jnp.maximum(m, jnp.max(s, axis=-1)), -1e29)
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "qkgs,skd->qkgd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def ring_attention(
    q: jax.Array,          # [T, H, D], T sharded over sp
    k: jax.Array,          # [T, KVH, D]
    v: jax.Array,
    mesh: Mesh,
    scale: Optional[float] = None,
    causal: bool = True,
    window: Optional[int] = None,
) -> jax.Array:            # [T, H, D]
    """Exact attention over a sequence sharded across the sp axis."""
    if window is not None:
        raise NotImplementedError(
            "ring_attention has no attention window: a sliding-window layer "
            "must run through ops.attention.attention_with_kv_update")
    T, H, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    sp = mesh.shape[AXIS_SP]
    if sp == 1:
        # Degenerate ring: plain flash on one shard.
        return _single_shard_attention(q, k, v, scale, causal)
    assert T % sp == 0, f"T={T} must divide over sp={sp}"
    Tl = T // sp

    def body(q_loc, k_loc, v_loc):
        rank = jax.lax.axis_index(AXIS_SP)
        q_pos = rank * Tl + jnp.arange(Tl, dtype=jnp.int32)
        q_max = q_pos[-1]
        KVH = k_loc.shape[1]
        G = q_loc.shape[1] // KVH

        init = (jnp.full((Tl, KVH, G), -1e29, jnp.float32),
                jnp.zeros((Tl, KVH, G), jnp.float32),
                jnp.zeros((Tl, KVH, G, D), jnp.float32))

        carry = init
        kv = (k_loc, v_loc)
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        for step in range(sp):
            src = (rank - step) % sp           # chunk's origin rank
            k_pos = src * Tl + jnp.arange(Tl, dtype=jnp.int32)
            k_cur, v_cur = kv
            if causal:
                # Entire chunk in this shard's future -> skip its FLOPs.
                carry = jax.lax.cond(
                    src * Tl <= q_max,
                    lambda c: _flash_block(q_loc, k_cur, v_cur, q_pos,
                                           k_pos, scale, True, c),
                    lambda c: c,
                    carry)
            else:
                carry = _flash_block(q_loc, k_cur, v_cur, q_pos, k_pos,
                                     scale, False, carry)
            if step < sp - 1:
                kv = jax.lax.ppermute(kv, AXIS_SP, perm)
        m, l, acc = carry
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(Tl, q_loc.shape[1], D).astype(q_loc.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS_SP, AXIS_TP, None), P(AXIS_SP, AXIS_TP, None),
                  P(AXIS_SP, AXIS_TP, None)),
        out_specs=P(AXIS_SP, AXIS_TP, None),
        check_vma=False,
    )(q, k, v)


def _single_shard_attention(q, k, v, scale, causal):
    T = q.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    init = (jnp.full((T, k.shape[1], q.shape[1] // k.shape[1]), -1e29,
                     jnp.float32),
            jnp.zeros((T, k.shape[1], q.shape[1] // k.shape[1]), jnp.float32),
            jnp.zeros((T, k.shape[1], q.shape[1] // k.shape[1], q.shape[2]),
                      jnp.float32))
    m, l, acc = _flash_block(q, k, v, pos, pos, scale, causal, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(q.shape).astype(q.dtype)


def attention_reference_dense(q, k, v, scale=None, causal=True):
    """O(T^2) full-softmax oracle for tests."""
    T, H, D = q.shape
    KVH = k.shape[1]
    G = H // KVH
    scale = scale if scale is not None else D ** -0.5
    qf = q.astype(jnp.float32).reshape(T, KVH, G, D) * scale
    s = jnp.einsum("qkgd,skd->qkgs", qf, k.astype(jnp.float32))
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("qkgs,skd->qkgd", p, v.astype(jnp.float32))
    return out.reshape(T, H, D).astype(q.dtype)
