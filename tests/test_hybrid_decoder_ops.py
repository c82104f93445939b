"""The device computations a decoder-hybrid-decoder stack adds, at op level
and at the published geometry (``tests/test_hybrid_decoder.py`` has the
engine's side): the Mamba-1 selective scan and one-token update
(``ops/pallas/ssm1_scan.py`` interpreted, and the XLA forms of
``ops/ssm.py``) against a float32 numpy recurrence a token at a time, the
state's dtype and the slots held there; heads fed to the GQA paths as pairs
against differential attention written out; the one-query read of another
layer's plane against the chunked XLA recurrence.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.models.config import get_config
from llm_d_tpu.ops import attention as attn_ops
from llm_d_tpu.ops import ssm as ssm_ops

PRESET = "tiny-hybrid-decoder"


INNER, N, K, CHUNK = 5120, 16, 4, 128


def _token_by_token(x, dt, A, B, C, D, s0):
    """One row, float32: x, dt [n, inner], A [N, inner], B, C [n, N], s0
    [N, inner] -> (y [n, inner], s_n)."""
    s, ys = s0.copy(), []
    for t in range(x.shape[0]):
        s = np.exp(dt[t][None, :] * A) * s \
            + B[t][:, None] * (dt[t] * x[t])[None, :]
        ys.append((s * C[t][:, None]).sum(0) + D * x[t])
    return np.stack(ys), s


@pytest.fixture
def interpreted(monkeypatch):
    """``ssm1_state_update``'s Pallas branch on the CPU: kernels
    interpreted."""
    from llm_d_tpu.ops.pallas import ssm1_scan
    for name in ("ssm1_decode_update", "ssm1_chunk_scan"):
        monkeypatch.setattr(ssm1_scan, name, functools.partial(
            getattr(ssm1_scan, name), interpret=True))


def _mixed_step(seed=0, T=512):
    qlen = np.array([1, 200, 1, 130, 3, 0, 0, 0])
    ctx = np.array([9, 300, 0, 0, 37, 0, 0, 0])    # tokens before the chunk
    slot = np.array([1, 2, 3, 4, 6, 0, 0, 0])
    S, L, slots = len(qlen), 2, 8
    n = int(qlen.sum())
    qstart = np.cumsum(qlen) - qlen
    rows = np.repeat(np.arange(S), qlen)
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    x = jax.random.normal(next(k), (T, INNER)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(next(k), (T, INNER)) - 3.0)
    A = -jnp.exp(jax.random.uniform(next(k), (N, INNER), maxval=2.7))
    B = (jax.random.normal(next(k), (T, N)) * 0.3).astype(jnp.bfloat16)
    C = (jax.random.normal(next(k), (T, N)) * 0.3).astype(jnp.bfloat16)
    D = jax.random.normal(next(k), (INNER,))
    pool = jax.random.normal(next(k), (L, slots, N, INNER))
    pad = np.zeros(T - n, int)
    batch = {
        "query_start": qstart, "query_len": qlen, "state_slot": slot,
        "seq_lens": ctx + qlen,
        "token_seq_ids": np.concatenate([rows, pad]),
        "token_qpos": np.concatenate([np.arange(n) - qstart[rows], pad]),
        "qtok_idx": np.zeros((S, 256))}
    batch = {name: jnp.asarray(v, jnp.int32) for name, v in batch.items()}
    return (x, dt, A, B, C, D, pool, batch), (qlen, ctx, slot, qstart)


@pytest.mark.parametrize("backend", ["pallas", "reference"])
def test_state_computations_against_a_token_by_token_scan(backend,
                                                          interpreted):
    """A mixed step at 5,120 channels x 16 states, pieces of 128: rows of
    one token (a decode row with context, a one-token prompt from zero), a
    chunk of 200 (no multiple of 128) that continues a prompt, a chunk of
    130 from position 0, a chunk of 3, padded rows: y and the float32
    states the slots hold afterwards, slots of rows not in the step and the
    other plane untouched.  Both the kernels (interpreted) and the XLA
    forms."""
    assert not ssm_ops.ssm1_pallas_ineligible_reason(INNER, N, CHUNK)
    args, (qlen, ctx, slot, qstart) = _mixed_step()
    x, dt, A, B, C, D, pool, batch = args
    y, new = jax.jit(ssm_ops.ssm1_state_update, static_argnums=(9, 10))(
        *args, jnp.int32(1), CHUNK, backend)
    assert new.dtype == jnp.float32 and y.dtype == jnp.float32
    f = [np.asarray(a, np.float32) for a in (x, dt, A, B, C, D)]
    for r in range(len(qlen)):
        if not qlen[r]:
            continue
        tok = slice(qstart[r], qstart[r] + qlen[r])
        s0 = np.asarray(pool[1, slot[r]]) * (ctx[r] > 0)
        want_y, want_s = _token_by_token(
            f[0][tok], f[1][tok], f[2], f[3][tok], f[4][tok], f[5], s0)
        np.testing.assert_allclose(np.asarray(y[tok]), want_y,
                                   atol=2e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(new[1, slot[r]]), want_s,
                                   atol=2e-4, rtol=1e-4)
    untouched = [s for s in range(1, 8) if s not in slot]
    assert jnp.array_equal(new[1, jnp.asarray(untouched)],
                           pool[1, jnp.asarray(untouched)])
    assert jnp.array_equal(new[0], pool[0])             # the other layer


def test_a_pure_decode_step_holds_no_scan(interpreted):
    S, T, slots = 4, 16, 6
    k = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    x = jax.random.normal(next(k), (T, INNER)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(next(k), (T, INNER)) - 3.0)
    A = -jnp.exp(jax.random.uniform(next(k), (N, INNER), maxval=2.7))
    B = jax.random.normal(next(k), (T, N)).astype(jnp.bfloat16)
    C = jax.random.normal(next(k), (T, N)).astype(jnp.bfloat16)
    pool = jax.random.normal(next(k), (1, slots, N, INNER))
    batch = {name: jnp.asarray(v, jnp.int32) for name, v in {
        "query_start": [0, 1, 2, 0], "query_len": [1, 1, 1, 0],
        "state_slot": [5, 2, 4, 0], "seq_lens": [7, 1, 90, 0],
        "token_seq_ids": [0, 1, 2] + [0] * 13, "token_qpos": [0] * 16,
        "qtok_idx": np.zeros((S, 1))}.items()}
    args = (x, dt, A, B, C, jnp.zeros((INNER,)), pool, batch, jnp.int32(0),
            CHUNK)
    fn = jax.jit(ssm_ops.ssm1_state_update, static_argnums=(9, 10))
    lowered = fn.lower(*args, "pallas").as_text()
    assert "ssm1_chunk_scan" not in lowered
    y, new = fn(*args, "pallas")
    y0, new0 = fn(*args, "reference")
    np.testing.assert_allclose(y[:3], y0[:3], atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(new[:, 1:], new0[:, 1:], atol=1e-5)
    assert float(jnp.abs(new0[0, 2]).max()) > 0        # seq_lens 1: fresh,
    np.testing.assert_allclose(                        # from zero
        new0[0, 2], B[1].astype(jnp.float32)[:, None]
        * (dt[1] * x[1].astype(jnp.float32))[None, :], atol=1e-6)


def test_a_geometry_the_kernels_refuse_takes_the_xla_forms():
    assert "128 lanes" in ssm_ops.ssm1_pallas_ineligible_reason(96, 16, 128)
    assert "sublane" in ssm_ops.ssm1_pallas_ineligible_reason(256, 4, 128)
    assert "sublane" in ssm_ops.ssm1_pallas_ineligible_reason(256, 16, 12)
    c = get_config(PRESET)
    assert ssm_ops.ssm1_pallas_ineligible_reason(
        c.ssm_inner_size, c.ssm_state_size, c.ssm_chunk_size)


# ---------------------------------------------------------------------------
# heads in pairs, and one query over another layer's plane
# ---------------------------------------------------------------------------

def _diff_attention_written_out(q, k, v, lam, window):
    """q [T, H, D], k, v [T, KVH, D] float32, causal under ``window``:
    [T, H / 2, 2 D]."""
    T, H, D = q.shape
    group = (H // 2) // (k.shape[1] // 2)
    pos = np.arange(T)
    mask = (pos[None, :] <= pos[:, None]) \
        & (pos[None, :] > pos[:, None] - window)
    out = np.zeros((T, H // 2, 2 * D), np.float32)
    for i in range(H // 2):
        j = i // group
        vv = np.concatenate([v[:, 2 * j], v[:, 2 * j + 1]], -1)
        ps = []
        for s in (0, 1):
            sc = q[:, 2 * i + s] @ k[:, 2 * j + s].T / np.sqrt(D)
            sc = np.where(mask, sc, -np.inf)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            ps.append(p / p.sum(-1, keepdims=True))
        out[:, i] = ps[0] @ vv - lam * (ps[1] @ vv)
    return out


@pytest.mark.parametrize("backend", ["reference", "chunked"])
def test_heads_in_pairs_against_differential_attention_written_out(backend):
    """8 query heads over 4 key-value heads of 8 (2 query pairs a key-value
    pair), one row of 21 tokens under a window of 9, fed to the GQA path as
    8 heads over 2 of 16 with the scale of the true head size."""
    T, H, KVH, D, bs, window, lam = 21, 8, 4, 8, 8, 9, 0.37
    k = iter(jax.random.split(jax.random.PRNGKey(3), 4))
    q = jax.random.normal(next(k), (T, H, D))
    kx = jax.random.normal(next(k), (T, KVH, D))
    vx = jax.random.normal(next(k), (T, KVH, D))
    batch = {name: jnp.asarray(v, jnp.int32) for name, v in {
        "positions": np.arange(T), "token_seq_ids": np.zeros(T),
        "token_qpos": np.arange(T), "slot_mapping": bs + np.arange(T),
        "block_tables": [[1, 2, 3, 0]], "seq_lens": [T],
        "qtok_idx": np.arange(32).clip(0, T)[None, :]}.items()}
    cache = jnp.zeros((1, 5 * bs, KVH * D), jnp.float32)
    out, *_ = attn_ops.attention_with_kv_update(
        attn_ops.diff_pair_queries(q), kx.reshape(T, KVH // 2, 2 * D),
        vx.reshape(T, KVH // 2, 2 * D), cache, cache, batch, block_size=bs,
        scale=D ** -0.5, backend=backend, layer=jnp.int32(0),
        window=jnp.int32(window))
    got = attn_ops.diff_combine(out, lam)
    want = _diff_attention_written_out(
        *(np.asarray(a) for a in (q, kx, vx)), lam, window)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_one_query_read_kernel_against_the_xla_path():
    """``paged_attention_read`` interpreted (40 heads over 10 of 128, the
    published rows of 1,280) against the chunked XLA recurrence: rows of
    context 1, 37 (a page and a bit), 96 (whole pages) and a padded row;
    plane 1 of 2; nothing written."""
    from llm_d_tpu.ops.pallas.paged_attention import paged_attention_read
    S, H, KVH, D, bs, pages = 4, 40, 10, 128, 32, 4
    k = iter(jax.random.split(jax.random.PRNGKey(5), 4))
    q = jax.random.normal(next(k), (S, H, D)).astype(jnp.bfloat16)
    kc = jax.random.normal(next(k), (2, 16 * bs, KVH * D)).astype(jnp.bfloat16)
    vc = jax.random.normal(next(k), (2, 16 * bs, KVH * D)).astype(jnp.bfloat16)
    view = {"block_tables": jnp.asarray(
        [[3, 0, 0, 0], [5, 9, 0, 0], [2, 7, 4, 0], [0, 0, 0, 0]], jnp.int32),
        "seq_lens": jnp.asarray([1, 37, 96, 0], jnp.int32)}
    want = attn_ops.attention_one_query(
        q, kc, vc, view, bs, scale=0.125, backend="reference",
        layer=jnp.int32(1))
    got = paged_attention_read(
        q, kc, vc, view["block_tables"], view["seq_lens"], block_size=bs,
        num_kv_heads=KVH, scale=0.125, layer=jnp.int32(1), interpret=True)
    np.testing.assert_allclose(np.asarray(got[:3], np.float32),
                               np.asarray(want[:3], np.float32), atol=3e-2)
    assert float(jnp.abs(got[3].astype(jnp.float32)).max()) == 0.0
    # a query alone with its own key returns that key's value row
    np.testing.assert_allclose(
        np.asarray(got[0, 0], np.float32),
        np.asarray(vc[1, 3 * bs, :D], np.float32), atol=1e-2)
