"""Gated delta-rule linear attention over the state pool
(ops/linear_attention.py, ops/pallas/delta_update.py, delta_scan.py), at op
level in float32 on the CPU, against the recurrence token by token:

  - the chunked form equals the recurrence with chunk boundaries anywhere,
    rows of one token beside rows of many, pieces of one to four sub-blocks;
  - at the gate's floor on every channel of every token (g = -5) nothing
    overflows: every exponent is taken against a sub-block's middle;
  - the convolution's tail and the state carry across steps through a slot,
    a fresh row starts from zero whatever its slot holds;
  - the Pallas kernels (interpreted, at the published 128 x 128 state)
    against the XLA forms;
  - what the chip's limits cannot see is held here: a state kept in bf16
    leaves the float32 recurrence by a thousand times the chunked form's
    distance from it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.ops import linear_attention as la
from llm_d_tpu.ops import ssm as ssm_ops
from llm_d_tpu.ops.pallas.delta_scan import delta_chunk_scan
from llm_d_tpu.ops.pallas.delta_update import delta_decode_update

F32 = jnp.float32
TOL = 2e-5          # the chunked form against the recurrence, float32


@pytest.fixture(autouse=True)
def exact_dots():
    with jax.default_matmul_precision("highest"):
        yield


def recurrence(q, k, v, g, beta, s0, state_dtype=F32):
    """Token by token; the state rounded to ``state_dtype`` after each."""
    def token(s, inp):
        qt, kt, vt, gt, bt = inp
        s = s * jnp.exp(gt)[..., None]
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt))
        s = (s + kt[..., None] * u[:, None, :]).astype(state_dtype).astype(F32)
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    last, o = jax.lax.scan(token, s0, (q, k, v, g, beta))
    return o, last


def inputs(T, H, K, V, g_of=None, seed=0):
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(T, H, K)) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * K ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = (-np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (T, H, K)))
         if g_of is None else np.full((T, H, K), g_of))
    return tuple(jnp.asarray(a, F32) for a in (
        q, k, rng.normal(size=(T, H, V)), g, rng.uniform(size=(T, H))))


def batch_of(lens, starts, slots):
    """The fields of a packed batch the state ops read."""
    lens, starts = np.asarray(lens), np.asarray(starts)
    S, T = len(lens), int(lens.sum())
    first = np.cumsum(lens) - lens
    row = np.repeat(np.arange(S), lens)
    qtok = np.full((S, max(lens)), T)
    for s in range(S):
        qtok[s, :lens[s]] = first[s] + np.arange(lens[s])
    return {name: jnp.asarray(a, jnp.int32) for name, a in dict(
        token_seq_ids=row, token_qpos=np.arange(T) - first[row],
        state_slot=slots, query_start=first, query_len=lens,
        seq_lens=starts + lens, qtok_idx=qtok).items()}


def against_recurrence(lens, starts, chunk, H=4, K=16, V=8, g_of=None,
                       update=la.state_update):
    """Max |difference| of outputs and of final states, and the outputs'
    own size, over the rows of one step."""
    T = sum(lens)
    q, k, v, g, beta = inputs(T, H, K, V, g_of, seed=T)
    slots = list(range(1, len(lens) + 1))
    pool = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, len(lens) + 1, H, K, V)), F32)
    batch = batch_of(lens, starts, slots)
    o, after = jax.jit(lambda *a: update(
        *a, batch, jnp.int32(1), chunk, "xla"))(q, k, v, g, beta, pool)
    assert jnp.array_equal(after[0], pool[0])       # the other plane
    worst, at = 0.0, 0
    for n, start, slot in zip(lens, starts, slots):
        rows = slice(at, at + n)
        s0 = pool[1, slot] if start else jnp.zeros((H, K, V), F32)
        want, last = recurrence(q[rows], k[rows], v[rows], g[rows],
                                beta[rows], s0)
        assert bool(jnp.isfinite(o[rows]).all())
        worst = max(worst, float(jnp.abs(o[rows] - want).max()),
                    float(jnp.abs(after[1, slot] - last).max()))
        at += n
    return worst


@pytest.mark.parametrize("lens,starts,chunk", [
    ((70, 1, 33, 5), (0, 9, 40, 0), 32),        # mixed: decode row among
    ((16,), (0,), 16), ((17,), (3,), 16),       # a piece exactly; one over
    ((2, 2, 2), (0, 5, 0), 16),                 # rows far under a sub-block
    ((129,), (0,), 64), ((100, 28), (64, 0), 64),   # four sub-blocks a piece
    ((1, 1, 1), (0, 7, 30), 32),                # pure decode: no scan at all
    ((47, 81), (11, 0), 48)])                   # three sub-blocks a piece
def test_chunked_form_is_the_token_recurrence(lens, starts, chunk):
    assert against_recurrence(lens, starts, chunk) < TOL


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_the_gate_floor_on_every_channel_overflows_nothing(chunk):
    """g = -5 throughout: exp(-G) over a piece would be exp(320); against a
    sub-block's middle no exponent passes 8 x 5."""
    assert against_recurrence((130, 1, 20), (0, 4, 9), chunk, g_of=-5.0) < TOL
    assert float(jnp.exp(jnp.float32(la.SUB // 2 * 10.0))) < float("inf")


def test_no_decay_is_the_plain_delta_rule():
    assert against_recurrence((90,), (0,), 32, g_of=0.0) < TOL


def test_a_state_in_bf16_is_seen_here_and_nowhere_on_the_chip():
    """The pool's dtype is float32, not an option: a state rounded to bf16
    after every token leaves the float32 recurrence by far more than the
    chunked form does (and than this file's bound), while log-probabilities
    on the chip move under the served reading (falcon-h1.batch's record)."""
    T, H, K, V = 200, 4, 16, 8
    q, k, v, g, beta = inputs(T, H, K, V, seed=5)
    s0 = jnp.zeros((H, K, V), F32)
    exact, _ = recurrence(q, k, v, g, beta, s0)
    rounded, _ = recurrence(q, k, v, g, beta, s0, jnp.bfloat16)
    assert float(jnp.abs(rounded - exact).max()) > 50 * TOL


def test_state_and_tail_cross_steps_through_the_slot():
    """A row in one step of 100 tokens, and in steps of 37, 1, 46 and 16
    through its slot of a pool full of garbage: the same outputs and state;
    the convolution the same way through its tail."""
    H, K, V, T = 4, 16, 8, 100
    q, k, v, g, beta = inputs(T, H, K, V, seed=3)
    garbage = jnp.full((1, 3, H, K, V), 1e4, F32)
    whole, after = la.state_update(
        q, k, v, g, beta, garbage, batch_of([T], [0], [2]), jnp.int32(0), 32,
        "xla")
    pool, parts, at = garbage, [], 0
    for n in (37, 1, 46, 16):
        rows = slice(at, at + n)
        o, pool = la.state_update(
            q[rows], k[rows], v[rows], g[rows], beta[rows], pool,
            batch_of([n], [at], [2]), jnp.int32(0), 32, "xla")
        parts.append(o)
        at += n
    np.testing.assert_allclose(jnp.concatenate(parts), whole, atol=TOL)
    np.testing.assert_allclose(pool[0, 2], after[0, 2], atol=TOL)
    assert float(jnp.abs(pool[0, 1]).min()) == 1e4      # untouched slot

    C, Kc = 24, 4
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(T, C)), F32)
    w = jnp.asarray(rng.normal(size=(C, Kc)), F32)
    b = jnp.zeros((C,), F32)
    tails = jnp.full((1, 3, Kc - 1, C), -50.0, F32)
    whole, _ = ssm_ops.causal_conv(u, w, b, tails, batch_of([T], [0], [2]),
                                   jnp.int32(0))
    parts, at = [], 0
    for n in (37, 1, 2, 44, 16):
        out, tails = ssm_ops.causal_conv(
            u[at:at + n], w, b, tails, batch_of([n], [at], [2]), jnp.int32(0))
        parts.append(out)
        at += n
    np.testing.assert_allclose(jnp.concatenate(parts), whole, atol=1e-5)


def test_a_reused_slot_starts_from_zero():
    H, K, V = 4, 16, 8
    q, k, v, g, beta = inputs(41, H, K, V, seed=9)
    batch = batch_of([40, 1], [0, 0], [1, 2])
    zero = jnp.zeros((1, 3, H, K, V), F32)
    a, pa = la.state_update(q, k, v, g, beta, zero, batch, jnp.int32(0), 16,
                            "xla")
    b, pb = la.state_update(q, k, v, g, beta, zero + 1e4, batch,
                            jnp.int32(0), 16, "xla")
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(pa[0, 1:], pb[0, 1:])


# ---------------------------------------------------------------------------
# the Pallas kernels, interpreted, at the published state of 128 x 128
# ---------------------------------------------------------------------------

def interpreted(monkeypatch):
    import functools

    import llm_d_tpu.ops.pallas.delta_scan as scan_mod
    import llm_d_tpu.ops.pallas.delta_update as update_mod
    monkeypatch.setattr(la, "resolve_backend", lambda backend: "pallas")
    monkeypatch.setattr(update_mod, "delta_decode_update", functools.partial(
        delta_decode_update, interpret=True))
    monkeypatch.setattr(scan_mod, "delta_chunk_scan", functools.partial(
        delta_chunk_scan, interpret=True))


@pytest.mark.parametrize("lens,starts", [
    ((70, 1, 1, 33, 130), (0, 9, 0, 40, 0)),
    ((1, 1, 1, 1), (5, 0, 77, 1))])
def test_kernels_interpreted_against_the_recurrence(lens, starts,
                                                    monkeypatch):
    interpreted(monkeypatch)
    assert not la.pallas_ineligible_reason(8, 128, 128, 64)
    assert against_recurrence(lens, starts, 64, H=8, K=128, V=128) < TOL


def test_kernels_interpreted_against_the_xla_forms():
    H, K, V, chunk = 8, 128, 128, 64
    lens, starts, slots = (70, 1, 33), (0, 9, 40), (1, 2, 3)
    T = sum(lens)
    q, k, v, g, beta = inputs(T, H, K, V, g_of=-5.0, seed=2)
    pool = jnp.asarray(np.random.default_rng(4).normal(
        size=(2, 4, H, K, V)), F32)
    batch = batch_of(lens, starts, slots)
    tok = batch["query_start"]
    one = batch["query_len"] == 1
    slot1 = jnp.where(one, batch["state_slot"], 0)
    args = (q[tok], k[tok], v[tok], g[tok], beta[tok], pool, jnp.int32(1),
            slot1, ssm_ops.fresh_rows(batch))
    o, p = delta_decode_update(*args, interpret=True)
    want_o, want_p = la.decode_update_reference(*args)
    np.testing.assert_allclose(o[1], want_o[1], atol=1e-6)
    np.testing.assert_allclose(p[1, 1:], want_p[1, 1:], atol=1e-6)
    np.testing.assert_array_equal(p[0], pool[0])
    pc = ssm_ops.scan_pieces(batch, T, chunk)
    terms = la._by_piece(q, k, v, g, beta, pc, chunk)
    o, p = delta_chunk_scan(*terms, pool, jnp.int32(1), pc["slot"],
                            pc["first"], pc["fresh"], pc["live"],
                            interpret=True)
    want_o, want_p = la.chunk_scan(q, k, v, g, beta, pool, jnp.int32(1),
                                   batch, chunk)
    many = np.asarray(~one)[np.asarray(batch["token_seq_ids"])]
    np.testing.assert_allclose(
        o[pc["tok_piece"], :, pc["tok_off"]][many], want_o[many], atol=1e-6)
    np.testing.assert_allclose(p[1, 1:], want_p[1, 1:], atol=1e-6)


@pytest.mark.parametrize("H,K,V,chunk,why", [
    (32, 128, 128, 64, ""), (8, 256, 128, 16, ""),
    (4, 16, 8, 32, "128 x 128 tiles"), (32, 128, 64, 64, "128 x 128 tiles"),
    (12, 128, 128, 64, "multiples of 8")])
def test_the_kernels_are_chosen_by_geometry_alone(H, K, V, chunk, why):
    reason = la.pallas_ineligible_reason(H, K, V, chunk)
    assert (why in reason) if why else not reason
