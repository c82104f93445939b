"""The held bf16 experts' kernels (``ops/pallas/moe_held.py``), interpreted
on the CPU at small lane-whole widths, against the XLA form of
``ops.moe._held_expert_ffn`` (what the CPU serves and the kernels'
reference) and against ``ops.moe.moe_ffn_reference`` with every expert held
elsewhere zeroed.

The two forms share operands and roundings (bf16 operands, f32
accumulation, ``silu(h) * u`` rounded to bf16, the combine weight and the
k-sum in f32) and differ in the order of the f32 partial sums over blocks of
the expert width: a few results round to the neighbouring bf16, so the
comparison allows one bf16 step of the largest value.  What compiles for
the chip is ``tests/test_tpu_compile.py``'s to say, what it costs
``chip_smoke.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.models.config import ModelConfig
from llm_d_tpu.ops import moe as moe_ops
from llm_d_tpu.ops.pallas import moe_held

H, I, RT, BI = 1024, 256, 16, 128      # one slab of 8 x 128 a row, 2 blocks


def _weights(shape_prefix, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    mk = lambda k, s: (jax.random.normal(k, shape_prefix + s, jnp.float32)
                       * s[0] ** -0.5).astype(jnp.bfloat16)
    return mk(ks[0], (H, I)), mk(ks[1], (H, I)), mk(ks[2], (I, H))


def _routing(T, E, k, seed, idx=None):
    ks = jax.random.split(jax.random.PRNGKey(100 + seed), 3)
    x = jax.random.normal(ks[0], (T, H), jnp.float32).astype(jnp.bfloat16)
    router = jax.random.normal(ks[1], (H, E), jnp.float32)
    c = ModelConfig(num_experts=E, num_experts_per_tok=k,
                    moe_renormalize=True)
    weights, routed = moe_ops.route(
        jnp.dot(x.astype(jnp.float32), router), c)
    return x, router, c, weights, (routed if idx is None else idx)


def _row(*ids):
    return jnp.asarray([ids], jnp.int32)


# name: (T, E, k, first id, held, planes, plane, idx builder or None)
CASES = {
    "no_slot_held": (16, 16, 2, 8, 4, None, None,
                     lambda T: jnp.tile(_row(0, 15), (T, 1))),
    "every_slot_held": (48, 8, 8, 0, 8, None, None, None),
    "two_and_eight_of_a_token": (
        20, 16, 8, 4, 8, None, None,
        lambda T: jnp.concatenate([
            _row(4, 5, 6, 7, 8, 9, 10, 11),            # all eight held
            _row(0, 1, 2, 3, 12, 13, 5, 10),           # two held
            jnp.tile(_row(0, 1, 2, 3, 12, 13, 14, 15), (T - 2, 1))])),
    "an_expert_no_row_selects": (
        40, 16, 2, 4, 4, None, None,
        lambda T: jnp.tile(_row(4, 7), (T, 1)).at[::3, 1].set(6)),
    "plane_2_of_3_stacked": (37, 16, 4, 4, 4, 3, 2, None),
    "T16_first_id_0": (16, 16, 4, 0, 4, None, None, None),
    "T_no_multiple_of_the_tile": (45, 8, 4, 2, 4, 2, 0, None),
    "first_id_12_last_share": (33, 16, 4, 12, 4, None, None, None),
    "second_tiles_walk_backward": (
        64, 8, 2, 2, 2, None, None,
        lambda T: jnp.tile(_row(2, 3), (T, 1))),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_match_the_xla_form_and_the_reference(case):
    T, E, k, e0, held, planes, plane, build = CASES[case]
    x, router, c, weights, idx = _routing(
        T, E, k, seed=len(case), idx=build(T) if build else None)
    prefix = (held,) if planes is None else (planes, held)
    wg, wu, wd = _weights(prefix, seed=len(case))
    pl_ = None if plane is None else jnp.int32(plane)

    got = moe_held.held_expert_ffn(
        x, weights, idx, wg, wu, wd, e0, pl_, row_tile=RT, block=BI,
        interpret=True)
    assert got.shape == (T, H) and got.dtype == x.dtype
    got = np.asarray(got.astype(jnp.float32))
    xla = np.asarray(moe_ops._held_expert_ffn(
        x, weights, idx, wg, wu, wd, e0, pl_).astype(x.dtype)
        .astype(jnp.float32))
    step = 2.0 ** -7 * max(np.abs(xla).max(), 1e-3)    # one bf16 step
    np.testing.assert_allclose(got, xla, atol=step)
    assert np.mean(np.abs(got - xla)) <= 1e-3 * max(np.abs(xla).mean(), 1e-6)

    # The reference over the router's whole width, the experts held
    # elsewhere zeroed: float32 throughout, so bf16's rounding of the
    # activations is the tolerance.
    layer = (lambda w: w) if planes is None else (lambda w: w[plane])
    full = [jnp.zeros((E,) + w.shape[-2:], jnp.float32)
            .at[e0:e0 + held].set(layer(w).astype(jnp.float32))
            for w in (wg, wu, wd)]
    if build is None:
        want = np.asarray(moe_ops.moe_ffn_reference(
            x.astype(jnp.float32), router, *full, c))
    else:       # the case's own routing: the reference's arithmetic
        comb = moe_ops._combine_matrix(T, E, idx, weights)
        xf = x.astype(jnp.float32)
        y = jnp.einsum("tei,eih->teh", jax.nn.silu(
            jnp.einsum("th,ehi->tei", xf, full[0]))
            * jnp.einsum("th,ehi->tei", xf, full[1]), full[2])
        want = np.asarray(jnp.einsum("te,teh->th", comb, y))
    np.testing.assert_allclose(got, want, atol=4 * step + 1e-6)

    # What the tile tables say of the case.
    TT = moe_held.COMBINE_TOKENS
    (pos, tok_pad, tile_expert, tile_rows, tile_back, num_tiles, held_list,
     start) = map(np.asarray, moe_held.held_layout(idx, e0, held, RT, TT))
    lid = np.asarray(idx).reshape(-1) - e0
    is_held = (lid >= 0) & (lid < held)
    n = int(num_tiles)
    assert tile_expert.shape[0] == -(-T * k // RT) + held   # the worst case
    assert (pos >= 0).sum() == is_held.sum() == tile_rows.sum()
    assert (tile_rows[n:] == 0).all() and (tile_rows[:n] > 0).all()
    counts = np.bincount(lid[is_held], minlength=held)
    assert n == sum(-(-int(m) // RT) for m in counts)
    assert set(tile_expert[:n]) == set(np.nonzero(counts)[0])
    # every held slot has a padded slot of its own, with its token and weight
    assert len(set(pos[is_held])) == is_held.sum()
    np.testing.assert_array_equal(tok_pad[pos[is_held]],
                                  np.nonzero(is_held)[0] // k)
    # the combine's list: the held slots in token order, by program
    where = np.nonzero(is_held)[0]
    np.testing.assert_array_equal(
        held_list[:len(where)], pos[where] * (TT * k) + where % (TT * k))
    np.testing.assert_array_equal(
        start, [np.sum(where < i * TT * k) for i in range(-(-T // TT) + 1)])
    if case == "no_slot_held":
        assert n == 0 and not got.any()
    if case == "every_slot_held":
        assert is_held.all()                       # nothing dropped
    if case == "an_expert_no_row_selects":
        assert 1 not in tile_expert[:n] and counts[1] == 0   # not streamed
    if case == "second_tiles_walk_backward":
        assert list(tile_back[:n]) == [0, 1, 0, 1] * 2
    if case == "two_and_eight_of_a_token":
        assert is_held.reshape(T, k).sum(1)[:3].tolist() == [8, 2, 0]
        assert not got[2:].any()


@pytest.mark.parametrize("what,x_dtype,w_dtype,hidden,width,eligible", [
    ("published", jnp.bfloat16, jnp.bfloat16, 5120, 1536, True),
    ("float32 rows", jnp.float32, jnp.bfloat16, 5120, 1536, False),
    ("float32 experts", jnp.bfloat16, jnp.float32, 5120, 1536, False),
    ("hidden of half a slab", jnp.bfloat16, jnp.bfloat16, 1536, 1536, False),
    ("width of no lane tile", jnp.bfloat16, jnp.bfloat16, 1024, 96, False),
    ("rows too wide for VMEM", jnp.bfloat16, jnp.bfloat16, 32768, 256,
     False),
])
def test_eligibility_goes_by_dtype_and_shape(what, x_dtype, w_dtype, hidden,
                                             width, eligible):
    reason = moe_held.ineligible_reason(
        jax.ShapeDtypeStruct((16, hidden), x_dtype),
        jax.ShapeDtypeStruct((5, 32, hidden, width), w_dtype))
    assert (reason is None) == eligible, reason


def test_block_rule_at_the_published_widths():
    """512 of 1,536 columns a step at hidden 5,120, the same for every T;
    the whole width where it fits."""
    assert moe_held.pick_block(5120, 1536) == 512
    assert moe_held.pick_block(1024, 256) == 256
    assert moe_held._vmem_bytes(5120, 512) <= moe_held.VMEM_LIMIT \
        < moe_held._vmem_bytes(5120, 768)
