"""EPLB: placement planning, physical dispatch, numeric equivalence.

The invariant that matters: routing through an EPLB physical placement
(replicated hot experts, arbitrary slot permutation) must produce exactly
the same model output as the logical layout — replicas are copies.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.models.config import ModelConfig
from llm_d_tpu.ops import moe as moe_ops
from llm_d_tpu.parallel.eplb import (
    EplbConfig, EplbController, LoadTracker, align_plan, gather_physical,
    plan_delta, plan_placement)
from llm_d_tpu.parallel.mesh import MeshConfig, make_mesh


def test_plan_shapes_and_constraints():
    load = [100, 1, 1, 1, 50, 1, 1, 1]
    plan = plan_placement(load, num_redundant=8, ep=4)
    assert plan.num_physical == 16
    assert plan.slots_per_shard == 4
    # Every logical expert has >= 1 replica; hottest has the most.
    assert plan.num_replicas.min() >= 1
    assert plan.num_replicas[0] == plan.num_replicas.max()
    # replica_table entries point back at their logical expert.
    for e in range(8):
        for r in range(plan.num_replicas[e]):
            assert plan.phys_to_logical[plan.replica_table[e, r]] == e


def test_plan_rejects_bad_divisibility():
    with pytest.raises(ValueError):
        plan_placement([1.0] * 8, num_redundant=3, ep=4)


def test_plan_balances_hot_expert():
    # One expert carries ~all load; with redundancy its replicas must spread
    # over distinct shards.
    load = [1000, 1, 1, 1]
    plan = plan_placement(load, num_redundant=4, ep=4)
    hot_slots = plan.replica_table[0, :plan.num_replicas[0]]
    shards = set(int(s) // plan.slots_per_shard for s in hot_slots)
    assert len(shards) == len(hot_slots)       # each replica on its own shard


def test_physical_dispatch_matches_logical():
    E, k, T, H, I = 8, 2, 16, 32, 24
    c = ModelConfig(num_experts=E, num_experts_per_tok=k, moe_renormalize=True)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(T, H), jnp.float32)
    router = jnp.asarray(rng.randn(H, E), jnp.float32)
    wg = jnp.asarray(rng.randn(E, H, I) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.randn(E, H, I) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.randn(E, I, H) * 0.1, jnp.float32)

    weights, idx = moe_ops.route(jnp.dot(x, router), c)
    logical = moe_ops.expert_ffn(x, weights, idx, wg, wu, wd)

    plan = plan_placement(rng.rand(E), num_redundant=8, ep=4)
    idx_p = moe_ops.to_physical_experts(
        idx, jnp.asarray(plan.replica_table), jnp.asarray(plan.num_replicas))
    physical = moe_ops.expert_ffn(
        x, weights, idx_p,
        jnp.asarray(gather_physical(np.asarray(wg), plan)),
        jnp.asarray(gather_physical(np.asarray(wu), plan)),
        jnp.asarray(gather_physical(np.asarray(wd), plan)))
    np.testing.assert_allclose(np.asarray(physical), np.asarray(logical),
                               rtol=1e-5, atol=1e-5)


def test_load_tracker_window():
    t = LoadTracker(4, window_size=2)
    t.record(np.asarray([0, 0, 1]))
    t.record(np.asarray([2]))
    assert t.load.tolist() == [2, 1, 1, 0]
    t.record(np.asarray([3, 3]))               # evicts first step
    assert t.load.tolist() == [0, 0, 1, 2]
    assert t.imbalance() == pytest.approx(2 / 0.75)


def test_load_tracker_window_counts_steps_not_samples():
    """A sample covering N engine steps occupies N steps of the window
    (record_interval > 1 / fused retire must not silently widen it)."""
    t = LoadTracker(4, window_size=4)
    t.record(np.zeros((2, 3, 1), np.int64), steps=3)   # layer-leading
    t.record(np.ones((2, 3, 1), np.int64), steps=3)    # 3+3 > 4: evicts 1st
    assert t.load.tolist() == [0.0, 6.0, 0.0, 0.0]
    # Per-layer counts track the layer-leading samples and evict in step.
    assert t.layer_load.shape == (2, 4)
    assert t.layer_load.sum(axis=1).tolist() == [3.0, 3.0]


# ---------------------------------------------------------------------------
# delta plans: align-then-diff
# ---------------------------------------------------------------------------

def test_identity_plan_zero_moves():
    """Regression (ISSUE 17): a plan identical to the serving one must
    cost NOTHING — the old rebalance re-sourced every slot from replica 0
    even when unchanged."""
    load = [5.0, 1.0, 1.0, 1.0]
    cur = plan_placement(load, num_redundant=4, ep=4)
    fresh = plan_placement(load, num_redundant=4, ep=4)
    aligned = align_plan(fresh, cur)
    assert plan_delta(cur, aligned) == []
    assert aligned.phys_to_logical.tolist() == cur.phys_to_logical.tolist()


def test_align_plan_preserves_placement_and_cuts_moves():
    cur = plan_placement(np.ones(8), num_redundant=8, ep=4)
    hot = np.ones(8)
    hot[0] = 40.0
    new = plan_placement(hot, num_redundant=8, ep=4)
    aligned = align_plan(new, cur)
    spp = new.slots_per_shard
    for s in range(4):    # same placement: per-shard expert multiset kept
        assert sorted(aligned.phys_to_logical[s * spp:(s + 1) * spp]) == \
            sorted(new.phys_to_logical[s * spp:(s + 1) * spp])
    moves = plan_delta(cur, aligned)
    naive = int((cur.phys_to_logical != new.phys_to_logical).sum())
    assert 0 < len(moves) <= naive
    for dst, src in moves:        # only changed slots move, sources valid
        assert cur.phys_to_logical[dst] != aligned.phys_to_logical[dst]
        assert cur.phys_to_logical[src] == aligned.phys_to_logical[dst]


# ---------------------------------------------------------------------------
# live migration engine: budget, hysteresis, per-layer plans, atomic flip
# ---------------------------------------------------------------------------

L, E, D = 2, 8, 3


def _controller(**over):
    cfg = dict(num_redundant_experts=8, window_size=100, step_interval=4,
               imbalance_threshold=1.0, move_budget=64)
    cfg.update(over)
    return EplbController(E, 4, EplbConfig.from_dict(cfg))


def _fake_params():
    rng = np.random.RandomState(0)
    return {"moe_layers": {
        "router": jnp.zeros((L, 4, E), jnp.float32),
        "w_gate": jnp.asarray(rng.randn(L, E, D), jnp.float32),
        "w_up": jnp.asarray(rng.randn(L, E, D), jnp.float32),
        "w_down": jnp.asarray(rng.randn(L, E, 2), jnp.float32),
        # int8 sibling planes must travel with their parent weights.
        "w_up_q": jnp.asarray(rng.randint(-127, 127, (L, E, D)), jnp.int8),
        "w_up_s": jnp.asarray(rng.rand(L, E, 1), jnp.float32),
    }}


@pytest.fixture()
def mesh4(devices):
    return make_mesh(MeshConfig(tp=4), jax.devices()[:4])


def _skewed_ids(hot_by_layer, tokens=256):
    """Layer-leading [L, T, 1] routed ids, one hot expert per layer."""
    ids = np.zeros((L, tokens, 1), np.int64)
    for li, e in enumerate(hot_by_layer):
        ids[li, :, 0] = e
    return ids


def test_migration_respects_budget_and_flips_atomically(mesh4):
    ctrl = _controller(move_budget=2)
    raw = _fake_params()
    logical = {k: np.asarray(v) for k, v in raw["moe_layers"].items()}
    params = ctrl.install(raw, mesh4, None)
    before = {k: params["moe_layers"][k] for k in ("w_gate", "w_up_q")}

    params = ctrl.on_step(_skewed_ids([0, 5]), 4, params, mesh4)
    assert ctrl.migrating          # plan fired, staging began
    total = ctrl._migration.total_moves
    assert total > ctrl.move_budget    # forces multiple ticks
    # While staging, serving params are UNTOUCHED (flip is atomic).
    ticks = 1
    while ctrl.migrating and ticks < 100:
        assert params["moe_layers"]["w_gate"] is before["w_gate"]
        if not ctrl._migration.moves:
            # Everything is staged and the flip waits for the async
            # copies: give them time, as a served step between two ticks
            # does (bare ticks outran them on a loaded machine).
            time.sleep(0.01)
        params = ctrl.on_step(None, 4 + ticks, params, mesh4)
        ticks += 1
    assert not ctrl.migrating
    assert ctrl.num_rebalances == 1
    # budget bound: staging alone needs ceil(total/budget) ticks
    assert ticks >= -(-total // ctrl.move_budget)
    assert ctrl.migrated_bytes > 0
    assert ctrl.last_flip_stall_s < 0.25

    # Per-layer plans: each layer replicated ITS hot expert.
    assert ctrl.plans[0].num_replicas[0] == ctrl.plans[0].num_replicas.max()
    assert ctrl.plans[1].num_replicas[5] == ctrl.plans[1].num_replicas.max()
    # Weights (incl. the int8 sibling plane) match the new plans exactly.
    ml = params["moe_layers"]
    for name in ("w_gate", "w_up", "w_down", "w_up_q", "w_up_s"):
        got = np.asarray(ml[name])
        for li in range(L):
            np.testing.assert_array_equal(
                got[li], logical[name][li][ctrl.plans[li].phys_to_logical],
                err_msg=f"{name} layer {li}")
    # Tables in params are the stacked form of the serving plans.
    rt, nr = ctrl._stacked_tables(L)
    np.testing.assert_array_equal(np.asarray(ml["replica_table"]),
                                  np.asarray(rt))
    np.testing.assert_array_equal(np.asarray(ml["num_replicas"]),
                                  np.asarray(nr))


def test_hysteresis_suppresses_balanced_load(mesh4):
    ctrl = _controller(imbalance_threshold=2.0)
    params = ctrl.install(_fake_params(), mesh4, None)
    ids = np.tile(np.arange(E), 32).reshape(L, -1, 1)   # perfectly even
    params = ctrl.on_step(ids, 4, params, mesh4)
    assert not ctrl.migrating
    assert ctrl.num_rebalances == 0
    assert ctrl.num_suppressed == 1


def test_min_delta_suppression_identity_load(mesh4):
    """Even with the hysteresis gate open, a plan that aligns to the
    serving placement stages nothing."""
    ctrl = _controller(imbalance_threshold=0.0)
    params = ctrl.install(_fake_params(), mesh4, None)
    ids = np.tile(np.arange(E), 32).reshape(L, -1, 1)   # uniform = initial
    ml_before = params["moe_layers"]
    params = ctrl.on_step(ids, 4, params, mesh4)
    assert not ctrl.migrating
    assert ctrl.num_rebalances == 0
    assert params["moe_layers"] is ml_before
    assert ctrl.migrated_bytes == 0


# ---------------------------------------------------------------------------
# sim mirror: skew-proven step-time delta at cluster scale
# ---------------------------------------------------------------------------

def test_sim_online_eplb_beats_static_under_zipf_skew():
    """Zipf-1.2 routing: static placement pays the hot-shard overhang on
    every decode step forever; online EPLB pays it only until the
    budgeted migration flips, then the balanced overhang — with zero
    stall charged at the flip."""
    from llm_d_tpu.sim.simulator import InferenceSimulator, SimConfig
    kw = dict(tpot_ms=10.0, eplb_skew=1.2, eplb_step_interval=16,
              eplb_move_budget=8)
    off = InferenceSimulator(SimConfig(model="sim-off", tpot_ms=10.0))
    static = InferenceSimulator(SimConfig(model="sim-static",
                                          eplb_mode="static", **kw))
    online = InferenceSimulator(SimConfig(model="sim-online",
                                          eplb_mode="online", **kw))

    assert off._eplb_step_extra_ms() == 0.0      # mirror off: inert
    skewed = static._eplb_step_extra_ms()
    assert skewed > 0.0
    # Staging overlaps decode: before the flip online pays the SAME
    # skewed cost (no stall spike), after it strictly less.
    assert online._eplb_step_extra_ms() == skewed
    rep = online.eplb_report()
    assert rep["moves"] > 0
    assert rep["stage_steps"] == -(-rep["moves"] // 8)
    online._eplb_steps = rep["flip_step"]
    assert online._eplb_step_extra_ms() < skewed
    # Static never converges, whatever the step count.
    static._eplb_steps = 10_000
    assert static._eplb_step_extra_ms() == skewed
    assert static.eplb_report()["flip_step"] is None


def test_sim_eplb_hysteresis_keeps_placement():
    """An imbalance threshold above the observed skew suppresses the
    migration — the online mirror then behaves like static."""
    from llm_d_tpu.sim.simulator import InferenceSimulator, SimConfig
    sim = InferenceSimulator(SimConfig(
        model="sim-hyst", tpot_ms=10.0, eplb_skew=1.2,
        eplb_mode="online", eplb_imbalance_threshold=1e9))
    rep = sim.eplb_report()
    assert rep["flip_step"] is None
    sim._eplb_steps = 10_000
    assert sim._eplb_step_extra_ms() > 0.0
