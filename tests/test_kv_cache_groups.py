"""A paged cache in groups by layer kind (engine/kv_cache.py): the window
group holds a window of pages, a prefix hit needs both groups, and a stack of
one kind has one group whose block ids are the ones it always had."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_tpu.engine.engine import (
    EngineConfig, EngineCore, derive_group_blocks, window_group_blocks)
from llm_d_tpu.engine.kv_cache import KVCacheManager
from llm_d_tpu.engine.request import Request, RequestState
from llm_d_tpu.models import get_config
from llm_d_tpu.ops.sampling import SamplingParams

BS, WINDOW = 4, 10          # a window that is no multiple of the page
TAIL = 3                    # pages under the 9 keys before a boundary


def manager(full=64, window=32, **kw):
    return KVCacheManager(full, BS, window_blocks=window,
                          sliding_window=WINDOW, **kw)


def request(rid, tokens):
    return Request(request_id=rid, prompt_token_ids=list(tokens),
                   sampling=SamplingParams())


def run_to_end(kv, req, chunk=8):
    """Admit ``req`` and compute its prompt in chunks, as the engine does:
    allocate, (launch,) advance, give the passed pages back, hash."""
    blocks, n = kv.find_cached_prefix(req)
    req.num_computed_tokens = n
    first = True
    while req.num_computed_tokens < req.num_tokens:
        upto = min(req.num_computed_tokens + chunk, req.num_tokens)
        assert kv.allocate(req, upto, blocks if first else ()) is not None
        first = False
        req.num_computed_tokens = upto
        kv.release_passed(req)
        kv.cache_full_blocks(req)
    return n


def test_window_group_holds_a_window_of_pages():
    kv = manager()
    req = request("a", range(100, 150))
    kv.allocate(req, 8)
    assert len(req.block_ids) == len(req.window_block_ids) == 2
    # Nothing goes back before the step is launched and the row advanced.
    assert kv.release_passed(req) == 0 and all(req.window_block_ids)
    req.num_computed_tokens = 8
    assert kv.release_passed(req) == 0          # query 8 sees keys 0 ..
    kv.allocate(req, 24)
    held = list(req.window_block_ids)
    req.num_computed_tokens = 24                # query 24 sees keys 15 ..
    assert kv.release_passed(req) == 3
    assert req.window_block_ids == [0, 0, 0] + held[3:]
    assert req.window_first_block == 3 and kv.window_pages_released == 3
    assert len(req.block_ids) == 6 and all(req.block_ids)   # full: all held
    # Given back means free for the next owner, or kept by content hash.
    win = kv.groups[1]
    assert win.pages_held == 3 + 3 and len(win.ref) == 3
    assert [win.hash_of[b] for b in held[:3]] == \
        kv.request_block_hashes(req)[:3]
    kv.free(req)
    assert win.ref == {} and kv.groups[0].ref == {}
    assert req.window_block_ids == [] and req.window_first_block == 0


def test_unconfirmed_pages_go_back_unhashed():
    kv = manager(enable_prefix_caching=True)
    req = request("a", range(100, 108))
    kv.allocate(req, 8)
    req.num_computed_tokens = 8
    # Tokens being sampled are placeholders: only confirmed ones are hashed.
    req.inflight_token_ids = [-1] * 40
    kv.allocate(req, 48)
    req.num_computed_tokens = 48
    assert kv.release_passed(req) == 9
    win = kv.groups[1]
    assert len(win.hash_of) == 2 and len(win.evictor[0]) == 2


@pytest.mark.parametrize("n_tokens,want", [(50, 48), (49, 48), (48, 44),
                                           (9, 8), (3, 0)])
def test_a_hit_needs_both_groups(n_tokens, want):
    kv = manager()
    tokens = list(range(100, 100 + n_tokens))
    cold = request("cold", tokens)
    run_to_end(kv, cold)
    kv.free(cold)
    assert kv.groups[1].ref == {}
    warm = request("warm", tokens)
    blocks, n = kv.find_cached_prefix(warm)
    assert n == want and len(blocks) == want // BS
    warm.num_computed_tokens = n
    assert kv.allocate(warm, n_tokens, blocks) is not None
    k = min(TAIL, want // BS)
    assert warm.window_first_block == want // BS - k
    assert warm.window_block_ids[:want // BS - k] == [0] * (want // BS - k)
    assert all(warm.window_block_ids[want // BS - k:])
    assert kv.hit_tokens_lost == {"full": 0, "window": 0}


@pytest.mark.parametrize("evict,want,lost", [
    ([11], 44, 4),              # the last page: the hit ends a page earlier
    ([10], 40, 8),              # inside the tail: ends before the hole
    ([9], 36, 12),
    ([0, 1, 2, 8], 48, 0),      # pages the hit never needed
    (list(range(12)), 0, 48)])
def test_evicting_window_pages_shortens_the_hit(evict, want, lost):
    kv = manager()
    tokens = list(range(100, 150))
    cold = request("cold", tokens)
    run_to_end(kv, cold)
    kv.free(cold)
    win, hashes = kv.groups[1], kv.request_block_hashes(request("x", tokens))
    for i in evict:
        win.uncache(win.cached[hashes[i]])
    warm = request("warm", tokens)
    blocks, n = kv.find_cached_prefix(warm)
    assert n == want
    warm.num_computed_tokens = n
    assert kv.allocate(warm, 50, blocks) is not None
    assert kv.hit_tokens_lost == {"full": 0, "window": lost}
    # What is attached is what the hit said: never a page of other content.
    for i, b in enumerate(warm.window_block_ids[:n // BS]):
        assert b == 0 or win.hash_of[b] == hashes[i]


def test_evicting_full_pages_shortens_the_hit_and_counts_it():
    kv = manager()
    tokens = list(range(100, 150))
    cold = request("cold", tokens)
    run_to_end(kv, cold)
    kv.free(cold)
    hashes = kv.request_block_hashes(request("x", tokens))
    kv.uncache_block(kv.lookup_hash(hashes[6]))
    warm = request("warm", tokens)
    blocks, n = kv.find_cached_prefix(warm)
    assert n == 24
    warm.num_computed_tokens = n
    kv.allocate(warm, 50, blocks)
    assert kv.hit_tokens_lost == {"full": 24, "window": 0}


def test_a_lookup_that_attaches_nothing_counts_nothing():
    kv = manager()
    tokens = list(range(100, 150))
    cold = request("cold", tokens)
    run_to_end(kv, cold)
    kv.free(cold)
    win, hashes = kv.groups[1], kv.request_block_hashes(request("x", tokens))
    win.uncache(win.cached[hashes[11]])
    warm = request("warm", tokens)
    for _ in range(3):              # the queue's head, passed over thrice
        kv.find_cached_prefix(warm)
    assert kv.hit_tokens_lost == {"full": 0, "window": 0}
    kv.free(warm)
    assert kv._lost_of_req == {}


def test_allocation_takes_both_groups_or_neither():
    kv = manager(full=64, window=6)         # 5 window pages
    a = request("a", range(100, 140))
    assert kv.allocate(a, 24) is None       # 6 pages: the window group lacks
    assert a.block_ids == [] and a.window_block_ids == []
    assert kv.groups[0].num_free == 63 and kv.groups[1].num_free == 5
    assert kv.allocate(a, 20) is not None
    assert not kv.can_allocate(1) and kv.free_blocks_for(a) == 0
    assert not kv.has_room(1) and kv.num_free_blocks == 58
    a.num_computed_tokens = 20
    kv.release_passed(a)                    # query 20 sees keys 11 ..
    assert kv.free_blocks_for(a) == 2 and kv.can_allocate(2)


def test_kv_events_describe_the_full_group():
    kv = manager()
    stored, removed = [], []
    kv.on_block_stored.append(lambda h, b: stored.append(b))
    kv.on_block_removed.append(lambda h, b: removed.append(b))
    req = request("a", range(100, 150))
    run_to_end(kv, req)
    assert stored == req.block_ids[:12]
    kv.free(req)
    win = kv.groups[1]
    while win.take() is not None:           # the window group's LRU empties
        pass
    assert removed == [] and win.eviction_count == 12
    assert kv.eviction_count == 12


# What the parent commit's one-pool manager answered to this operation list
# (24 blocks of 4 tokens; recorded from PR 44's engine/kv_cache.py): a stack
# of one kind has one group, and its block ids are these.
OPS = [
    ["new", "a", list(range(100, 130)), 12], ["grow", "a", 30],
    ["new", "b", list(range(100, 116)) + list(range(7, 21)), 20],
    ["grow", "b", 30], ["free", "a"],
    ["new", "c", list(range(100, 130)) + [1, 2, 3], 33], ["grow", "c", 33],
    ["new", "d", list(range(200, 240)), 40], ["free", "b"], ["grow", "d", 40],
    ["new", "e", list(range(300, 330)), 30], ["free", "c"],
    ["new", "e2", list(range(300, 330)), 30], ["grow", "e2", 30],
    ["free", "d"], ["new", "f", list(range(200, 240)) + [9], 41],
    ["free", "e2"], ["free", "f"], ["new", "g", list(range(100, 130)), 30]]
PARENT = [
    ["new", "a", 0, [1, 2, 3]], ["grow", "a", [4, 5, 6, 7, 8]],
    ["new", "b", 16, [1, 2, 3, 4, 9]], ["grow", "b", [10, 11, 12]],
    ["free", "a", 15, 0], ["new", "c", 28, [1, 2, 3, 4, 5, 6, 7, 13, 14]],
    ["grow", "c", []],
    ["new", "d", 0, [15, 16, 17, 18, 19, 20, 21, 22, 23, 8]],
    ["free", "b", 4, 0], ["grow", "d", []], ["new", "e", 0, None],
    ["free", "c", 13, 0], ["new", "e2", 0, [12, 14, 11, 10, 9, 13, 7, 6]],
    ["grow", "e2", []], ["free", "d", 15, 6],
    ["new", "f", 40, [15, 16, 17, 18, 19, 20, 21, 22, 23, 8, 5]],
    ["free", "e2", 12, 7], ["free", "f", 23, 7],
    ["new", "g", 16, [1, 2, 3, 4, 6, 5, 7, 13]]]


def test_one_group_replays_the_parents_block_ids():
    kv = KVCacheManager(24, 4)
    assert len(kv.groups) == 1 and kv._ref is kv.groups[0].ref
    reqs, trace = {}, []
    for op in OPS:
        kind, rid = op[0], op[1]
        if kind == "new":
            reqs[rid] = request(rid, op[2])
            blocks, n = kv.find_cached_prefix(reqs[rid])
            reqs[rid].num_computed_tokens = n
            trace.append(["new", rid, n, kv.allocate(reqs[rid], op[3],
                                                     blocks)])
        elif kind == "grow":
            r = reqs[rid]
            got = kv.allocate(r, op[2])
            if got is not None:
                r.num_computed_tokens = min(op[2], len(r.prompt_token_ids))
                kv.cache_full_blocks(r)
            trace.append(["grow", rid, got])
            assert kv.release_passed(r) == 0 and r.window_block_ids == []
        else:
            kv.free(reqs.pop(rid))
            trace.append(["free", rid, kv.num_free_blocks,
                          kv.eviction_count])
    assert trace == PARENT


# ---- the engine: pages go back at the launch, and every page comes home ----

MELLUM = dataclasses.replace(get_config("tiny-mellum"), dtype="float32")


def make_engine(num_blocks=160, seqs=4, budget=64, block_size=16, **kw):
    engine = EngineCore(EngineConfig(
        model=MELLUM.name, model_config=MELLUM, block_size=block_size,
        num_blocks=num_blocks, max_num_seqs=seqs,
        max_num_batched_tokens=budget, **kw))
    engine.kv_cache = {name: buf.astype(jnp.float32)
                       for name, buf in engine.kv_cache.items()}
    return engine


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, MELLUM.vocab_size, n).tolist()


def submit(engine, rid, prompt, n_gen):
    req = Request(request_id=rid, prompt_token_ids=list(prompt),
                  sampling=SamplingParams(temperature=0.0, max_tokens=n_gen,
                                          ignore_eos=True, logprobs=0))
    engine.add_request(req)
    return req


def drain(engine):
    got = {}
    while engine.has_work():
        for out in engine.step():
            ids, lps = got.setdefault(out.request_id, ([], []))
            ids.extend(out.new_token_ids)
            lps.extend(out.logprobs or [])
    return got


def test_the_groups_are_sized_from_the_engines_limits():
    engine = make_engine()
    full, window = [g.num_blocks for g in engine.kv_manager.groups]
    # every slot: window - 1 + a step's tokens, in pages, + 2; half again
    assert window == window_group_blocks(48, 16, 4, 64) == 4 * 9 * 3 // 2 + 1
    layers = MELLUM.num_layers
    assert full == (160 * layers - window * 6) // 2      # the rest, 2 layers
    assert full * 2 + window * 6 <= 160 * layers < (full + 1) * 2 + window * 6
    # one plane: each layer's region after the last
    assert engine.kv_cache["k"].shape == (1, 16 * (2 * full + 6 * window), 32)
    # a pool no larger than the rule's window group: one group, all of it
    assert derive_group_blocks(MELLUM, 16, 64, 2048, 40) == (40, 0)


def test_pages_go_back_when_the_step_is_launched():
    engine = make_engine()
    req = submit(engine, "r", prompt_of(200), 4)
    kvm, seen = engine.kv_manager, []
    launch = engine._launch

    def spy(sched, ahead):
        # Composed and not yet launched: the row holds every page its
        # chunk's queries read, from the window before its first token.
        first = max(req.num_computed_tokens - 47, 0) // 16
        assert req.window_first_block <= first
        assert all(req.window_block_ids[first:])
        rec = launch(sched, ahead)
        seen.append(rec.kv["kv_window_pages_released"])
        return rec

    engine._launch = spy
    while engine.has_work():
        engine.step()
        if req.state is RequestState.RUNNING:
            assert req.window_first_block == max(
                req.num_computed_tokens - 47, 0) // 16
    assert sum(seen) == kvm.window_pages_released > 0
    assert kvm.groups[0].ref == {} and kvm.groups[1].ref == {}


@pytest.mark.parametrize("how", ["abort", "preempt", "finish"])
def test_every_page_of_both_groups_comes_home(how):
    engine = make_engine(num_blocks=24, seqs=2, budget=32)
    kvm = engine.kv_manager
    free0 = [g.num_free for g in kvm.groups]
    a = submit(engine, "a", prompt_of(150, 1), 40)
    b = submit(engine, "b", prompt_of(150, 2), 40)
    for _ in range(6):
        engine.step()
    assert a.window_block_ids and b.window_block_ids
    if how == "abort":
        engine.abort_request("a")
        engine.abort_request("b")
    elif how == "preempt":
        sch = engine.scheduler
        assert sch._preempt_for(a, set(), set())
        assert b.state is RequestState.PREEMPTED
        assert b.block_ids == [] and b.window_block_ids == []
        assert b.window_first_block == 0 and b.num_computed_tokens == 0
    drain(engine)
    assert [g.ref for g in kvm.groups] == [{}, {}]
    assert [g.num_free for g in kvm.groups] == free0


def test_a_pool_too_small_for_one_uniform_pool_serves_the_same_logits():
    prompts = [prompt_of(300, 5), prompt_of(300, 6)]
    small = make_engine(num_blocks=30, seqs=2, budget=32)
    full = small.kv_manager.groups[0].num_blocks
    # As one pool of every layer, 29 pages of 16 hold 464 tokens: fewer
    # than the two contexts (2 x 306), so one of them would be preempted.
    assert 29 * 16 < 2 * 306 <= (full - 1) * 16
    large = make_engine(num_blocks=400, seqs=2, budget=32)
    got = []
    for engine in (small, large):
        for i, p in enumerate(prompts):
            submit(engine, f"r{i}", p, 6)
        got.append(drain(engine))
        assert engine.scheduler.num_preemptions == 0
    for rid in ("r0", "r1"):
        assert got[0][rid][0] == got[1][rid][0]
        np.testing.assert_allclose(got[0][rid][1], got[1][rid][1], atol=1e-5)


def test_run_ahead_keeps_the_pages_of_the_step_in_flight():
    """Every slot taken, so the loop composes a step while its predecessor
    runs: the answers are those of an engine that never runs ahead."""
    prompts = [prompt_of(120, 7), prompt_of(140, 8)]
    ahead = make_engine(seqs=2, budget=64)
    plain = make_engine(seqs=4, budget=64)
    got = []
    for engine in (ahead, plain):
        for i, p in enumerate(prompts):
            submit(engine, f"r{i}", p, 12)
        got.append(drain(engine))
    assert ahead.metrics.run_ahead_steps._value.get() > 0
    assert plain.metrics.run_ahead_steps._value.get() == 0
    for rid in ("r0", "r1"):
        assert got[0][rid][0] == got[1][rid][0]
        np.testing.assert_allclose(got[0][rid][1], got[1][rid][1], atol=1e-5)


def _served(engine, prompts, n_gen=6):
    for i, p in enumerate(prompts):
        submit(engine, f"r{i}", p, n_gen)
    return drain(engine)


@pytest.mark.parametrize("feature,kw", [
    ("multistep", {"num_scheduler_steps": 4}),
    ("spec_decode", {"spec_k": 2}),
    ("kv_offload", {"kv_offload_blocks": 8}),
    ("kv_transfer", {"kv_transfer": True})])
def test_what_knows_one_group_gets_one_group(feature, kw):
    """Asked for something that knows one group of pages, the engine serves
    the stack as one group, says so once and keeps the feature."""
    from llm_d_tpu.utils.metrics import parse_prometheus_text
    engine = make_engine(**kw)
    assert engine._window_blocks == 0
    assert len(engine.kv_manager.groups) == 1
    assert engine.kv_manager.num_blocks == 160
    assert engine.kv_cache["k"].shape[0] == MELLUM.num_layers
    m = parse_prometheus_text(engine.metrics.render().decode())
    [(series, n)] = [(k, v) for k, v in m.items()
                     if "engine_feature_disabled_total" in k
                     and 'feature="cache_groups"' in k]
    assert n == 1 and f"{feature} asked for" in series
    if feature == "kv_transfer":
        engine.kv_connector = connector = object()
        assert engine.kv_connector is connector
        engine.kv_connector = None
    # the same tokens as the grouped engine (logits: the parity tests)
    prompts = [prompt_of(150, 3), prompt_of(90, 4)]
    want = _served(make_engine(), prompts)
    got = _served(engine, prompts)
    for rid in want:
        assert got[rid][0] == want[rid][0]


def test_a_pool_no_larger_than_the_window_group_is_one_group():
    """The rule reads the engine's limits: where the window group would be
    no smaller than one pool of every layer, grouping frees nothing and the
    stack is served as every stack of one kind is, nothing counted."""
    from llm_d_tpu.utils.metrics import parse_prometheus_text
    engine = make_engine(num_blocks=48)       # the rule's window group: 55
    assert engine._window_blocks == 0
    assert len(engine.kv_manager.groups) == 1
    assert engine.kv_manager.num_blocks == 48
    assert engine.kv_cache["k"].shape == (MELLUM.num_layers, 48 * 16, 32)
    assert not engine._layout(64, 4, 64).groups
    m = parse_prometheus_text(engine.metrics.render().decode())
    assert not any('feature="cache_groups"' in k for k in m)
    prompts = [prompt_of(150, 3), prompt_of(90, 4)]
    want, got = _served(make_engine(), prompts), _served(engine, prompts)
    for rid in want:
        assert got[rid][0] == want[rid][0]
        np.testing.assert_allclose(got[rid][1], want[rid][1], atol=2e-5)
    assert "kv_pages_window" not in engine._step_kv


def test_a_grouped_engine_refuses_a_kv_connector():
    """Built without ``kv_transfer``, so in groups: a connector attached
    afterwards would move one group's pages."""
    engine = make_engine()
    with pytest.raises(ValueError, match="one group's pages"):
        engine.kv_connector = object()
    engine.kv_connector = None


def test_metrics_by_group():
    from llm_d_tpu.utils.metrics import parse_prometheus_text
    engine = make_engine(num_blocks=24, seqs=2, budget=32)
    for i in range(3):
        submit(engine, f"r{i}", prompt_of(150, i), 4)
        drain(engine)
    m = parse_prometheus_text(engine.metrics.render().decode())
    label = 'model_name="tiny-mellum"'
    assert m['llmd_tpu:kv_window_pages_released_total{%s}' % label] == \
        engine.kv_manager.window_pages_released > 0
    for g in engine.kv_manager.groups:
        assert m['llmd_tpu:kv_group_pages_in_use{group="%s",%s}'
                 % (g.name, label)] == g.pages_held
        assert m['llmd_tpu:kv_group_evictions_total{group="%s",%s}'
                 % (g.name, label)] == g.eviction_count
        assert 'llmd_tpu:prefix_cache_hit_tokens_lost_total{group="%s",%s}' \
            % (g.name, label) in m
    assert engine.kv_manager.groups[1].eviction_count > 0
