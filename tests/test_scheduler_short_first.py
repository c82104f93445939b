"""A prefill that ends in this step goes before one that does not
(``engine/scheduler.py``, module docstring): what passes what, what it may
not take from those it passes, and that a stream with no such pair is
scheduled exactly as first come first served."""

import pytest

from llm_d_tpu.engine.kv_cache import KVCacheManager
from llm_d_tpu.engine.request import Request, RequestState
from llm_d_tpu.engine.scheduler import (ScheduledRequest, Scheduler,
                                        SchedulerOutput)
from llm_d_tpu.ops.sampling import SamplingParams

BS = 4


def mk_req(rid, n_tokens, base=0, arrival=None, **kw):
    """``base`` makes the token ids (so the content hashes) a request's own."""
    r = Request(request_id=rid,
                prompt_token_ids=list(range(base, base + n_tokens)),
                sampling=SamplingParams(**kw))
    if arrival is not None:
        r.arrival_time = arrival
    return r


def mk_sched(num_blocks=512, budget=16, **kw):
    return Scheduler(KVCacheManager(num_blocks, BS, **kw.pop("kv", {})),
                     max_num_batched_tokens=budget, **kw)


def ran(out):
    return [(sr.request.request_id, sr.num_new_tokens) for sr in out.scheduled]


def step(s, out, max_tokens=10 ** 9):
    """What the engine does with a pass: the tokens are computed, a request
    whose known tokens are all computed emits one, full blocks are cached."""
    for sr in out.scheduled:
        r = sr.request
        r.num_computed_tokens += sr.num_new_tokens
        if r.num_computed_tokens >= r.num_tokens:
            r.output_token_ids.append(1)
        s.kv.cache_full_blocks(r)
        if len(r.output_token_ids) >= max_tokens:
            s.finish(r, RequestState.FINISHED_LENGTH)


def fifo_pass(s):
    """The rule before this one, re-stated: decode entries, then running
    chunks in admission order, then waiting requests first come first served
    (no cap, no preemption: the pools of these tests never fill)."""
    budget, out = s.max_num_batched_tokens, []
    for r in sorted(s.running, key=lambda r: not s._is_decode(r)):
        n = min(max(r.num_tokens - r.num_computed_tokens, 1), budget)
        if n and s.kv.allocate(r, r.num_computed_tokens + n) is not None:
            out.append(ScheduledRequest(r, n))
            budget -= n
    for r in sorted(s.waiting, key=lambda r: (r.slo_tier, r.priority,
                                              r.arrival_time)):
        if budget < 1 or len(s.running) >= s.max_num_seqs:
            break
        reuse, hit = s.kv.find_cached_prefix(r)
        n = min(r.num_tokens - hit, budget)
        if s.kv.allocate(r, hit + n, reuse) is None:
            break
        r.num_computed_tokens = hit
        s.waiting.remove(r)
        s.running.append(r)
        r.state = RequestState.RUNNING
        out.append(ScheduledRequest(r, n, is_first_schedule=True))
        budget -= n
    return SchedulerOutput(out, [], sum(x.num_new_tokens for x in out))


def test_short_ask_is_funded_ahead_of_a_running_long_chunk():
    s = mk_sched(budget=16)
    long = mk_req("long", 100)
    s.add_request(long)
    step(s, s.schedule())
    assert long.num_computed_tokens == 16
    s.add_request(mk_req("short", 6, base=1000))
    out = s.schedule()
    # The short ask first, whole; the long chunk takes the rest of the budget.
    assert ran(out) == [("short", 6), ("long", 10)]
    assert out.prefill_tokens == 16 and out.prefill_ahead_tokens == 6
    assert s.last_schedule_stats["prefill_ahead_tokens"] == 6
    assert s.last_schedule_stats["budget_left"] == 0
    step(s, out)
    out = s.schedule()          # "short" decodes now; nothing is passed
    assert ran(out) == [("short", 1), ("long", 15)]
    assert out.prefill_ahead_tokens == 0


def test_short_ask_passes_a_waiting_long_context_too():
    s = mk_sched(budget=16)
    for r in (mk_req("long", 100, arrival=1.0),
              mk_req("s1", 5, base=1000, arrival=2.0),
              mk_req("s2", 4, base=2000, arrival=3.0)):
        s.add_request(r)
    out = s.schedule()
    assert ran(out) == [("s1", 5), ("s2", 4), ("long", 7)]
    assert out.prefill_ahead_tokens == 9
    assert [sr.is_first_schedule for sr in out.scheduled] == [True] * 3


def test_arrival_order_inside_a_class_and_the_class_outside():
    s = mk_sched(budget=16)
    std_long = mk_req("std_long", 100, arrival=1.0)
    shed_short = mk_req("shed_short", 3, base=1000, arrival=2.0)
    shed_short.criticality = "sheddable"
    std_b = mk_req("std_b", 3, base=2000, arrival=4.0)
    std_a = mk_req("std_a", 3, base=3000, arrival=3.0)
    crit_short = mk_req("crit_short", 3, base=4000, arrival=5.0)
    crit_short.criticality = "critical"
    low_prio = mk_req("low_prio", 3, base=5000, arrival=0.5)
    low_prio.priority = 1
    for r in (std_long, shed_short, std_b, std_a, crit_short, low_prio):
        s.add_request(r)
    out = s.schedule()
    # Critical first; the standard shorts pass the standard long context in
    # the order they arrived; nothing of a lesser class (a higher priority
    # value, a sheddable tier) passes it, however early it came.
    assert ran(out) == [("crit_short", 3), ("std_a", 3), ("std_b", 3),
                        ("std_long", 7)]
    assert out.prefill_ahead_tokens == 6        # std_a, std_b


def _stream(kind):
    if kind == "every_prompt_fits_a_step":
        sizes = [5, 16, 9, 12, 3, 16, 7, 11, 2, 14, 8, 6, 13, 4, 10, 15]
    else:
        sizes = [40, 17, 65, 33, 18, 50, 29, 71, 23, 36]
    return [mk_req(f"{kind[0]}{i}", n, base=1000 * i, arrival=float(i))
            for i, n in enumerate(sizes)]


@pytest.mark.parametrize("kind", ["every_prompt_fits_a_step",
                                  "no_prompt_fits_a_step"])
@pytest.mark.parametrize("at_once", [1, 3, 16])
def test_a_stream_of_one_kind_is_scheduled_as_first_come_first_served(
        kind, at_once):
    """Where every prompt fits a step, or none does, the pass composes pass
    by pass exactly what the rule before composed (``fifo_pass``)."""
    passes = {}
    for rule in ("fifo", "short_first"):
        s = mk_sched(budget=16, max_num_seqs=6)
        todo, log = _stream(kind), []
        for _ in range(200):
            for r in todo[:at_once]:
                s.add_request(r)
            todo = todo[at_once:]
            out = fifo_pass(s) if rule == "fifo" else s.schedule()
            if not out.scheduled and not todo:
                break
            log.append([(sr.request.request_id, sr.num_new_tokens,
                         sr.is_first_schedule) for sr in out.scheduled])
            assert getattr(out, "prefill_ahead_tokens", 0) == 0
            step(s, out, max_tokens=3)
        assert not s.has_work() and not todo
        passes[rule] = log
    assert passes["short_first"] == passes["fifo"]
    assert len(passes["fifo"]) > 10


def test_a_long_context_loses_only_what_short_asks_took():
    """Short asks at half the budget, every pass: the long context advances
    by the other half every pass, and ends within the bound of the module
    docstring, ceil(R / ((1 - s) * B)) passes."""
    B, R = 16, 200
    s = mk_sched(budget=B, max_num_seqs=64)
    long = mk_req("long", R)
    s.add_request(long)
    passes = 0
    while long.num_computed_tokens < R:
        s.add_request(mk_req(f"s{passes}", B // 2, base=1000 * (passes + 1)))
        before = long.num_computed_tokens
        out = s.schedule()
        step(s, out, max_tokens=1)      # a short ask leaves with its token
        passes += 1
        if R - before > B:              # (its last chunk ends in a step: first)
            assert dict(ran(out))[f"s{passes - 1}"] == B // 2
            assert long.num_computed_tokens - before == B // 2
            assert out.prefill_ahead_tokens == B // 2
    assert 13 < passes <= -(-R // (B // 2))     # 25, against 13 alone


def test_the_walk_stops_at_an_ask_that_a_whole_step_could_finish():
    """12 tokens fit a step of 16 but not the 6 this pass has left: neither
    long nor short, so nobody passes it (it ends in the next step)."""
    s = mk_sched(budget=16)
    for i, n in enumerate((10, 12, 3)):
        s.add_request(mk_req(f"r{i}", n, base=1000 * i, arrival=float(i)))
    out = s.schedule()
    assert ran(out) == [("r0", 10), ("r1", 6)]
    assert out.prefill_ahead_tokens == 0


def test_a_passed_request_keeps_its_sequence_slot():
    s = mk_sched(budget=16, max_num_seqs=2)
    run = mk_req("run", 100)
    s.add_request(run)
    step(s, s.schedule())
    # One slot left, and an older long context waits for it.
    run.num_computed_tokens = 100
    run.output_token_ids.append(1)          # a decode entry now
    s.add_request(mk_req("long", 100, base=1000, arrival=1.0))
    s.add_request(mk_req("short", 4, base=2000, arrival=2.0))
    out = s.schedule()
    assert ran(out) == [("run", 1), ("long", 15)]
    assert out.prefill_ahead_tokens == 0


def test_a_short_ask_takes_pages_only_with_the_budgets_pages_to_spare():
    """13 usable pages; the long chunk holds 4 and the budget's 16 tokens
    may ask for 4 more.  A short ask of 2 pages passes with 7 free (2 + 4
    + 1), not with 6: the long chunk then finds every page it found
    before."""
    for spare, first in ((0, "short"), (1, "long")):
        s = mk_sched(num_blocks=14, budget=16)
        long = mk_req("long", 100)
        s.add_request(long)
        step(s, s.schedule())
        hold = mk_req("hold", 4 * (2 + spare), base=3000)
        assert s.kv.allocate(hold, hold.num_tokens) is not None
        assert s.kv.num_free_blocks == 7 - spare
        s.add_request(mk_req("short", 6, base=1000))
        out = s.schedule()
        assert ran(out)[0][0] == first
        assert dict(ran(out))["long"] == (10 if first == "short" else 16)
        assert not out.preempted and s.num_preemptions == 0


def test_cap_bounds_what_ends_in_a_step():
    """Under the engine's per-chunk cap a request ends in this step only if
    its remainder is within the cap."""
    s = mk_sched(budget=32)
    s.prefill_chunk_cap = lambda decode_tokens: 8
    for r in (mk_req("long", 100, arrival=1.0),
              mk_req("mid", 12, base=1000, arrival=2.0),
              mk_req("short", 8, base=2000, arrival=3.0)):
        s.add_request(r)
    out = s.schedule()
    assert ran(out) == [("short", 8), ("long", 8), ("mid", 8)]
    assert out.prefill_ahead_tokens == 8


def test_kept_look_up_is_not_walked_again_and_a_fresh_one_admits():
    s = mk_sched(budget=16)
    calls = []
    find = s.kv.find_cached_prefix
    s.kv.find_cached_prefix = lambda r: calls.append(r.request_id) or find(r)
    s.add_request(mk_req("a", 100, arrival=1.0))
    s.add_request(mk_req("b", 100, base=1000, arrival=2.0))
    for _ in range(5):
        step(s, s.schedule())
    # "a": the pass that first saw it, and the round that admitted it (the
    # same pass).  "b": looked up once, and never again while it waits.
    assert calls == ["a", "b", "a"]
    assert s.waiting[0].prefix_hit == ([], 0, 20)     # the block that decides


def _two_asks_of_one_context(doc=40, own=4):
    cold = mk_req("cold", doc + own)
    warm = mk_req("warm", doc)
    warm.prompt_token_ids += list(range(5000, 5000 + own))
    other = mk_req("other", 100, base=9000, arrival=cold.arrival_time - 1)
    return cold, warm, other


def test_an_ask_waits_for_its_context_in_flight_then_passes():
    """The warm ask arrives while its session's context is being computed:
    its hit is partial, and taking it would compute again what the running
    chunk is computing.  It is looked at again when the block that decides
    has been cached, and goes first then, with the whole hit."""
    s = mk_sched(budget=16)
    cold, warm, other = _two_asks_of_one_context()
    s.add_request(cold)
    step(s, s.schedule())                   # cold: 16 of 44
    s.add_request(other)
    s.add_request(warm)
    step(s, out := s.schedule())            # cold: 32 of 44
    assert ran(out) == [("cold", 16)] and warm.prefix_hit[:2] == ([], 0)
    step(s, out := s.schedule())            # cold ends: 12, the rest other's
    assert ran(out) == [("cold", 12), ("other", 4)]
    out = s.schedule()
    assert ran(out)[:2] == [("cold", 1), ("warm", 4)]
    assert warm.num_cached_prompt_tokens == 40
    assert out.prefill_ahead_tokens == 4


@pytest.mark.parametrize("grouped", [False, True])
def test_an_evicted_hit_falls_back_without_losing_budget_or_pages(grouped):
    """The warm ask's pages are evicted between its look-up and its
    admission: ``allocate`` refuses the stale hit, nothing is attached, and
    the ask takes the first-come-first-served round with a fresh look-up."""
    kv = dict(window_blocks=40, sliding_window=8) if grouped else {}
    s = mk_sched(num_blocks=40, budget=16, kv=kv)
    doc = mk_req("doc", 41)
    s.add_request(doc)
    for _ in range(3):
        step(s, s.schedule(), max_tokens=1)
    assert not s.has_work()                 # 10 blocks of "doc" are cached
    long = mk_req("long", 60, base=1000, arrival=1.0)
    s.add_request(long)
    step(s, s.schedule())
    warm = mk_req("warm", 40, arrival=2.0)
    warm.prompt_token_ids += [7000, 7001, 7002]
    s.add_request(warm)
    reuse, n_cached = s._prefix_hit(warm, False)
    assert n_cached == 40 and len(reuse) == 10
    # Something else takes every free page but six: the LRU gives up pages
    # of the document (in the grouped cache, the window group's too).
    hog = mk_req("hog", 4 * (s.kv.num_free_blocks - 6), base=3000)
    assert s.kv.allocate(hog, hog.num_tokens) is not None
    assert not s.kv.holds_prefix(warm, reuse)
    out = s.schedule()
    # Without the budget's pages to spare the ask passes nobody.
    assert ran(out) == [("long", 16)] and warm.prefix_hit[1] == 40
    step(s, out)
    s.kv.free(hog)
    free = [g.num_free for g in s.kv.groups]
    out = s.schedule()
    # Pages to spare now, but the hit is stale: nothing is attached for it,
    # the long chunk keeps its place and takes what it wants of the budget,
    # and the ask takes the rest after a fresh look-up (what the LRU left).
    assert ran(out) == [("long", 16)]
    assert out.prefill_ahead_tokens == 0 and out.prefill_tokens == 16
    assert s.last_schedule_stats["budget_left"] == 0
    assert warm in s.waiting and not warm.block_ids
    assert not warm.window_block_ids and warm.num_computed_tokens == 0
    assert warm.prefix_hit is None          # the stale answer is dropped
    assert [g.num_free for g in s.kv.groups] == [f - 4 for f in free]
    for _ in range(20):
        step(s, out)
        out = s.schedule()
        assert out.prefill_ahead_tokens == 0
    assert warm.num_computed_tokens >= 43
    assert warm.num_cached_prompt_tokens < 40


@pytest.mark.parametrize("grouped", [False, True])
def test_a_short_ask_without_pages_is_skipped_and_stops_nobody(grouped):
    kv = dict(window_blocks=12, sliding_window=8) if grouped else {}
    s = mk_sched(num_blocks=12, budget=16, kv=kv)
    long = mk_req("long", 100)
    s.add_request(long)
    step(s, s.schedule())                   # 4 of 11 pages
    hog = mk_req("hog", 16, base=3000)
    assert s.kv.allocate(hog, 16) is not None       # 3 pages left
    s.add_request(mk_req("short", 16, base=1000, arrival=1.0))
    s.add_request(mk_req("tiny", 2, base=2000, arrival=2.0))
    out = s.schedule()
    # Neither short ask has the budget's pages to spare beside its own, so
    # nothing is passed, nothing preempted, and the long chunk takes the
    # pages that are there.
    assert ran(out) == [("long", 12)]
    assert not out.preempted and s.num_preemptions == 0
    assert s.last_schedule_stats["budget_left"] == 4
    assert [r.request_id for r in s.waiting] == ["short", "tiny"]


def test_remote_prefill_consumers_one_token_is_short():
    """PD consumer: its KV arrived through the connector, one prompt token
    is computed locally; by the same rule it passes a long chunk."""
    s = mk_sched(budget=16)
    long = mk_req("long", 100)
    s.add_request(long)
    step(s, s.schedule())
    pd = mk_req("pd", 20, base=1000)
    pd.do_remote_prefill = True
    assert s.kv.allocate(pd, 20) is not None
    pd.num_computed_tokens = 19
    s.add_request(pd)
    out = s.schedule()
    assert ran(out) == [("pd", 1), ("long", 15)]
    assert not out.scheduled[0].is_first_schedule
    assert out.prefill_ahead_tokens == 1


def test_block_diffusion_a_denoising_pass_is_no_short_prefill():
    """A waiting request whose prompt holds no whole block goes straight to
    a denoising pass (a decode entry): it keeps its place where nobody was
    passed and passes nobody; a prompt of whole blocks that fits does."""
    s = Scheduler(KVCacheManager(256, BS), max_num_batched_tokens=16,
                  block_length=4)
    for r in (mk_req("long", 100, arrival=1.0),
              mk_req("open", 3, base=1000, arrival=2.0),
              mk_req("short", 8, base=2000, arrival=3.0)):
        s.add_request(r)
    out = s.schedule()
    assert ran(out) == [("short", 8), ("long", 8)]
    assert out.prefill_ahead_tokens == 8 and out.decode_tokens == 0
    step(s, out)
    out = s.schedule()      # short: its first denoising pass, a decode entry
    assert ran(out) == [("short", 4), ("long", 12)]
    assert out.scheduled[0].denoise and out.decode_tokens == 4
    s2 = Scheduler(KVCacheManager(256, BS), max_num_batched_tokens=16,
                   block_length=4)
    s2.add_request(mk_req("open", 3, arrival=1.0))
    s2.add_request(mk_req("short", 8, base=2000, arrival=2.0))
    out = s2.schedule()
    assert ran(out) == [("open", 4), ("short", 8)]
    assert out.scheduled[0].denoise and out.prefill_ahead_tokens == 0


def test_speculative_look_ahead_is_funded_before_any_short_ask():
    s = mk_sched(budget=16)
    s.spec_lookahead = lambda r: 3
    d = mk_req("d", 4)
    s.add_request(d)
    step(s, s.schedule())
    s.add_request(mk_req("long", 100, base=1000, arrival=1.0))
    s.add_request(mk_req("short", 5, base=2000, arrival=2.0))
    out = s.schedule()
    assert ran(out) == [("d", 1), ("short", 5), ("long", 7)]
    assert out.scheduled[0].num_draft_tokens == 3
    assert (out.decode_tokens, out.spec_tokens, out.prefill_tokens,
            out.prefill_ahead_tokens) == (1, 3, 12, 5)
    assert s.last_schedule_stats["budget_left"] == 0


def test_a_short_ask_is_never_a_preemption_victim_of_the_chunk_it_passed():
    """The long chunk, funded after the short ask, may preempt for pages:
    never the request scheduled earlier in the same pass."""
    s = mk_sched(num_blocks=12, budget=16)      # 11 usable pages
    long = mk_req("long", 100)
    s.add_request(long)
    step(s, s.schedule())                       # 4 pages
    s.add_request(mk_req("short", 4, base=1000))
    out = s.schedule()                          # 1 + 4 + 1 <= 7 free
    assert ran(out) == [("short", 4), ("long", 12)]
    assert not out.preempted and s.num_preemptions == 0
    assert {r.request_id for r in s.running} == {"long", "short"}
