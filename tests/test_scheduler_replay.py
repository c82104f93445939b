"""The benchmark's pinned traffic replayed through the REAL scheduler and KV
manager in virtual time (no device): a step costs 13.3 ms + 81 ms x
(tokens / 2,048), ``mellum2.ide``'s measured decode step and mixed step
(ledger, PR 46).  What the short-first rule (``engine/scheduler.py``) buys
on ``ide-sessions``, and that ``batch`` and ``longdoc-8`` are scheduled pass
for pass as first come first served."""

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent.parent / "benchmarks"
sys.path.insert(0, str(BENCH))

import traffic  # noqa: E402
from llm_d_tpu.engine.kv_cache import KVCacheManager  # noqa: E402
from llm_d_tpu.engine.request import Request, RequestState  # noqa: E402
from llm_d_tpu.engine.scheduler import Scheduler  # noqa: E402
from llm_d_tpu.ops.sampling import SamplingParams  # noqa: E402
from test_scheduler_short_first import fifo_pass  # noqa: E402

SEED, VOCAB, BUDGET = 7, 50000, 2048


def replay(mix_name, rule, seconds, rate=None):
    """Play ``seconds`` of the mix's window; returns each request's wait for
    its first token, the passes, and the counts."""
    mix = json.loads((BENCH / "traffic" / f"{mix_name}.json").read_text())
    plan = traffic.build_schedule(mix, SEED, seconds, "window", rate=rate)
    prefix = traffic.shared_prefix(mix, SEED, VOCAB)
    todo = [Request(f"r{i}", traffic.prompt_ids(prefix, q, SEED, "window", i,
                                                VOCAB),
                    SamplingParams(max_tokens=q["max_tokens"]),
                    arrival_time=q["due"])
            for i, q in enumerate(plan["requests"])]
    limit = {r.request_id: q["max_tokens"]
             for r, q in zip(todo, plan["requests"])}
    kv = KVCacheManager(20000, 32)
    walks = []
    find = kv.find_cached_prefix
    kv.find_cached_prefix = lambda r: walks.append(r.request_id) or find(r)
    s = Scheduler(kv, max_num_seqs=64, max_num_batched_tokens=BUDGET,
                  max_model_len=10 ** 6)
    closed, idle = plan["loop"] == "closed", plan["clients"]
    t, nxt, passes, ahead, wait = 0.0, 0, [], 0, {}
    while True:
        while nxt < len(todo) and t < seconds and (
                idle if closed else todo[nxt].arrival_time <= t):
            if closed:
                idle -= 1
                todo[nxt].arrival_time = t
            s.add_request(todo[nxt])
            nxt += 1
        out = fifo_pass(s) if rule == "fifo" else s.schedule()
        if not out.scheduled:
            if closed or nxt == len(todo):
                break
            t = max(t, todo[nxt].arrival_time)
            continue
        ahead += getattr(out, "prefill_ahead_tokens", 0)
        passes.append([(sr.request.request_id, sr.num_new_tokens)
                       for sr in out.scheduled])
        t += 0.0133 + 0.081 * out.total_tokens / BUDGET
        for sr in out.scheduled:
            r = sr.request
            r.num_computed_tokens += sr.num_new_tokens
            if r.num_computed_tokens >= r.num_tokens:
                wait.setdefault(r.request_id, t - r.arrival_time)
                r.output_token_ids.append(1)
            kv.cache_full_blocks(r)
            if len(r.output_token_ids) >= limit[r.request_id]:
                s.finish(r, RequestState.FINISHED_LENGTH)
                idle += 1
    return {"wait": wait, "passes": passes, "ahead": ahead, "walks": walks,
            "prefill": sum(n for p in passes for _, n in p)}


def test_ide_sessions_warm_asks_leave_the_tail():
    """``mellum2.ide``'s pinned window: 110 requests, 11 of them cold, 7 of
    those due inside 1.7 s.  First come first served, the warm asks of those
    seconds wait for every chunk of the burst; short first, a warm ask waits
    for its own session's context and no other."""
    old = replay("ide-sessions", "fifo", 45.0, rate=0.24)
    new = replay("ide-sessions", "short_first", 45.0, rate=0.24)
    assert len(old["wait"]) == len(new["wait"]) == 110
    w_old, w_new = sorted(old["wait"].values()), sorted(new["wait"].values())
    # The parent's shape (ISSUE 47's sizing: p50 49 ms, rank 105 3.27 s).
    assert 0.03 < w_old[55] < 0.06 and 3.1 < w_old[104] < 3.4
    # Rank 105 of 110 falls by more than a fifth (3.27 s -> 2.53 s): it is
    # a warm ask of a session whose own cold context is fifth in the burst,
    # which it cannot go before (ISSUE 47 sized a quarter and more with every
    # warm ask's hit in place on arrival; three of the burst's four are not).
    assert w_new[104] < 0.8 * w_old[104]
    assert w_new[103] < 0.76 * w_old[103]
    # No cold context is starved: the largest wait rises by under 5 %.
    assert w_new[-1] < 1.05 * w_old[-1]
    assert abs(w_new[55] - w_old[55]) < 0.02 * w_old[55]
    # Every warm ask that waited over a second, first come first served,
    # waits less now, and none of the others more than a step longer.
    warm = [k for k, w in old["wait"].items() if w > 1.0
            and new["wait"][k] < w - 0.2]
    assert len(warm) >= 4
    assert max(new["wait"][k] - w for k, w in old["wait"].items()) < 0.3
    # A few percent of the prefill went ahead, and the same work was done.
    assert 0 < new["ahead"] < 0.05 * new["prefill"]
    assert abs(new["prefill"] - old["prefill"]) < 0.01 * old["prefill"]
    # No pass walks a waiting context's pages again: a request is looked up
    # when first seen, when the first-come-first-served round admits it, and
    # between those only when the one block it asks about has appeared (134
    # walks in 2,272 passes, against 110 first come first served).
    assert max(new["walks"].count(k) for k in new["wait"]) <= 5
    assert len(new["walks"]) < 1.3 * len(old["walks"])


@pytest.mark.parametrize("mix", ["batch", "longdoc-8"])
def test_a_mix_of_one_kind_replays_as_first_come_first_served(mix):
    """Every prompt of ``batch`` fits a step and none of ``longdoc-8`` does:
    identical passes, and nothing funded ahead."""
    old = replay(mix, "fifo", 12.0)
    new = replay(mix, "short_first", 12.0)
    assert new["passes"] == old["passes"] and len(old["passes"]) > 300
    assert new["ahead"] == 0 and new["wait"] == old["wait"]
