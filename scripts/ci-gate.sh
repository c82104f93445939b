#!/usr/bin/env bash
# Merge gate (reference doctrine: CONTRIBUTING.md:135 "gate merges on
# compilation and passing tests"): compile every module, lint the config
# surface, run the fast test tier.  The slow tier (heavy numerical-parity
# oracles) runs pre-release via scripts/run-all-tests.sh.
set -euo pipefail
cd "$(dirname "$0")/.."
python -m compileall -q llm_d_tpu tests scripts __graft_entry__.py
# llmd-check: the contract-enforcing static-analysis suite (wire headers,
# metric registry, env knobs, jit/host-sync hygiene, async blocking,
# Pallas DMA invariants, Dockerfiles).  Fail-fast BEFORE any test
# collection: contract drift is cheaper to report in <1s than to debug
# through a red integration suite.  (scripts/lint-envvars.py and
# lint-dockerfile.py are absorbed as passes ENV / DOCKER.)
python scripts/llmd_check.py
# The analyzer's own gate (seeded-violation/fixed-twin per RACE/TASK/
# PAIR/FAULT rule + the PR-9 slot-leak mutation check): a rule that can
# no longer demonstrably fire is indistinguishable from one that never
# runs, so this suite runs fail-fast right behind the checker itself.
python -m pytest tests/test_llmd_race.py -q
for f in scripts/*.sh docs/monitoring/scripts/*.sh; do bash -n "$f"; done
# Resilience + lifecycle gates first, fail-fast (injected fault schedules
# against the sim stack + tiny engines; deadline/SLO-class/drain contract;
# docs/resilience.md): a green happy path with a broken failure or
# lifecycle path must not merge.  The full tier then skips them so each
# suite runs exactly once.
python -m pytest tests/test_chaos.py -q
python -m pytest tests/test_lifecycle.py -q
# Mid-stream recovery gate (journaled decode failover): engine death
# under sustained streaming load must produce ZERO client-visible
# stream breaks — restore-or-recompute resume, offset dedupe, breaker
# exclusion, and the LLMD_STREAM_RESUME=0 fail-fast contract.
python -m pytest tests/test_stream_recovery.py -q
# llmd-trace gate (end-to-end request tracing): connected span trees
# across the sim stack, resume-attempt spans under the original trace
# id with zero orphans after a seeded engine kill, the TTFT
# decomposition summing to measured TTFT within 5%, sampling knobs,
# the TRACE coverage rules, and the no-host-sync JIT meta-guard.
python -m pytest tests/test_tracing.py -q
# Attention numerics fail-fast: the served attention entry points against
# plain f32 attention at the cells' head geometry, pool sizing from an HBM
# budget: a silent KV-numerics break must not merge.
python -m pytest tests/test_attention_oplevel.py tests/test_kv_cache.py -q
# Quantized EP/TP collective contract fail-fast (round 10: int8
# dispatch/combine wire + quantized allreduce parity, scale-plane
# alignment, per-collective accuracy bounds on real routed traces,
# env-knob fallback): a silent wire-numerics break must not merge.
python -m pytest tests/test_collective_quant.py -q
# Speculative-decode contract fail-fast (round 12: MTP draft-and-verify
# — greedy + seeded byte-identical parity vs non-spec decode, rejection
# rollback leaving the paged-KV pool leak-free and the prefix cache
# accepted-content-only, adaptive-K backoff, the LLMD_SPEC_DECODE=off
# kill switch, chaos resume during spec decode with exact multi-token
# journal offsets, and the no-new-host-sync JIT meta-gate).
python -m pytest tests/test_spec_decode.py -q
# Mixed-round fusion contract fail-fast (round 15: ONE fused program for
# prefill-chunk + decode + spec-verify rows — byte-identical parity vs
# solo runs (greedy AND seeded, spec on AND off), spec-stays-on across
# prefill joins with zero draft rollbacks, rejected-draft leak-freedom
# inside fused rounds, decode-priority budget invariants, adaptive chunk
# sizing, and the LLMD_PREFILL_CHUNK=<n> kill switch).
python -m pytest tests/test_mixed_fusion.py -q
# Everything-on contract fail-fast (round 16: spec decode folded into
# the fused-multistep pipeline — byte-identical parity of the full
# composition (spec + mixed fusion + N-round multistep + async +
# stacked-dp + EPLB) vs each feature alone and all-off, logprobs rows
# on the spec path, per-shard rollback leak-freedom, the ~N x
# step/dispatch amortization counters, LLMD_SPEC_STRICT refusing a
# degraded boot, and chaos resume from a kill MID N-round dispatch).
python -m pytest tests/test_everything_on.py -q
# Cluster chaos-testbed fail-fast (round 18: discrete-event cluster sim
# with the REAL EPP/datastore/breaker/flow-control/WVA stack in the
# loop — zone kills and P<->D partitions with zero client-visible
# critical breaks, breaker convergence on dead endpoints, closed-loop
# autoscaling beating the identical-seed baseline, and the
# byte-identical-scoreboard determinism contract).
python -m pytest tests/test_cluster_sim.py -q
# KV-placement contract fail-fast (round 20: transfer-cost-aware prefix
# placement — restorable_prefix source ranking, LRU refresh-on-query,
# TransferCostModel analytic prior + ridge fit + env knobs, cost-scorer
# saturation un-pinning a loaded full-match replica, verdict header +
# metrics): the global prefix-cache fabric must not silently re-pin.
python -m pytest tests/test_kv_placement.py -q
# Live-EPLB contract fail-fast (round 17: delta-plan migration — budget
# and hysteresis invariants, atomic double-buffered flip with exact
# post-flip weights, byte-identical greedy AND seeded parity across a
# mid-stream migration, and a chaos kill landing mid-staging leaving
# the serving table entirely old and the KV pool leak-free).
python -m pytest tests/test_eplb.py tests/test_eplb_integration.py -q
python -m pytest tests/ --ignore=tests/test_chaos.py \
    --ignore=tests/test_lifecycle.py --ignore=tests/test_kv_quant.py \
    --ignore=tests/test_mla_quant.py \
    --ignore=tests/test_collective_quant.py \
    --ignore=tests/test_stream_recovery.py \
    --ignore=tests/test_llmd_race.py \
    --ignore=tests/test_spec_decode.py \
    --ignore=tests/test_mixed_fusion.py \
    --ignore=tests/test_everything_on.py \
    --ignore=tests/test_eplb.py \
    --ignore=tests/test_eplb_integration.py \
    --ignore=tests/test_cluster_sim.py \
    --ignore=tests/test_kv_placement.py \
    --ignore=tests/test_tracing.py
