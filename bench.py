"""Single-chip serving benchmark: dense + MoE, through the REAL engine path.

Two models run through the full engine (continuous batching, paged KV,
on-device sampling, fused async decode) on ONE TPU chip.  Without a TPU the
run fails (``_require_tpu``): a CPU timing is not a measurement of this
system.  One process per chip.

  - ``deepseek-v3-bench`` — the north-star proxy: DeepSeek-V3's serving
    structure (MLA latent cache, sigmoid group-limited routing, shared
    expert, top-8-of-64 routed experts, int8 expert weights) scaled to one
    chip's HBM.  The headline metric is its best decode tok/s/chip, the
    same axis as the reference's wide-EP headline (2,200 output tok/s/GPU,
    DeepSeek-R1 on 32x H200 — BASELINE.md; /root/reference/README.md:20).
  - ``llama3-1b`` — the dense regression canary tracked since round 1.

Methodology: per model ONE engine is built; each batch size gets a full
warmup pass (identical shapes, disjoint token ids) so every bucket and the
fused multistep program are compiled before timing — steady-state numbers,
not XLA compile time.  Extras carry MFU and HBM-roofline attribution per
batch size so regressions are attributable.  The persistent compilation
cache (``llm_d_tpu/utils/compile_cache.py``: ``JAX_COMPILATION_CACHE_DIR``
or ``<checkout>/.jax_cache``) makes repeat runs cheap.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tok/s/chip", "vs_baseline": r,
   "extras": {...}}
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

import jax

from llm_d_tpu.utils.compile_cache import configure_compile_cache

configure_compile_cache()

from llm_d_tpu.engine.engine import EngineConfig, EngineCore
from llm_d_tpu.engine.request import Request
from llm_d_tpu.ops.sampling import SamplingParams

BASELINE_TOK_S_PER_CHIP = 2200.0
# Round-5 verdict bar: MoE decode must reach this share of its own HBM
# roofline at bs256 (the yield target the weight-DMA overlap exists to
# clear; 36.9% measured in round 5).
MOE_ROOFLINE_TARGET_PCT = 55.0

# (bf16 peak FLOP/s, HBM bytes/s) per chip, keyed by a lower-cased
# substring of ``device_kind``.  Source: Google Cloud TPU documentation,
# "System architecture" pages per generation (v5e: 197 TFLOP/s bf16,
# 819 GB/s HBM).  A device that is not in the table is an error, not a
# default: a roofline share against the wrong peak is a wrong number.
_CHIP_SPECS = {
    "v3": (123e12, 900e9),
    "v4": (275e12, 1228e9),
    "v5 lite": (197e12, 819e9),
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v5": (459e12, 2765e9),
    "v6 lite": (918e12, 1638e9),
    "v6e": (918e12, 1638e9),
}


def _chip_spec(device) -> tuple:
    kind = getattr(device, "device_kind", "").lower()
    for key, spec in _CHIP_SPECS.items():
        if key in kind:
            return spec
    raise RuntimeError(
        f"no published peaks for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to _CHIP_SPECS with its "
        f"source rather than assuming another chip's")


def _require_tpu() -> None:
    """The benchmark measures the chip; it does not fall back to the CPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind}).  Nothing was measured.")


def _param_bytes(params) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))


def _active_param_count(c) -> int:
    """Per-token *active* parameters (MoE: only routed-to experts count)."""
    total = 0
    dh = c.head_dim_
    Lm = c.num_layers - c.first_dense_layers if c.is_moe else 0
    Ld = c.num_layers - Lm
    # Attention per layer.
    if c.use_mla:
        qh = c.qk_nope_head_dim + c.qk_rope_head_dim
        attn = (c.hidden_size * c.q_lora_rank
                + c.q_lora_rank * c.num_heads * qh
                if c.q_lora_rank else c.hidden_size * c.num_heads * qh)
        attn += c.hidden_size * (c.kv_lora_rank + c.qk_rope_head_dim)
        attn += c.kv_lora_rank * c.num_heads * (c.qk_nope_head_dim
                                                + c.v_head_dim)
        attn += c.num_heads * c.v_head_dim * c.hidden_size
    else:
        attn = c.hidden_size * dh * (c.num_heads + 2 * c.num_kv_heads) \
            + c.num_heads * dh * c.hidden_size
    total += attn * c.num_layers
    # Dense MLPs.
    total += Ld * 3 * c.hidden_size * c.intermediate_size
    # MoE layers: routed (k experts) + shared.
    if c.is_moe:
        per_expert = 3 * c.hidden_size * c.moe_intermediate_size
        total += Lm * (c.num_experts_per_tok * per_expert
                       + c.num_shared_experts * per_expert
                       + c.hidden_size * c.num_experts)
    return total


def _run_workload(engine, reqs):
    """Returns (prefill_seconds, prefill_steps, decode_seconds,
    decode_tokens)."""
    for r in reqs:
        engine.add_request(r)
    n_prefill_steps = 0
    t0 = time.perf_counter()
    while any(r.num_computed_tokens < r.num_prompt_tokens for r in reqs):
        engine.step()
        n_prefill_steps += 1
    t_prefill = time.perf_counter() - t0

    tokens_before = sum(len(r.output_token_ids) for r in reqs)
    t1 = time.perf_counter()
    while engine.has_work():
        engine.step()
    t_decode = time.perf_counter() - t1
    tokens_after = sum(len(r.output_token_ids) for r in reqs)
    return t_prefill, n_prefill_steps, t_decode, tokens_after - tokens_before


def _make_reqs(tag, n, prompt_len, decode_steps, offset):
    return [
        Request(
            request_id=f"{tag}-{i}",
            prompt_token_ids=[(7 * i + 13 * j + offset) % 32000 + 1
                              for j in range(prompt_len)],
            sampling=SamplingParams(temperature=0.0,
                                    max_tokens=decode_steps + 1,
                                    ignore_eos=True),
        )
        for i in range(n)
    ]


def bench_model(model: str, batch_sizes, prompt_len=128, decode_steps=128,
                quantization=None, repeats=None):
    """One engine, a workload per batch size (warmup + timed).  Returns
    {bs: {prefill_tok_s, decode_tok_s, ...}} plus roofline attribution.

    ``repeats`` maps batch size -> N timed runs (default 1): gated
    headline numbers use median-of-N with a printed min/max band so the
    regression gate can tell a real drop from the chip's measured ±4-6%
    run-to-run variance (VERDICT r5 #4)."""
    max_bs = max(batch_sizes)
    # KV sized to the workload + slack: expert weights take most of the
    # chip's 16 GB, so a fixed large pool OOMs the MoE run.
    block_size = 64     # fewer, larger page DMAs (~2% over bs=32; 128 measured worse)
    num_scheduler_steps = 32
    blocks_per_seq = -(-(prompt_len + decode_steps + num_scheduler_steps + 1)
                       // block_size)
    cfg = EngineConfig(
        model=model,
        block_size=block_size,
        num_blocks=max_bs * blocks_per_seq + block_size,
        max_num_seqs=max_bs,
        max_num_batched_tokens=8192,
        num_scheduler_steps=num_scheduler_steps,
        async_scheduling=True,
        # Disjoint warmup/timed prompts must not share KV anyway; disabling
        # removes any chance the warmup pass warms more than the compiles.
        enable_prefix_caching=False,
        quantization=quantization,
    )
    engine = EngineCore(cfg)
    c = engine.model_config
    peak_flops, hbm_bw = _chip_spec(jax.devices()[0])
    param_bytes = _param_bytes(engine.params)
    embed_bytes = c.vocab_size * c.hidden_size * 2
    active = _active_param_count(c)
    head_flops = 2 * c.vocab_size * c.hidden_size
    # Decode HBM roofline: each step reads every (quantized) weight byte
    # except the embedding table (only S rows gathered) plus each
    # sequence's KV context.  MoE note: at bs*k >= E every expert is
    # touched every step, so the full expert set streams regardless of
    # batch size — the wide-EP decode economics this bench exists to show.
    kv_row = engine.kv_bytes_per_token_layer()   # bytes/token/layer

    out = {}
    for bs in batch_sizes:
        offset = 1000 * bs
        _run_workload(engine, _make_reqs(
            f"warm{bs}", bs, prompt_len, decode_steps, 50000 + offset))
        n_rep = (repeats or {}).get(bs, 1)
        prefill_runs, decode_runs = [], []
        n_prefill_steps = 1
        for rep in range(n_rep):
            # Disjoint token ids per repeat: identical-argument jitted
            # calls can be served from a remote cache (perf-notes-r5).
            t_prefill, n_prefill_steps, t_decode, decode_tokens = \
                _run_workload(
                    engine, _make_reqs(f"bench{bs}r{rep}", bs, prompt_len,
                                       decode_steps, offset + 97 * rep))
            prefill_runs.append(bs * prompt_len / t_prefill)
            decode_runs.append(decode_tokens / t_decode)
        prompt_tokens = bs * prompt_len
        prefill_tok_s = statistics.median(prefill_runs)
        decode_tok_s = statistics.median(decode_runs)
        t_prefill = prompt_tokens / prefill_tok_s
        t_decode = bs * decode_steps / decode_tok_s

        body_flops = 2 * active
        prefill_mfu = (body_flops * prompt_tokens + head_flops * bs) \
            / t_prefill / peak_flops
        decode_mfu = decode_tok_s * (body_flops + head_flops) / peak_flops
        avg_ctx = prompt_len + decode_steps // 2
        kv_bytes_per_step = bs * c.num_layers * avg_ctx * kv_row
        step_bytes = param_bytes - embed_bytes + kv_bytes_per_step
        roofline_tok_s = hbm_bw / step_bytes * bs
        out[bs] = {
            # The KV byte stream one decode step reads at avg context.
            "kv_bytes_per_step": kv_bytes_per_step,
            "prefill_tok_s": round(prefill_tok_s, 1),
            "decode_tok_s": round(decode_tok_s, 1),
            "prefill_mfu_pct": round(100 * prefill_mfu, 2),
            "decode_mfu_pct": round(100 * decode_mfu, 2),
            "decode_hbm_roofline_pct": round(
                100 * decode_tok_s / roofline_tok_s, 1),
            "decode_ms_per_step": round(1000 * t_decode / decode_steps, 2),
            # Per-ENGINE-step prefill cost (chunked prefill: a step is
            # one max_num_batched_tokens-bounded forward), matching
            # decode_ms_per_step.
            "prefill_ms_per_step": round(
                1000 * t_prefill / max(n_prefill_steps, 1), 2),
            "prefill_steps": n_prefill_steps,
        }
        if n_rep > 1:
            out[bs]["decode_tok_s_runs"] = [round(v, 1) for v in decode_runs]
            out[bs]["decode_tok_s_band"] = [round(min(decode_runs), 1),
                                            round(max(decode_runs), 1)]
            # Roofline YIELD band (same runs, divided by the model's own
            # roofline): the gated quantity for the MoE bs256 metric —
            # yield regressions must fail the gate even when a bigger
            # batch inflates raw tok/s.
            out[bs]["decode_hbm_roofline_pct_band"] = [
                round(100 * min(decode_runs) / roofline_tok_s, 1),
                round(100 * max(decode_runs) / roofline_tok_s, 1)]
            out[bs]["decode_band_spread_pct"] = round(
                100 * (max(decode_runs) - min(decode_runs))
                / max(decode_tok_s, 1e-9), 1)
            out[bs]["prefill_tok_s_runs"] = [round(v, 1)
                                             for v in prefill_runs]
            out[bs]["prefill_tok_s_band"] = [round(min(prefill_runs), 1),
                                             round(max(prefill_runs), 1)]
            out[bs]["prefill_band_spread_pct"] = round(
                100 * (max(prefill_runs) - min(prefill_runs))
                / max(prefill_tok_s, 1e-9), 1)
    out["param_bytes"] = param_bytes
    out["kv_bytes_per_token_layer"] = kv_row
    out["num_blocks"] = engine.config.num_blocks
    return out


# Speculative-decode bench point (round 12): draft depth and the seeded
# per-draft acceptance rate the gated accepted-tok/s metric is quoted at.
# 0.7/draft is the DeepSeek-V3 MTP ballpark (their reported 85-90% is
# first-draft acceptance; the geometric prefix at 0.7 emits ~2.2
# tokens/step at K=4).  The REAL verifier replaces the coin in serving —
# spec_fixed_accept exists so the metric measures the engine, not the
# random-init drafter's ~0% hit rate.
SPEC_BENCH_K = 4
SPEC_BENCH_ACCEPT = 0.7


def bench_spec(model: str, bs: int, K: int, fixed_accept: float,
               prompt_len: int = 128, decode_steps: int = 128,
               quantization=None, repeats: int = 1) -> dict:
    """Accepted tok/s through the draft-and-verify engine at a fixed
    seeded acceptance rate.

    One spec engine (spec_k=K, spec_fixed_accept so accepted-length
    schedules are deterministic and drafter-independent), warmup pass
    then median-of-N timed runs — same methodology as bench_model.  The
    quantity is ACCEPTED output tokens per second: every emitted token
    passed target-model verification, so this is client-visible
    throughput, directly comparable to the non-spec decode_tok_s."""
    block_size = 64
    blocks_per_seq = -(-(prompt_len + decode_steps + K + 2) // block_size)
    cfg = EngineConfig(
        model=model,
        block_size=block_size,
        num_blocks=bs * blocks_per_seq + block_size,
        max_num_seqs=bs,
        max_num_batched_tokens=8192,
        num_scheduler_steps=1,          # spec owns the multi-token step
        enable_prefix_caching=False,
        quantization=quantization,
        spec_k=K,
        spec_fixed_accept=fixed_accept,
    )
    engine = EngineCore(cfg)
    assert engine.spec_k == K, "spec decode failed to arm"
    runs, acc_rates = [], []
    for rep in range(max(1, repeats) + 1):      # rep 0 = warmup
        offset = 1000 * bs + 97 * rep
        reqs = _make_reqs(f"spec{K}b{bs}r{rep}", bs, prompt_len,
                          decode_steps, offset)
        _, _, t_decode, decode_tokens = _run_workload(engine, reqs)
        if rep == 0:
            continue
        runs.append(decode_tokens / t_decode)
        drafted = sum(r.spec_drafted for r in reqs)
        accepted = sum(r.spec_accepted for r in reqs)
        acc_rates.append(accepted / drafted if drafted else 0.0)
    tok_s = statistics.median(runs)
    row = {
        "decode_tok_s": round(tok_s, 1),        # accepted tokens only
        "spec_k": K,
        "fixed_accept": fixed_accept,
        "spec_acceptance_pct": round(
            100 * statistics.median(acc_rates), 1),
        # Accepted tokens per engine step = 1 + measured acceptance * K
        # in expectation; reported from the same runs' bookkeeping.
        "accepted_tokens_per_step": round(
            1 + statistics.median(acc_rates) * K, 2),
    }
    if len(runs) > 1:
        row["decode_tok_s_runs"] = [round(v, 1) for v in runs]
        row["decode_tok_s_band"] = [round(min(runs), 1),
                                    round(max(runs), 1)]
    return {bs: row}


# Mixed-round fusion bench point (round 15): the prefill-join fraction
# the gated moe_mixed_tok_s_bs256 metric is quoted at (a quarter of the
# decode batch re-prefills during the timed window — the steady
# churn a serving replica actually sees, not a pure-decode idealization).
MIXED_BENCH_SHARE = 0.25


def bench_mixed(model: str, bs: int, K: int, fixed_accept: float,
                prompt_len: int = 128, decode_steps: int = 128,
                quantization=None, repeats: int = 1,
                shares=(0.0, MIXED_BENCH_SHARE, 0.5)) -> dict:
    """Fused mixed-round throughput: a bs-wide spec-decode batch with
    prefill requests JOINING mid-decode (round 15).

    For each prefill share s, int(s*bs) fresh prompts are injected one
    per step into a decoding batch and the whole window is timed —
    every injected prompt's chunks ride the SAME fused program as the
    decode/verify rows, so this measures what the single-dispatch round
    (one expert-weight stream for both populations) delivers under
    churn.  Reports total emitted tok/s and the p99 step time (the
    decode rows' inter-token latency) per share; the s=MIXED_BENCH_SHARE
    point is the gated ``moe_mixed_tok_s_bs256`` number."""
    block_size = 64
    n_seqs = bs + int(max(shares) * bs)
    blocks_per_seq = -(-(prompt_len + decode_steps + K + 2) // block_size)
    cfg = EngineConfig(
        model=model,
        block_size=block_size,
        num_blocks=n_seqs * blocks_per_seq + block_size,
        max_num_seqs=n_seqs,
        max_num_batched_tokens=8192,
        num_scheduler_steps=1,          # spec owns the multi-token step
        enable_prefix_caching=False,
        quantization=quantization,
        spec_k=K,
        spec_fixed_accept=fixed_accept,
    )
    engine = EngineCore(cfg)
    assert engine.spec_k == K, "spec decode failed to arm"

    def run_share(share, tag, offset):
        reqs = _make_reqs(f"{tag}base", bs, prompt_len, decode_steps,
                          offset)
        for r in reqs:
            engine.add_request(r)
        while any(r.num_computed_tokens < r.num_prompt_tokens
                  for r in reqs):
            engine.step()
        n_join = int(share * bs)
        joiners = _make_reqs(f"{tag}join", n_join, prompt_len,
                             decode_steps // 2, offset + 7777)
        all_reqs = reqs + joiners
        before = sum(len(r.output_token_ids) for r in all_reqs)
        step_ms = []
        j = 0
        t0 = time.perf_counter()
        while engine.has_work() or j < n_join:
            if j < n_join:
                engine.add_request(joiners[j])
                j += 1
            s0 = time.perf_counter()
            engine.step()
            step_ms.append(1e3 * (time.perf_counter() - s0))
        dt = time.perf_counter() - t0
        tokens = sum(len(r.output_token_ids) for r in all_reqs) - before
        step_ms.sort()
        p99 = step_ms[min(len(step_ms) - 1, int(0.99 * len(step_ms)))]
        return tokens / dt, p99

    table = {}
    gated_runs = []
    for rep in range(max(1, repeats) + 1):      # rep 0 = warmup
        offset = 2000 * bs + 131 * rep
        for share in shares:
            tok_s, p99 = run_share(
                share, f"mix{int(100 * share)}r{rep}",
                offset + int(1000 * share))
            if rep == 0:
                continue
            row = table.setdefault(
                f"{share:.2f}", {"tok_s_runs": [], "tpot_p99_ms_runs": []})
            row["tok_s_runs"].append(round(tok_s, 1))
            row["tpot_p99_ms_runs"].append(round(p99, 3))
            if share == MIXED_BENCH_SHARE:
                gated_runs.append(tok_s)
    for row in table.values():
        row["tok_s"] = round(statistics.median(row["tok_s_runs"]), 1)
        row["tpot_p99_ms"] = round(
            statistics.median(row["tpot_p99_ms_runs"]), 3)
    med = statistics.median(gated_runs)
    gated = {
        "decode_tok_s": round(med, 1),          # emitted under churn
        "spec_k": K,
        "fixed_accept": fixed_accept,
        "prefill_share": MIXED_BENCH_SHARE,
    }
    if len(gated_runs) > 1:
        gated["decode_tok_s_runs"] = [round(v, 1) for v in gated_runs]
        gated["decode_tok_s_band"] = [round(min(gated_runs), 1),
                                      round(max(gated_runs), 1)]
    return {bs: gated, "tpot_vs_prefill_share": table}


# Everything-on bench point (round 16): the headline N (rounds per
# dispatch) the gated moe_decode_everything_on_bs256 metric is quoted
# at, and the sweep the extras.rounds_per_dispatch table walks.
EVERYTHING_BENCH_ROUNDS = 4
EVERYTHING_ROUNDS_SWEEP = (1, 2, 4, 8)


def bench_everything_on(model: str, bs: int, K: int, fixed_accept: float,
                        prompt_len: int = 128, decode_steps: int = 128,
                        quantization=None, repeats: int = 1,
                        rounds_sweep=EVERYTHING_ROUNDS_SWEEP) -> tuple:
    """ACCEPTED tok/s with the whole round-16 composition on at once:
    spec decode + mixed fusion + fused multistep (num_scheduler_steps=N)
    + async double-buffering + EPLB, one engine per N.

    Returns (gated_sweep, rounds_table): the gated point is quoted at
    N=EVERYTHING_BENCH_ROUNDS (same accepted-tok/s quantity as
    bench_spec — every emitted token passed target verification); the
    table sweeps N over ``rounds_sweep`` and reports the measured
    steps-per-dispatch ratio alongside throughput, the host-round-trip
    amortization the fused-multistep pipeline exists to buy.  Stacked
    dp is exercised by the parity suite, not here: the bench box's
    device set belongs to tp for throughput numbers."""
    block_size = 64
    gated = None
    table = {}
    for N in rounds_sweep:
        # Worst-case cover: every draft accepted every round of every
        # dispatch, plus the successor dispatch's pre-allocation.
        cover = prompt_len + decode_steps + 2 * N * (K + 1) + 2
        blocks_per_seq = -(-cover // block_size)
        cfg = EngineConfig(
            model=model,
            block_size=block_size,
            num_blocks=bs * blocks_per_seq + block_size,
            max_num_seqs=bs,
            max_num_batched_tokens=8192,
            num_scheduler_steps=N,
            async_scheduling=N > 1,
            enable_eplb=True,
            enable_prefix_caching=False,
            quantization=quantization,
                spec_k=K,
            spec_fixed_accept=fixed_accept,
        )
        engine = EngineCore(cfg)
        assert engine.spec_k == K, "spec decode failed to arm"
        runs = []
        steps = dispatches = 0
        n_rep = max(1, repeats) if N == EVERYTHING_BENCH_ROUNDS else 1
        for rep in range(n_rep + 1):            # rep 0 = warmup
            offset = 4000 * bs + 89 * rep + N
            reqs = _make_reqs(f"eon{N}b{bs}r{rep}", bs, prompt_len,
                              decode_steps, offset)
            s0, d0 = engine._step_count, engine._dispatch_count
            _, _, t_decode, decode_tokens = _run_workload(engine, reqs)
            if rep == 0:
                continue
            runs.append(decode_tokens / t_decode)
            steps += engine._step_count - s0
            dispatches += engine._dispatch_count - d0
        tok_s = statistics.median(runs)
        row = {
            "decode_tok_s": round(tok_s, 1),    # accepted tokens only
            "steps_per_dispatch": round(steps / max(1, dispatches), 2),
        }
        table[str(N)] = row
        if N == EVERYTHING_BENCH_ROUNDS:
            gated = {
                "decode_tok_s": round(tok_s, 1),
                "spec_k": K,
                "fixed_accept": fixed_accept,
                "rounds_per_dispatch": N,
                "steps_per_dispatch": row["steps_per_dispatch"],
            }
            if len(runs) > 1:
                gated["decode_tok_s_runs"] = [round(v, 1) for v in runs]
                gated["decode_tok_s_band"] = [round(min(runs), 1),
                                              round(max(runs), 1)]
    if gated is None and table:
        # Custom sweep without the headline N: quote the largest N run
        # so the gated point is never silently absent.
        N = max(int(n) for n in table)
        gated = {"decode_tok_s": table[str(N)]["decode_tok_s"],
                 "spec_k": K, "fixed_accept": fixed_accept,
                 "rounds_per_dispatch": N,
                 "steps_per_dispatch": table[str(N)]["steps_per_dispatch"]}
    return {bs: gated}, table


# Live-EPLB bench point (round 17): the Zipf exponent of the routed-id
# skew the gated moe_decode_eplb_skew_bs256 metric is quoted under —
# heavy-tailed expert popularity a static placement cannot balance,
# matching the sim cost model and the kernel_bench --eplb sweep.
EPLB_BENCH_ZIPF = 1.2


def bench_eplb_skew(model: str, bs: int, K: int, fixed_accept: float,
                    prompt_len: int = 128, decode_steps: int = 128,
                    quantization=None, repeats: int = 1) -> dict:
    """ACCEPTED tok/s with online EPLB live-migrating under a
    Zipf(EPLB_BENCH_ZIPF) routing skew.

    Before every run a synthetic Zipf-skewed routed trace dominates the
    controller's load window, so the next interval crossing plans a REAL
    delta migration that stages and flips INSIDE the timed region: the
    number charges delta planning, background weight staging and the
    atomic table flip against decode throughput — the claim under test
    is that live migration costs no measurable step time (the flip
    stall rides along in the gated row so a blocking flip fails loudly
    rather than hiding in the median)."""
    import numpy as np
    block_size = 64
    blocks_per_seq = -(-(prompt_len + decode_steps + K + 2) // block_size)
    cfg = EngineConfig(
        model=model,
        block_size=block_size,
        num_blocks=bs * blocks_per_seq + block_size,
        max_num_seqs=bs,
        max_num_batched_tokens=8192,
        num_scheduler_steps=1,          # spec owns the multi-token step
        enable_eplb=True,
        # Short interval so the migration lands early in the timed
        # window and the steady state AFTER the flip dominates the
        # median; the wide window keeps the synthetic trace in charge.
        eplb_config={"window_size": 512, "step_interval": 32},
        enable_prefix_caching=False,
        quantization=quantization,
        spec_k=K,
        spec_fixed_accept=fixed_accept,
    )
    engine = EngineCore(cfg)
    assert engine.spec_k == K, "spec decode failed to arm"
    eplb = engine.eplb
    assert eplb is not None, "EPLB failed to arm"
    p = np.arange(1, eplb.E + 1, dtype=np.float64) ** -EPLB_BENCH_ZIPF
    p /= p.sum()
    rng = np.random.RandomState(1234)
    runs = []
    migrations = 0
    for rep in range(max(1, repeats) + 1):      # rep 0 = warmup
        ids = rng.choice(eplb.E, size=(eplb.n_layers, 4096, 2), p=p)
        eplb.tracker.record(ids)                # dominate the window
        before = eplb.num_rebalances
        offset = 6000 * bs + 97 * rep
        reqs = _make_reqs(f"eplb{bs}r{rep}", bs, prompt_len,
                          decode_steps, offset)
        _, _, t_decode, decode_tokens = _run_workload(engine, reqs)
        if rep == 0:
            continue
        runs.append(decode_tokens / t_decode)
        migrations += eplb.num_rebalances - before
    tok_s = statistics.median(runs)
    row = {
        "decode_tok_s": round(tok_s, 1),        # accepted tokens only
        "zipf_skew": EPLB_BENCH_ZIPF,
        "spec_k": K,
        "fixed_accept": fixed_accept,
        # >= 1 per timed run whenever the mesh has an EP axis (the
        # forced skew crosses the 32-step interval inside every decode
        # window); 0 on a single-shard mesh, where every placement is
        # trivially balanced and the delta planner correctly suppresses
        # — the migration path itself is proven on the 8-device parity
        # and chaos suites (tests/test_eplb_integration.py).
        "ep": eplb.ep,
        "migrations": migrations,
        "migrated_mb": round(eplb.migrated_bytes / 1e6, 3),
        # Host blocking time of the last atomic flip — the stall-free
        # claim, quoted next to the throughput it must not dent.
        "flip_stall_ms": round(eplb.last_flip_stall_s * 1e3, 3),
    }
    if len(runs) > 1:
        row["decode_tok_s_runs"] = [round(v, 1) for v in runs]
        row["decode_tok_s_band"] = [round(min(runs), 1),
                                    round(max(runs), 1)]
    return {bs: row}


def _eplb_skew_delta_table() -> dict:
    """Balanced-vs-static steady-state step time under the bench skew,
    from the sim cost model (extras.eplb_skew.balanced_vs_static).

    The single-chip bench above cannot show the placement win (every
    expert lives on the one chip), so the cluster-scale claim is
    quantified here: per-step hot-shard overhang under Zipf-1.2 routing
    with a STATIC uniform placement vs. the ONLINE delta-migrated one,
    at the bench box's EP degree and the v5p-256 paper model's.  Both
    columns come from the REAL planner (parallel.eplb) driven by the
    sim's mirror — the same code path `llm-d-sim --eplb-skew` serves."""
    from llm_d_tpu.sim.simulator import InferenceSimulator, SimConfig
    table = {}
    for ep in (8, 32):
        rows = {}
        for mode in ("static", "online"):
            sim = InferenceSimulator(SimConfig(
                model=f"eplb-delta-ep{ep}", tpot_ms=10.0,
                eplb_skew=EPLB_BENCH_ZIPF, eplb_mode=mode, eplb_ep=ep))
            st = sim._eplb_model()
            sim._eplb_steps = (0 if st["flip_step"] is None
                               else st["flip_step"])  # steady state
            rows[mode] = {
                "step_ms": round(10.0 + sim._eplb_step_extra_ms(), 3),
                "report": {k: (round(v, 4) if isinstance(v, float) else v)
                           for k, v in sim.eplb_report().items()
                           if k in ("initial_imbalance",
                                    "balanced_imbalance", "moves",
                                    "stage_steps", "flip_step")},
            }
        s, o = rows["static"]["step_ms"], rows["online"]["step_ms"]
        table[f"ep{ep}"] = {
            "static_step_ms": s,
            "online_step_ms": o,
            "step_time_win_pct": round(100 * (s - o) / s, 1),
            "moves": rows["online"]["report"]["moves"],
            "stage_steps": rows["online"]["report"]["stage_steps"],
        }
    return table


def _spec_acceptance_table(model: str, bs: int, fixed_accept: float,
                           k_sweep=(1, 2, 4, 8)) -> dict:
    """Per-K acceptance x accepted-tok/s table (extras.spec_acceptance):
    where the draft-depth sweet spot sits at this acceptance rate —
    deeper K buys tokens/step at geometrically falling marginal
    acceptance while the verify forward widens linearly."""
    table = {}
    for K in k_sweep:
        row = bench_spec(model, bs, K, fixed_accept, decode_steps=64,
                         quantization="int8")[bs]
        table[str(K)] = {
            "accepted_tok_s": row["decode_tok_s"],
            "spec_acceptance_pct": row["spec_acceptance_pct"],
            "accepted_tokens_per_step": row["accepted_tokens_per_step"],
        }
    return {"bs": bs, "fixed_accept": fixed_accept, "per_k": table}


def project_v5p256(measured_roofline_frac: float,
                   decode_bs_per_chip: int = 256,
                   context_len: int = 2048,
                   collective_dtype: str = "int8") -> dict:
    """Paper model: wide-EP decode of REAL DeepSeek-V3 on a v5p-256 slice.

    The single-chip bench can't measure a 256-chip slice, so this projects
    the north-star number (BASELINE.md: >= 2,200 output tok/s/chip on
    32x H200) from first-principles byte/FLOP counts with the MEASURED
    single-chip decode roofline fraction as the efficiency factor — the
    projection inherits exactly the inefficiency we actually achieve, not
    an optimistic 100%-of-roofline assumption.

    Arithmetic (per chip, per decode step, int8 experts / bf16 rest):
      - expert weights: every expert is hit at wide-EP batch sizes
        (256 chips x bs x 8 choices >> 256 experts), so each chip streams
        its 1/256 expert residency once per step.
      - MLA latent KV: bs sequences x context x (kv_lora 512 + rope 64)
        bf16 rows per layer — the tiny-cache memory profile that makes
        wide-EP decode HBM-viable at all.
      - dense/attention weights: per-chip share of the non-expert params
        (replicated compute per dp shard, tp-sharded within a host).
      - ICI all-to-all: each (token, choice) row crosses the wire twice
        (dispatch + combine) at ``collective_dtype`` bytes — the
        quantized-collective accounting of
        parallel/quant_collectives.py (round 10: int8 rows + f32 row
        scales by default; "f32-combine" reproduces the pre-round-10
        wire the implementation actually shipped, for the delta log).
        DBO overlaps the exchange with expert compute (the structural
        overlap the engine enforces), so step time is max(HBM, ICI),
        not the sum.
    Chip specs: v5p = 459 TFLOP/s bf16, 2765 GB/s HBM, ~600 GB/s ICI per
    chip (3D torus, aggregate of 6 links; 90% usable assumed).
    """
    # --- chip ---
    HBM_BW = 2765e9
    ICI_BW = 0.9 * 600e9
    PEAK = 459e12
    N_CHIPS = 256
    # --- DeepSeek-V3 (config.json of deepseek-ai/DeepSeek-V3) ---
    L, L_moe = 61, 58
    H = 7168
    E, k = 256, 8
    I_moe = 2048
    n_shared = 1
    kv_lora, rope = 512, 64
    q_lora, heads, qk_nope, v_head = 1536, 128, 128, 128
    # Routed expert params (int8 = 1 B/param).
    expert_bytes_total = L_moe * E * 3 * H * I_moe          # 673e9
    expert_bytes_chip = expert_bytes_total / N_CHIPS
    # Non-expert params (bf16): attention + shared experts + dense MLPs
    # + embeddings, tp-sharded 8-way within a host (dp replicates).
    attn_per_layer = (H * q_lora + q_lora * heads * (qk_nope + rope)
                      + H * (kv_lora + rope)
                      + kv_lora * heads * (qk_nope + v_head)
                      + heads * v_head * H)
    shared_per_layer = n_shared * 3 * H * I_moe
    dense_mlp = (L - L_moe) * 3 * H * 18432
    other_params = L * attn_per_layer + L_moe * shared_per_layer \
        + dense_mlp + 129280 * H * 2
    tp = 8
    other_bytes_chip = other_params * 2 / tp
    bs = decode_bs_per_chip
    # --- per-step HBM bytes/chip ---
    # bf16 latent cache: 2 B/value, the dtype the engine serves.
    kv_row = (kv_lora + rope) * 2
    kv_bytes = bs * context_len * kv_row * L
    hbm_bytes = expert_bytes_chip + other_bytes_chip + kv_bytes
    t_hbm = hbm_bytes / HBM_BW
    # --- per-step ICI bytes/chip (dispatch + combine, by wire mode) ---
    # Honest all-to-all charging (round 10).  Two corrections over the
    # earlier model, both against us: (1) on the 8x8x4 v5p torus a
    # dispatched row crosses ~5 links on average (dim/4 hops per axis
    # with wraparound, summed over 3 axes), so uniform a2a traffic sees
    # aggregate/avg_hops of effective per-chip bandwidth, not the full
    # link aggregate; (2) DBO can hide the exchange only inside the
    # EXPERT phase — the a2a consumes the same layer's attention output,
    # so it cannot overlap attention/dense work — meaning the overlap
    # window is the expert stream+GEMM time, not the whole step.  Under
    # this accounting the pre-round-10 f32-combine wire FAILS the 2.2k
    # bar outright; the int8 wire is what keeps the exchange inside the
    # expert-phase window (see extras.v5p256_wire_delta).
    A2A_AVG_HOPS = 5.0
    from llm_d_tpu.parallel.quant_collectives import ep_a2a_bytes_per_token
    a2a_bytes = bs * ep_a2a_bytes_per_token(H, k, collective_dtype, L_moe)
    t_ici = a2a_bytes * A2A_AVG_HOPS / ICI_BW
    # --- per-step MXU: per-token active FLOPs as THIS chip computes them:
    # routed experts land on their owner chip (fair share = bs tokens x
    # k/E of the routed params), everything else is tp-sharded 8-way.
    routed_active = expert_bytes_total * k / E     # params/token (int8=1B)
    flops_per_tok = 2 * (routed_active + other_params / tp)
    t_mxu = bs * flops_per_tok / PEAK
    # The expert phase the chunked a2a pipelines against (DBO).
    t_expert = expert_bytes_chip / HBM_BW + bs * 2 * routed_active / PEAK
    # HBM and MXU serialize at the measured efficiency; the a2a overlaps
    # the expert phase only.
    t_step_ideal = (t_hbm + t_mxu - t_expert) + max(t_expert, t_ici)
    t_step = t_step_ideal / max(measured_roofline_frac, 1e-6)
    tok_s_chip = bs / t_step
    return {
        "projected_v5p256_tok_s_chip": round(tok_s_chip, 1),
        "assumptions": {
            "chips": N_CHIPS, "bs_per_chip": bs, "context_len": context_len,
            "efficiency_from_measured_roofline_pct":
                round(100 * measured_roofline_frac, 1),
            "expert_gb_per_chip": round(expert_bytes_chip / 1e9, 2),
            "collective_dtype": collective_dtype,
            "ici_a2a_gb_per_step": round(a2a_bytes / 1e9, 3),
            "ici_avg_hops": A2A_AVG_HOPS,
            "hbm_ms_per_step": round(1e3 * t_hbm, 2),
            "ici_a2a_ms_per_step": round(1e3 * t_ici, 2),
            "mxu_ms_per_step": round(1e3 * t_mxu, 2),
            "expert_phase_ms_per_step": round(1e3 * t_expert, 2),
            "bound": "ici" if t_ici > t_expert else "hbm+mxu",
        },
    }


def v5p256_sensitivity(measured_roofline_frac: float,
                       collective_dtype: str = "int8") -> dict:
    """VERDICT r5 #6: sweep the projection over context x bs/chip instead
    of quoting the single friendliest point.  Reports the margin vs the
    2,200 tok/s/chip bar per point and the first point (sweep order:
    context ascending, then bs descending) where the bar fails — the
    honest statement of how far the thin 4.8% margin actually extends.
    The measured single-chip efficiency factor is held constant across
    the sweep (its context term is modeled, not re-measured)."""
    bar = BASELINE_TOK_S_PER_CHIP
    points = {}
    first_fail = None
    for ctx in (2048, 8192, 32768):
        for bs in (256, 128):
            p = project_v5p256(measured_roofline_frac,
                               decode_bs_per_chip=bs, context_len=ctx,
                               collective_dtype=collective_dtype)
            tok_s = p["projected_v5p256_tok_s_chip"]
            key = f"ctx{ctx}_bs{bs}"
            points[key] = {
                "tok_s_chip": tok_s,
                "margin_vs_2200_pct": round(100 * (tok_s / bar - 1), 1),
                "bound": p["assumptions"]["bound"],
            }
            if first_fail is None and tok_s < bar:
                first_fail = key
    return {"points": points, "first_failing_point": first_fail,
            "bar_tok_s_chip": bar, "collective_dtype": collective_dtype}


def _regression_gate(dense: dict, moe: dict,
                     spec: dict = None, mixed: dict = None,
                     everything_on: dict = None,
                     eplb_skew: dict = None) -> dict:
    """Band-aware regression gate over the headline metrics (two decode,
    one prefill, one decode roofline YIELD — prefill and yield regressions
    used to land silently; the yield one could hide behind batch
    inflation).

    ``*_delta_pct`` is the MEDIAN's delta vs the best recorded number;
    ``*_regressed`` is True only when the run band's MAX is below it —
    i.e. not even the luckiest of N runs reached the old number, which a
    ±4-6% noise band cannot explain.  Gate on ``*_regressed``, read
    ``*_delta_pct`` for trend.  A metric whose best is None is being
    RECORDED for the first time (no verdict until a chip run pins it)."""
    gate = {}
    for name, sweep, bs, phase, best in (
            ("dense_bs64", dense, 64, "decode", 11196.7),   # round-3 chip record (pre-PR-1, deleted)
            ("moe_bs256", moe, 256, "decode", 16060.6),     # r5 final
            # BENCH_r05 moe bs64 prefill (the 11.46%-MFU number the
            # streamed kernel exists to beat).
            ("moe_prefill_tok_s_bs64", moe, 64, "prefill", 17105.1),
            # MoE decode HBM-roofline YIELD at bs256 — first-class and
            # band-gated so a yield drop fails even when a bigger batch
            # inflates raw tok/s (r5 measured 36.9% here; the round-9
            # target is >= 55%).
            ("moe_decode_roofline_bs256", moe, 256, "roofline", 36.9),
            # Speculative decode (round 12): ACCEPTED tok/s through the
            # MTP draft-and-verify engine at bs256, fixed seeded
            # acceptance (SPEC_BENCH_K drafts at SPEC_BENCH_ACCEPT per
            # draft) — the idle-FLOP-spend metric.  First chip run
            # records the best.
            ("moe_decode_spec_bs256", spec or {}, 256, "decode", None),
            # Mixed-round fusion (round 15): emitted tok/s at bs256 with
            # a quarter of the batch re-prefilling through the SAME
            # fused program as the decode/verify rows
            # (MIXED_BENCH_SHARE) — the single-dispatch churn metric.
            # First chip run records the best.
            ("moe_mixed_tok_s_bs256", mixed or {}, 256, "decode", None),
            # Everything-on (round 16): ACCEPTED tok/s at bs256 with
            # spec + mixed fusion + fused multistep
            # (EVERYTHING_BENCH_ROUNDS rounds per dispatch) + async +
            # EPLB composed in ONE engine — the default-config metric.
            # First chip run records the best.
            ("moe_decode_everything_on_bs256", everything_on or {}, 256,
             "decode", None),
            # Live EPLB (round 17): ACCEPTED tok/s at bs256 with the
            # online migration engine planning, staging and flipping a
            # real delta INSIDE the timed window under Zipf-1.2 routing
            # skew — the stall-free-migration metric.  First chip run
            # records the best.
            ("moe_decode_eplb_skew_bs256", eplb_skew or {}, 256,
             "decode", None)):
        gate[f"{name}_best_recorded"] = best
        if phase == "roofline":
            gate[f"{name}_target_pct"] = MOE_ROOFLINE_TARGET_PCT
            value_key, band_key = ("decode_hbm_roofline_pct",
                                   "decode_hbm_roofline_pct_band")
        else:
            value_key, band_key = f"{phase}_tok_s", f"{phase}_tok_s_band"
        if bs not in sweep or value_key not in sweep[bs]:
            gate[f"{name}_delta_pct"] = None
            continue
        row = sweep[bs]
        med = row[value_key]
        if phase == "roofline":
            gate[f"{name}_meets_target"] = bool(
                med >= MOE_ROOFLINE_TARGET_PCT)
        if best is None:
            gate[f"{name}_recorded"] = med
            gate[f"{name}_delta_pct"] = None
            gate[f"{name}_regressed"] = None
            band = row.get(band_key)
            if band is not None:
                gate[f"{name}_band"] = band
            continue
        gate[f"{name}_delta_pct"] = round(100 * (med / best - 1), 1)
        if phase == "prefill" and f"{phase}_mfu_pct" in row:
            # The ≥20% prefill-MFU target rides along with the verdict.
            gate[f"{name}_mfu_pct"] = row[f"{phase}_mfu_pct"]
        band = row.get(band_key)
        if band is None:
            # Single sample (--quick / --gate-repeats 1): a point inside
            # the ±4-6% noise band must not be called a regression — no
            # verdict without a band.
            gate[f"{name}_regressed"] = None
        else:
            gate[f"{name}_band"] = band
            gate[f"{name}_regressed"] = bool(band[1] < best)
    return gate


def _ep_a2a_bytes_table() -> dict:
    """EP dispatch+combine wire bytes per token by dtype mode, on the
    bench MoE model's shapes AND the v5p-256 paper model's — the
    acceptance quantity (int8 must be <= 0.35x the f32-combine baseline)
    measured from the one shared accounting helper."""
    from llm_d_tpu.models.config import get_config
    from llm_d_tpu.parallel.quant_collectives import (
        ep_a2a_bytes_per_token, resolve_collective_dtype)
    modes = ("f32-combine", "bf16", "int8-dispatch", "int8")

    def table(h, k, layers):
        per_layer = {m: ep_a2a_bytes_per_token(h, k, m) for m in modes}
        base = per_layer["f32-combine"]
        return {
            "per_layer": per_layer,
            "per_step_all_moe_layers": {
                m: b * layers for m, b in per_layer.items()},
            "ratio_vs_f32_combine": {
                m: round(b / base, 4) for m, b in per_layer.items()},
        }

    c = get_config("deepseek-v3-bench")
    Lm = c.num_layers - c.first_dense_layers
    return {
        "resolved_mode": resolve_collective_dtype(),
        "bench_model": table(c.hidden_size, c.num_experts_per_tok, Lm),
        "deepseek_v3_v5p256": table(7168, 8, 58),
    }


def _wire_delta(measured_roofline_frac: float) -> dict:
    """Projection at the old f32-combine wire vs the quantized wire, same
    measured efficiency — the logged old-vs-new delta."""
    old = project_v5p256(measured_roofline_frac,
                         collective_dtype="f32-combine")
    new = project_v5p256(measured_roofline_frac, collective_dtype="int8")
    o, n = (old["projected_v5p256_tok_s_chip"],
            new["projected_v5p256_tok_s_chip"])
    return {
        "f32_combine_tok_s_chip": o,
        "int8_tok_s_chip": n,
        "delta_pct": round(100 * (n / o - 1), 1),
        "f32_combine_bound": old["assumptions"]["bound"],
        "int8_bound": new["assumptions"]["bound"],
        "margin_vs_2200_pct": {
            "f32_combine": round(100 * (o / BASELINE_TOK_S_PER_CHIP - 1), 1),
            "int8": round(100 * (n / BASELINE_TOK_S_PER_CHIP - 1), 1),
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="one batch size per model (dev loop)")
    ap.add_argument("--gate-repeats", type=int, default=5,
                    help="median-of-N runs for the gated headline "
                         "numbers (>=5 for the band to mean anything)")
    args = ap.parse_args()

    _require_tpu()

    moe_sizes = [256] if args.quick else [64, 256, 512]
    dense_sizes = [64] if args.quick else [64, 128, 256]
    # --quick is the dev loop: single runs, no band (the gate still
    # prints medians-of-1; only full runs are quotable).
    n = 1 if args.quick else max(1, args.gate_repeats)

    # bs64 repeats feed the prefill gate metric's band; bs256 the decode
    # headline's AND the roofline-yield gate's.
    moe = bench_model("deepseek-v3-bench", moe_sizes, quantization="int8",
                      repeats={256: n, 64: n})
    dense = bench_model("llama3-1b", dense_sizes, repeats={64: n})
    # Speculative decode (round 12): the gated accepted-tok/s point at
    # bs256 plus the per-K acceptance table.  --quick skips both (the
    # metric is band-gated; the table builds one engine per K).
    spec = (None if args.quick else bench_spec(
        "deepseek-v3-bench", 256, SPEC_BENCH_K, SPEC_BENCH_ACCEPT,
        quantization="int8", repeats=n))
    spec_table = (None if args.quick else _spec_acceptance_table(
        "deepseek-v3-bench", 256, SPEC_BENCH_ACCEPT))
    # Mixed-round fusion (round 15): the gated emitted-tok/s point at
    # bs256 under prefill churn, plus the TPOT-p99 vs prefill-share
    # table.  --quick skips it (band-gated; one engine, three shares).
    mixed = (None if args.quick else bench_mixed(
        "deepseek-v3-bench", 256, SPEC_BENCH_K, SPEC_BENCH_ACCEPT,
        quantization="int8", repeats=n))
    # Everything-on (round 16): the gated accepted-tok/s point at bs256
    # with the full composition (spec + mixed fusion + fused multistep +
    # async + EPLB) plus the rounds-per-dispatch sweep.  --quick skips
    # it (band-gated; one engine per N).
    eon, eon_rounds = ((None, None) if args.quick else
                       bench_everything_on(
                           "deepseek-v3-bench", 256, SPEC_BENCH_K,
                           SPEC_BENCH_ACCEPT, quantization="int8",
                           repeats=n))
    # Live EPLB under skew (round 17): the gated accepted-tok/s point
    # at bs256 with a real delta migration staged and flipped inside the
    # timed window.  --quick skips it (band-gated); the sim-backed
    # balanced-vs-static table is cheap and always included.
    eplb_skew = (None if args.quick else bench_eplb_skew(
        "deepseek-v3-bench", 256, SPEC_BENCH_K, SPEC_BENCH_ACCEPT,
        quantization="int8", repeats=n))

    best_bs = max(moe_sizes, key=lambda b: moe[b]["decode_tok_s"])
    headline = moe[best_bs]["decode_tok_s"]

    extras = {
        "backend": jax.default_backend(),
        "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        "moe_model": "deepseek-v3-bench (MLA + sigmoid top-8/64 + int8 "
                     "experts, scaled DeepSeek-V3)",
        "moe_batch_size": best_bs,
        "decode_steps": 128,
        "moe_param_gb": round(moe["param_bytes"] / 1e9, 2),
        "moe_sweep": {str(b): moe[b] for b in moe_sizes},
        # The latent KV byte accounting the roofline divides by (per-row
        # sweep entries carry kv_bytes_per_step at each batch size):
        # 576 values lane-padded to 640, 2 B each.
        "moe_latent": {
            "kv_bytes_per_token_layer": moe["kv_bytes_per_token_layer"],
        },
        "dense_model": "llama3-1b",
        "dense_param_gb": round(dense["param_bytes"] / 1e9, 2),
        "dense_sweep": {str(b): dense[b] for b in dense_sizes},
        # Speculative decode: the gated bs256 point (accepted tok/s at
        # fixed seeded acceptance — every emitted token passed target
        # verification, so directly comparable to moe decode_tok_s) and
        # the per-K acceptance x accepted-tok/s table.
        "spec_decode": (None if spec is None else
                        {"256": spec[256], "k": SPEC_BENCH_K,
                         "fixed_accept": SPEC_BENCH_ACCEPT}),
        "spec_acceptance": spec_table,
        # Mixed-round fusion: the gated bs256 point (emitted tok/s with
        # MIXED_BENCH_SHARE of the batch re-prefilling through the one
        # fused program) and the decode-latency cost of prefill churn —
        # TPOT p99 per prefill share, the table LLMD_PREFILL_CHUNK /
        # LLMD_STEP_TIME_TARGET_MS exist to flatten.
        "mixed_fusion": (None if mixed is None else
                         {"256": mixed[256], "k": SPEC_BENCH_K,
                          "fixed_accept": SPEC_BENCH_ACCEPT,
                          "tpot_vs_prefill_share":
                              mixed["tpot_vs_prefill_share"]}),
        # Everything-on: the gated bs256 point (accepted tok/s, whole
        # composition in one engine) and the N-sweep showing measured
        # steps-per-dispatch — the host-round-trip amortization table.
        "everything_on": (None if eon is None else
                          {"256": eon[256], "k": SPEC_BENCH_K,
                           "fixed_accept": SPEC_BENCH_ACCEPT,
                           "rounds_per_dispatch": eon_rounds}),
        # Live EPLB: the gated bs256 point (accepted tok/s with a real
        # mid-window migration; flip_stall_ms rides in the row) and the
        # cluster-scale balanced-vs-static step-time win from the sim
        # cost model — the single-chip box cannot show the placement
        # win, so the claim is quantified at EP 8 and EP 32.
        "eplb_skew": {
            "256": None if eplb_skew is None else eplb_skew[256],
            "zipf_skew": EPLB_BENCH_ZIPF,
            "balanced_vs_static": _eplb_skew_delta_table(),
        },
        "decode_output_tok_s_per_chip_llama1b_bs64":
            dense[64]["decode_tok_s"] if 64 in dense else None,
        # EP interconnect bytes one token pays per MoE layer and per step
        # (dispatch + combine, by wire mode) on the bench model's shapes —
        # the quantity LLMD_COLLECTIVE_DTYPE=int8 exists to cut (round
        # 10; parallel/quant_collectives.py is the shared accounting).
        # "f32-combine" is the pre-round-10 wire the acceptance ratio is
        # quoted against.
        "ep_a2a_bytes_per_token": _ep_a2a_bytes_table(),
        # North-star paper model: real DeepSeek-V3 wide-EP on v5p-256,
        # scaled by the roofline fraction this chip ACTUALLY achieved at
        # the projection's own per-chip batch size (256 — using the
        # headline bs would mis-mix efficiency regimes).
        # BASELINE.md bar: >= 2,200 tok/s/chip on 32x H200.  The ICI
        # term charges the int8 wire the engine now serves under
        # LLMD_COLLECTIVE_DTYPE=auto on TPU.
        "v5p256_projection": project_v5p256(
            moe[256]["decode_hbm_roofline_pct"] / 100.0
            if 256 in moe else
            moe[best_bs]["decode_hbm_roofline_pct"] / 100.0),
        # Old-vs-new wire charged at the SAME measured efficiency: the
        # honest statement of what the quantized collectives bought the
        # projection (f32-combine = the wire the implementation shipped
        # before round 10).
        "v5p256_wire_delta": _wire_delta(
            moe[256]["decode_hbm_roofline_pct"] / 100.0
            if 256 in moe else
            moe[best_bs]["decode_hbm_roofline_pct"] / 100.0),
        # Projection sensitivity (VERDICT r5 #6): the 2.2k bar must be
        # checked off the friendliest point too — with the quantized
        # interconnect bytes charged at every point.
        "v5p256_sensitivity": v5p256_sensitivity(
            moe[256]["decode_hbm_roofline_pct"] / 100.0
            if 256 in moe else
            moe[best_bs]["decode_hbm_roofline_pct"] / 100.0),
        # Regression gate (VERDICT r5 #4): median-of-N with a min/max
        # band.  A metric REGRESSES only when its whole band sits below
        # the best recorded number — a point sample inside the chip's
        # measured ±4-6% variance is noise, not a regression.
        "regression_gate": _regression_gate(dense, moe, spec, mixed, eon,
                                            eplb_skew),
    }
    result = {
        "metric": "decode_output_tok_s_per_chip_moe",
        "value": headline,
        "unit": "tok/s/chip",
        "vs_baseline": round(headline / BASELINE_TOK_S_PER_CHIP, 3),
        "extras": extras,
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
