#!/usr/bin/env python3
"""Per-kernel MoE int8 microbench: the measured crossover table.

Times each member of the int8 MoE kernel family — dense all-experts
streaming, fused-routing routed, sorted+padded grouped, chunk-streamed —
through its ACTUAL ``ops.moe`` glue across a token-count sweep, and
emits the measured crossover table as one JSON document.  This is how
the ``LLMD_MOE_DENSE_KERNEL_MAX_T`` / ``LLMD_MOE_GROUPED_MIN_T`` /
``LLMD_MOE_PREFILL_KERNEL`` defaults get re-derived on a real chip
instead of hand-extrapolated (docs/perf-notes-r7.md).

Two modes:

  - default (TPU): deepseek-v3-bench expert shapes (E=64, H=2048, I=512,
    k=8), warmed + repeated timings, ``timings_valid: true``.  Paths
    with hard shape limits are bounded: the dense kernel's T*E compute
    and the routed kernel's whole-batch VMEM residency cap out via
    ``--dense-max-t`` / ``--routed-max-t``.
  - ``--interpret`` (CPU CI): tiny shapes, every kernel runs through the
    Pallas interpreter so tier-1 exercises the full dispatch glue of all
    four kernels without a TPU.  Timings are emitted but flagged
    ``timings_valid: false`` — the interpreter's constant factors mean
    nothing; only the wiring is under test.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

# The --a2a CPU smoke needs a multi-device mesh; the virtual-device flag
# must land before JAX initializes its backend (same mechanism as
# tests/conftest.py).
if (("--a2a" in sys.argv or "--eplb" in sys.argv)
        and "--interpret" in sys.argv):
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import jax.numpy as jnp

from llm_d_tpu.utils.compile_cache import configure_compile_cache

configure_compile_cache()


def _build_case(key, T, E, H, I, k, Lm=2, plane=1):
    """Random routed batch + stacked int8 payloads addressing a non-zero
    plane (exercises the scalar-prefetch layer indexing everywhere)."""
    from llm_d_tpu.ops.quant import quantize_int8
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (T, H), jnp.bfloat16)
    idx = jax.random.randint(ks[1], (T, k), 0, E)
    w = jnp.abs(jax.random.normal(ks[2], (T, k), jnp.float32)) * 0.3
    quant = {"layer": jnp.int32(plane)}
    for name, kk, shape in (("w_gate", ks[3], (E, H, I)),
                            ("w_up", ks[4], (E, H, I)),
                            ("w_down", ks[5], (E, I, H))):
        q, s = quantize_int8(
            jax.random.normal(kk, shape, jnp.float32) * 0.05)
        quant[f"{name}_q"] = jnp.broadcast_to(q[None], (Lm,) + q.shape)
        quant[f"{name}_s"] = jnp.broadcast_to(s[None], (Lm,) + s.shape)
    return x, w, idx, quant


def _paths(interpret: bool, streamed_chunk_t):
    """name -> thunk-factory over (x, w, idx, quant).  Factories return
    None when the path is inapplicable at this shape."""
    from llm_d_tpu.ops import moe as moe_ops

    def dense(x, w, idx, quant):
        return lambda: moe_ops._dense_int8_kernel_path(
            x, w, idx, quant, interpret=interpret)

    def routed(x, w, idx, quant):
        return lambda: moe_ops._routed_int8_kernel_path(
            x, w, idx, quant, interpret=interpret)

    def grouped(x, w, idx, quant):
        return lambda: moe_ops._grouped_int8_kernel_path(
            x, w, idx, quant, interpret=interpret)

    def streamed(x, w, idx, quant):
        return lambda: moe_ops._streamed_int8_kernel_path(
            x, w, idx, quant, chunk_t=streamed_chunk_t,
            interpret=interpret)

    return {"dense": dense, "routed": routed, "grouped": grouped,
            "streamed": streamed}


def _time_ms(thunk, iters: int) -> float:
    thunk().block_until_ready()            # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = thunk()
    out.block_until_ready()
    return 1000.0 * (time.perf_counter() - t0) / iters


def _recommend(points: list) -> dict:
    """Derive the three dispatch knobs from the per-T winners: the dense
    window's top, the routed window's top, and the prefill kernel choice
    (streamed vs grouped at the largest measured T where both ran)."""
    fastest = {}
    for p in points:
        ms = {k: v for k, v in p["ms"].items() if v is not None}
        if ms:
            fastest[p["T"]] = min(ms, key=ms.get)
    dense_max = max((t for t, w in fastest.items() if w == "dense"),
                    default=None)
    routed_max = max((t for t, w in fastest.items() if w == "routed"),
                     default=None)
    prefill = None
    for p in sorted(points, key=lambda p: -p["T"]):
        g, s = p["ms"].get("grouped"), p["ms"].get("streamed")
        if g is not None and s is not None:
            prefill = "streamed" if s <= g else "grouped"
            break
    return {
        "fastest_by_T": {str(t): w for t, w in sorted(fastest.items())},
        "LLMD_MOE_DENSE_KERNEL_MAX_T": dense_max,
        "LLMD_MOE_GROUPED_MIN_T": routed_max,
        "LLMD_MOE_PREFILL_KERNEL": prefill,
    }


# ---------------------------------------------------------------------------
# EP all-to-all sweep (tokens x collective dtype): the quantized-wire
# crossover table for the wide-EP dispatch/combine (round 10;
# parallel/quant_collectives.py).  Three wire modes through the REAL
# ``expert_ffn_a2a`` glue — bf16 both ways, int8 dispatch only, int8 both
# ways — with the per-token wire-byte accounting alongside so the table
# shows what each mode ships, not just what it costs.  On CPU
# (--interpret) the dense all_to_all fallback carries the identical
# quantized payloads over 8 virtual devices, so tier-1 exercises every
# exchange (payload, scale plane, expert ids) without a multi-chip slice;
# timings are flagged invalid there.
# ---------------------------------------------------------------------------

def run_a2a(args) -> dict:
    import numpy as np
    from llm_d_tpu.ops import moe as moe_ops
    from llm_d_tpu.parallel.mesh import MeshConfig, make_mesh
    from llm_d_tpu.parallel.quant_collectives import ep_a2a_bytes_per_token

    n_dev = len(jax.devices())
    if n_dev < 2:
        # A single chip cannot host an exchange; say so rather
        # than silently timing the wrong path.
        return {"mode": "ep_a2a", "backend": jax.default_backend(),
                "error": f"needs >= 2 devices for the EP mesh, have "
                         f"{n_dev}; CPU smoke uses --interpret (8 "
                         f"virtual devices)"}
    mesh = (make_mesh(MeshConfig(dp=n_dev // 2, sp=1, tp=2))
            if n_dev % 2 == 0 else make_mesh(MeshConfig(dp=n_dev)))
    ep = n_dev
    if args.interpret:
        E, H, I, k = 8, 64, 32, 2
        sweep = [16, 32]
        iters = args.iters or 1
    else:
        E, H, I, k = 64, 2048, 512, 8       # deepseek-v3-bench experts
        sweep = [256, 1024, 4096]
        iters = args.iters or 10
    if args.t_sweep:
        sweep = [int(t) for t in args.t_sweep.split(",") if t]
    assert E % ep == 0, (E, ep)
    modes = ("bf16", "int8-dispatch", "int8")

    points = []
    for i, T in enumerate(sweep):
        T = max(T, ep) // ep * ep            # a2a needs T % ep == 0
        rng = np.random.default_rng(i)
        x = jnp.asarray(rng.standard_normal((T, H)), jnp.bfloat16)
        w = jnp.abs(jnp.asarray(rng.standard_normal((T, k)),
                                jnp.float32)) * 0.3
        idx = jnp.asarray(rng.integers(0, E, (T, k)), jnp.int32)
        wg = jnp.asarray(rng.standard_normal((E, H, I)) * 0.2, jnp.bfloat16)
        wu = jnp.asarray(rng.standard_normal((E, H, I)) * 0.2, jnp.bfloat16)
        wd = jnp.asarray(rng.standard_normal((E, I, H)) * 0.2, jnp.bfloat16)
        ms = {}
        for mode in modes:
            # Under jit, as the models call it: an eager shard_map runs its
            # body a primitive at a time on every device.
            fn = jax.jit(functools.partial(
                moe_ops.expert_ffn_a2a, mesh=mesh, collective_dtype=mode))
            ms[mode] = round(_time_ms(
                lambda fn=fn: fn(x, w, idx, wg, wu, wd), iters), 3)
        points.append({
            "T": T, "ms": ms,
            # What each mode actually ships per token per MoE layer
            # (dispatch + combine + index plane; "f32-combine" = the
            # pre-round-10 wire, the acceptance baseline).
            "wire_bytes_per_token_layer": {
                m: ep_a2a_bytes_per_token(H, k, m)
                for m in modes + ("f32-combine",)},
        })
    return {
        "mode": "ep_a2a",
        "backend": jax.default_backend(),
        "interpret": args.interpret,
        "timings_valid": not args.interpret,
        "shapes": {"E": E, "H": H, "I": I, "k": k, "ep": ep},
        "iters": iters,
        "points": points,
    }


# --- speculative decode: draft-depth (K) sweep through the real engine ---
# Accepted tok/s vs K at a fixed seeded acceptance rate — how the
# LLMD_SPEC_K default gets re-derived on a real chip (bench.py gates the
# single bs256 point; this sweeps the depth).  One engine per K: spec_k
# is baked into the fused draft+verify program's shapes.  --interpret
# (CPU CI) runs the tiny model so tier-1 exercises the whole glue —
# scheduler draft allocation, the spec program, rejection rollback —
# with timings flagged invalid.


def run_spec(args) -> dict:
    from llm_d_tpu.engine.engine import EngineConfig, EngineCore
    from llm_d_tpu.engine.request import Request
    from llm_d_tpu.ops.sampling import SamplingParams

    if args.interpret:
        model, bs, prompt_len, decode_steps = "tiny", 4, 16, 12
        quant = None
        sweep = [1, 2, 4]
        vocab = 500
    else:
        model, bs, prompt_len, decode_steps = ("deepseek-v3-bench", 256,
                                               128, 64)
        quant = "int8"
        sweep = [1, 2, 4, 8]
        vocab = 32000
    if args.k_sweep:
        sweep = [int(k) for k in args.k_sweep.split(",") if k]
    accept = args.spec_accept
    block_size = 32 if args.interpret else 64

    def make_reqs(tag, offset):
        return [
            Request(
                request_id=f"{tag}-{i}",
                prompt_token_ids=[(7 * i + 13 * j + offset) % vocab + 1
                                  for j in range(prompt_len)],
                sampling=SamplingParams(temperature=0.0,
                                        max_tokens=decode_steps + 1,
                                        ignore_eos=True))
            for i in range(bs)]

    def run_workload(engine, reqs):
        for r in reqs:
            engine.add_request(r)
        while any(r.num_computed_tokens < r.num_prompt_tokens
                  for r in reqs):
            engine.step()
        before = sum(len(r.output_token_ids) for r in reqs)
        t0 = time.perf_counter()
        while engine.has_work():
            engine.step()
        dt = time.perf_counter() - t0
        return sum(len(r.output_token_ids) for r in reqs) - before, dt

    points = []
    for K in sweep:
        blocks_per_seq = -(-(prompt_len + decode_steps + K + 2)
                           // block_size)
        engine = EngineCore(EngineConfig(
            model=model, block_size=block_size,
            num_blocks=bs * blocks_per_seq + block_size,
            max_num_seqs=bs, max_num_batched_tokens=8192,
            enable_prefix_caching=False, quantization=quant,
            spec_k=K, spec_fixed_accept=accept))
        assert engine.spec_k == K, "spec decode failed to arm"
        run_workload(engine, make_reqs(f"warm{K}", 50000))  # compile pass
        reqs = make_reqs(f"spec{K}", 1000)
        steps0 = engine._step_count
        tokens, dt = run_workload(engine, reqs)
        n_steps = engine._step_count - steps0
        drafted = sum(r.spec_drafted for r in reqs)
        accepted = sum(r.spec_accepted for r in reqs)
        points.append({
            "K": K,
            "accepted_tok_s": round(tokens / dt, 1),
            "ms_per_step": round(1e3 * dt / max(1, n_steps), 3),
            "acceptance_pct": round(100 * accepted / drafted, 1)
            if drafted else None,
        })
    best = max(points, key=lambda p: p["accepted_tok_s"])
    return {
        "mode": "spec",
        "backend": jax.default_backend(),
        "interpret": args.interpret,
        "timings_valid": not args.interpret,
        "model": model, "bs": bs, "fixed_accept": accept,
        "points": points,
        "recommended_k": best["K"],
    }


# ---------------------------------------------------------------------------
# Mixed-round fusion sweep (round 15): ONE streamed int8 program over the
# COMBINED prefill-chunk + decode/verify token population vs the same work
# as TWO programs (streamed over the chunk, plus the decode-regime kernel
# over the decode/verify rows).  The fused engine batches both populations
# into a single expert_ffn call per layer, so every layer's expert weights
# stream from HBM once instead of once per program — this sweep measures
# that amortization at the ops level (the engine-level companion is
# bench.py's gated ``moe_mixed_tok_s_bs256``).  --interpret runs tiny
# shapes on CPU so tier-1 exercises the sweep glue (timings flagged
# invalid).
# ---------------------------------------------------------------------------

def _decode_regime(decode_T, args) -> str:
    """The kernel the two-program baseline runs over the decode/verify
    rows alone — the same small-T regime ladder ops.moe dispatches on."""
    if decode_T <= args.dense_max_t:
        return "dense"
    if decode_T <= args.routed_max_t:
        return "routed"
    return "streamed"


def _time_ms_sync_each(thunk, iters: int, n: int) -> float:
    """Time ``n`` back-to-back dispatches with a host sync after EACH —
    the per-round retire cadence the engine pays without fused
    multistep."""
    thunk().block_until_ready()            # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        for _ in range(n):
            thunk().block_until_ready()
    return 1000.0 * (time.perf_counter() - t0) / iters


def _run_mixed_multistep(args, paths, E, H, I, k, chunk_T, decode_T,
                         iters) -> list:
    """The --multistep axis: ONE ``lax.scan``-compiled N-round program
    (each round the full mixed streamed kernel, output chained into the
    next round's activations) vs the same N rounds as N single
    dispatches with a host sync between each.  This is the ops-level
    mirror of the engine's fused-multistep dispatch amortization
    (``llmd_tpu:engine_steps_total / llmd_tpu:engine_dispatch_total``):
    the scan column pays one dispatch + one sync for N rounds."""
    from llm_d_tpu.ops import moe as moe_ops

    total_T = chunk_T + decode_T
    x, w, idx, quant = _build_case(
        jax.random.PRNGKey(97), total_T, E, H, I, k)
    single = paths["streamed"](x, w, idx, quant)

    def scan_thunk(N):
        @jax.jit
        def f(x0):
            def body(c, _):
                y = moe_ops._streamed_int8_kernel_path(
                    c, w, idx, quant, interpret=args.interpret)
                return y.astype(c.dtype), None
            c, _ = jax.lax.scan(body, x0, None, length=N)
            return c
        return lambda: f(x)

    rows = []
    for N in args.multistep:
        scan_ms = _time_ms(scan_thunk(N), iters)
        singles_ms = _time_ms_sync_each(single, iters, N)
        rows.append({
            "N": N, "total_T": total_T,
            "ms": {"scan": round(scan_ms, 3),
                   "singles": round(singles_ms, 3)},
            "syncs_per_round": {"scan": round(1.0 / N, 3), "singles": 1.0},
        })
    return rows


def run_mixed(args) -> dict:
    if args.interpret:
        E, H, I, k = 8, 256, 128, 2
        chunk_sweep = [16, 32]
        decode_s, spec_k = 4, 1
        iters = args.iters or 1
        streamed_chunk_t = 16    # force multi-chunk even at tiny T
    else:
        E, H, I, k = 64, 2048, 512, 8       # deepseek-v3-bench experts
        chunk_sweep = [256, 512, 1024, 2048]
        decode_s, spec_k = 256, 4           # the gated bs256 decode point
        iters = args.iters or 10
        streamed_chunk_t = None  # LLMD_MOE_PREFILL_CHUNK_T / default
    if args.t_sweep:
        chunk_sweep = [int(t) for t in args.t_sweep.split(",") if t]

    paths = _paths(args.interpret, streamed_chunk_t)
    Qv = spec_k + 1
    decode_T = decode_s * Qv                # verify rows: K+1 slots each
    points = []
    for i, chunk_T in enumerate(chunk_sweep):
        total_T = chunk_T + decode_T
        fused_case = _build_case(
            jax.random.PRNGKey(3 * i), total_T, E, H, I, k)
        prefill_case = _build_case(
            jax.random.PRNGKey(3 * i + 1), chunk_T, E, H, I, k)
        decode_case = _build_case(
            jax.random.PRNGKey(3 * i + 2), decode_T, E, H, I, k)
        fused_ms = _time_ms(paths["streamed"](*fused_case), iters)
        decode_path = _decode_regime(decode_T, args)
        split_ms = (_time_ms(paths["streamed"](*prefill_case), iters)
                    + _time_ms(paths[decode_path](*decode_case), iters))
        points.append({
            "chunk_T": chunk_T, "decode_S": decode_s, "total_T": total_T,
            "decode_path": decode_path,
            "ms": {"fused": round(fused_ms, 3),
                   "split": round(split_ms, 3)},
            "tok_s": {
                "fused": round(1e3 * total_T / max(fused_ms, 1e-9), 1),
                "split": round(1e3 * total_T / max(split_ms, 1e-9), 1)},
        })
    doc = {
        "mode": "mixed",
        "backend": jax.default_backend(),
        "interpret": args.interpret,
        "timings_valid": not args.interpret,
        "shapes": {"E": E, "H": H, "I": I, "k": k,
                   "spec_k": spec_k, "Qv": Qv},
        "iters": iters,
        "points": points,
    }
    if args.multistep:
        doc["multistep"] = _run_mixed_multistep(
            args, paths, E, H, I, k, chunk_sweep[0], decode_T, iters)
    return doc


# ---------------------------------------------------------------------------
# Live-EPLB migration sweep (round 17): the migration ENGINE itself,
# isolated from serving — a skew x move-budget grid over the delta
# planner + double-buffered stager + atomic flip.  Each point builds a
# fresh controller on real device arrays, dominates the load window with
# a Zipf(skew) routed trace (popularity rolled per layer so per-layer
# plans genuinely differ), then drives ``_begin_migration`` +
# ``_migration_tick`` to convergence: moves queued, ticks-to-converge,
# bytes staged, flip stall, and the shard imbalance the migration
# actually bought.  This is how LLMD_EPLB_MOVE_BUDGET gets re-derived on
# a chip (staging bandwidth vs. ticks-to-converge); --interpret runs
# tiny shapes on CPU so tier-1 exercises the full machinery
# (timings flagged invalid).
# ---------------------------------------------------------------------------

def run_eplb(args) -> dict:
    import numpy as np
    from llm_d_tpu.parallel.eplb import EplbConfig, EplbController
    from llm_d_tpu.parallel.mesh import MeshConfig, make_mesh

    if args.interpret:
        E, Lm, D, ep = 8, 2, 64, 4
        skews = [0.8, 1.2]
        budgets = [1, 4]
        tokens = 2048
    else:
        E, Lm, D, ep = 64, 4, 65536, 8      # 256 KiB/plane/slot (f32)
        skews = [0.6, 1.2, 2.0]
        budgets = [4, 16, 64]
        tokens = 1 << 16
    ndev = 1
    for n in range(min(ep, len(jax.devices())), 0, -1):
        if (2 * E) % n == 0:                 # P = E + E redundant slots
            ndev = n
            break
    mesh = make_mesh(MeshConfig(tp=ndev), jax.devices()[:ndev])

    def fake_params(rng):
        ml = {"router": rng.standard_normal((Lm, 4, E)).astype(np.float32)}
        for name in ("w_gate", "w_up", "w_down"):
            ml[name] = rng.standard_normal((Lm, E, D)).astype(np.float32)
        # int8 sibling planes ride every move with their scales.
        ml["w_up_q"] = rng.integers(-127, 127, (Lm, E, D)).astype(np.int8)
        ml["w_up_s"] = rng.random((Lm, E, 1)).astype(np.float32)
        return {"moe_layers": ml}

    def shard_imbalance(plans, layer_load):
        vals = []
        for li, plan in enumerate(plans):
            per_rep = layer_load[li] / plan.num_replicas
            shard = np.zeros(ep)
            for slot, e in enumerate(plan.phys_to_logical):
                shard[slot // plan.slots_per_shard] += per_rep[e]
            vals.append(shard.max() / max(shard.mean(), 1e-12))
        return round(float(np.mean(vals)), 4)

    points = []
    for skew in skews:
        pop = np.arange(1, E + 1, dtype=np.float64) ** -float(skew)
        for budget in budgets:
            rng = np.random.default_rng(1234)
            ctrl = EplbController(E, ep, EplbConfig.from_dict({
                "num_redundant_experts": E,
                "window_size": 100,
                "step_interval": 1,
                "imbalance_threshold": 0.0,
                "move_budget": budget,
            }))
            raw = fake_params(rng)
            logical = {k: np.asarray(v)
                       for k, v in raw["moe_layers"].items()}
            params = ctrl.install(raw, mesh, None)
            ids = np.stack([rng.choice(E, size=(tokens, 2),
                                       p=np.roll(pop, li) / pop.sum())
                            for li in range(Lm)])
            ctrl.tracker.record(ids)
            before_plans = list(ctrl.plans)
            load = ctrl.tracker.layer_load

            t0 = time.perf_counter()
            ctrl._begin_migration(0)
            moves = (ctrl._migration.total_moves if ctrl.migrating else 0)
            ticks = 0
            while ctrl.migrating:
                params = ctrl._migration_tick(params, mesh)
                ticks += 1
                if ctrl.migrating and not ctrl._migration.moves:
                    # Staging drained but slabs still in flight: wait so
                    # the next tick flips (the serving loop just keeps
                    # decoding here — this sweep wants convergence time).
                    for arr in ctrl._migration.staged.values():
                        jax.block_until_ready(arr)
            wall_ms = 1e3 * (time.perf_counter() - t0)

            # Post-flip weights must equal the logical gather exactly —
            # the sweep doubles as a device-array consistency check.
            ok = all(
                np.array_equal(
                    np.asarray(params["moe_layers"][name][li]),
                    logical[name][li][plan.phys_to_logical])
                for name in ("w_gate", "w_up_q", "w_up_s")
                for li, plan in enumerate(ctrl.plans))
            points.append({
                "skew": skew,
                "budget": budget,
                "moves": moves,
                "ticks": ticks,
                "staged_mb": round(ctrl.migrated_bytes / 1e6, 3),
                "converge_wall_ms": round(wall_ms, 3),
                "flip_stall_ms": round(1e3 * ctrl.last_flip_stall_s, 3),
                "imbalance_before": shard_imbalance(before_plans, load),
                "imbalance_after": shard_imbalance(ctrl.plans, load),
                "weights_consistent": ok,
            })

    doc = {
        "mode": "eplb",
        "backend": jax.default_backend(),
        "interpret": args.interpret,
        "timings_valid": not args.interpret,
        "shapes": {"E": E, "layers": Lm, "plane_elems": D, "ep": ep,
                   "devices": ndev, "trace_tokens": tokens},
        "points": points,
    }
    if not all(p["weights_consistent"] for p in points):
        doc["error"] = "post-flip weights diverged from the logical gather"
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--interpret", action="store_true",
                    help="tiny shapes through the Pallas interpreter "
                         "(CPU CI: exercises every kernel's dispatch "
                         "glue; timings not meaningful)")
    ap.add_argument("--a2a", action="store_true",
                    help="run the EP all-to-all tokens x collective-dtype "
                         "sweep (bf16 / int8 dispatch-only / int8 both "
                         "ways) through the real expert_ffn_a2a glue "
                         "instead of the MoE kernel family; needs a "
                         "multi-device mesh (--interpret forces 8 "
                         "virtual CPU devices)")
    ap.add_argument("--spec", action="store_true",
                    help="run the speculative-decode draft-depth (K) "
                         "sweep through the real draft+verify engine at "
                         "a fixed seeded acceptance (--spec-accept) "
                         "instead of the MoE kernel family; --interpret "
                         "runs the tiny model on CPU (glue smoke)")
    ap.add_argument("--mixed", action="store_true",
                    help="run the mixed-round fusion sweep (one streamed "
                         "program over combined prefill-chunk + "
                         "decode/verify tokens vs the same work as two "
                         "programs) instead of the MoE kernel family; "
                         "--t-sweep sets the chunk sizes")
    ap.add_argument("--eplb", action="store_true",
                    help="run the live-EPLB skew x move-budget migration "
                         "sweep (delta planning, double-buffered staging, "
                         "atomic flip) on real device arrays instead of "
                         "the MoE kernel family; --interpret runs tiny "
                         "shapes on CPU (full-machinery smoke)")
    ap.add_argument("--multistep", type=lambda s: [int(n) for n in
                                                   s.split(",") if n],
                    default=None,
                    help="mixed mode: comma-separated round counts N — "
                         "additionally time ONE lax.scan-compiled "
                         "N-round mixed program (single dispatch + "
                         "single sync) against N single dispatches with "
                         "a host sync each, the ops-level mirror of the "
                         "engine's fused-multistep amortization")
    ap.add_argument("--k-sweep", type=str, default=None,
                    help="spec mode: comma-separated draft depths "
                         "(default 1,2,4,8 on chip; 1,2,4 interpreted)")
    ap.add_argument("--spec-accept", type=float, default=0.7,
                    help="spec mode: seeded per-draft acceptance rate "
                         "(bench.py SPEC_BENCH_ACCEPT quotes the gated "
                         "metric at the same rate)")
    ap.add_argument("--t-sweep", type=str, default=None,
                    help="comma-separated token counts (default: "
                         "64..8192 on chip, 8..64 interpreted)")
    ap.add_argument("--iters", type=int, default=None,
                    help="timed iterations per point (default 10, or 1 "
                         "interpreted)")
    ap.add_argument("--dense-max-t", type=int, default=1024,
                    help="skip the all-experts dense kernel above this T "
                         "(T*E compute)")
    ap.add_argument("--routed-max-t", type=int, default=1024,
                    help="skip the whole-batch-resident routed kernel "
                         "above this T (VMEM residency)")
    ap.add_argument("--out", type=str, default=None,
                    help="also write the JSON document to this path")
    args = ap.parse_args(argv)

    if not args.interpret and jax.default_backend() != "tpu":
        # Timed mode measures the chip; only --interpret runs on the CPU.
        print(f"kernel_bench: timed mode needs a TPU, JAX found "
              f"{jax.default_backend()!r}; use --interpret for the CPU "
              f"wiring smoke", file=sys.stderr)
        return 1

    if args.a2a or args.spec or args.mixed or args.eplb:
        doc = (run_spec(args) if args.spec
               else run_mixed(args) if args.mixed
               else run_eplb(args) if args.eplb else run_a2a(args))
        text = json.dumps(doc)
        print(text)
        if args.out:
            with open(args.out, "w") as f:
                f.write(text + "\n")
        # A mode that could not run (e.g. --a2a without a multi-device
        # mesh — a programmatic caller that imported this module after
        # JAX initialized misses the sys.argv device bootstrap above)
        # must fail loudly, not hand an error document to a harness
        # that only checks the exit code.
        return 1 if "error" in doc else 0

    if args.interpret:
        E, H, I, k = 8, 256, 128, 2
        sweep = [8, 16, 48, 64]
        iters = args.iters or 1
        streamed_chunk_t = 16    # force multi-chunk even at tiny T
    else:
        E, H, I, k = 64, 2048, 512, 8       # deepseek-v3-bench experts
        sweep = [64, 128, 256, 512, 1024, 2048, 4096, 8192]
        iters = args.iters or 10
        streamed_chunk_t = None  # LLMD_MOE_PREFILL_CHUNK_T / default
    if args.t_sweep:
        sweep = [int(t) for t in args.t_sweep.split(",") if t]

    paths = _paths(args.interpret, streamed_chunk_t)
    points = []
    for i, T in enumerate(sweep):
        x, w, idx, quant = _build_case(jax.random.PRNGKey(i), T, E, H, I, k)
        ms = {}
        for name, factory in paths.items():
            if name == "dense" and T > args.dense_max_t:
                ms[name] = None
                continue
            if name == "routed" and T > args.routed_max_t:
                ms[name] = None
                continue
            ms[name] = round(_time_ms(factory(x, w, idx, quant), iters), 3)
        points.append({"T": T, "ms": ms})

    doc = {
        "backend": jax.default_backend(),
        "interpret": args.interpret,
        "timings_valid": not args.interpret,
        "shapes": {"E": E, "H": H, "I": I, "k": k},
        "iters": iters,
        "points": points,
        "crossover": _recommend(points),
    }
    text = json.dumps(doc)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
