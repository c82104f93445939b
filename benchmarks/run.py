#!/usr/bin/env python3
"""Run ONE benchmark cell once; the last line of stdout is its JSON result.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip.  It builds ``llmd-serve``'s engine through the
server's own config path, serves the aiohttp app on a real socket, and
starts ``loadgen.py`` as a child that never imports JAX and talks to it over
HTTP.  Everything that belongs to one cell is data found by name:
``BENCHMARK.json`` (workloads, metric lists) -> ``configs/<config>.json``,
``traffic/<mix>.json``, ``layer_metrics/<metric>.json`` ->
``readers/<reader>.py``.  See README.md beside this file.

Not used by the driver: ``--rehearse`` (a tiny preset on the CPU, every
metric prefixed ``cpu_rehearsal.``) and ``--sweep`` (several arrival rates
after one set-up, to find an open-loop cell's knee).
"""

from __future__ import annotations

import time

T_PROCESS = time.time()          # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
import urllib.request  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)
# The program's span rings default to 2048 spans a component; a window
# holds more steps than that.  Same value in every run, traced or not.
os.environ.setdefault("LLMD_TRACE_BUFFER", "400000")

import clientmetrics  # noqa: E402
import modelcfg  # noqa: E402
import traffic  # noqa: E402

REHEARSAL_PREFIX = "cpu_rehearsal."
TRACE_SLICE_S = 5.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoResult(Exception):
    """The run cannot give a result (no chip, unknown cell, ...)."""


# --------------------------------------------------------------------------
# compile accounting (copied from chip_smoke.py): seconds in XLA compiles
# and persistent-cache hits/misses, from JAX's own monitoring events.
# --------------------------------------------------------------------------

class CompileStats:
    def __init__(self) -> None:
        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0

    def install(self) -> None:
        import jax.monitoring as mon
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def snapshot(self) -> Dict[str, float]:
        return {"hits": self.hits, "misses": self.misses,
                "compiles": self.compiles, "compile_s": self.compile_s}


STATS = CompileStats()


# --------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the data files
# --------------------------------------------------------------------------

def load_cell(workload: str) -> Dict[str, Any]:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise NoResult("no BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise NoResult(f"no workload {workload!r} in BENCHMARK.json")

    def here(metric):
        return "workloads" not in metric or cell["name"] in metric[
            "workloads"]

    conf = modelcfg.load_config(cell["config"])
    return {
        "name": cell["name"], "conf": conf,
        "chips": int(cell.get("chips", conf.get("chips", 1))),
        "mix": modelcfg.load_json("traffic", f"{cell['traffic']}.json"),
        # name -> unit, as BENCHMARK.json has them
        "end_to_end": {m["name"]: m["unit"] for m in bench["end_to_end"]
                       if here(m)},
        "per_layer": [modelcfg.load_json("layer_metrics",
                                         f"{m['name']}.json")
                      for m in bench["per_layer"] if here(m)]}


# --------------------------------------------------------------------------
# the server: llmd-serve's own path, on a real socket
# --------------------------------------------------------------------------

def build_engine(cell: Dict[str, Any], seed: int, rehearse: bool):
    """build_arg_parser -> engine_config_from_args -> EngineCore, with the
    weights made on the device by ONE jitted program from the seed."""
    import jax

    from llm_d_tpu.engine.engine import EngineCore
    from llm_d_tpu.models.config import ModelConfig
    from llm_d_tpu.server.openai import (build_arg_parser,
                                         engine_config_from_args)
    conf = cell["conf"]
    mc = ModelConfig(**modelcfg.model_config_fields(conf, rehearse))
    args = build_arg_parser().parse_args(
        ["--model", conf["name"], *modelcfg.serve_args(conf, rehearse)])
    # --seed may exceed 31 bits; the engine's PRNG keys take an int32.
    cfg = dataclasses.replace(engine_config_from_args(args),
                              model_config=mc, seed=seed % (2**31 - 1))
    t0 = time.time()
    init = jax.jit(modelcfg.make_init_fn(mc, cfg.quantization))
    params = jax.block_until_ready(init(jax.random.PRNGKey(cfg.seed)))
    log(f"   weights on the device in {time.time() - t0:.1f}s")
    engine = EngineCore(cfg, params=params)
    del params
    gc.collect()
    return args, engine.config, engine


def buckets(lo: int, hi: int) -> List[int]:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    return out + [hi]


def bucket_of(n: int, lo: int, hi: int) -> int:
    return next(b for b in buckets(lo, hi) if b >= n or b == hi)


def plan_warm_steps(floor_tokens: int, max_tokens: int,
                    floor_seqs: int, max_seqs: int, max_prompt: int
                    ) -> List[Dict[str, Any]]:
    """Steps that probe the engine on a doubling grid of what a step can be
    seen to hold: rows (sequences), tokens, and the longest prompt chunk.

    A serving engine compiles one program per SHAPE of step and picks the
    shape from those three numbers; this engine rounds each up to a power
    of two.  The grid has one cell for every (tokens in (T/2, T], rows in
    (S/2, S], longest chunk in (Q/2, Q]) with T, S, Q doubling from the
    floors to the deployment's ``--max-num-batched-tokens`` /
    ``--max-num-seqs`` and the mix's longest prompt, and the plan has one
    step inside every cell that a step can reach: ``n_dec`` running decode
    rows plus new prompts of ``lens`` tokens.  The floors merge the cells
    below them into one (an engine with a smallest shape compiles the same
    program for all of them); 1 and 1 are always right, only slower.  An
    engine with FEWER shapes (edges on a subset of the powers of two) is
    covered by the same plan; one with edges elsewhere is not, and shows it
    as a compile inside the window.  Pure arithmetic (checked by
    rehearse.py)."""
    t_b = buckets(floor_tokens, max_tokens)
    s_b = buckets(min(floor_seqs, max_seqs), max_seqs)
    q_max = min(max_prompt, max_tokens)
    q_b = [q for q in t_b if q == t_b[0] or q // 2 < q_max]
    plan = []
    for si, S in enumerate(s_b):
        # Enough decode rows that the level's pure-decode step lands in
        # this sequence bucket (more than half of it; the lowest: one).
        level = 1 if si == 0 else S // 2 + 1
        plan.append({"n_dec": level, "lens": [], "shape": (
            bucket_of(level, t_b[0], t_b[-1]), S, 1)})
        for T in t_b:
            for Q in (q for q in q_b if q <= T):
                lo_t = 2 if T == t_b[0] else T // 2 + 1
                # (a longest prompt of ONE token makes a Q = 1 program)
                lo_q = 2 if Q == t_b[0] else Q // 2 + 1
                lens = None
                # Prefer the level's own count of decode rows; a triple
                # that needs more prompts than that leaves room for (many
                # short chunks in a large token bucket) takes fewer.
                for n_dec in range(level, -1, -1):
                    k_lo = 1 if si == 0 else max(1, S // 2 + 1 - n_dec)
                    for k in range(k_lo, S - n_dec + 1):
                        # k prompts of at most Q tokens, one of at least
                        # lo_q, n_dec + their sum inside (T / 2, T].
                        need = max(lo_t - n_dec, lo_q + (k - 1))
                        if need > k * Q or n_dec + need > T:
                            continue
                        lens = [lo_q] + [1] * (k - 1)
                        rest = need - lo_q - (k - 1)
                        for j in range(k):
                            take = min(Q - lens[j], rest)
                            lens[j] += take
                            rest -= take
                        break
                    if lens is not None:
                        plan.append({"n_dec": n_dec, "lens": lens,
                                     "shape": (T, S, Q)})
                        break
    return plan


def warm_step_shapes(engine, cfg, max_prompt: int, seed: int) -> int:
    """Run the steps of ``plan_warm_steps`` through the engine's public
    add_request / step / abort_request, before the server's loop starts.
    Each shape of step is one XLA program; after this none is left to
    compile in the window (the child then plays the mix's own
    ``warmup_seconds`` on top)."""
    import numpy as np

    from llm_d_tpu.engine.request import Request
    from llm_d_tpu.ops.sampling import SamplingParams
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x57]))
    vocab = engine.model_config.vocab_size
    serial = [0]

    def add(n_prompt: int, max_tokens: int) -> str:
        serial[0] += 1
        rid = f"warm-{serial[0]}"
        engine.add_request(Request(
            request_id=rid,
            prompt_token_ids=rng.integers(1, vocab, size=n_prompt).tolist(),
            sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens,
                                    ignore_eos=True)))
        return rid

    decoders: List[str] = []
    steps = 0
    # The floors are a hint, read where the engine's configuration has them.
    for item in plan_warm_steps(getattr(cfg, "min_token_bucket", 1),
                                cfg.max_num_batched_tokens,
                                getattr(cfg, "min_seq_bucket", 1),
                                cfg.max_num_seqs, max_prompt):
        while len(decoders) > item["n_dec"]:
            engine.abort_request(decoders.pop())
        while len(decoders) < item["n_dec"]:   # long-running decode rows
            decoders.append(add(4, 100000))
        while engine.scheduler.num_waiting:
            engine.step()                      # their prefills
            steps += 1
        for n in item["lens"]:
            add(n, 1)                          # one token: gone next step
        engine.step()
        steps += 1
    for rid in decoders:
        engine.abort_request(rid)
    while engine.has_work():
        engine.step()
    return steps


def make_generate(live, server):
    """``generate(prompt ids, n) -> (token ids, chosen-token logprobs)`` of
    one greedy request through the served engine's own request path, on
    the server's loop: the one place where a token's id and its logprob
    come out together (correctness.py (c) and (d))."""
    import asyncio
    import itertools

    from llm_d_tpu.engine.request import Request
    from llm_d_tpu.ops.sampling import SamplingParams
    serial = itertools.count()

    async def go(prompt, n):
        req = Request(
            request_id=f"check-{id(serial)}-{next(serial)}",
            prompt_token_ids=list(prompt),
            sampling=SamplingParams(temperature=0.0, max_tokens=n,
                                    ignore_eos=True, logprobs=0))
        ids, lps = [], []
        async for out in server.async_engine.generate(req):
            ids.extend(out.new_token_ids)
            lps.extend(out.logprobs or [])
        return ids, lps

    return lambda prompt, n: asyncio.run_coroutine_threadsafe(
        go(prompt, n), live.loop).result(timeout=600)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class LiveServer:
    """The aiohttp app on a real socket in a background thread (copied
    from chip_smoke.py)."""

    def __init__(self, server) -> None:
        import asyncio

        from aiohttp import web
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.loop = asyncio.new_event_loop()
        self._runner = web.AppRunner(server.build_app())
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self.loop)
            self.loop.run_until_complete(self._runner.setup())
            self.loop.run_until_complete(
                web.TCPSite(self._runner, "127.0.0.1", self.port).start())
            started.set()
            self.loop.run_forever()
            self.loop.run_until_complete(self._runner.cleanup())

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()
        if not started.wait(timeout=120):
            raise RuntimeError("server did not start")

    def stop(self) -> None:
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=60)
        if not self._thread.is_alive():
            self.loop.close()


def http_get(url: str, timeout: float = 60) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def scrape_counters(url: str) -> Dict[str, float]:
    """``/metrics`` summed over labels: {metric name: value}."""
    out: Dict[str, float] = {}
    for line in http_get(url + "/metrics").splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        name = name.split("{", 1)[0]
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            pass
    return out


def scrape_spans(url: str, t0: float, t1: float) -> List[Dict[str, Any]]:
    spans = [json.loads(line) for line in
             http_get(url + "/debug/traces", timeout=300).splitlines()
             if line.strip()]
    return [s for s in spans if s.get("dur") is not None
            and s["ts"] >= t0 and s["ts"] + s["dur"] <= t1]


# --------------------------------------------------------------------------
# one played window: the child, its events, the traced slice
# --------------------------------------------------------------------------

def play(url: str, cell: Dict[str, Any], mix: Dict[str, Any], seed: int,
         seconds: float, out_dir: str, tag: str, vocab: int,
         trace_dir: Optional[str] = None,
         rate: Optional[float] = None) -> Dict[str, Any]:
    """Start the load generator child for one warm-up + window; returns the
    window's bounds, compile counts and counters at its two ends.  ``rate``
    is the sweep's: it replaces the mix's arrival rate."""
    records = os.path.join(out_dir, f"records-{tag}.jsonl")
    plan = {"url": url, "model": cell["conf"]["name"], "mix": mix,
            "seed": seed, "seconds": seconds, "vocab": vocab,
            "warmup_seconds": mix["warmup_seconds"],
            "drain_seconds": mix["drain_seconds"], "records": records,
            "rate_rps": rate}
    plan_path = os.path.join(out_dir, f"plan-{tag}.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    env = dict(os.environ, PYTHONPATH=HERE)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen.py"), plan_path],
        stdout=subprocess.PIPE, env=env, text=True)
    info: Dict[str, Any] = {"records": records, "trace_window_s": None}
    tracer: Optional[threading.Thread] = None
    try:
        for line in proc.stdout:
            try:
                ev = json.loads(line)
            except ValueError:
                log("loadgen: " + line.rstrip())
                continue
            if ev["event"] == "window_start":
                info["t0"] = ev["epoch"]
                # Name any program that compiles from here on (stderr).
                import jax
                jax.config.update("jax_log_compiles", True)
                info["compile_before"] = STATS.snapshot()
                info["counters_before"] = scrape_counters(url)
                if trace_dir is not None:
                    tracer = threading.Thread(
                        target=trace_slice, daemon=True,
                        args=(trace_dir, ev["epoch"], seconds, info))
                    tracer.start()
            elif ev["event"] == "window_end":
                info["t1"] = ev["epoch"]
                info["counters_after"] = scrape_counters(url)
            elif ev["event"] == "done":
                info["compile_after"] = STATS.snapshot()
                import jax
                jax.config.update("jax_log_compiles", False)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        if tracer is not None:
            tracer.join(timeout=300)
    if rc != 0 or "compile_after" not in info:
        raise RuntimeError(f"load generator failed (exit {rc})")
    return info


def trace_slice(trace_dir: str, t0: float, seconds: float,
                info: Dict[str, Any]) -> None:
    """A jax.profiler trace of a slice in the middle of the window."""
    import jax
    length = min(TRACE_SLICE_S, seconds / 2)
    time.sleep(max(0.0, t0 + (seconds - length) / 2 - time.time()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # the host loop is Python: tracing
    opts.host_tracer_level = 1          # it would slow what is measured
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    a = time.perf_counter()
    time.sleep(length)
    info["trace_window_s"] = time.perf_counter() - a
    jax.profiler.stop_trace()


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------

def device_info(chips: int, rehearse: bool) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if rehearse:
        return device
    if device["platform"] != "tpu":
        raise NoResult(f"this cell needs a TPU; JAX found {device}")
    if device["count"] < chips:
        raise NoResult(f"this cell needs {chips} chip(s); JAX found "
                       f"{device}")
    peaks = modelcfg.load_json("peaks.json")
    if device["kind"] not in peaks:
        raise NoResult(f"device kind {device['kind']!r} is not in "
                       f"peaks.json: an unknown chip is an error")
    return device


def memory_peak() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def per_layer_metrics(cell, ctx) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell["per_layer"]:
        reader = importlib.import_module(f"readers.{m['reader']}")
        value = reader.read(ctx, **m.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def sweep(url, cell, mix, seed, seconds, out_dir, vocab, rates) -> None:
    """Several arrival rates after one set-up: the table that finds an
    open-loop cell's knee (requests in flight must not grow over the
    window's second half).  Prints to stderr and chiprun_out/."""
    rows = []
    for rate in rates:
        info = play(url, cell, mix, seed, seconds, out_dir,
                    f"sweep-{rate}", vocab, rate=rate)
        header, records = clientmetrics.load_records(info["records"])
        m = clientmetrics.end_to_end(
            header, records, [n for n in cell["end_to_end"]
                              if n != "setup_s"])
        c = clientmetrics.counts(header, records)
        w0, w1 = header["window"]

        def in_flight(t):
            return sum(1 for r in records if r["phase"] == "window"
                       and r["due"] <= t and (r["end"] or 1e18) > t)

        quarters = [in_flight(w0 + (w1 - w0) * f)
                    for f in (0.25, 0.5, 0.625, 0.75, 0.875, 1.0)]
        rows.append({"rate_rps": rate, **c, **m,
                     "in_flight_at_quarters": quarters,
                     "compiles_in_window": info["compile_after"]["compiles"]
                     - info["compile_before"]["compiles"]})
        log("SWEEP " + json.dumps(rows[-1]))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"sweep-{cell['name']}.json"),
              "w") as f:
        json.dump(rows, f, indent=1)


def run(args) -> Dict[str, Any]:
    cell = load_cell(args.workload)
    rehearse = args.rehearse
    if rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from llm_d_tpu.utils.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    # Cache every program, also those that compile in under half a second:
    # a serving engine has dozens, and a warm run should compile nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # No eviction: one cell's step programs are about 200 MiB (some 125
    # executables of 1.5-2.5 MB).  Under a least-recently-used cap just below
    # that (the chip machines set JAX_COMPILATION_CACHE_MAX_SIZE to 192 MiB)
    # every run walks the programs in the same order and evicts each before
    # it is read again: no hit, ever, and every run compiles for minutes.
    jax.config.update("jax_compilation_cache_max_size", -1)
    STATS.install()
    device = device_info(cell["chips"], rehearse)
    log(f"jax {jax.__version__}; devices {device}; compile cache "
        f"{cache_dir}; cell {cell['name']}")

    out_dir = os.path.join(
        ROOT, ".bench_out", f"{cell['name']}-s{args.seed}-t{args.trace}"
        + ("-rehearsal" if rehearse else ""))
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    mix = traffic.resolve_mix(cell["mix"], rehearse)

    from llm_d_tpu.server.openai import build_server
    t0 = time.time()
    serve_args, cfg, engine = build_engine(cell, args.seed, rehearse)
    log(f"   engine built in {time.time() - t0:.1f}s: {cfg.num_blocks} "
        f"blocks x {cfg.block_size}, max_num_seqs {cfg.max_num_seqs}; "
        f"compile so far {STATS.snapshot()}")
    vocab = engine.model_config.vocab_size
    t0 = time.time()
    max_prompt = (int(mix.get("shared_prefix_tokens", 0))
                  + int(traffic.quantiles(mix["prompt_tokens"], 64).max()))
    steps = warm_step_shapes(engine, cfg, max_prompt, args.seed)
    log(f"   {steps} warm-up steps over the step-program buckets in "
        f"{time.time() - t0:.1f}s; compile so far {STATS.snapshot()}")
    server = build_server(cfg, serve_args.tokenizer, engine=engine)
    live = LiveServer(server)
    try:
        for _ in range(600):
            try:
                http_get(live.url + "/v1/models", timeout=5)
                break
            except OSError:
                time.sleep(0.1)
        if args.sweep:
            sweep(live.url, cell, mix, args.seed, args.seconds, out_dir,
                  vocab, [float(r) for r in args.sweep.split(",")])
            raise NoResult("a sweep gives a table, not a result")

        trace_dir = os.path.join(out_dir, "trace") if args.trace else None
        info = play(live.url, cell, mix, args.seed, args.seconds, out_dir,
                    "window", vocab, trace_dir=trace_dir)
        setup_s = info["t0"] - T_PROCESS
        header, records = clientmetrics.load_records(info["records"])
        counts = clientmetrics.counts(header, records)
        compiled = (info["compile_after"]["compiles"]
                    - info["compile_before"]["compiles"])
        log(f"   window done: {counts}, compiles inside it: {compiled}; "
            f"compile totals {info['compile_after']}")

        correct, why = True, []
        if compiled:
            correct = False
            why.append(f"{compiled} program(s) compiled inside the window")
            for sp in scrape_spans(live.url, info["t0"], time.time()):
                if sp["name"] == "engine.step" and sp["dur"] > 1.0:
                    log(f"   a step of {sp['dur']:.1f}s: {sp.get('attrs')}")
        if counts["failed"]:
            correct = False
            why.append(f"{counts['failed']} request(s) failed")
        import correctness
        try:
            chk = (cell["conf"]["rehearsal"] if rehearse
                   else cell["conf"])["correctness"]
            model = cell["conf"]["name"]
            correctness.well_formed(live.url, model, vocab, args.seed,
                                    cfg.block_size)
            cases = correctness.generate_cases(
                make_generate(live, server), vocab, args.seed,
                chk["prompt_lens"], chk["n_gen"])
            correctness.decode_vs_prefill(
                make_generate(live, server), cases, chk["ks"], log)
            name = cell["conf"]["reference"]
            rows = correctness.reference_rows(engine, name, cases)
            with open(os.path.join(out_dir, "reference_check.json"),
                      "w") as f:
                json.dump(rows, f)
            if args.check_sensitivity:
                with open(os.path.join(out_dir, "sensitivity.json"),
                          "w") as f:
                    json.dump(correctness.sensitivity(
                        engine, name, cases, chk["reference_tolerance"],
                        log), f)
            correctness.reference_check(rows, name,
                                        chk["reference_tolerance"], log)
            if engine.model_config.is_moe and cfg.quantization == "int8":
                correctness.moe_op_parity(
                    engine, args.seed,
                    chk["moe_op_sizes"], log)
        except correctness.Incorrect as e:
            correct = False
            why.append(str(e))
        except Exception as e:      # a check that could not run is no pass
            traceback.print_exc()
            correct = False
            why.append(f"a correctness check failed to run: "
                       f"{type(e).__name__}: {str(e)[:300]}")
        if server.async_engine.dead is not None:
            correct = False
            why.append("the engine loop died")
        for w in why:
            log(f"   NOT CORRECT: {w}")

        device["memory_peak_bytes"] = memory_peak()
        prefix = REHEARSAL_PREFIX if rehearse else ""
        result: Dict[str, Any] = {
            "correct": correct, **counts, "metrics": {}, "device": device}
        if not args.trace:
            e2e = clientmetrics.end_to_end(
                header, records, [n for n in cell["end_to_end"]
                                  if n != "setup_s"])
            e2e["setup_s"] = setup_s
            for name, unit in cell["end_to_end"].items():
                result["metrics"][prefix + name] = {
                    "value": float(e2e[name]), "unit": unit}
        else:
            import tracereduce
            xplane = tracereduce.find_xplane(trace_dir)
            tr = (tracereduce.reduce_trace(xplane, info["trace_window_s"])
                  if xplane else None)
            if tr is None and not rehearse:
                raise RuntimeError("the traced slice holds no device plane")
            ctx = {"header": header, "records": records,
                   "spans": scrape_spans(live.url, info["t0"], info["t1"]),
                   "counters": {"before": info["counters_before"],
                                "after": info["counters_after"]},
                   "trace": tr}
            for name, m in per_layer_metrics(cell, ctx).items():
                result["metrics"][prefix + name] = m
            if tr is not None:
                device["busy_s"] = tr["busy_s"]
                device["window_s"] = tr["window_s"]
                result["breakdown"] = tr["breakdown"]
        return result
    finally:
        live.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset on the CPU; metrics are prefixed "
                         f"{REHEARSAL_PREFIX!r}; never used by the driver")
    ap.add_argument("--sweep", default="",
                    help="comma list of arrival rates (requests/s) to play "
                         "one after the other after one set-up; prints a "
                         "table, no result; never used by the driver")
    ap.add_argument("--check-sensitivity", action="store_true",
                    help="after the checks, run the plain reference again "
                         "with one thing wrong at a time and print what "
                         "the comparison refuses; never used by the driver")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except NoResult as e:
        log(f"no result: {e}")
        return 3
    except Exception:
        traceback.print_exc()
        log("no result: the run failed")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # os._exit: daemon threads of the server and JAX's own may not stop.
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
