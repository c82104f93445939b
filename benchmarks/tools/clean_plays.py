#!/usr/bin/env python3
"""An open-loop cell at several arrival rates after ONE set-up, a seed each.

    python3 benchmarks/tools/clean_plays.py --workload <cell> \\
        --plays 0.25:3000001101,0.3:3000001203 [--seconds 45]

``run.py --sweep`` plays its rates with one seed.  For a mix with
``sessions`` a later rate's documents are then prefixes of an earlier
rate's and hit the prefix cache, so only the first rate of a sweep is
clean.  Here every play draws its token ids from a seed of its own (the
weights are the first play's), so no play finds another's documents in the
cache; it does find the cache FULL of them, as a server that has been up
for a while does.  Each play is ``run.sweep`` with one rate: the same table
row (``SWEEP`` on stderr; ttft, requests in flight at the window's
quarters, compiles in the window), and the play's records are copied to
``chiprun_out/plays-<cell>/`` for a closer look at the second half.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                     # benchmarks/
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))    # the checkout

import run  # noqa: E402
import traffic  # noqa: E402


@contextlib.contextmanager
def serving(cell, seed: int, rehearse: bool):
    """The cell's engine behind its HTTP server, set up as ``run.run``
    does: weights from the seed, the warm-up plan, a real socket."""
    import jax

    from llm_d_tpu.server.openai import build_server
    from llm_d_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    run.STATS.install()
    mix = traffic.resolve_mix(cell["mix"], rehearse)
    serve_args, cfg, engine = run.build_engine(cell, seed, rehearse)
    run.warm_step_shapes(
        engine, cfg, int(mix.get("shared_prefix_tokens", 0)) + int(
            traffic.quantiles(mix["prompt_tokens"], 64).max()), seed)
    server = build_server(cfg, serve_args.tokenizer, engine=engine)
    live = run.LiveServer(server)
    try:
        for _ in range(600):
            try:
                run.http_get(live.url + "/v1/models", timeout=5)
                break
            except OSError:
                time.sleep(0.1)
        yield mix, engine, server, live
    finally:
        live.stop()


def plays(cell, mix, engine, live, pairs, seconds: float, rehearse: bool
          ) -> None:
    out_dir = os.path.join(run.ROOT, ".bench_out", f"{cell['name']}-plays"
                           + ("-rehearsal" if rehearse else ""))
    keep = os.path.join(run.ROOT, "chiprun_out", f"plays-{cell['name']}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    os.makedirs(keep, exist_ok=True)
    for rate, seed in pairs:
        run.log(f"PLAY {rate}/s, seed {seed}")
        run.sweep(live.url, cell, mix, seed, seconds, out_dir,
                  engine.model_config.vocab_size, [rate])
        shutil.copy(os.path.join(out_dir, f"records-sweep-{rate}.jsonl"),
                    os.path.join(keep, f"records-{rate}-s{seed}.jsonl"))


def parse_plays(text: str):
    return [(float(r), int(s)) for r, s in
            (item.split(":") for item in text.split(","))]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plays", required=True, type=parse_plays,
                    help="comma list of rate:seed")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the tiny presets, on the CPU")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cell = run.load_cell(args.workload)
    with serving(cell, args.plays[0][1], args.rehearse) as (
            mix, engine, _, live):
        plays(cell, mix, engine, live, args.plays, args.seconds,
              args.rehearse)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
