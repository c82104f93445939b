#!/usr/bin/env python3
"""Can the comparison that decides ``correct`` see block diffusion's mechanisms?

    python3 benchmarks/tools/sdar_mechanism_check.py --workload <cell> [--seed n]

Run by hand, on the chip, for a configuration whose reference is
``sdar_moe``.  It builds the cell's engine as ``run.py`` does, serves the
configuration's check prompts once (greedy, the logprob each token was
revealed with), and holds the SAME served answers against the plain
reference five times: as it is; with a causal mask in place of the block
mask; with the conditioning one pass stale (the slot before the revealed
one still masked where the served pass had it revealed); with earlier
blocks' keys taken from their last denoising pass instead of the commit
pass (``references/sdar_moe.py``: ``WRONG``); and with the int8 experts
rounded to int4 on the same scales, the nearest precision below the one
served.  The file's ``reference_tolerance`` must refuse all four, or it is
too loose to tell a block-masked kernel from a causal one, a commit pass
from none, or the precision stated from the next one down.  Prints one
line per reference and prompt length; exits 1 if it does not.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                     # benchmarks/
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))    # the checkout

import correctness  # noqa: E402
import references.plain as plain  # noqa: E402
import references.sdar_moe as ref  # noqa: E402
import run  # noqa: E402
from tools.mechanism_check import int4_experts  # noqa: E402


def check(cell, engine, generate, seed: int, rehearse: bool) -> bool:
    """Serve the check prompts once, hold the answers against each
    reference; True if the reference passes and every wrong one is
    refused."""
    import jax
    conf = cell["conf"]
    chk = (conf["rehearsal"] if rehearse else conf)["correctness"]
    cases = correctness.generate_cases(
        generate, engine.model_config.vocab_size, seed, chk["prompt_lens"],
        chk["n_gen"])
    c, k = engine.model_config, chk["n_gen"]
    experts = plain.experts
    refused = []
    for what, wrong, int4 in (
            ("as published", "", False),
            ("a causal mask in place of the block mask", "causal_mask",
             False),
            ("conditioning one pass stale", "stale_pass", False),
            ("earlier blocks' keys from a denoising pass", "denoise_keys",
             False),
            ("experts rounded to int4", "", True)):
        def fn(params, tokens, chosen):
            lp = ref.tail_logprobs(params, c, tokens, k)
            return (jax.numpy.take_along_axis(lp, chosen[:, None], 1)[:, 0],
                    lp.max(axis=-1))

        ref.WRONG = wrong
        if int4:
            plain.experts = lambda lp, c, x: experts(int4_experts(lp), c, x)
        try:
            rows = correctness.against_reference(jax.jit(fn), engine.params,
                                                 cases)
        finally:
            ref.WRONG, plain.experts = "", experts
        for n in [None] + list(chk["prompt_lens"]):
            part = [r for r in rows if n in (None, r["prompt_tokens"])]
            s = correctness.summarise(part)
            why = correctness.refusal(s, chk["reference_tolerance"])
            print(f"MECHANISM {what}; prompt {n or 'all'}: median "
                  f"{s['median']:.4f} p90 {s['p90']:.4f} max {s['max']:.4f} "
                  f"over {s['positions']} -> "
                  + (f"REFUSED ({why})" if why else "passes"), flush=True)
            if n is None:
                refused.append(bool(why))
    return not refused[0] and all(refused[1:])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny preset, on the CPU")
    args = ap.parse_args()
    import jax

    from llm_d_tpu.server.openai import build_server
    from llm_d_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    cell = run.load_cell(args.workload)
    serve_args, cfg, engine = run.build_engine(cell, args.seed,
                                               args.rehearse)
    server = build_server(cfg, serve_args.tokenizer, engine=engine)
    live = run.LiveServer(server)
    try:
        ok = check(cell, engine, run.make_generate(live, server), args.seed,
                   args.rehearse)
    finally:
        live.stop()
    return 0 if ok else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
