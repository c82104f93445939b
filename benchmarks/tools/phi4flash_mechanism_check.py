#!/usr/bin/env python3
"""Can the comparison that decides ``correct`` see a decoder-hybrid-decoder's mechanisms?

    python3 benchmarks/tools/phi4flash_mechanism_check.py --workload <cell> [--seed n] [--only fault,...]

Run by hand, on the chip, for a configuration whose reference is
``phi4flash``.  It builds the cell's engine as ``run.py`` does, serves the
configuration's check prompts once (greedy, chosen-token logprobs), and
holds the SAME served answers against the plain reference with one thing
wrong at a time (``references/phi4flash.py``: ``FAULTS``): every weight
matrix rounded to int8 a column, the nearest precision below the one
served; the differential term dropped (lambda 0); the window one key off;
the gated memory units reading the Mamba layer's GATED output in place of
its memory; the cross-decoder's attentions reading no key of the query's
own chunk but its own; the recurrent state carried in bf16.  The file's
``reference_tolerance`` must pass the reference as it is and refuse every
fault in ``MUST_REFUSE`` (a non-finite logprob is a refusal), or it cannot
tell the mechanism from its absence.  The others are reported and not held:
where the chip's readings show that a fault moves the log-probabilities of
seeded random weights no more than bf16 activations do, no limit above the
served readings can refuse it, and tier-1 holds it at op level instead
(``tests/test_hybrid_decoder.py``: the window by a mask written out, the
state's dtype by the kernels' output).  Prints one line per reference and
prompt length; exits 1 if a fault that must be refused passes.
"""

from __future__ import annotations

import argparse
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                     # benchmarks/
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))    # the checkout

import correctness  # noqa: E402
import references.phi4flash as ref  # noqa: E402
import run  # noqa: E402

WRONG = (
    ("as published", None),
    ("every weight matrix rounded to int8 a column", "int8_weights"),
    ("the differential term dropped", "no_diff_term"),
    ("the window one key off", "window_off_by_one"),
    ("the GMUs read the gated output, not the memory", "gmu_reads_gated"),
    ("cross attention reads no key of the current chunk",
     "cross_misses_chunk"),
    ("the state carried in bf16", "bf16_state"),
)
MUST_REFUSE = {"int8_weights", "no_diff_term", "gmu_reads_gated",
               "cross_misses_chunk"}


def check(cell, engine, generate, seed: int, rehearse: bool,
          only=None) -> bool:
    """Serve the check prompts once, hold the answers against each
    reference; True if the reference passes and every wrong one that must
    be is refused."""
    import jax
    import numpy as np
    conf = cell["conf"]
    chk = (conf["rehearsal"] if rehearse else conf)["correctness"]
    cases = correctness.generate_cases(
        generate, engine.model_config.vocab_size, seed, chk["prompt_lens"],
        chk["n_gen"])
    c, k = engine.model_config, chk["n_gen"]
    ref.FAULT_CHUNK = engine.config.max_num_batched_tokens
    verdicts = []
    for what, fault in WRONG:
        if only and fault is not None and fault not in only:
            continue

        def fn(params, tokens, chosen):
            lp = ref.tail_logprobs(params, c, tokens, k)
            return (jax.numpy.take_along_axis(lp, chosen[:, None], 1)[:, 0],
                    lp.max(axis=-1))

        ref.FAULTS = {fault} if fault else set()
        try:
            jitted = jax.jit(fn)
            rows = []
            for case in cases:      # as against_reference, non-finite kept
                ids = case["ids"]
                same, best = (np.asarray(a, np.float64) for a in jitted(
                    engine.params,
                    jax.numpy.asarray(case["prompt"] + ids[:-1], "int32"),
                    jax.numpy.asarray(ids, "int32")))
                rows += [{"prompt_tokens": len(case["prompt"]), "j": j,
                          "served": lp, "reference": float(same[j]),
                          "reference_best": float(best[j])}
                         for j, lp in enumerate(case["lps"])]
        finally:
            ref.FAULTS = set()
        for n in [None] + list(chk["prompt_lens"]):
            part = [r for r in rows if n in (None, r["prompt_tokens"])]
            if not all(math.isfinite(r["reference"]) for r in part):
                why, line = "a non-finite logprob", "not finite"
            else:
                s = correctness.summarise(part)
                why = correctness.refusal(s, chk["reference_tolerance"])
                line = (f"median {s['median']:.4f} p90 {s['p90']:.4f} max "
                        f"{s['max']:.4f} over {s['positions']}")
            print(f"MECHANISM {what}; prompt {n or 'all'}: {line} -> "
                  + (f"REFUSED ({why})" if why else "passes"), flush=True)
            if n is None:
                verdicts.append(not why if fault is None
                                else bool(why) or fault not in MUST_REFUSE)
    return all(verdicts)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default="",
                    help="comma list of faults (the reference as published "
                         "always runs)")
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
                   args.rehearse, set(filter(None, args.only.split(","))))
    finally:
        live.stop()
    return 0 if ok else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
