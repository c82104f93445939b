#!/usr/bin/env python3
"""Can the comparison that decides ``correct`` see a state-space mixer's mechanisms?

    python3 benchmarks/tools/ssm_mechanism_check.py --workload <cell> [--seed n]

Run by hand, on the chip, for a configuration whose reference is
``falcon_h1``.  It builds the cell's engine as ``run.py`` does, serves the
configuration's check prompts once (greedy, chosen-token logprobs), and
holds the SAME served answers against the plain reference with one thing
wrong at a time (``references/falcon_h1.py``: ``FAULTS``): the recurrent
state carried in bf16 where float32 is served; the convolution's tail
zeroed at a chunk boundary; the ``mup_vector`` left out; the
``key_multiplier`` left out; B and C of the groups swapped; no softplus on
dt; and two precisions below the ones served: the MLP's and the head's bf16
weights rounded to int8, keys and values rounded to int8 rows.  The file's
``reference_tolerance`` must pass the reference as it is and refuse every
fault in ``MUST_REFUSE`` (a non-finite logprob is a refusal), or it cannot
tell the mechanism from its absence.  Three faults are reported and not
held: measured on the chip (PR 34) they move the log-probabilities of
seeded random weights no more than bf16 activations do over the check's
2,314 tokens, so no limit above the served readings can refuse them; tier-1
holds them at op level instead (``tests/test_ssm_hybrid.py``).  Prints one
line per reference and prompt length; exits 1 if a fault that must be
refused passes.
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
import references.falcon_h1 as ref  # noqa: E402
import references.plain as plain  # noqa: E402
import run  # noqa: E402
from tools.mechanism_check import int8_rows  # noqa: E402

WRONG = (
    ("as published", None),
    ("the state carried in bf16", "bf16_state"),
    ("the convolution's tail zeroed at a chunk boundary", "zero_conv_tail"),
    ("no mup_vector", "no_mup_vector"),
    ("no key_multiplier", "no_key_multiplier"),
    ("B and C of the groups swapped", "swap_groups"),
    ("no softplus on dt", "no_softplus"),
    ("MLP and head weights rounded to int8", "int8_weights"),
    ("keys and values rounded to int8 rows", "int8_kv"),
)
MUST_REFUSE = {"no_mup_vector", "no_key_multiplier", "swap_groups",
               "no_softplus", "int8_weights"}


def check(cell, engine, generate, seed: int, rehearse: bool) -> bool:
    """Serve the check prompts once, hold the answers against each
    reference; True if the reference passes and every wrong one is
    refused."""
    import jax
    import numpy as np
    conf = cell["conf"]
    chk = (conf["rehearsal"] if rehearse else conf)["correctness"]
    cases = correctness.generate_cases(
        generate, engine.model_config.vocab_size, seed, chk["prompt_lens"],
        chk["n_gen"])
    c, k = engine.model_config, chk["n_gen"]
    ref.FAULT_CHUNK = engine.config.max_num_batched_tokens
    exact = plain.causal_attention
    refused = []
    for what, fault in WRONG:
        def fn(params, tokens, chosen):
            lp = ref.tail_logprobs(params, c, tokens, k)
            return (jax.numpy.take_along_axis(lp, chosen[:, None], 1)[:, 0],
                    lp.max(axis=-1))

        ref.FAULTS = {fault} if fault else set()
        if fault == "int8_kv":
            plain.causal_attention = lambda q, kk, v, s: exact(
                q, int8_rows(kk), int8_rows(v), s)
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
            ref.FAULTS, plain.causal_attention = set(), exact
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
                refused.append(bool(why) if fault in MUST_REFUSE | {None}
                               else True)
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
