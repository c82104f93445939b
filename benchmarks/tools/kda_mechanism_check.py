#!/usr/bin/env python3
"""Can the comparison that decides ``correct`` see a stack's mechanisms?

    python3 benchmarks/tools/kda_mechanism_check.py --workload <cell> [--seed n]
        [--only fault ...] [--rows file]

Run by hand, on the chip, for a configuration whose reference module
(``references/<the file's "reference">.py``) brings a ``FAULT_TABLE`` of
(name, what it is, must the limits refuse it) beside its ``FAULTS`` switch:
``ling_linear`` has one (no decay, no delta correction, beta = 1, the
convolution's tail zeroed at a chunk boundary, a reused slot not zeroed, the
output gate or the router's group limit left out, and the nearest precision
below the one served: every weight matrix rounded to int8 a column).  A later
configuration adds a table to its reference and no copy of this tool.

It builds the cell's engine as ``run.py`` does, serves the configuration's
check prompts once (greedy, chosen-token logprobs), and holds the SAME
served answers against the plain reference as published and with one thing
wrong at a time, through the harness's own summary and limits
(``correctness.summarise`` / ``refusal`` under the file's
``reference_tolerance``).  The limits must pass the reference as it is and
refuse every fault the table says they must (a non-finite logprob is a
refusal), or they cannot tell the mechanism from its absence; a fault they
need not refuse is reported and not held.  Prints one line per reference and
prompt length; exits 1 if the reference as published is refused or a fault
that must be refused passes.  ``--only``: these faults alone beside the
reference as published (a control read on many seeds).  ``--rows``: every
position's served and reference logprob by reference, as JSON, for setting
the limits from more than a summary.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                     # benchmarks/
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))    # the checkout

import correctness  # noqa: E402
import run  # noqa: E402


def check(cell, engine, generate, seed: int, rehearse: bool,
          only=None, rows_out=None) -> bool:
    """Serve the check prompts once, hold the answers against each
    reference; True if the reference passes and every wrong one that must
    be is refused."""
    import jax
    import numpy as np
    conf = cell["conf"]
    ref = importlib.import_module(f"references.{conf['reference']}")
    known = [name for name, _, _ in ref.FAULT_TABLE]
    if only is not None and set(only) - set(known):
        raise SystemExit(f"--only: {conf['reference']} has {known}")
    chk = (conf["rehearsal"] if rehearse else conf)["correctness"]
    cases = correctness.generate_cases(
        generate, engine.model_config.vocab_size, seed, chk["prompt_lens"],
        chk["n_gen"])
    c, k = engine.model_config, chk["n_gen"]
    ok, kept = True, {}
    for fault, what, must in (None, "as published", False), *ref.FAULT_TABLE:
        if only is not None and fault not in (None, *only):
            continue

        def fn(params, tokens, chosen):
            lp = ref.tail_logprobs(params, c, tokens, k)
            return (jax.numpy.take_along_axis(lp, chosen[:, None], 1)[:, 0],
                    lp.max(axis=-1))

        ref.FAULTS = {fault} if fault else set()
        try:
            jitted = jax.jit(fn)        # FAULTS is read while tracing
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
        kept[fault or "as_published"] = rows
        for n in [None] + sorted(set(chk["prompt_lens"])):
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
            if n is None and (bool(why) if fault is None
                              else must and not why):
                ok = False
    if rows_out:
        with open(rows_out, "w") as f:
            json.dump({"seed": seed, "tolerance": chk["reference_tolerance"],
                       "rows": kept}, f)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny preset, on the CPU")
    ap.add_argument("--only", nargs="+", default=None,
                    help="names of the reference's FAULT_TABLE")
    ap.add_argument("--rows", default=None, help="write every row here")
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
                   args.rehearse, args.only, args.rows)
    finally:
        live.stop()
    return 0 if ok else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
