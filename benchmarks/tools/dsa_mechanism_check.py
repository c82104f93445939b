#!/usr/bin/env python3
"""Can the comparison that decides ``correct`` see a sparse latent stack's mechanisms?

    python3 benchmarks/tools/dsa_mechanism_check.py --workload <cell> [--seed n]
        [--only fault ...]

Run by hand, on the chip, for a configuration whose reference is
``dots3_note``.  It builds the cell's engine as ``run.py`` does, serves the
configuration's check prompts once (greedy, chosen-token logprobs), and
holds the SAME served answers against the plain reference with one thing
wrong at a time (``references/dots3_note.py``: ``FAULTS``): the latents not
rescaled; the headwise gate left out; the full layers DENSE (every visible
key attended to, no selection); the window one key too wide; and two
precisions below the ones served: every weight matrix rounded to int8 a
column, the latent rows rounded to int8.  The file's ``reference_tolerance``
must pass the reference as it is and refuse every fault in ``MUST_REFUSE``
(a non-finite logprob is a refusal), or it cannot tell the mechanism from
its absence.  The faults outside ``MUST_REFUSE`` are reported and not held:
what they move is said in the configuration's ``reference_tolerance_note``
and tier-1 holds them at op level (``tests/test_dots_sparse_mla.py``).

It also prints, per check prompt, the reference's SELECTION margins (the gap
between the index score of the last key selected and the first left out,
``dots3_note.selection_margins``): with random weights the scores near rank
``index_topk`` lie close together, so bf16 rounding of the cached index keys
flips a few members of a set, as it flips expert sets.  Prints one line per
reference and prompt length; exits 1 if a fault that must be refused passes.
``--only``: these faults alone beside the reference as published, and no
margins (a control read on many seeds: a reference is 100 s a fault).
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
import references.dots3_note as ref  # noqa: E402
import run  # noqa: E402

WRONG = (
    ("as published", None),
    ("latents not rescaled", "no_rescale"),
    ("no headwise gate", "no_gate"),
    ("full layers dense (no selection)", "dense_full"),
    ("the window one key too wide", "window_plus_one"),
    ("every weight matrix rounded to int8", "int8_weights"),
    ("latent rows rounded to int8", "int8_kv"),
)
MUST_REFUSE = {"no_rescale", "no_gate", "dense_full", "int8_weights"}


def margins(engine, cases) -> None:
    """The reference's selection margins of each case, printed."""
    import jax
    import numpy as np
    c = engine.model_config
    fn = jax.jit(lambda p, t: ref.selection_margins(p, c, t))
    for case in cases:
        tokens = jax.numpy.asarray(case["prompt"] + case["ids"][:-1],
                                   "int32")
        m = np.asarray(fn(engine.params, tokens))
        m = m[np.isfinite(m)]
        if not m.size:
            print(f"MARGIN prompt {len(case['prompt'])}: nothing is left "
                  f"out (the context is under index_topk)", flush=True)
            continue
        print(f"MARGIN prompt {len(case['prompt'])}: {m.size} selections "
              f"that leave a key out; gap between the last kept and the "
              f"first left out: median {np.median(m):.5f}, p10 "
              f"{np.quantile(m, 0.1):.5f}, min {m.min():.6f}; under 0.004 "
              f"(a bf16 ulp of a score of 1): "
              f"{100.0 * (m < 0.004).mean():.1f} %", flush=True)


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
    if only is None:
        margins(engine, cases)
    refused = []
    for what, fault in WRONG:
        if only is not None and fault not in (None, *only):
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
                refused.append(bool(why) if fault in MUST_REFUSE | {None}
                               else True)
    return not refused[0] and all(refused[1:])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny preset, on the CPU")
    ap.add_argument("--only", nargs="+", default=None,
                    choices=[fault for _, fault in WRONG if fault])
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
                   args.rehearse, args.only)
    finally:
        live.stop()
    return 0 if ok else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
