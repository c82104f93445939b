#!/usr/bin/env python3
"""Can the comparison that decides ``correct`` see this stack's mechanisms?

    python3 benchmarks/tools/mellum_mechanism_check.py --workload mellum2.ide [--seed n] [--only fault ...]

``mechanism_check.py``'s pattern for a stack of window and full layers whose
rotary rule goes by layer kind.  Run by hand, on the chip.  It builds the
cell's engine as ``run.py`` does, serves the configuration's check prompts
once (greedy, chosen-token logprobs), and holds the SAME served answers
against the configuration's plain reference (``references/mellum.py``) as it
is and with one thing wrong at a time (``WRONG``): every layer full; YaRN
left off the full layers; YaRN on the sliding layers too; ``attention_factor``
1; the int8 experts rounded to int4 on the same scales (the nearest precision
below the one served).  The file's ``reference_tolerance`` must pass the first
and refuse ``MUST_REFUSE``, or it is too loose to tell a windowed kernel from
a masked-nothing one, a rotary rule by kind from one rule, or the precision
stated from the next one down.  ``attention_factor`` 1 is tried and printed
but not required: pooled over the four prompts it reads 1.7 x the served
median and 1.6-2.8 x the served p90 (two seeds on the chip, PERF.md section
6), refused on one seed and not on the other; only at the longest prompt is
it refused on both.  Prints one line per reference and prompt length; exits 1
if the published reference is refused or a fault of ``MUST_REFUSE`` passes.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                     # benchmarks/
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))    # the checkout
sys.path.insert(2, HERE)                                      # its siblings

import correctness  # noqa: E402
import references.plain as plain  # noqa: E402
import run  # noqa: E402
from mechanism_check import int4_experts  # noqa: E402

# (what the line says, the fault's name; None: as published)
WRONG = (("as published", None),
         ("every layer full", "all_full"),
         ("YaRN left off the full layers", "no_yarn"),
         ("YaRN on the sliding layers too", "yarn_everywhere"),
         ("attention_factor 1", "attention_factor_1"),
         ("experts rounded to int4", "int4_experts"))
MUST_REFUSE = {"all_full", "no_yarn", "yarn_everywhere", "int4_experts"}


def faulty(mod, config, fault):
    """(config, rotary rules of (full, sliding) layers, whether the experts
    are rounded) as the reference reads them under ``fault``."""
    from llm_d_tpu.models.config import NO_WINDOW
    full, sliding = mod.rope_rules(config)
    if fault == "all_full":
        config = dataclasses.replace(config, sliding_window=NO_WINDOW)
    elif fault == "no_yarn":
        full = sliding
    elif fault == "yarn_everywhere":
        sliding = full
    elif fault == "attention_factor_1":
        full = full[:-1] + (1.0,)
    return config, (full, sliding), fault == "int4_experts"


def check(cell, engine, generate, seed: int, rehearse: bool, only) -> bool:
    """Serve the check prompts once, hold the answers against each
    reference; True if the published one passes and every fault asked for
    is refused."""
    import jax

    conf = cell["conf"]
    chk = (conf["rehearsal"] if rehearse else conf)["correctness"]
    cases = correctness.generate_cases(
        generate, engine.model_config.vocab_size, seed, chk["prompt_lens"],
        chk["n_gen"])
    mod = importlib.import_module(f"references.{conf['reference']}")
    k = chk["n_gen"]
    experts = plain.experts
    ok = True
    for what, fault in WRONG:
        if fault and only and fault not in only:
            continue
        config, rules, rounded = faulty(mod, engine.model_config, fault)

        def fn(params, tokens, chosen, config=config, rules=rules):
            lp = mod.tail_logprobs(params, config, tokens, k, rules=rules)
            return (jax.numpy.take_along_axis(lp, chosen[:, None], 1)[:, 0],
                    lp.max(axis=-1))

        if rounded:
            plain.experts = lambda lp, c, x: experts(int4_experts(lp), c, x)
        try:
            rows = correctness.against_reference(jax.jit(fn), engine.params,
                                                 cases)
        finally:
            plain.experts = experts
        for n in [None] + list(chk["prompt_lens"]):
            part = [r for r in rows if n in (None, r["prompt_tokens"])]
            s = correctness.summarise(part)
            why = correctness.refusal(s, chk["reference_tolerance"])
            print(f"MECHANISM {what}; prompt {n or 'all'}: median "
                  f"{s['median']:.4f} p90 {s['p90']:.4f} max {s['max']:.4f} "
                  f"over {s['positions']} -> "
                  + (f"REFUSED ({why})" if why else "passes"), flush=True)
            if n is None and (fault is None or fault in MUST_REFUSE):
                ok &= bool(why) == bool(fault)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", nargs="*", default=None,
                    help="faults to try beside the published reference "
                         "(default: all; 'none' for the served readings "
                         "of one more seed)")
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
                   args.rehearse, args.only)
    finally:
        live.stop()
    return 0 if ok else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
