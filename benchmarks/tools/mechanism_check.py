#!/usr/bin/env python3
"""Can the comparison that decides ``correct`` see a mixed stack's mechanisms?

    python3 benchmarks/tools/mechanism_check.py --workload <cell> [--seed n]

Run by hand, on the chip, for a configuration with ``layer_types``.  It
builds the cell's engine as ``run.py`` does, serves the configuration's
check prompts once (greedy, chosen-token logprobs), and holds the SAME
served answers against the configuration's plain reference five times: as
it is; with every layer full (the window never binds) and with the rotary
embedding on every layer (a wrong mechanism); with the int8 experts rounded
to int4 and with keys and values rounded to int8 rows (the nearest
precision below the one served, for the experts and for the cache).  The
file's ``reference_tolerance`` must refuse both wrong mechanisms and at
least one of the cheaper precisions, or it is too loose to tell a windowed
kernel from a masked-nothing one, or the precision stated from the next
one down.  Prints one line per reference and prompt length; exits 1 if it
does not.
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

import correctness  # noqa: E402
import references.plain as plain  # noqa: E402
import run  # noqa: E402


def int4_experts(lp):
    """One layer's served int8 expert matrices rounded to 4 bits on the same
    scales: the nearest precision below the one the configuration serves."""
    import jax.numpy as jnp
    return {name: (jnp.clip(jnp.round(leaf.astype(jnp.float32) / 16), -8, 7)
                   * 16).astype(leaf.dtype) if name.endswith("_q") else leaf
            for name, leaf in lp.items()}


def int8_rows(x):
    """Keys or values as an int8 cache would hold them: 8 bits a (token,
    head) row."""
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0
    return jnp.round(x / scale) * scale


def check(cell, engine, generate, seed: int, rehearse: bool) -> bool:
    """Serve the check prompts once, hold the answers against each
    reference; True if both wrong mechanisms and one of the cheaper
    precisions are refused."""
    import jax

    from llm_d_tpu.models.config import NO_WINDOW
    conf = cell["conf"]
    chk = (conf["rehearsal"] if rehearse else conf)["correctness"]
    cases = correctness.generate_cases(
        generate, engine.model_config.vocab_size, seed, chk["prompt_lens"],
        chk["n_gen"])
    mod = importlib.import_module(f"references.{conf['reference']}")
    c = engine.model_config
    k = chk["n_gen"]
    experts, attend = plain.experts, mod.masked_attention
    refused = []
    # (what is wrong, the config the reference reads, module functions the
    # reference looks up when traced, replaced for this pass)
    for what, config, patch in (
            ("as published", c, {}),
            ("every layer full",
             dataclasses.replace(c, sliding_window=NO_WINDOW), {}),
            ("RoPE on every layer",
             dataclasses.replace(c, rope_on_full_attention=True), {}),
            ("experts rounded to int4", c, {(plain, "experts"): (
                lambda lp, c, x: experts(int4_experts(lp), c, x))}),
            ("keys and values rounded to int8 rows", c, {
                (mod, "masked_attention"): (lambda q, k, v, mask, s: attend(
                    q, int8_rows(k), int8_rows(v), mask, s))})):
        def fn(params, tokens, chosen, config=config):
            lp = mod.tail_logprobs(params, config, tokens, k)
            return (jax.numpy.take_along_axis(lp, chosen[:, None], 1)[:, 0],
                    lp.max(axis=-1))

        for (where, name), wrong in patch.items():
            setattr(where, name, wrong)
        try:
            rows = correctness.against_reference(jax.jit(fn), engine.params,
                                                 cases)
        finally:
            plain.experts, mod.masked_attention = experts, attend
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
    return not refused[0] and all(refused[1:3]) and any(refused[3:])


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
