#!/usr/bin/env python3
"""Do two trees lower the same step programs?  The sha1 of the StableHLO text.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/lowered_step_programs.py
        [--root <checkout>] [--out <dir>] [config ...]

For every configuration of ``<checkout>/BENCHMARK.json`` (or those named), at
its rehearsal sizes on the CPU: the engine is built as ``llmd-serve`` builds
it, and the largest pure-decode and the largest mixed step program of
``EngineCore.step_shapes()`` are lowered (``_step_fn.lower(...).as_text()``:
no debug info, so a moved line changes nothing).  Prints one line a program,
``<config> (T, S, Q) <sha1> <characters>``; ``--out`` keeps the texts for a
``diff``.  Run it on the parent (``--root`` a ``git archive`` of it: this
file's code, that tree's program) and on the change, and compare the lines:
a PR that adds a layer kind, a cache entry or a field must leave the accepted
configurations' lines as they were, or name each difference in PERF.md.  A
configuration the tree cannot build is reported and skipped (the parent of
the PR that adds it).  A block-diffusion stack has no decode program.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    ap.add_argument("--out", default=None)
    ap.add_argument("configs", nargs="*")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, os.path.join(root, "benchmarks"))
    sys.path.insert(1, root)
    import jax
    import modelcfg

    from llm_d_tpu.engine.engine import EngineCore
    from llm_d_tpu.models.config import ModelConfig
    from llm_d_tpu.server.openai import (build_arg_parser,
                                         engine_config_from_args)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        names = args.configs or [
            os.path.basename(c["file"])[:-len(".json")]
            for c in json.load(f)["configs"]]
    if args.out:
        os.makedirs(args.out, exist_ok=True)
    for name in names:
        try:
            conf = modelcfg.load_config(name)
            mc = ModelConfig(**modelcfg.model_config_fields(conf, True))
        except (TypeError, ValueError, OSError) as e:
            print(f"{name}: this tree cannot build it ({type(e).__name__}: "
                  f"{str(e)[:120]})", flush=True)
            continue
        serve = build_arg_parser().parse_args(
            ["--model", conf["name"], *modelcfg.serve_args(conf, True)])
        eng = EngineCore(dataclasses.replace(
            engine_config_from_args(serve), model_config=mc, seed=1))
        shapes = eng.step_shapes()
        decode = [s for s in shapes if s[2] == 1]
        mixed = [s for s in shapes if s[2] > 1]
        for T, S, Q in decode[-1:] + mixed[-1:]:
            layout = eng._layout(T, S, Q, dp=eng.dp)
            packed = jax.device_put(layout.new_buffer(), eng._replicated)
            text = eng._step_fn.lower(eng.params, eng.kv_cache, packed,
                                      eng._rng, *eng._fed, layout).as_text()
            if args.out:
                with open(os.path.join(
                        args.out, f"{name}-{T}-{S}-{Q}.txt"), "w") as f:
                    f.write(text)
            print(name, (T, S, Q), hashlib.sha1(text.encode()).hexdigest(),
                  len(text), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
