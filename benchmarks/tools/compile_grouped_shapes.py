#!/usr/bin/env python3
"""``compile_step_shapes.py`` for a stack whose pages the engine keeps in
groups by layer kind (``engine/kv_cache.py``): every classic step program
of a configuration compiled for a DESCRIBED v5e chip in the form that is
SERVED: the packed batch brings the window group's table and write slots,
the cache is one plane with a region a layer.  No chip, nothing runs.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_grouped_shapes.py <config> [--workers 5]
        [--only T,S,Q ...]

``compile_step_shapes.py`` and ``compile_rehearsal.py`` build one plane a
layer and a batch of one table: the form of a stack of one kind, and of a
window stack at limits under which the engine keeps one pool
(``engine.derive_group_blocks``: ``trinity-mini`` at its cell's).  For a
stack the engine does group (``mellum2-12b-a2.5b``) that form still lowers
but is never served; this tool takes the engine's own rule and layout
(``EngineCore._layout``) and says which it compiled.  Weight init and the
plain reference are ``compile_step_shapes.py``'s to compile.  Prints a line
a program with its memory account; exits 1 if one failed.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import compile_step_shapes as css


def served(name: str):
    """(engine shell, abstract params, abstract cache, sds) with the
    engine's own decision on groups taken from its limits."""
    import jax
    import jax.numpy as jnp

    import modelcfg
    from llm_d_tpu.engine.engine import derive_group_blocks, derive_num_blocks
    from llm_d_tpu.models.config import SLIDING
    eng, cfg, mc, _, one = css.shell(name)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(modelcfg.make_init_fn(mc, cfg.quantization),
                       sds((2,), jnp.uint32)))
    layout = eng.model.kv_cache_layout(mc)
    pool = derive_num_blocks(cfg.kv_cache_hbm_bytes, layout, mc.num_layers,
                             cfg.block_size) \
        if cfg.kv_cache_hbm_bytes else cfg.num_blocks
    full, eng._window_blocks = derive_group_blocks(
        mc, cfg.block_size, cfg.max_num_seqs, cfg.max_num_batched_tokens,
        pool) if mc.kv_cache_groups else (pool, 0)
    if eng._window_blocks:
        slots = cfg.block_size * sum(
            eng._window_blocks if t == SLIDING else full
            for t in mc.layer_types)
        kv = {n: sds((1, slots, w), jnp.bfloat16) for n, w in layout.items()}
    else:
        kv = {n: sds((mc.num_layers, pool * cfg.block_size, w), jnp.bfloat16)
              for n, w in layout.items()}
    return eng, params, kv, sds, (full, eng._window_blocks)


def compile_some(name: str, triples) -> int:
    import jax.numpy as jnp
    eng, params, kv, sds, pages = served(name)
    print(json.dumps({"program": "cache", "pages_full": pages[0],
                      "pages_window": pages[1],
                      "bytes": sum(2 * x.size for x in kv.values())}),
          flush=True)
    step = eng._build_step_fn(packed=True)
    failed = 0
    for T, S, Q in triples:
        lay = eng._layout(T, S, Q)
        row = {"program": f"step T={T} S={S} Q={Q}", "groups": lay.groups}
        try:
            c = step.lower(params, kv, sds(lay.shape, jnp.int32),
                           sds((2,), jnp.uint32), *eng._fed, lay).compile()
            m = c.memory_analysis()
            row.update(arguments=m.argument_size_in_bytes,
                       temporaries=m.temp_size_in_bytes,
                       kernels=c.as_text().count("tpu_custom_call"))
        except Exception as e:      # the verdict is the point
            failed += 1
            row["error"] = f"{type(e).__name__}: {str(e)[:400]}"
        print(json.dumps(row), flush=True)
    return failed


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--workers", type=int, default=5)
    ap.add_argument("--only", nargs="*", default=None,
                    help="T,S,Q triples (a worker's share)")
    args = ap.parse_args()
    if args.only is not None:
        return 1 if compile_some(args.config, [
            tuple(int(x) for x in t.split(",")) for t in args.only]) else 0
    eng, *_ = css.shell(args.config)
    shapes = eng.step_shapes()
    print(f"{args.config}: {len(shapes)} step programs over "
          f"{args.workers} workers", flush=True)
    env = dict(os.environ, ALLOW_MULTIPLE_LIBTPU_LOAD="1",
               JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), args.config, "--only",
         *(",".join(map(str, s)) for s in shapes[w::args.workers])],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True, env=env)
        for w in range(args.workers)]
    rows = []
    for p in procs:
        for line in p.stdout:
            print(line, end="", flush=True)
            try:
                rows.append(json.loads(line))
            except ValueError:
                pass
        p.wait()
    steps = [r for r in rows if r["program"].startswith("step")]
    bad = [r for r in steps if "error" in r]
    print(f"{len(steps) - len(bad)} of {len(shapes)} step programs compiled "
          f"(groups: {sorted({r['groups'] for r in steps})}); {len(bad)} "
          f"failed; largest temporaries "
          f"{css.gib(max((r.get('temporaries', 0) for r in steps), default=0))}")
    return 1 if bad or len(steps) != len(shapes) or any(
        p.returncode for p in procs) else 0


if __name__ == "__main__":
    sys.exit(main())
