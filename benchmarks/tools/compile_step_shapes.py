#!/usr/bin/env python3
"""Compile EVERY classic step program of a configuration for a DESCRIBED v5e
chip, in the served (packed) form, several at a time.  No chip, nothing runs.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_step_shapes.py <config> [--workers 6]
        [--only T,S,Q ...] [--skip-init] [--skip-reference]

Run by hand before the first chip call after a change to a step program
(PERF.md Open question 0 (a4): PR 28 met a crash of the compiler itself at
one bucket triple of one configuration, which no kernel test shows).  What
``compile_rehearsal.py`` does for two triples this does for all of
``EngineCore.step_shapes()``, plus the weight-init program and the plain
reference, and it knows a state pool beside the paged cache (a stack with a
state-space mixer: the model module's ``state_pool_shapes``).  One process
a worker, each with its own libtpu: give every worker
``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` (set here for the children only).

Prints a line a program with its memory account and, last, the largest
temporaries and the count that failed.  A compile that passes is not a chip
run; no time comes out of this.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                     # benchmarks/
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))    # the checkout


def gib(n: int) -> str:
    return f"{n / 2**30:.2f} GiB"


def shell(name: str):
    """(engine shell, its config, ModelConfig, the described chip's
    sharding): everything ``_build_step_fn`` reads, nothing that needs a
    device to hold an array."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import modelcfg
    from llm_d_tpu.engine.engine import EngineCore
    from llm_d_tpu.models import get_model
    from llm_d_tpu.models.config import ModelConfig
    from llm_d_tpu.parallel.mesh import MeshConfig, make_mesh
    from llm_d_tpu.server.openai import (build_arg_parser,
                                         engine_config_from_args)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = topo.devices[0]
    one = SingleDeviceSharding(dev)
    # The program asks jax.default_backend() to pick its TPU branches.
    jax.default_backend = lambda: "tpu"
    conf = modelcfg.load_config(name)
    mc = ModelConfig(**modelcfg.model_config_fields(conf))
    args = build_arg_parser().parse_args(
        ["--model", name, *modelcfg.serve_args(conf)])
    cfg = dataclasses.replace(engine_config_from_args(args), model_config=mc)
    eng = EngineCore.__new__(EngineCore)
    eng.config, eng.model_config, eng.model = cfg, mc, get_model(mc)
    eng.mesh = make_mesh(MeshConfig(), [dev])
    eng.eplb = None
    eng.dp = 1
    eng.max_blocks_per_seq = -(-mc.max_model_len // cfg.block_size)
    eng._replicated = one
    eng._fed = () if mc.diffusion_block_length else (
        jax.ShapeDtypeStruct((cfg.max_num_seqs,), jnp.int32, sharding=one),)
    return eng, cfg, mc, conf, one


def compile_some(name: str, triples, init: bool, reference: bool) -> int:
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    import modelcfg
    from llm_d_tpu.engine.engine import derive_num_blocks
    from llm_d_tpu.engine.packed_batch import BatchLayout
    eng, cfg, mc, conf, one = shell(name)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    def account(what, compiled):
        m = compiled.memory_analysis()
        print(json.dumps({
            "program": what, "arguments": m.argument_size_in_bytes,
            "outputs": m.output_size_in_bytes,
            "temporaries": m.temp_size_in_bytes,
            "aliased": m.alias_size_in_bytes,
            "kernels": compiled.as_text().count("tpu_custom_call")}),
            flush=True)

    init_fn = modelcfg.make_init_fn(mc, cfg.quantization)
    key = sds((2,), jnp.uint32)
    params = on_chip(jax.eval_shape(init_fn, key))
    failed = 0
    if init:
        n_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(params))
        print(json.dumps({"program": "parameters", "bytes": n_bytes}))
        account("weight init", jax.jit(init_fn, out_shardings=one).lower(
            key).compile())
    layout = eng.model.kv_cache_layout(mc)
    blocks = derive_num_blocks(cfg.kv_cache_hbm_bytes, layout, mc.num_layers,
                               cfg.block_size) \
        if cfg.kv_cache_hbm_bytes else cfg.num_blocks
    kv = {n: sds((mc.num_layers, blocks * cfg.block_size, w), jnp.bfloat16)
          for n, w in layout.items()}
    if hasattr(eng.model, "state_pool_shapes"):
        kv.update(on_chip(eng.model.state_pool_shapes(
            mc, cfg.max_num_seqs + 1)))
    if init:
        print(json.dumps({"program": "cache and state pool", "blocks": blocks,
                          **{n: int(np.prod(x.shape)) * x.dtype.itemsize
                             for n, x in kv.items()}}))
    step = eng._build_step_fn(packed=True)
    rng = sds((2,), jnp.uint32)
    for T, S, Q in triples:
        lay = BatchLayout(T, S, Q, eng.max_blocks_per_seq,
                          R=mc.diffusion_block_length or 1,
                          state=mc.has_recurrent_state)
        try:
            account(f"step T={T} S={S} Q={Q}", step.lower(
                params, kv, sds(lay.shape, jnp.int32), rng, *eng._fed,
                lay).compile())
        except Exception as e:      # the verdict is the point
            failed += 1
            print(json.dumps({"program": f"step T={T} S={S} Q={Q}",
                              "error": f"{type(e).__name__}: "
                              f"{str(e)[:400]}"}), flush=True)
    if reference:
        ref = importlib.import_module(f"references.{conf['reference']}")
        chk = conf["correctness"]
        T, k = max(chk["prompt_lens"]) + chk["n_gen"] - 1, chk["n_gen"]
        account(f"plain reference T={T} k={k}", jax.jit(
            lambda p, t: ref.tail_logprobs(p, mc, t, k)).lower(
                params, sds((T,), jnp.int32)).compile())
    return failed


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--workers", type=int, default=6)
    ap.add_argument("--only", nargs="*", default=None,
                    help="T,S,Q triples (a worker's share)")
    ap.add_argument("--skip-init", action="store_true")
    ap.add_argument("--skip-reference", action="store_true")
    args = ap.parse_args()
    if args.only is not None:
        return compile_some(
            args.config, [tuple(int(x) for x in t.split(","))
                          for t in args.only],
            not args.skip_init, not args.skip_reference)
    eng, *_ = shell(args.config)
    shapes = eng.step_shapes()
    print(f"{args.config}: {len(shapes)} step programs over "
          f"{args.workers} workers", flush=True)
    env = dict(os.environ, ALLOW_MULTIPLE_LIBTPU_LOAD="1",
               JAX_PLATFORMS="cpu")
    procs = []
    for w in range(args.workers):
        share = [",".join(map(str, s)) for s in shapes[w::args.workers]]
        cmd = [sys.executable, os.path.abspath(__file__), args.config,
               "--only", *share]
        if w or args.skip_init:
            cmd.append("--skip-init")
        if w != 1 % args.workers or args.skip_reference:
            cmd.append("--skip-reference")
        procs.append(subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                      stderr=subprocess.DEVNULL, text=True,
                                      env=env))
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
    print(f"{len(steps) - len(bad)} of {len(shapes)} step programs compiled; "
          f"{len(bad)} failed; largest temporaries "
          f"{gib(max((r.get('temporaries', 0) for r in steps), default=0))}")
    return 1 if bad or len(steps) != len(shapes) or any(
        p.returncode for p in procs) else 0


if __name__ == "__main__":
    sys.exit(main())
