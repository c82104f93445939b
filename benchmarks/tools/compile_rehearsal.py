#!/usr/bin/env python3
"""Compile a configuration's programs for a DESCRIBED v5e chip. No chip, nothing runs.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_rehearsal.py <config> [...]

Run by hand before spending chip time on a new configuration or depth.  For
each configuration file it compiles, at the published widths and the file's
depth, for ``v5e:2x2``'s first device:

  - the one weight-init program the harness runs (``modelcfg.make_init_fn``),
  - one prefill step (T = Q = --max-num-batched-tokens, S = 8),
  - one decode step (T = S = --max-num-seqs, Q = 1),
  - the configuration's plain reference at its longest check prompt,

and prints ``memory_analysis()`` of each: the memory account of PERF.md §4.
A compile that passes is not a chip run; no time comes out of this.
"""

from __future__ import annotations

import dataclasses
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                     # benchmarks/
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))    # the checkout

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import modelcfg  # noqa: E402


def gib(n: int) -> str:
    return f"{n / 2**30:.2f} GiB"


def report(what: str, compiled) -> None:
    m = compiled.memory_analysis()
    print(f"   {what}: arguments {gib(m.argument_size_in_bytes)}, outputs "
          f"{gib(m.output_size_in_bytes)}, temporaries "
          f"{gib(m.temp_size_in_bytes)}, aliased "
          f"{gib(m.alias_size_in_bytes)}; live at peak about "
          f"{gib(m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes)}",
          flush=True)


def main(names) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

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
    # The program asks jax.default_backend() to pick its TPU branches (int8
    # kernels, Pallas attention); here that would say "cpu".
    jax.default_backend = lambda: "tpu"

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    for name in names:
        conf = modelcfg.load_config(name)
        mc = ModelConfig(**modelcfg.model_config_fields(conf))
        args = build_arg_parser().parse_args(
            ["--model", name, *modelcfg.serve_args(conf)])
        cfg = dataclasses.replace(engine_config_from_args(args),
                                  model_config=mc)
        print(f"== {name}: {mc.num_layers} layers, serve_args "
              f"{modelcfg.serve_args(conf)}", flush=True)
        init = modelcfg.make_init_fn(mc, cfg.quantization)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
        param_shapes = jax.eval_shape(init, key)
        n_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(param_shapes))
        print(f"   parameters as served: {gib(n_bytes)}")
        report("weight init (one program)",
               jax.jit(init, out_shardings=one).lower(key).compile())

        # An engine shell: everything _build_step_fn reads, nothing that
        # needs a device to hold an array.
        eng = EngineCore.__new__(EngineCore)
        eng.config, eng.model_config, eng.model = cfg, mc, get_model(mc)
        eng.mesh = make_mesh(MeshConfig(), [dev])
        eng.eplb = None
        layout = eng.model.kv_cache_layout(mc)
        from llm_d_tpu.engine.engine import derive_num_blocks
        blocks = derive_num_blocks(cfg.kv_cache_hbm_bytes, layout,
                                   mc.num_layers, cfg.block_size)
        slots = blocks * cfg.block_size
        kv = {n: jax.ShapeDtypeStruct((mc.num_layers, slots, w),
                                      jnp.bfloat16, sharding=one)
              for n, w in layout.items()}
        kv_bytes = sum(int(np.prod(x.shape)) * 2 for x in kv.values())
        print(f"   KV pool: {blocks} blocks of {cfg.block_size} = {slots} "
              f"tokens, {gib(kv_bytes)}")
        B = -(-mc.max_model_len // cfg.block_size)
        step = eng._build_step_fn()
        params = on_chip(param_shapes)
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
        for what, (T, S, Q) in (
                ("prefill step", (cfg.max_num_batched_tokens, 8,
                                  cfg.max_num_batched_tokens)),
                ("decode step", (cfg.max_num_seqs, cfg.max_num_seqs, 1))):
            batch = on_chip(jax.tree.map(
                jnp.asarray, eng._empty_batch_np(T, S, Q, B)))
            compiled = step.lower(params, kv, batch, rng).compile()
            report(f"{what} T={T} S={S} Q={Q}", compiled)
            print(f"      tpu_custom_call x"
                  f"{compiled.as_text().count('tpu_custom_call')}")

        # The plain reference at the longest check prompt: it runs beside
        # the served engine, so its temporaries must fit what is left.
        import importlib
        ref = importlib.import_module(f"references.{conf['reference']}")
        chk = conf["correctness"]
        T, k = max(chk["prompt_lens"]) + chk["n_gen"] - 1, chk["n_gen"]
        tokens = jax.ShapeDtypeStruct((T,), jnp.int32, sharding=one)
        report(f"plain reference {conf['reference']} T={T} k={k}",
               jax.jit(lambda p, t: ref.tail_logprobs(p, mc, t, k)).lower(
                   params, tokens).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or ["qwen3-30b-a3b", "kanana-2-30b-a3b"]))
