#!/usr/bin/env python3
"""Record the small trace that ``readers/device_parts.py``,
``readers/part_roofline.py`` and ``xplanemeta.py`` are checked on
(``testdata/v5e_parts.xplane.pb``): a short ``jax.profiler`` slice of a small
MoE engine with latent attention (``deepseek-v3-bench`` cut to ``--layers``
layers, int8 experts) serving mixed and decode steps on the chip, at the
harness's tracer levels.  Every operation of its step programs lies under an
``llmd.<part>`` scope (llm_d_tpu/ops/parts.py) and the engine thread's
``llmd.dispatch`` / ``llmd.post`` annotations carry the steps' counts
(engine/step_clock.py) on ``/host:CPU`` of the same trace.

    python3 benchmarks/tools/record_parts_trace.py [--seconds 0.05,0.1,0.15]

Needs a TPU; writes ``chiprun_out/parts_trace-<layers>/<seconds>.xplane.pb``
and prints each file's size and its table by part.  Commit the longest one
under 2 MB.  The ``/host:metadata`` plane (the traced programs' HLO protos,
1.2 MB a program of this stack, read by nothing here) is dropped from each
file; every other byte is the profiler's.  The loop of clients is ``record_phases_trace.py``'s.  The file
is a sample of the trace's structure, not a measurement.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(2, ROOT)

import record_phases_trace as phases  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seconds", default="0.05,0.1,0.15")
    # Long enough for every shape of step to have compiled (3-5 s each,
    # cold): a slice traced while the engine thread compiles holds nothing.
    ap.add_argument("--warm-seconds", type=float, default=150.0)
    ap.add_argument("--layers", type=int, default=3)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print(f"this needs a TPU; JAX found {jax.devices()}")
        return 3
    from llm_d_tpu.engine.engine import EngineConfig, EngineCore
    from llm_d_tpu.models.config import get_config
    mc = dataclasses.replace(get_config("deepseek-v3-bench"),
                             num_layers=args.layers)
    engine = EngineCore(EngineConfig(
        model="deepseek-v3-bench", model_config=mc, quantization="int8",
        num_blocks=512, max_num_seqs=16, max_num_batched_tokens=512))
    out_dir = os.path.join(ROOT, "chiprun_out", f"parts_trace-{args.layers}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    asyncio.run(phases.serve(engine, args.warm_seconds,
                             [float(x) for x in args.seconds.split(",")],
                             out_dir))
    import xplanemeta
    from readers import device_parts, part_roofline
    for name in sorted(os.listdir(out_dir)):
        path = os.path.join(out_dir, name)
        with open(path, "rb") as f:
            kept = xplanemeta.without_planes(f.read(), ("/host:metadata",))
        with open(path, "wb") as f:
            f.write(kept)
        print(f"== {name}: {os.path.getsize(path)} bytes; counts "
              f"{part_roofline.annotation_counts(path)}")
        for line in device_parts.table(path, largest=3):
            print("   " + line)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
