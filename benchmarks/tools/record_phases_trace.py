#!/usr/bin/env python3
"""Record the small trace that ``readers/idle_under.py`` is checked on
(``testdata/v5e_phases.xplane.pb``): a short ``jax.profiler`` slice of the
engine loop serving mixed prefill and decode steps on the chip, at the
harness's tracer levels, so that the ``llmd.<phase>`` annotations of the
engine thread lie on ``/host:CPU`` beside the device plane.

    python3 benchmarks/tools/record_phases_trace.py [--seconds 0.05,0.1,0.2]

Needs a TPU; writes ``chiprun_out/phases_trace-<layers>/<seconds>.xplane.pb``
and prints each file's size, planes and lines.  Commit the longest one under
2 MB.  The model is ``llama3-1b`` cut to ``--layers`` layers so that a step
is a few hundred device operations: the file is a sample of the trace's
structure, not a measurement.
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
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, ROOT)

import tracereduce  # noqa: E402


def request(client: int, serial: int):
    import numpy as np

    from llm_d_tpu.engine.request import Request
    from llm_d_tpu.ops.sampling import SamplingParams
    from llm_d_tpu.utils.tracing import TraceContext
    rng = np.random.default_rng([client, serial])
    r = Request(
        request_id=f"c{client}-{serial}",
        prompt_token_ids=rng.integers(
            1, 1000, size=int(rng.integers(40, 200))).tolist(),
        sampling=SamplingParams(temperature=0.0, ignore_eos=True,
                                max_tokens=int(rng.integers(16, 64))))
    r.trace_ctx = TraceContext("a" * 32, "b" * 16, True)
    return r


async def serve(engine, warm_s: float, slices, out_dir: str) -> None:
    """A closed loop of 8 clients through AsyncEngine (short answers, so a
    prompt joins the decode batch every few steps); after ``warm_s`` seconds
    of it, when every shape of step has compiled, trace one slice of each
    length."""
    import jax

    from llm_d_tpu.engine.async_engine import AsyncEngine
    ae = AsyncEngine(engine)
    await ae.start()
    stop = asyncio.Event()

    async def client(c):
        serial = 0
        while not stop.is_set():
            async for _ in ae.generate(request(c, serial)):
                pass
            serial += 1

    tasks = [asyncio.ensure_future(client(c)) for c in range(8)]
    try:
        await asyncio.sleep(warm_s)
        for s in slices:
            d = os.path.join(out_dir, f"raw-{s}")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # run.py's levels
            opts.host_tracer_level = 1
            jax.profiler.start_trace(d, profiler_options=opts)
            await asyncio.sleep(s)
            jax.profiler.stop_trace()
            shutil.copy(tracereduce.find_xplane(d),
                        os.path.join(out_dir, f"{s}.xplane.pb"))
            shutil.rmtree(d)
            await asyncio.sleep(0.5)
        stop.set()
        await asyncio.gather(*tasks)
    finally:
        ae.stop()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seconds", default="0.05,0.1,0.2")
    ap.add_argument("--warm-seconds", type=float, default=20.0)
    ap.add_argument("--layers", type=int, default=4)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print(f"this needs a TPU; JAX found {jax.devices()}")
        return 3
    from llm_d_tpu.engine.engine import EngineConfig, EngineCore
    from llm_d_tpu.models.config import get_config
    mc = dataclasses.replace(get_config("llama3-1b"), num_layers=args.layers)
    engine = EngineCore(EngineConfig(
        model="llama3-1b", model_config=mc, num_blocks=512, max_num_seqs=16,
        max_num_batched_tokens=512))
    out_dir = os.path.join(ROOT, "chiprun_out",
                           f"phases_trace-{args.layers}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    asyncio.run(serve(engine, args.warm_seconds,
                      [float(x) for x in args.seconds.split(",")], out_dir))
    from readers import idle_under
    for name in sorted(os.listdir(out_dir)):
        path = os.path.join(out_dir, name)
        tr = tracereduce.reduce_trace(path)
        print(f"== {name}: {os.path.getsize(path)} bytes; busy "
              f"{tr['busy_s']:.4f}s of {tr['window_s']:.4f}s; idle under: "
              f"{idle_under.shares(path)}")
        for line in tracereduce.describe(path):
            print("   " + line)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
