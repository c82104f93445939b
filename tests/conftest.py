"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

Multi-chip hardware isn't available in CI; sharding/collective code is
validated on ``--xla_force_host_platform_device_count=8`` CPU devices, the
same mechanism the driver's ``dryrun_multichip`` uses.

The suite is a CPU suite by construction: the pin below goes through
``jax.config`` (before first backend use), so it holds whatever
``JAX_PLATFORMS`` says — plain ``JAX_PLATFORMS=cpu`` in the environment is
sufficient with the installed JAX, and this is the ONE mechanism kept so a
bare ``pytest tests/`` never reaches for a chip.  The only tests that talk
to the TPU compiler (``tests/test_tpu_compile.py``) describe an unattached
topology from inside a fixture and are unaffected by the pin.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, devs
    return devs


@pytest.fixture(scope="session")
def under_jit():
    """``under_jit(fn, *args, **kw)``: ``fn(*args, **kw)`` inside ONE jitted
    program, as the models call the sharded ops.  Called eagerly, a
    ``shard_map`` runs its body a primitive at a time on every device: 15-40
    s a call of ``ops.moe.expert_ffn_a2a`` on the 8 CPU devices, every call
    anew, where the program compiles in 2-6 s and runs in none.  Arrays,
    dicts of arrays and ``None`` are traced; a mesh, a string or a number
    is closed over."""
    import numpy as np

    def traced(v):
        return v is None or isinstance(v, (jax.Array, np.ndarray, dict))

    def call(fn, *args, **kw):
        pos = {i: a for i, a in enumerate(args) if traced(a)}
        named = {k: v for k, v in kw.items() if traced(v)}

        def program(pos, named):
            return fn(*(pos.get(i, a) for i, a in enumerate(args)),
                      **{**kw, **named})
        return jax.jit(program)(pos, named)
    return call


# Persistent compile cache: the suite's cost is dominated by XLA CPU
# compiles of near-identical programs; warm runs skip them.  Placed by the
# one helper every entry point uses (JAX_COMPILATION_CACHE_DIR if set,
# else <checkout>/.jax_cache).
from llm_d_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()
