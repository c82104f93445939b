"""Where the persistent XLA compile cache lives — decided in ONE place.

Every entry point (``llmd-serve``, ``benchmarks/run.py``,
``chip_smoke.py``, ``__graft_entry__.py``, the test suite)
calls :func:`configure_compile_cache` before its first compile.

  - ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing here
    sets another directory.  An operator's ``--compilation-cache-dir``
    loses to it (logged).
  - otherwise: the operator's directory if given, else
    ``<checkout>/.jax_cache`` resolved from this package's ``__file__``.
    Never the cwd, a temp name, a pid or a time: the path is part of the
    cache key, so a directory that moves never hits.

The key covers a program's debug info (``jax_compilation_cache_include_
metadata_in_key``): the ``llmd.<part>`` scopes (ops/parts.py) are debug info,
and the profiler reads them off the executable that ran.  Under JAX's
default, a key blind to them, a cache filled by another tree hands back
executables with that tree's names, or none.  The price is the one programs
with a Pallas kernel paid already (a Mosaic payload holds its source
locations): a tree that moves a traced line compiles its programs once.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax

logger = logging.getLogger(__name__)

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache(flag_dir: Optional[str] = None) -> str:
    """Returns the directory in effect."""
    # Cache small programs too: a serving engine compiles dozens of
    # sub-second bucket variants.  (Not a directory: safe in both cases.)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        if flag_dir and flag_dir != env_dir:
            logger.warning(
                "--compilation-cache-dir %s ignored: %s=%s wins",
                flag_dir, ENV_VAR, env_dir)
        return env_dir
    path = flag_dir or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
