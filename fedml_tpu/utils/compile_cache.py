"""ONE definition of where the persistent XLA compile cache lives.

Cold compiles dominate a fresh process on the chip (minutes at LLM widths);
the persistent cache lets the next process reuse executables. The directory
is part of nothing the program decides at run time:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, so this
  module touches no directory setting — whoever launched the process (the
  chip tool, a test harness, an operator) placed the cache.
- unset: a fixed ``<checkout>/.jax_cache`` (gitignored). The path is part of
  the cache key's environment, so it is never built from a temp dir, a pid
  or a timestamp — a directory that moves never hits.

Callers: ``fedml_tpu.init``, ``LLMTrainer``, ``serving/replica_main.py``,
``bench.py`` stage entry and ``chip_smoke.py`` — all resolve the SAME
directory through here, so the cache is never split.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one home; returns the
    directory in effect. Call before the first compile of the process."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
