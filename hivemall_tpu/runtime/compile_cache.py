"""Persistent XLA compilation cache, placed from outside.

Every entry point that compiles on the device (chip_smoke.py,
scripts/bench_*.py, ModelRegistry, runtime/launch.py) calls
``enable_compile_cache()`` before its first jit. A chip machine starts each
command with no compiled code, and this repo compiles many small programs
(42 serving buckets per deploy, one step per learner), so a cold run is
mostly compilation; the cache is what lets the second process of a command —
or the next command, where the machine provides a directory — skip it.

Where the cache lives is the environment's decision:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this module
  sets no directory.
- unset: ``<checkout>/.jax_cache`` (gitignored). The path is fixed because
  it must be the same in every process that should share entries — never
  a tempfile, pid or timestamp.

jax's defaults only persist programs that took >= 1 s to compile; most of
this repo's programs are faster than that, so the threshold drops to 0
unless the environment set it too.

A process whose default backend is the CPU is left alone: a cold CPU start
is not what anyone waits for, and the installed jaxlib's CPU loader reports
every cached executable as "compiled for another machine" (the
prefer-no-scatter/-gather pseudo-features), ~6 KB of stderr per program.
"""

from __future__ import annotations

import os
from typing import Optional

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
MIN_COMPILE_SECS_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on for an accelerator process
    (idempotent); returns the directory in use, None on a CPU-only process.
    Initializes the jax backend — under multi-process jax, call it after
    ``jax.distributed.initialize``."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    if not os.environ.get(MIN_COMPILE_SECS_ENV):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
