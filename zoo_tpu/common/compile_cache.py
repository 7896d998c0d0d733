# zoo-lint: jax-free
"""Where the persistent XLA compile cache lives.

Every process that compiles for the chip — a trainer, a serving
replica, ``chip_smoke.py`` — calls :func:`ensure_compile_cache` before
its first jit, so a BERT-base step or the six serving executables
compile once per checkout instead of once per process.

The rule has two cases and no knob:

* ``JAX_COMPILATION_CACHE_DIR`` is set: whoever runs the program has
  placed the cache (a directory that outlives the machine, say). jax
  reads the variable itself; this module sets nothing.
* it is not set: the cache is ``<checkout>/.jax_cache`` (git-ignored).
  The path is part of the cache key, so it is a fixed place in the
  checkout — one that is the same for every process started from it.
  It goes into the environment too, so replica seats and workers
  spawned from here share the same cache.
"""

from __future__ import annotations

import os
import sys

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def ensure_compile_cache() -> str:  # zoo-lint: config-parse
    """Point jax's persistent compile cache at its one place and return
    that directory. Call before the first jit of the process."""
    # every compile and cache load from here on is a `jit.compile` span
    # in the ring (docs/observability.md): what set-up cost. (Only once
    # jax is imported; LLMEngine and KerasNet.compile ask again.)
    from zoo_tpu.obs.tracing import watch_compiles
    watch_compiles()
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        return placed
    os.environ[CACHE_DIR_ENV] = DEFAULT_CACHE_DIR
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read the environment when it was imported; tell it now
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
