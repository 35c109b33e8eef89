"""Where compiled XLA programs persist between processes.

One rule for every entry point that compiles for the chip (``chip_smoke.py``,
``benchmark/run.py``, ``__graft_entry__``): a cache directory placed
from outside (``JAX_COMPILATION_CACHE_DIR``) is the one JAX already reads and
is left alone; otherwise the cache lives at ``<checkout>/.jax_cache`` — a
fixed path, because the path is part of how a later process finds the entries
(a directory named after a pid, a temp name or the time never hits).
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the directory described
    above and return it. Call before the first compile of the process."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    # cache every program, not only those that took >= 1 s to compile: the
    # device steps are many and small, and a threshold on a timing makes what
    # a second run finds depend on how fast the first one happened to be
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
