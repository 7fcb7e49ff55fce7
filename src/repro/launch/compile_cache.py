"""JAX's persistent compilation cache for the repo's entry points.

`enable_compile_cache` is called by the scripts a user runs
(``chip_smoke.py``, ``benchmarks/sched_perf.py``,
``benchmarks/paper_figs.py``) before they compile anything — never at
import time, so importing the package changes no JAX setting.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this helper sets no other directory.  Otherwise the cache goes to a
fixed directory inside the checkout, ``<repo>/.jax_cache`` (listed in
``.gitignore``): the path is part of nothing that changes between runs,
so a second run of the same program finds what the first one compiled.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch -> the checkout root
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
