"""JAX's persistent compilation cache for the entry points.

Entry points (`launch.abc_run`, `launch.serve`, `launch.abc_serve`,
`benchmarks.run`, `chip_smoke.py`) call `enable_compile_cache()` once at
start; nothing enables it at import time, so tests and library callers keep
whatever cache setting they chose.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (listed in .gitignore)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is used as it is (JAX reads it
    itself; no other directory is set in code). Otherwise the cache lives
    in `.jax_cache/` at the root of the checkout: a fixed path, because the
    directory is part of what makes a later process find an entry again.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
