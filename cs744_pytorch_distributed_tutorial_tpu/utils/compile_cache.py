"""Where the entry points keep JAX's persistent compilation cache.

A cold ResNet-18 or 12-layer LM step is minutes of XLA compile; the
cache makes the second process to need a program a reader, not a
compiler. The directory is part of the cache key's lookup, so it must
not move between runs: either the operator places it
(``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself) or it is one
fixed, git-ignored directory at the root of the checkout.

Called from the mains (``cli``, ``lm_cli``, ``serve_cli``,
``chip_smoke.py``), never at package import: a library user and the test
suite keep JAX's own default (no persistent cache).
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX at the compile cache and return the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
