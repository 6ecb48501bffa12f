"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, where it is set, names the directory and no
other is set in code.  Otherwise the cache lives at ``<checkout>/.jax_cache``
(listed in ``.gitignore``).  A cache entry is only found again at the same
path, so the path is fixed: never derived from a temp name, a pid or the
time.
"""

from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory; returns it.

    Call before the first compilation of the process."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
