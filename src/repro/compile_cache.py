"""JAX's persistent compilation cache, placed by the entry points.

``benchmarks/run.py`` calls ``enable`` before its first compile;
``python3 chipbench/run.py``, the chip's entry point, places the cache
at the same ``<checkout>/.jax_cache`` itself.  Importing ``repro``
never turns the cache on, so tests and compile rehearsals for a
described chip stay silent (a program compiled for a chip that is not
attached is written to the cache but cannot be read back).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable(checkout) -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other directory.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, never built from a
    temporary name, a pid or the time, so a later run from the same
    checkout finds what an earlier one compiled.
    """
    import jax
    path = os.environ.get(ENV)
    if not path:
        path = str(Path(checkout).resolve() / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
