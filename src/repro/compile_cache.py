"""JAX's persistent compilation cache for the scripts that drive the chip.

:func:`enable` is called by ``chip_smoke.py`` and ``benchmarks/run.py``
before their first compile, never while the library is imported. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
sets another directory. Otherwise the cache lives in one fixed directory of
the checkout, ``.jax_cache/`` (git-ignored), so that every run of the same
checkout finds what the previous one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout's own cache directory (``src/repro/`` is two levels down)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on and return the directory it uses."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
