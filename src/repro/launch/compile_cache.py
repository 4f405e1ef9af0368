"""Where JAX keeps its persistent compilation cache.

Call :func:`use_compile_cache` before the first compile.  A cache is only
found again when its path is the same from one run to the next, so the
path is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when that is set, otherwise
``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's compilation cache at its fixed directory; return it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
