"""JAX's persistent compilation cache, placed from outside or in the checkout.

A cache is only found again at the same path, so a path that changes from
run to run never hits. The cache lives where ``JAX_COMPILATION_CACHE_DIR``
says when it is set, and otherwise at one fixed directory inside the
checkout (listed in ``.gitignore``) -- never at a path built from a temp
name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

#: The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Call before the first compile. ``JAX_COMPILATION_CACHE_DIR`` wins when
    set (and no other path is set in code); otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
