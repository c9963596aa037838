"""Where XLA's persistent compilation cache lives for entry points."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep compiled programs for the next process of this checkout.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, so
    nothing is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``:
    a fixed path, because a later process finds an entry again only under
    the same directory.  Entry points call this; tests never do.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
