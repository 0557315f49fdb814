"""Persistent compilation cache for the command-line entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, nothing here
overrides it.  Otherwise the cache goes to ``<checkout>/.jax_cache``, a
fixed path (the cache key includes it, so a path that moved between runs
would never hit).  Library imports and tests never call this: only the
``main()`` of a CLI does.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout root: src/repro/launch/compile_cache.py -> three levels up
CHECKOUT = Path(__file__).resolve().parents[3]


def use_checkout_cache(root: Path | str = CHECKOUT) -> str:
    """Point JAX's persistent compilation cache at ``<root>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` already names one; returns the
    directory in use."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
