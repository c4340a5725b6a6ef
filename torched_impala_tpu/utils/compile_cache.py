"""One rule for JAX's persistent compilation cache, for every entry point
(`run.main`, `chip_smoke.py`, `benchmark/program.py`, `tests/conftest.py`).

If `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this code
sets no directory. Otherwise the cache lives at `<checkout>/.jax_cache`
(git-ignored): a fixed path, because the path is part of the cache key —
a directory named after a user, a pid, a time or a temp name never hits.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def configure_compile_cache() -> str:
    """Apply the rule; returns the directory in use. Call before the
    first compile (no backend is touched)."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
