"""Where the entry points keep JAX's persistent compilation cache.

`$JAX_COMPILATION_CACHE_DIR`, when it is set: JAX reads that variable
itself and nothing here overrides it. Otherwise one fixed directory of the
checkout, `<repo>/.jax_cache` (listed in .gitignore). The path is part of
the cache key, so it must not move between runs: no temporary name,
process id or time goes into it.

Only the entry points call `use_compile_cache`, from their `main`; library
code and tests never do, so importing the package writes nothing.
"""
from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir,
    ".jax_cache"))


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
