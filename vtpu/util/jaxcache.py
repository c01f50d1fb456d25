"""Where JAX's persistent compilation cache lives, decided in one place.

The directory is part of the cache key, so it must not move between runs:
whoever launches the program may place it with ``JAX_COMPILATION_CACHE_DIR``
(JAX reads that variable itself, and then no code here sets another);
otherwise it is ``<checkout>/.jax_cache`` (listed in ``.gitignore``), never a
temp name, pid or time. Entry points call ``place_compile_cache()`` under
``__main__`` before their first compile; tests use the same rule.
"""

from __future__ import annotations

import os
from pathlib import Path

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = Path(__file__).resolve().parents[2]


def compile_cache_dir() -> str:
    """The cache directory under the rule above (touches neither JAX nor
    the filesystem — usable to build a child process's environment)."""
    return os.environ.get(_ENV) or str(_CHECKOUT / ".jax_cache")


def place_compile_cache() -> str:
    """Apply the rule to this process's JAX and return the directory. With
    the variable set this is a no-op: JAX already took it from the
    environment at import."""
    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
