"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
nothing here overrides it. Otherwise the cache lives in ``.jax_cache/`` at
the root of the checkout: a fixed path, because the path is part of what
makes a later run find an entry again.

An entry's key includes the program's metadata (each operation's
``op_name``). Without it, a program that differs from a cached one only in
its named scopes would be served the cached executable, whose operations
carry the old names, and a profile of it would read the wrong scopes.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Point the persistent compile cache at its directory and return it.
    Call before the first compile: JAX opens the cache once."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
