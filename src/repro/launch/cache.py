"""JAX's persistent compilation cache, placed from outside.

A process that compiles the same programs again (a smoke run, a
benchmark) skips the compile when the cache holds them.  The cache
directory is part of what makes an entry findable, so it is either the
one ``JAX_COMPILATION_CACHE_DIR`` names or a fixed path inside the
checkout — never one built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    the variable itself, and this sets no other); otherwise the cache
    lives at ``<repo>/.jax_cache``, which git ignores.  Call it from a
    program's entry point, never at import.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
