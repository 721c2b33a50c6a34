"""JAX's persistent compilation cache, switched on by entry points.

Library code never enables the cache; a program's ``main`` calls
:func:`enable` once, before its first compilation. The cache directory is
``JAX_COMPILATION_CACHE_DIR`` when that variable is set (JAX reads it
itself, and no other path is set here); otherwise a fixed directory inside
the checkout, ``<repo>/.jax_cache`` — fixed because the path is part of
what a later run must find again.
"""
from __future__ import annotations

import os

import jax

__all__ = ["ENV_DIR", "default_dir", "enable"]

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str | None:
    """``<repo>/.jax_cache`` for a source checkout (``src/repro/...`` next
    to ``pyproject.toml``), else None."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    if not os.path.exists(os.path.join(root, "pyproject.toml")):
        return None
    return os.path.join(root, ".jax_cache")


def enable() -> str | None:
    """Turn the persistent compilation cache on; return its directory (None
    when neither the variable nor a checkout gives one). Every compilation
    is cached, however short."""
    path = os.environ.get(ENV_DIR) or None
    if path is None:
        path = default_dir()
        if path is None:
            return None
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
