"""Placement of JAX's persistent compilation cache.

Every process that compiles for the chip — the driver, each SPMD rank
(``spmd/worker_main.py``) and each serving replica
(``serve/replica_main.py``) — calls :func:`ensure_compile_cache` at its
entry, so a BERT-base step compiled once is found again by the next
process and the next run. This module is the only place in the tree that
sets the cache path.

The directory is part of the cache key's lookup, so it must not move:

* ``JAX_COMPILATION_CACHE_DIR`` set — jax reads the variable itself;
  nothing is set in code, and child processes inherit the variable.
* unset — one fixed directory inside the checkout (``.jax_cache/``,
  git-ignored). Children compute the same path from the same package
  location; it never contains a pid, a time or a temporary name.
* ``JAX_PLATFORMS=cpu`` — the process was asked to stay off the chip
  (tests, ETL workers); nothing it compiles is for the chip and no
  cache is configured.
"""
from __future__ import annotations

import os
from typing import Optional

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def cpu_requested() -> bool:
    """Whether the environment asks this process (and the children that
    inherit it) to stay off the chip: ``JAX_PLATFORMS=cpu``."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def compile_cache_dir() -> Optional[str]:
    """The directory this process has to set in code, or None when the
    environment already decides (see module docstring)."""
    if os.environ.get(CACHE_DIR_ENV) or cpu_requested():
        return None
    return DEFAULT_CACHE_DIR


def ensure_compile_cache() -> Optional[str]:
    """Point jax at the cache directory before the first compile.
    Returns the directory set in code (None when none was)."""
    path = compile_cache_dir()
    if path is not None:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path

