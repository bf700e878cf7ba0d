"""Persistent XLA compile cache placement — one rule for every entry point.

The main model compiles 24 unrolled transformer blocks; a cold compile of
the train step is minutes of wall time that every restart, gang respawn and
second process would pay again.  JAX's persistent compilation cache removes
that, provided every process of a job agrees on ONE directory that stays
put between runs (a cache in ``tempfile``/a pid/a timestamp never hits).

The rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself at import — that
  is the whole configuration, this module touches nothing.
- unset: ``<checkout>/.jax_cache`` (the directory holding the ``bagua_tpu``
  package).

Call :func:`configure_compile_cache` before the first compile: JAX decides
once per process whether the cache is in use (at the first compile), so a
later call cannot turn it on.  :func:`bagua_tpu.init_process_group` and the
entry scripts call it; the launcher exports the resolved directory so gang
workers and gang restarts share it.
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``: next to the ``bagua_tpu`` package."""
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(checkout, ".jax_cache")


def resolve_cache_dir() -> str:
    """The directory the cache lives in, without configuring anything (the
    launcher exports this into its workers' environment)."""
    return os.environ.get(CACHE_DIR_ENV) or default_cache_dir()


def configure_compile_cache() -> str:
    """Apply the rule above; returns the cache directory in effect."""
    if not os.environ.get(CACHE_DIR_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", default_cache_dir())
    return resolve_cache_dir()
