"""Persistent XLA compilation cache wiring (DESIGN.md §7).

The engine's one-compile-many-scenarios design (DESIGN.md §1) moves the
cost wall from *running* sweeps to *compiling* them, and a compile is a
pure function of the program, so it should be paid once per (jax version,
program) — not once per process.  :mod:`repro.core.engine` therefore calls
:func:`enable` on import:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
  module sets no other directory;
* otherwise the cache lives in ``.jax_cache`` at the root of the checkout
  (listed in ``.gitignore``) — one fixed path, because the directory is
  part of what a cache hit needs.

``JAX_ENABLE_COMPILATION_CACHE=false`` turns the cache off (cold-compile
timings).  jax's own persistence thresholds apply: executables that took
at least a second to compile are written, i.e. every engine program and
none of the trivial helper jits.
"""
from __future__ import annotations

import os
import pathlib

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn jax's persistent compilation cache on at the directory chosen
    above and return it.  Idempotent."""
    env_dir = os.environ.get(ENV_DIR)
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_DIR))
    return str(CHECKOUT_DIR)


def active_dir() -> str | None:
    """The directory jax caches compiles in, or ``None`` when it is off."""
    import jax
    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir or None
