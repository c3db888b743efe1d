"""Persistent XLA compilation cache — one rule for where it lives.

The reference pays no compile cost (eager PyTorch); the XLA trade is
whole-program optimization up front, and that cost recurs per *process*
(in-memory jit caches die with it) unless compiled programs persist on
disk. The serving engine alone compiles one prefill program per chunk
count plus the launch program at warm-up; a gang compiles the same train
step once per rank. So every process that imports the package shares one
cache, placed by one rule (``ensure_compilation_cache``):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already honours it; this code
  sets no directory, so a deployment (or the chip tool) places the cache
  from outside.
- not set: ``<checkout>/.xla_cache``, derived from this file's location.
  The directory is part of nothing's identity but must not move — a
  temp dir, pid or timestamp in the path would never hit.

Entries are keyed by program + backend fingerprint, so CPU and TPU runs
can share a directory; JAX's default floor (compiles under 1 s are not
written) applies.
"""

from __future__ import annotations

import os

import jax
from jax._src import compilation_cache as _jax_cache

#: ``<checkout>/.xla_cache`` — the package directory's parent is the checkout.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".xla_cache",
)


def ensure_compilation_cache() -> str:
    """Apply the placement rule (module docstring); returns the directory
    in force. Called at package import, i.e. before the first compile of
    every entry point that lives in the package; idempotent."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    if jax.config.jax_compilation_cache_dir != CHECKOUT_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
        # JAX binds its cache object to a directory (or to "none") at the
        # first compile; if the embedding program compiled before it
        # imported this package, the update above is ignored until the
        # binding is dropped.
        _jax_cache.reset_cache()
    return CHECKOUT_CACHE_DIR


def jit_cache_size(fn) -> int:
    """Number of compiled programs held by one ``jax.jit`` callable —
    the in-process compile counter behind the serving engine's
    zero-recompiles-after-warmup invariant (each new (shape, dtype)
    signature adds one)."""
    return int(fn._cache_size())
