"""Where XLA's persistent compilation cache lives.

One rule for every entry script (``chip_smoke.py``, ``bench.py``) and
for the elastic driver's worker environment — not for ``hvd.init()``,
which leaves a library user's jax configuration alone:

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this code
  sets no directory at all, so whoever runs the program places the
  cache.
* unset: ``<checkout>/.jax_cache`` — a fixed path (the path is part of
  the cache key, so a directory that moves never hits), ignored by git.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def directory() -> str:
    """The cache directory in effect."""
    return os.environ.get(_ENV) or DEFAULT_DIR


def enable() -> str:
    """Turn the persistent cache on for this process; returns the
    directory in effect.  Call before the first compilation."""
    if not os.environ.get(_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return directory()
