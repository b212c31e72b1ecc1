"""The one place that says where compiled programs persist.

A cold process spends most of a short run compiling (the four-branch
ResNet50 ``switch``/``scan`` ring program, the decode programs), so
every process of this package shares JAX's persistent compilation
cache:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  this module sets nothing — the cache can be placed from outside;
* otherwise the cache lives at :data:`DEFAULT_DIR`, a fixed directory
  inside the checkout (listed in ``.gitignore``).  The directory is part
  of the cache key, so it is never derived from ``tempfile``, a pid or
  the time; child processes import the same package and resolve the
  same path.
"""

from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache``
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str:
    """Point JAX at the cache directory (unless the environment already
    does) and return the directory in force.  Called once, when the
    package is imported."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
