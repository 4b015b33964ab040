"""The two places the framework touches JAX's own configuration surface:
the ``shard_map`` entry point and the persistent compilation cache.

Written for the one installed JAX (``jax.shard_map`` with ``check_vma``);
there is no branch for any other release.
"""

from __future__ import annotations

import os

import jax

shard_map = jax.shard_map

#: where compiled executables persist when the environment names no cache:
#: one fixed, git-ignored directory at the root of the checkout, resolved
#: from this file's own path. The directory is part of every cache key,
#: so it must never depend on a temporary name, a pid or the time.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")


def enable_compilation_cache() -> str:
    """Make sure JAX's persistent compilation cache has a directory, and
    return it.

    One rule. If ``JAX_COMPILATION_CACHE_DIR`` is exported, JAX has
    already read it into its config — nothing is set here. Otherwise the
    cache goes to :data:`DEFAULT_CACHE_DIR`. JAX's own thresholds decide
    what is worth an entry. ``Trainer._start`` and both serving engines'
    constructors call this before their first compile; it is idempotent.
    """
    configured = jax.config.jax_compilation_cache_dir
    if configured:  # from the environment, or from an earlier call
        return configured
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


__all__ = ["shard_map", "enable_compilation_cache", "DEFAULT_CACHE_DIR"]
