"""Where JAX's persistent compilation cache lives.

JAX_COMPILATION_CACHE_DIR, when set, is the cache directory and nothing
here touches it (JAX reads the variable itself and workers inherit it).
Otherwise the cache sits at a FIXED path inside the checkout: the path
is part of the cache key, so a directory that moves never hits.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    return os.environ.get(ENV_VAR) or REPO_CACHE_DIR


def set_in_env(env: dict) -> dict:
    """Spawn-time half: hand a child that will import jax the cache
    path in its environment, so placing the cache costs it no import."""
    env.setdefault(ENV_VAR, REPO_CACHE_DIR)
    return env


def enable() -> str:
    """In-process half, for scripts that import jax themselves."""
    if not os.environ.get(ENV_VAR):
        import jax

        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return cache_dir()
