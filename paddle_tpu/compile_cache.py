"""Placement of JAX's persistent compilation cache — the one site in the
tree that sets it.

The directory is part of the cache key, so it must not move between
runs: where the environment names one (``JAX_COMPILATION_CACHE_DIR``,
which JAX reads by itself) nothing is touched; otherwise the cache lives
at a fixed path inside the checkout, ``<repo>/.jax_cache`` (listed in
.gitignore).  A process pinned to the CPU (``JAX_PLATFORMS=cpu``: the
tests, rehearsals) gets no cache: the checkout is copied between
machines, and XLA:CPU warns that a host executable loaded on another
CPU model can fault.  paddle_tpu/__init__ applies this on import, before
any backend exists.
"""

import os

__all__ = ["resolve_cache_dir", "apply_compile_cache"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def resolve_cache_dir(environ=None):
    """(directory, placed_by_env): the environment's directory when it
    names one; else None for a CPU-pinned process, the fixed in-checkout
    path for every other."""
    environ = os.environ if environ is None else environ
    if environ.get(_ENV):
        return environ[_ENV], True
    if environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None, False
    return _CHECKOUT_DIR, False


def apply_compile_cache(environ=None):
    """Point JAX at the resolved directory; a directory placed from
    outside is left for JAX to read from its own environment variable."""
    path, from_env = resolve_cache_dir(environ)
    if path is not None and not from_env:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
