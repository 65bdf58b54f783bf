"""The one compile-cache rule for every entry point.

Entry points (the model CLIs, ``chip_smoke.py``, the bench worker, the
service worker and the tools) call :func:`configure_compile_cache` before
their first compile. Library code does not: an embedding application owns
its own JAX configuration. The backend itself is whatever JAX selects;
``JAX_PLATFORMS=cpu`` is how a user asks for the CPU.
"""

from __future__ import annotations

import os

#: The checkout this package was imported from (the directory holding
#: ``stateright_tpu/``), derived from this file's path — never from the
#: current directory, so the cache path is the same from anywhere.
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Where compiled programs persist when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset. JAX keys cache entries by program, so one fixed path is what
#: lets a second process (or a second run) hit the first one's compiles.
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def compile_cache_dir() -> str:
    """The directory JAX's persistent compile cache uses under this rule."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def configure_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here. Otherwise the cache is ``<checkout>/.jax_cache``.
    Programs that compile in under a second are not cached."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return compile_cache_dir()
