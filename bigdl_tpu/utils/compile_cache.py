"""Persistent XLA compilation cache: one function, one directory.

Every entry point that compiles (``Engine.init``, the test suite, the
chip smoke) calls :func:`enable_persistent_cache`. A directory that moves
between runs never hits, so it is either the one the environment names
(``JAX_COMPILATION_CACHE_DIR``, which JAX reads by itself — nothing is set
in code then) or one fixed path inside the checkout.

No per-CPU sub-directory: jaxlib's cache key already hashes the CPU
backend's machine features (the serialized CPU topology lists ``+avx2``,
``+avx512f``, ...), so an XLA:CPU entry written on another
microarchitecture misses instead of loading.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger("bigdl_tpu")

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_persistent_cache() -> None:
    """Turn on JAX's persistent compilation cache.

    ``JAX_COMPILATION_CACHE_DIR`` set: that directory, and no directory
    is set in code; failing to create it raises, because the environment
    asked for it. Unset: ``<checkout>/.jax_cache``; a checkout that
    cannot be written (an installed package) runs with cold compiles and
    says so. Must run after ``import jax`` and before the first compile.
    """
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache:
        if "://" not in cache:
            os.makedirs(cache, exist_ok=True)
    else:
        cache = DEFAULT_CACHE_DIR
        try:
            os.makedirs(cache, exist_ok=True)
        except OSError as e:
            logger.warning("no persistent compile cache: cannot create "
                           "%s (%s)", cache, e)
            return
        jax.config.update("jax_compilation_cache_dir", cache)
    # every program is kept, however quick its compile: around any positive
    # threshold, programs land in the directory on one run and not on the
    # next, so a second run would still add entries; and the test suite
    # recompiles the same small programs from fresh jit objects hundreds of
    # times (tier-1 cold: 624 s at 0.5 s, 572 s at 0; 29 MB afterwards)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
