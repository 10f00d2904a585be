"""JAX's persistent compilation cache at a path that does not move.

The cache directory is part of every entry's key, so a directory that
changes between runs (a ``tempfile``, a pid, a timestamp) never hits.
The entry scripts that compile for the chip — ``chip_smoke.py``,
``perfbench/run.py`` and the JAX examples — call
:func:`enable_compile_cache` once before their first compile.  It is
not called at import, and the library and the tests never call it.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it
    into ``jax_compilation_cache_dir`` and nothing is changed; otherwise
    the cache goes to ``<checkout>/.jax_cache`` (git-ignored).
    """
    import jax

    from horovod_tpu import telemetry

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(CHECKOUT, ".jax_cache"))
    directory = jax.config.jax_compilation_cache_dir
    # What the cache holds now, against its cap (JAX's own option, -1
    # where JAX_COMPILATION_CACHE_MAX_SIZE is not set): a cell whose
    # programs outgrow the cap compiles everything on every run.
    cap = jax.config.jax_compilation_cache_max_size
    telemetry.cache_found(directory, cap if cap >= 0 else None)
    return directory
