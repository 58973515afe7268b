"""Where the persistent XLA compile cache goes — for entry points.

Called by the scripts a person runs (``chip_smoke.py``, ``bench.py``, the
chip-side tools), never at ``import paddle_tpu``: a library import must
not decide where a process writes.

The directory is part of every cache key's lookup, so it has to be the
same from one run to the next: ``JAX_COMPILATION_CACHE_DIR`` when the
environment places it (JAX reads that variable itself — nothing is set
here), otherwise one fixed directory at the root of this checkout, listed
in ``.gitignore``.  No temp name, pid or timestamp ever enters the path.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory used."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
