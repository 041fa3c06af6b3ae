"""JAX's persistent compilation cache, kept in one fixed place.

Entry points (``repro.launch.serve``, ``repro.launch.train``,
``benchmarks/run.py``, ``chip_smoke.py``) call ``enable_compile_cache``
before they compile anything, so a second run of the same program in the
same checkout reads its executables back instead of compiling them again.
"""

from __future__ import annotations

import os
from pathlib import Path

#: The checkout's cache directory (listed in ``.gitignore``).  The path is
#: fixed on purpose: it is part of what the cache is keyed by, so a
#: directory built from a temp dir, a pid or the time would never hit.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is configured; otherwise the cache goes to ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
