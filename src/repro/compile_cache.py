"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``, the scripts)
calls ``enable_compile_cache`` once, before its first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets no other directory.  Otherwise, on a TPU, the cache lives at
``.jax_cache/`` in the root of the checkout: a fixed path (never a temp
name, a pid or a time), so a second run from the same checkout finds what
the first wrote.  The directory is git-ignored.

On the CPU the in-checkout cache stays off: XLA:CPU reloads its entries
with a host-feature check that floods stderr on every hit, and CPU
compiles here are short.
"""

from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on; returns its directory, or None when
    it stays off (CPU backend, no ``JAX_COMPILATION_CACHE_DIR``).  Every
    compile is written, however short: the kernels of this system compile
    in well under JAX's default one-second threshold."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        if jax.default_backend() != "tpu":
            return None
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
