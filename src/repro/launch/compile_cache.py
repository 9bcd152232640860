"""Where JAX keeps compiled programs between processes of one checkout.

A process that reaches the chip compiles the train step and every kernel
shape it meets; its successors (a resumed run, the next phase of a smoke
run) find them again in JAX's persistent compilation cache. The cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says when the environment sets
it — JAX reads that variable itself, and nothing here overrides it — and
otherwise in one fixed directory of the checkout, ``.jax_cache`` (a
directory that moves never hits: its path is part of the key).

Entry points call :func:`use_compile_cache` once at start-up; importing
this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout's cache directory (gitignored), used when the environment
#: names none
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache for this process and
    return its directory. Every compiled program is kept, kernels that
    compile in well under a second included."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
