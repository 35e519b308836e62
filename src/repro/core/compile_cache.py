"""Where jax's persistent compilation cache lives.

The cache directory is part of what a later process needs to find a
compiled program again, so it is a fixed path: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (jax reads that variable itself, and nothing
here overrides it), otherwise ``<checkout>/.cache/jax``.  Entry points call
:func:`enable_compile_cache` once at start-up; library code never does.
The measured-plan cache (``kernels/autotune.py``) lives under the same
directory.
"""

from __future__ import annotations

import os
from pathlib import Path

_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache directory (git ignores ``.cache/``).
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".cache" / "jax")


def compile_cache_dir() -> str:
    """The directory jax's compilation cache uses (or will use once an
    entry point enables it)."""
    return os.environ.get(_ENV) or CHECKOUT_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it and no
    directory is set here."""
    if not os.environ.get(_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return compile_cache_dir()
