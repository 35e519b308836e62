"""Where jax's persistent compilation cache lives.

The cache directory is part of what a later process needs to find a
compiled program again, so it is a fixed path: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (jax reads that variable itself, and nothing
here overrides it), otherwise ``<checkout>/.cache/jax``.  Entry points call
:func:`enable_compile_cache` once at start-up; library code never does.

The cache key includes each op's metadata: the named-scope path that
``core/conv.py`` and ``kernels/ops.py`` put on every conv pass and its glue
(``repro.obs.trace``) lives only there, and a profile reads it from the
executable.  Without it, a program whose scopes changed but whose ops did
not would be served an executable compiled with the old scopes.
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
    :func:`compile_cache_dir`, keyed on op metadata too, and return that
    directory.  When ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already
    read it and no directory is set here."""
    import jax
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return compile_cache_dir()
