"""JAX's persistent compilation cache, placed for chip entry points.

A cold run on the chip compiles every program again, and the cache only
hits when its directory stays put between runs.  ``enable()`` is the one
place that decides where it lives: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself, so nothing is
overridden), else ``<checkout>/.jax_cache`` — a fixed path inside the
checkout (listed in ``.gitignore``), never a temporary directory.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable(root: pathlib.Path = CHECKOUT) -> str:
    """Turn the persistent cache on; returns its directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(pathlib.Path(root) / ".jax_cache"))
    return jax.config.jax_compilation_cache_dir
