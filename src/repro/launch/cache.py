"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only under the same cache path, so
the path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads that variable itself), otherwise ``.jax_cache`` at
the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (src/repro/launch/cache.py → three levels up)
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache(checkout: Path = CHECKOUT) -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
