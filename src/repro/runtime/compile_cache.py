"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only under the same cache path, so the
path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads the variable itself and nothing is set here), otherwise
``.jax_cache/`` at the root of the checkout.  Entry points call
``use_compile_cache`` once, before their first compile; tests never do.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
