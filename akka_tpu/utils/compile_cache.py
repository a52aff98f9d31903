"""Where the persistent XLA compilation cache lives.

Every entry point that builds a device runtime (chip_smoke.py,
examples/serving_gateway.py serve) calls
`enable_compile_cache()` before its first compile. The cache's path is part
of what JAX hashes into each entry's key, so it is either the directory
`JAX_COMPILATION_CACHE_DIR` names — JAX reads that variable itself and this
module then sets nothing — or ONE fixed in-checkout path, never a temporary
name. Tests do not call this: a checkout fattened by CPU cache entries
would be copied to the chip with everything else.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The directory the cache uses: `JAX_COMPILATION_CACHE_DIR` when set,
    else `<checkout>/.jax_cache` resolved from this package's location (the
    same path from any working directory)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX at `compile_cache_dir()`; call before the first compile.
    Returns the directory in use."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
