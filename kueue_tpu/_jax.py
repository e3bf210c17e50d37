"""Central JAX import for kueue_tpu.

Quota quantities are canonical int64 (milli-CPU / bytes) — values like
64Gi overflow int32 — so x64 mode is enabled here, before any kernel
builds arrays. All ops/core modules must import jax/jnp from this module
rather than directly, so the flag is set exactly once, first.

On TPU, int64 arithmetic is emulated by XLA; the solver tensors are tiny
relative to MXU workloads so this costs little, and exact integer math
is required for decision parity with the reference
(pkg/resources/requests.go keeps everything in int64 for the same
reason).
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

__all__ = ["jax", "jnp", "lax", "use_compile_cache"]


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Called by the entry points (server, CLI, benchmark, chip smoke)
    before their first compile, never on import, so tests write no
    cache. ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting
    and wins. Otherwise the cache is ``<checkout>/.jax_cache``: a fixed
    path, because the directory is part of what a later run must find."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
