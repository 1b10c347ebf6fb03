"""TPU compute kernels.

The dense-compute plane of the framework: GF(2^255-19) limb arithmetic,
Edwards25519 group operations, and batched ed25519 verification, written
as pure jax.numpy programs (TPU-native: int32 limb vectors on the VPU,
static shapes, lax control flow). There are no Pallas kernels in the
tree: every program here is lowered by XLA.

This replaces the reference's curve25519-voi dependency (go.mod:22, used
by crypto/ed25519/ed25519.go) with a TPU-first design: instead of a
randomized combined batch equation, every signature's cofactored ZIP-215
equation is checked data-parallel across lanes, which is both stronger
(deterministic, no randomizers) and byte-identical in acceptance.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Persistent XLA compile cache for every process that runs a
    kernel: nodes, bench, chip_smoke and the tests. The curve programs
    take tens of seconds each to compile, so a second process must find
    them on disk. Where JAX_COMPILATION_CACHE_DIR is set (jax reads it
    at import) no directory is set in code, so the cache can be placed
    from outside — a chip run keeps it in the one directory that
    survives the call; otherwise it is <checkout>/.jax_cache. Returns
    the directory in force.

    Called by ops/field.py, the first kernel module to import jax —
    not at package import, because ops/engine.py stays jax-free for
    host-only nodes (TM_TPU_CRYPTO=off)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(_CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return jax.config.jax_compilation_cache_dir
