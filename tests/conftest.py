"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware. These env vars must be set before jax is
imported.
"""

import os
import sys

import pytest

# Hard assignment: unit tests run on the virtual CPU mesh whatever the
# machine offers (a chip belongs to one process; the test session must
# not claim it).
os.environ["JAX_PLATFORMS"] = "cpu"
# Exercise the JAX batch-verify kernel in tests even though the backend is
# the virtual CPU mesh (TM_TPU_CRYPTO auto would pick the host path there).
os.environ.setdefault("TM_TPU_CRYPTO", "on")
# The production default fe_mul is the slice form, but XLA-CPU executes
# its Toeplitz slices pathologically (~8 sigs/s); the dot form is
# fast enough on CPU, and both forms are bit-identical
# (tests/test_field.py::test_mul_modes_agree_with_oracle pins slice
# parity explicitly). Semantics tests use dot.
os.environ.setdefault("TM_TPU_FE_MUL", "dot")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent compilation cache: the crypto kernels are compile-heavy.
from tendermint_tpu.ops import enable_compile_cache  # noqa: E402

enable_compile_cache()


@pytest.fixture
def small_mesh_cache(monkeypatch):
    """The sharded route's pubkey cache at 128 slots, made afresh: its
    size on the chip (16384 slots, 256 MiB a device, filled at that many
    rows) replicated over the virtual devices would hold gigabytes here
    and build for minutes."""
    from tendermint_tpu.parallel import sharded_verify

    monkeypatch.setattr(sharded_verify, "CACHE_SLOTS", 128)
    monkeypatch.setattr(sharded_verify, "_CACHES", {})
