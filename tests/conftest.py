"""Test configuration.

Force JAX onto CPU with 8 virtual devices so the multi-chip sharding path
(mesh/pjit) is exercised without TPU hardware, and enable the persistent
compilation cache so the big secp256k1 graphs compile once per machine.
"""

import os

# The suite runs on the deterministic 8-virtual-device CPU mesh whatever
# the machine has (`python chip_smoke.py` is what runs on the chip).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# Without the native library the pure-Python ECC golden model carries the
# signing load and the suite runs ~10x slower — build it here, and fail
# loudly rather than degrade silently.
from eges_tpu.crypto import native  # noqa: E402

native.ensure_built()


def pytest_configure(config):
    # the persistent compile cache at its one place (JAX_COMPILATION_
    # CACHE_DIR where set, else <checkout>/.jax_cache)
    from eges_tpu.crypto.aotstore import enable_persistent_cache

    enable_persistent_cache(min_compile_s=0.5)
