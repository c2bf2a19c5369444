"""Test configuration: force an 8-device virtual CPU mesh.

Tests never require real TPU hardware; sharding tests exercise
``jax.sharding.Mesh`` semantics over 8 virtual CPU devices
(``--xla_force_host_platform_device_count=8``).  ``JAX_PLATFORMS=cpu`` in
the environment is the whole pin: it is set here, before JAX is imported,
for runs that did not export it themselves.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from mochi_tpu.utils.runtime import enable_compile_cache  # noqa: E402

# Persistent compilation cache: the Ed25519 verify program takes minutes to
# compile on CPU; cache it across test processes/runs.
enable_compile_cache()
