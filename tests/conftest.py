"""Test-session bootstrap: pin the CPU and force 8 host devices before JAX
initializes.

The suite runs on the CPU backend.  Unless the caller chose a platform,
``JAX_PLATFORMS=cpu`` keeps it (and every subprocess a test starts, which
inherits the environment) off an attached accelerator: on a machine with
a TPU a plain ``pytest`` would otherwise claim the chip, and the
subprocess tests would start children that cannot get it.

Multi-device tests (sharding specs, production meshes, elastic rescale)
need >= 8 devices; on a CPU-only host XLA exposes 1 unless the host
platform is split.  Both variables must be in the environment before the
first ``import jax`` anywhere in the test session, which is why they live
here rather than in a fixture.  An operator-provided value wins.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
