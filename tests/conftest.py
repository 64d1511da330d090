"""Test configuration: the suite runs on the CPU, with Pallas kernels in
interpret mode, over an 8-device virtual CPU mesh so the multi-chip
sharding paths run without TPU hardware."""

import os

# Must be set before jax is imported anywhere: JAX reads both when it
# initializes its backends.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
