"""Test env: force an 8-device virtual CPU platform (SURVEY.md §4: the
reference's multi-GPU tests map onto XLA host-platform device-count
override)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# Tests and rehearsals run on the CPU only (the chip is reached through
# chip_smoke.py, one process per chip): pin the platform through the
# config API too, before any backend is initialized, so an environment
# that exports another JAX_PLATFORMS cannot move them.
import jax

jax.config.update("jax_platforms", "cpu")
