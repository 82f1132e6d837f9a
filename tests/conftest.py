"""Test config: run everything on 8 virtual CPU devices.

This is the fake-cluster mechanism the reference lacks entirely (it has no
tests; its only validation is launching two real processes, SURVEY §4):
``--xla_force_host_platform_device_count=8`` gives one process 8 XLA devices,
so every pipeline/ppermute/shard_map path — including multi-stage meshes with
data parallelism — runs hermetically without a TPU.

Must run before jax initializes its backends, hence module scope in conftest.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # tests run on the CPU; JAX reads this itself
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# the suite never uses the persistent compile cache, whatever directory an
# entry point under test (cli.main, chip_smoke.main) names for it
jax.config.update("jax_enable_compilation_cache", False)
