"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the reference's multi-node story is
in-process simulation over a shared clock, SURVEY.md §4; our multi-chip story
is jax.sharding over a Mesh, validated here without TPU hardware).  The real
TPU chip is exercised by ``chip_smoke.py`` and ``benchmarks/run.py``, not by the
unit suite.

JAX reads ``JAX_PLATFORMS`` itself; it is set here, before any test imports
jax, so the suite never looks for an accelerator.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# The tpu-backend tests run the Pallas kernel in interpret mode; its first
# (compile-bearing) dispatch can exceed the production 150 s watchdog budget
# on a loaded host, and a false latch fails device-path assertions.  Tests
# that exercise the watchdog itself set instance budgets explicitly.
os.environ.setdefault("STELLAR_TPU_FIRST_DISPATCH_BUDGET", "600")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
