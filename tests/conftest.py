"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8``) so that every multi-chip
sharding path (dp/fsdp/tp/pp/cp) is exercised without TPU hardware — the same
idea as the reference's envtest strategy (controllers/suite_test.go:51-89):
a headless stand-in that fully exercises the control logic.

Runs before the first backend init anywhere in the test process: the CPU
is forced through the environment before ``import jax``, so no test can
reach an accelerator (on-chip checks live in ``chip_smoke.py``).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

from paddle_operator_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

# Persistent compile cache: the sharded train-step compiles dominate suite
# wall-time on CPU; cache them across runs (same rule as the program).
enable_compile_cache()


def pytest_configure(config):
    # tier-1 (make tier1) runs -m 'not slow' under a hard 870s budget;
    # heavyweight serving sweeps whose invariants the dryrun gates also
    # pin carry this mark and run in the full (unfiltered) suite only
    config.addinivalue_line(
        "markers", "slow: heavyweight sweep excluded from tier-1")
