"""Test configuration: the suite runs on the CPU, on an 8-device virtual
platform, so sharding/parallel tests run anywhere.

``JAX_PLATFORMS=cpu`` plus the host-device count, both set before jax is
imported, is all it takes. ``PADDLE_TPU_REAL_CHIP=1`` (set by
tools/run_tpu_checks.py, which runs the TPU-only files on the chip) leaves
the platform to JAX and turns the persistent compile cache on."""

import os
import sys

import pytest

if os.environ.get("PADDLE_TPU_REAL_CHIP") == "1":
    # a chip call compiles everything anew; keep what it compiles where
    # the next process of the call (or a machine that keeps its disk)
    # finds it. The CPU suite below never turns the cache on.
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
else:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


@pytest.fixture(autouse=True)
def _reap_replica_processes():
    """Multi-host hygiene: no replica host process may outlive its
    test. Zero-cost unless the test imported serving.replica_host; a
    nonzero reap count means the test leaked — fail it loudly."""
    yield
    mod = sys.modules.get("paddle_tpu.serving.replica_host")
    if mod is not None:
        leaked = mod.reap_orphans()
        assert leaked == 0, (
            f"{leaked} replica host process(es) outlived the test "
            "and were SIGKILLed by the reaper")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run "
        "(multi-second multiprocess gangs, big models)")
    config.addinivalue_line(
        "markers", "faults: deterministic fault-injection (chaos) tests — "
        "kill/restart/torn-checkpoint scenarios driven by "
        "paddle_tpu.distributed.fault")
