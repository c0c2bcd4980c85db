"""Test configuration: run all tests on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding semantics are
validated on XLA's host platform with 8 virtual devices, which exercises the
same GSPMD partitioner and collective lowering paths as a real TPU slice.

The suite is a CPU suite: both settings go into the environment before
jax is imported, so jax honours them here and every child process a
test starts (CLI binaries, bench legs, replica fleets) inherits the same
explicit CPU request.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax
import pytest


@pytest.fixture
def locksmith_sanitizer(monkeypatch):
    """Runs a test with the lock sanitizer armed (testing/locksmith.py).

    The chaos suites opt in with a module-local autouse fixture so every
    seeded fault run doubles as a deadlock hunt: teardown FAILS the test
    on any lock-order cycle or hold-budget violation observed at
    runtime. Blocking-under-lock events are reported, not failed — chaos
    `delay` clauses land inside critical sections by design and the
    report is the point.
    """
    monkeypatch.setenv("T2R_LOCK_SANITIZER", "1")
    from tensor2robot_tpu.testing import locksmith

    locksmith.reset()
    yield locksmith
    cycles = locksmith.violations(locksmith.ORDER_CYCLE)
    over_budget = locksmith.violations(locksmith.HOLD_BUDGET)
    locksmith.reset()
    assert not cycles, f"lock-order cycle(s) observed at runtime: {cycles}"
    assert not over_budget, (
        f"lock hold-time budget exceeded: {over_budget}"
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: full-resolution / multi-step integration tests"
    )


def pytest_sessionstart(session):
    devices = jax.devices()
    assert devices[0].platform == "cpu", (
        f"Tests must run on the virtual CPU mesh, got {devices[0]}"
    )
    assert len(devices) == 8, f"Expected 8 virtual devices, got {len(devices)}"
