"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (multi-chip sharding is validated
without TPU hardware, as the reference's distributed paths are tested
in-process — SURVEY.md §4). These env vars must be set before jax imports.
"""

import os

# Tests run on the CPU backend whatever the machine has: the chip is
# checked by chip_smoke.py, on a machine that has one.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from tier-1 (-m 'not slow')")


@pytest.fixture
def no_thread_leaks():
    """Opt-in guard: the test must not leave any ThreadRegistry-tracked
    background thread behind (concurrency plane, ISSUE 13)."""
    from toplingdb_tpu.utils import concurrency as ccy

    before = {id(t) for t in ccy.registry.live()}
    yield
    leaked = [t.name for t in ccy.registry.live() if id(t) not in before]
    assert not leaked, f"test leaked registered threads: {leaked}"


@pytest.fixture
def mem_env():
    from toplingdb_tpu.env import MemEnv

    return MemEnv()


@pytest.fixture
def tmp_db_path(tmp_path):
    return str(tmp_path / "db")
