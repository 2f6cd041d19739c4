"""Test configuration: fake an 8-device TPU mesh on CPU.

Mirrors the reference's local-mode Spark "fake cluster" test strategy
(utils/.../test/TestSparkContext.scala:36-80): distributed semantics are
exercised on a single host — here via XLA's virtual CPU devices.
Must set flags before jax is imported anywhere.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")
# every train() appends stage observations to the shared cost history
# (tuning/costmodel.py); tests must not churn the repo's
# benchmarks/cost_history.json, so redirect to a throwaway file
import tempfile as _tempfile

os.environ.setdefault(
    "TMOG_COST_HISTORY",
    os.path.join(_tempfile.gettempdir(), "tmog_test_cost_history.json"))

import jax

# belt and braces: JAX_PLATFORMS=cpu above is honoured on its own
jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _reset_uids():
    from transmogrifai_tpu.utils.uid import reset_uids

    reset_uids()
    yield


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running scale tests (run in CI, skippable "
        "locally with -m 'not slow')")
    config.addinivalue_line(
        "markers", "faults: fault-injection tests that spawn/kill "
        "subprocesses (tests/test_resilience.py)")


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    """No test may leak an armed fault plan into the next one."""
    from transmogrifai_tpu.utils import faults

    faults.install_faults(None)
    yield
    faults.install_faults(None)
