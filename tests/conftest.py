"""Test configuration: JAX on a virtual 8-device CPU mesh.

Tests run on the CPU (``JAX_PLATFORMS=cpu``); multi-device tests simulate
the mesh with ``xla_force_host_platform_device_count=8`` (SURVEY.md §4).
The program runs on the chip through ``chip_smoke.py``.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from the tier-1 `-m 'not "
        "slow'` run (check.sh runs them)")


@pytest.fixture
def rng():
    return np.random.default_rng(1234)
