"""Shared fixtures.  NOTE: no XLA_FLAGS here -- tests run on the real
device count (1 CPU); multi-device tests spawn subprocesses that set
--xla_force_host_platform_device_count and JAX_PLATFORMS=cpu through
``subprocess_env``, so a child never reaches for a TPU."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def subprocess_env(n_devices: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}"
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    return env
