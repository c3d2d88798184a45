"""Shared test config.

IMPORTANT: do NOT set XLA_FLAGS / host-device-count here — smoke tests and
benchmarks must see the single real CPU device.  Tests that need many
placeholder devices run their script in a subprocess (see test_spmd.py).
"""
import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed_numpy():
    np.random.seed(0)
