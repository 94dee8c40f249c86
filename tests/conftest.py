"""Shared test config.

NOTE: no --xla_force_host_platform_device_count here — unit/smoke tests
run on the 1 real CPU device.  Multi-device distribution tests spawn
subprocesses (tests/dist_worker.py) that set the flag before importing
jax.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
