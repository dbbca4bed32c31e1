"""Test env: 8 host devices for the distributed tests (NOT the dry-run's
512 — that flag lives only in launch/dryrun.py per the assignment)."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
