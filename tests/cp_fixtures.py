"""Shared fixtures, loaded as a pytest plugin (``-p cp_fixtures`` in
pyproject.toml) rather than as ``tests/conftest.py``: ``bench/tests`` imports
helpers from its own ``conftest`` module, and two conftests under one
collection would shadow each other in ``sys.modules``.

NOTE: no XLA_FLAGS here — tests see the real (single) CPU device;
multi-device behaviour is covered by subprocess integration tests
(test_multidevice.py)."""
import numpy as np
import pytest

from repro.core.coo import random_sparse


@pytest.fixture(scope="session")
def small_tensor():
    return random_sparse((40, 30, 20), 600, seed=7, distribution="zipf")


@pytest.fixture(scope="session")
def small_tensor_4mode():
    return random_sparse((20, 15, 12, 10), 400, seed=8)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")
