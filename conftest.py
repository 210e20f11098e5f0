"""Fixtures shared by both test trees (tests/ and bench/tests/).

No test module imports this file by name: bench/tests has a conftest of
its own that its modules import, and only one module can be
``conftest`` at a time."""
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
