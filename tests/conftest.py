import os
import sys

# Tests must see exactly ONE device (the dry-run alone uses 512 fake ones).
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


def pytest_configure(config):
    # registered in pytest.ini too; kept here so bare `pytest tests/foo.py`
    # from another rootdir doesn't warn about an unknown marker.
    config.addinivalue_line(
        "markers",
        "slow: multi-minute subprocess/end-to-end tests "
        "(deselected by default; run with -m slow)")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
