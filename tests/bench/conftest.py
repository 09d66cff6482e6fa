"""Fixtures of the benchmark's tests."""
from pathlib import Path

import pytest

from tiny_cells import make_tiny_root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    return make_tiny_root(tmp_path_factory.mktemp("checkout"))
