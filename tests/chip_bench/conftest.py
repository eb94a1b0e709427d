"""The chip benchmark's tests run a tiny cell of each kind from a temporary
directory (`bench_fixtures.py`)."""

import pytest
from bench_fixtures import write_bench


@pytest.fixture
def tiny(tmp_path):
    return tmp_path, write_bench(tmp_path)
