"""Fixtures for the benchmark's own tests."""

import pytest

from benchmark_roots import make_root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))
