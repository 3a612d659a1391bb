"""Fixtures of the benchmark's tests."""

import pytest

from benchlib import make_tiny_root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """See :func:`benchlib.make_tiny_root`."""
    return make_tiny_root(tmp_path_factory.mktemp("bench_root"))
