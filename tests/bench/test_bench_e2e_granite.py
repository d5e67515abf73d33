"""Tiny end-to-end runs of the granite-3-8b family's cell path on the CPU."""

import bench_helpers  # noqa: F401  (the repository root on the path)

import pytest

from e2e_cases import *  # noqa: F401,F403


@pytest.fixture
def arch():
    return "granite-3-8b"

