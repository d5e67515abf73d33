"""Tiny end-to-end runs of the mamba2-780m family's cell path on the CPU."""

import bench_helpers  # noqa: F401  (the repository root on the path)

import pytest

from e2e_cases import *  # noqa: F401,F403


@pytest.fixture
def arch():
    return "mamba2-780m"
