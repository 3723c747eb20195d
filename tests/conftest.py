"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests see 1 CPU device;
meshes of production shape are checked as fake meshes (no devices)."""

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
