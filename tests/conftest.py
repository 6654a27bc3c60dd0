import os

# Tests must see the single real CPU device.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

from forest_helpers import _make_random_forest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def random_forest_factory():
    return _make_random_forest


# ----------------------------- forest zoo (traversal-strategy differentials)

@pytest.fixture(scope="session")
def depth_skewed_forest():
    """Mixed depth-2 / depth-12 chains: the shape the depth-bucketed engine
    exists for — shallow trees must stop early, deep trees must not."""
    return _make_random_forest(24, [2, 12], 6, seed=21, chain=True)


@pytest.fixture(scope="session")
def stump_forest():
    """Single-node trees only (boosted-stump shape): depth 0, the root IS
    the leaf. Exercises the scan's sentinel self-loop and leaf_path's
    empty-path scoring."""
    return _make_random_forest(17, [0], 4, seed=22)


@pytest.fixture(scope="session")
def all_categorical_forest():
    """Every split is a category-mask bit test (no numerical thresholds):
    the cat-code cast path with nothing to hide behind."""
    return _make_random_forest(12, [1, 3, 5], 4, seed=23,
                               cat_feats=(0, 1, 2, 3))


@pytest.fixture(scope="session")
def tiny_adult():
    """A small mixed-semantics training set shared by model-layer tests."""
    from repro.data.tabular import adult_like
    return adult_like(400, seed=3)
