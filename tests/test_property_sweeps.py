"""Hypothesis property sweeps (kernels vs oracles over random shapes/dtypes).

Kept in their own module so the rest of the engine/splitter tests stay
runnable when hypothesis is not installed: ``pytest.importorskip`` skips only
this file at collection time.
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

import repro.core.models as M  # noqa: E402
from repro.core import GradientBoostedTreesLearner  # noqa: E402
from repro.data.tabular import adult_like, train_test_split  # noqa: E402


def _gh_stats(rng, n):
    g = rng.normal(size=n)
    h = np.abs(rng.normal(size=n)) + 0.1
    return np.stack([g, h, np.ones(n)], 1)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(10, 120), f=st.integers(1, 4), nodes=st.integers(1, 5),
       bins=st.sampled_from([4, 16, 64]), seed=st.integers(0, 10_000))
def test_histogram_partition_property(n, f, nodes, bins, seed):
    """Histogram totals == direct per-node sums; bins partition examples."""
    from repro.core.splitters import build_histogram
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, bins, (n, f)).astype(np.uint8)
    stats = _gh_stats(rng, n)
    node_of = rng.integers(-1, nodes, n).astype(np.int32)
    hist = build_histogram(codes, stats, node_of, nodes, bins)
    assert hist.shape == (nodes, f, bins, 3)
    for node in range(nodes):
        sel = node_of == node
        np.testing.assert_allclose(hist[node, 0].sum(0), stats[sel].sum(0),
                                   atol=1e-4)
        # identical totals across features (each feature sees every example)
        np.testing.assert_allclose(hist[node].sum(1),
                                   np.broadcast_to(stats[sel].sum(0), (f, 3)),
                                   atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 300), f=st.integers(1, 6), s=st.integers(1, 5),
       nodes=st.integers(1, 9), bins=st.sampled_from([8, 32, 256]),
       dt=st.sampled_from(["float32", "float64"]), seed=st.integers(0, 99))
def test_histogram_kernel_sweep(n, f, s, nodes, bins, dt, seed):
    import jax.numpy as jnp
    from repro.kernels.histogram.ops import histogram
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, bins, (n, f)).astype(np.uint8)
    stats = rng.normal(size=(n, s)).astype(dt)
    node_of = rng.integers(-1, nodes, n).astype(np.int32)
    ref = np.asarray(histogram(jnp.asarray(codes), jnp.asarray(stats),
                               jnp.asarray(node_of), nodes, bins, impl="ref"))
    pal = np.asarray(histogram(jnp.asarray(codes), jnp.asarray(stats),
                               jnp.asarray(node_of), nodes, bins,
                               impl="interpret"))
    np.testing.assert_allclose(pal, ref, atol=1e-4, rtol=1e-4)


@pytest.mark.slow
@pytest.mark.traversal
@settings(max_examples=30, deadline=None)
@given(n=st.sampled_from([0, 1, 2, 7, 33]), trees=st.integers(1, 10),
       n_feats=st.integers(2, 6), out_dim=st.sampled_from([1, 3]),
       n_cat=st.integers(0, 2), hostile=st.booleans(),
       seed=st.integers(0, 10_000))
def test_traversal_strategy_equivalence_sweep(n, trees, n_feats, out_dim,
                                              n_cat, hostile, seed):
    """Property: the four CPU traversal strategies are ONE function — on
    random ragged forests (stumps through depth ~10, categorical splits,
    multi-output leaves) and hostile batches (0 rows, 1 row, NaN/±inf on
    numerical columns, unseen/negative category codes), every strategy's
    per-tree output is bit-identical to the vectorized engine."""
    from forest_helpers import _make_random_forest
    from repro.core.tree import (LEAF_PATH_BUDGET, compile_predict_raw,
                                 leaf_path_sizes, predict_naive)
    from repro.kernels.forest_infer.ops import forest_predict_bucketed
    rng = np.random.default_rng(seed)
    cat_feats = tuple(range(n_cat))
    splits = [int(s) for s in rng.integers(0, 11, size=min(trees, 4))]
    forest = _make_random_forest(trees, splits, n_feats, out_dim=out_dim,
                                 seed=seed, cat_feats=cat_feats)
    X = (rng.normal(size=(n, n_feats)) * 2).astype(np.float32)
    for j in cat_feats:
        # unseen (>=256) and negative codes clamp, matching the oracle
        X[:, j] = rng.integers(-5, 400, size=n)
    if hostile and n >= 4 and n_cat < n_feats:
        X[0, n_cat] = np.nan
        X[1, n_cat] = np.inf
        X[2, n_cat] = -np.inf
        X[3, n_cat] = 3e38
    want = compile_predict_raw(forest)(X)
    assert want.shape == (n, trees, out_dim)
    assert np.array_equal(predict_naive(forest, X), want)
    assert np.array_equal(
        np.asarray(forest_predict_bucketed(forest, X)), want)
    i, l = leaf_path_sizes(forest)
    if i * l <= LEAF_PATH_BUDGET:
        assert np.array_equal(np.asarray(
            forest_predict_bucketed(forest, X, strategy="leaf_path")), want)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 100), trees=st.integers(1, 5), seed=st.integers(0, 99))
def test_forest_infer_kernel_sweep(n, trees, seed):
    """Random trained forests (incl. categorical masks) on random inputs."""
    from repro.core.tree import predict_raw
    from repro.kernels.forest_infer.ops import forest_predict
    rng = np.random.default_rng(seed)
    train, _ = train_test_split(adult_like(300, seed=seed), 0.3, seed)
    m = GradientBoostedTreesLearner(label="income", num_trees=trees,
                                    max_depth=4, seed=seed).train(train)
    ds = M._as_vertical(train, m.spec)
    X = M.raw_matrix(ds, m.features)[:n]
    want = predict_raw(m.forest, X)
    got = np.asarray(forest_predict(m.forest, X, impl="interpret"))
    np.testing.assert_allclose(got, want, atol=1e-5)
