"""Differential traversal-strategy harness (DESIGN.md §10.5).

Every CPU traversal strategy — naive per-example loop, vectorized numpy,
depth-bucketed XLA scan, forced leaf-path matmul — must produce BIT-IDENTICAL
per-tree outputs on the same forest: they are four evaluation orders of one
function. The oracle is independent of the SoA engines entirely: typed
``py_tree`` trees (to_trees) walked by plain python conditions.

Covers: a forest zoo (depth-skewed, boosted stumps, all-categorical, ragged
mixed), every trained model family x task (RF/GBT/CART x cls/reg), the
engine-selection heuristic, CompiledPredictor pickling, and the
infer-bench ``--quick`` smoke on real data.
"""
import pickle

import numpy as np
import pytest

from repro.core import (
    CartLearner,
    GradientBoostedTreesLearner,
    RandomForestLearner,
    Task,
)
from repro.core.engines import (
    BUCKETED_MIN_WORK,
    available_engines,
    compile_predictor,
    select_cpu_engine,
)
from repro.core.py_tree import CategoricalIsIn, NumericalHigherThan
from repro.core.tree import (
    LEAF_PATH_BUDGET,
    compile_predict_raw,
    leaf_path_sizes,
    pack_depth_buckets,
    plan_depth_buckets,
    predict_naive,
    select_block_strategy,
    tree_depths,
)
from repro.kernels.forest_infer.ops import forest_predict_bucketed

from forest_helpers import _make_random_forest

pytestmark = pytest.mark.traversal


# ------------------------------------------------------------ typed oracle

def _oracle_per_tree(forest, X):
    """Reference traversal over typed py_tree nodes — shares NO code with
    the SoA engines (different layout, different condition dispatch)."""
    trees = forest.to_trees()
    O = forest.leaf_value.shape[-1]
    out = np.zeros((len(X), forest.n_trees, O), np.float32)
    for t, tree in enumerate(trees):
        for n, x in enumerate(X):
            node = tree.root
            while not node.is_leaf:
                c = node.condition
                if isinstance(c, NumericalHigherThan):
                    go = bool(x[c.feature] >= np.float32(c.threshold))
                elif isinstance(c, CategoricalIsIn):
                    # numpy float->int semantics for garbage values (§10.2):
                    # NaN / +-inf / |x| >= 2^63 cast to INT64_MIN, THEN clip
                    # — so +inf lands on code 0, not 255
                    with np.errstate(invalid="ignore"):
                        code = int(np.clip(
                            np.float32(x[c.feature]).astype(np.int64), 0, 255))
                    go = code in c.categories
                else:  # pragma: no cover - zoo forests are axis-aligned
                    raise AssertionError(f"unexpected condition {c}")
                node = node.pos_child if go else node.neg_child
            out[n, t] = node.value.vector()
    return out


STRATEGIES = ("naive", "vectorized", "bucketed", "leaf_path")


def _per_tree(forest, X, strategy):
    if strategy == "naive":
        return predict_naive(forest, X)
    if strategy == "vectorized":
        return compile_predict_raw(forest)(X)
    if strategy == "bucketed":
        return forest_predict_bucketed(forest, X)
    return forest_predict_bucketed(forest, X, strategy="leaf_path")


def _assert_strategies_bit_identical(forest, X, oracle=True):
    X = np.ascontiguousarray(X, np.float32)
    want = _oracle_per_tree(forest, X) if oracle \
        else np.asarray(_per_tree(forest, X, "naive"))
    for strategy in STRATEGIES:
        if strategy == "leaf_path":
            i, l = leaf_path_sizes(forest)
            if i * l > LEAF_PATH_BUDGET:
                continue
        got = np.asarray(_per_tree(forest, X, strategy))
        assert got.shape == want.shape, strategy
        assert np.array_equal(got, want), \
            f"strategy {strategy!r} diverges from the typed-tree oracle"


def _inputs_for(forest, n, seed=5, cat_feats=(), n_cats=300):
    """Serving inputs including the hostile numerics: NaN / +-inf / huge on
    numerical AND categorical columns — every strategy and the oracle share
    numpy's float->int cast-then-clip semantics for garbage codes (§10.2),
    so hostile categorical values are part of the bit-identity contract."""
    rng = np.random.default_rng(seed)
    F = len(forest.feature_names)
    X = (rng.normal(size=(n, F)) * 2).astype(np.float32)
    for j in cat_feats:
        X[:, j] = rng.integers(-2, n_cats, size=n)
    num = [j for j in range(F) if j not in cat_feats]
    if num and n >= 8:
        X[0, num[0]] = np.nan
        X[1, num[0]] = np.inf
        X[2, num[0]] = -np.inf
        X[3, num[0]] = 3e38
    if cat_feats and n >= 8:
        X[4, cat_feats[0]] = np.nan
        X[5, cat_feats[0]] = np.inf
        X[6, cat_feats[0]] = -np.inf
        X[7, cat_feats[0]] = 3e38      # >= 2^63: cast-then-clip, not clip-255
    return X


# ---------------------------------------------------------------- forest zoo

def test_depth_skewed_forest_all_strategies(depth_skewed_forest):
    assert sorted(set(tree_depths(depth_skewed_forest))) == [2, 12]
    X = _inputs_for(depth_skewed_forest, 64)
    _assert_strategies_bit_identical(depth_skewed_forest, X)


def test_stump_forest_all_strategies(stump_forest):
    assert set(tree_depths(stump_forest)) == {0}
    X = _inputs_for(stump_forest, 32)
    _assert_strategies_bit_identical(stump_forest, X)
    # stumps must carry their root leaf value, not silent zeros
    assert np.abs(_oracle_per_tree(stump_forest, X[:1])).sum() > 0


def test_all_categorical_forest_all_strategies(all_categorical_forest):
    X = _inputs_for(all_categorical_forest, 64, cat_feats=(0, 1, 2, 3))
    _assert_strategies_bit_identical(all_categorical_forest, X)


def test_ragged_mixed_forest_all_strategies():
    forest = _make_random_forest(15, [0, 1, 4, 9, 6], 7, out_dim=3, seed=31,
                                 cat_feats=(2, 5))
    X = _inputs_for(forest, 48, cat_feats=(2, 5))
    _assert_strategies_bit_identical(forest, X)


def test_zero_and_one_row_batches(depth_skewed_forest):
    f = depth_skewed_forest
    for strategy in STRATEGIES:
        empty = np.asarray(_per_tree(f, np.zeros((0, 6), np.float32), strategy))
        assert empty.shape == (0, f.n_trees, 1)
    _assert_strategies_bit_identical(f, _inputs_for(f, 1))


# ------------------------------------------- trained models: family x task

def _trained_models(tiny_adult):
    reg = dict(tiny_adult)
    cls = dict(tiny_adult)
    models = []
    for fam, learner in (("rf", RandomForestLearner),
                         ("gbt", GradientBoostedTreesLearner),
                         ("cart", CartLearner)):
        kw = {} if fam == "cart" else {"num_trees": 6}
        models.append((f"{fam}_cls",
                       learner(label="income", **kw).train(cls)))
        models.append((f"{fam}_reg",
                       learner(label="age", task=Task.REGRESSION,
                               **kw).train(reg)))
    return models


def test_trained_model_matrix_bit_identical(tiny_adult):
    """RF/GBT/CART x classification/regression: every strategy bit-equals
    the typed-tree oracle on encoded real data, and the full predict()
    head agrees across engines."""
    for name, model in _trained_models(tiny_adult):
        pred = compile_predictor(model, "naive")
        X = pred.encode(tiny_adult)[:80]
        _assert_strategies_bit_identical(model.forest, X)
        base = compile_predictor(model, "vectorized").predict_encoded(X)
        for engine in ("bucketed", "naive"):
            got = compile_predictor(model, engine).predict_encoded(X)
            assert np.array_equal(np.asarray(got), np.asarray(base)), \
                (name, engine)


def test_task_model_matrix_bit_identical():
    """Ranking/uplift/anomaly models (DESIGN.md §12) serve bit-identically:
    every traversal strategy bit-equals the typed-tree oracle, and the full
    predict head agrees across compiled engines."""
    from repro.data.tabular import grouped_relevance, planted_anomaly, \
        randomized_treatment
    from repro.tasks import IsolationForestLearner, UpliftTreesLearner
    ds_r = grouped_relevance(n_groups=30, seed=7)
    ds_u = randomized_treatment(n=400, seed=11)
    ds_a = planted_anomaly(n_inlier=150, n_anomaly=8, seed=13)
    models = [
        ("ranking", GradientBoostedTreesLearner(
            label="rel", task=Task.RANKING, num_trees=6,
            seed=1).train(ds_r), ds_r),
        ("uplift", UpliftTreesLearner(
            label="outcome", num_trees=4, seed=2).train(ds_u), ds_u),
        ("anomaly", IsolationForestLearner(
            label="anomaly", num_trees=6, seed=3).train(ds_a), ds_a),
    ]
    for name, model, data in models:
        pred = compile_predictor(model, "naive")
        X = pred.encode(data)[:80]
        _assert_strategies_bit_identical(model.forest, X)
        base = compile_predictor(model, "vectorized").predict_encoded(X)
        for engine in ("bucketed", "naive"):
            got = compile_predictor(model, engine).predict_encoded(X)
            assert np.array_equal(np.asarray(got), np.asarray(base)), \
                (name, engine)


# ------------------------------------------------- selection heuristic (§10.3)

def test_select_cpu_engine_pins():
    shallow = _make_random_forest(8, [3], 4, seed=1, chain=True)     # work 24
    deep = _make_random_forest(40, [12], 4, seed=2, chain=True)      # work 480
    mixed = _make_random_forest(30, [2, 12], 4, seed=3, chain=True)  # work 360
    assert select_cpu_engine(shallow) == "vectorized"
    assert select_cpu_engine(deep) == "bucketed"
    assert select_cpu_engine(mixed) == "bucketed"
    # the boundary is n_trees * max depth, not forest.depth metadata
    assert 8 * 3 < BUCKETED_MIN_WORK <= 40 * 12


def test_select_block_strategy_pins():
    # CPU cost model: the scan wins at EVERY depth (measured, §10.3)
    for depth in (0, 1, 2, 6, 12):
        assert select_block_strategy(depth, 2 ** max(1, depth) - 1,
                                     2 ** max(1, depth)) == "scan"
    # an MXU-class backend flips shallow, small-table buckets to leaf_path
    assert select_block_strategy(2, 3, 4, matmul_cheap=True) == "leaf_path"
    assert select_block_strategy(6, 63, 64, matmul_cheap=True) == "leaf_path"
    assert select_block_strategy(12, 4095, 4096,
                                 matmul_cheap=True) == "scan"  # depth gate
    assert select_block_strategy(
        4, 200, 200, matmul_cheap=True) == "scan"  # budget gate: 40k > 2^14


def test_plan_depth_buckets_partition_and_bounds():
    depths = np.array([2] * 12 + [12] * 12 + [5] * 3 + [0] * 2)
    buckets = plan_depth_buckets(depths)
    assert 1 <= len(buckets) <= 4
    assert all(len(b) >= 8 for b in buckets)
    got = np.sort(np.concatenate(buckets))
    assert np.array_equal(got, np.arange(len(depths)))  # exact partition
    # bucket depth ceilings ascend: shallow trees never pay deep rounds
    ceilings = [depths[b].max() for b in buckets]
    assert ceilings == sorted(ceilings)
    assert plan_depth_buckets(np.zeros(0, np.int32)) == []


def test_pack_depth_buckets_layout(depth_skewed_forest):
    bf = pack_depth_buckets(depth_skewed_forest)
    assert len(bf.buckets) == 2
    assert [b.depth for b in bf.buckets] == [2, 12]  # per-bucket early exit
    assert all(b.strategy == "scan" for b in bf.buckets)  # CPU cost model
    # forcing leaf_path is the benchmark/TPU escape hatch
    bf_lp = pack_depth_buckets(depth_skewed_forest, strategy="leaf_path")
    assert all(b.strategy == "leaf_path" for b in bf_lp.buckets)
    # inv_order really restores original tree order
    order = np.concatenate([b.trees for b in bf.buckets])
    assert np.array_equal(order[bf.inv_order],
                          np.arange(depth_skewed_forest.n_trees))


def test_available_engines_gates():
    shallow = _make_random_forest(6, [2], 4, seed=7)
    assert available_engines(shallow) == [
        "pallas", "bucketed", "leaf_path", "vectorized", "naive"]
    big = _make_random_forest(2, [400], 4, seed=8)  # leaf-path table blowup
    engines = available_engines(big)
    assert "leaf_path" not in engines and "bucketed" in engines


# ------------------------------------------------------ predictor pickling

def test_compiled_predictor_pickle_round_trip(tiny_adult):
    model = RandomForestLearner(label="income", num_trees=5,
                                max_depth=6).train(tiny_adult)
    for engine in ("vectorized", "bucketed"):
        pred = compile_predictor(model, engine)
        clone = pickle.loads(pickle.dumps(pred))
        # the regression this pins: the CHOSEN engine survives the
        # round-trip instead of falling back to a fresh heuristic run
        assert clone.name == pred.name == engine
        X = pred.encode(tiny_adult)[:40]
        assert np.array_equal(np.asarray(clone.predict_encoded(X)),
                              np.asarray(pred.predict_encoded(X)))
        assert clone.out_shape == pred.out_shape


def test_engine_auto_pickle_keeps_choice(tiny_adult):
    model = GradientBoostedTreesLearner(label="income",
                                        num_trees=4).train(tiny_adult)
    pred = compile_predictor(model)  # heuristic picks (small model -> numpy)
    clone = pickle.loads(pickle.dumps(pred))
    assert clone.name == pred.name


# ------------------------------------------------------- bench quick smoke

def test_infer_bench_quick_smoke_strategies():
    """The ``--quick`` bench path on real data: every CPU strategy column
    present, timed, and allclose against the seed predict path."""
    from benchmarks import infer_bench
    res = infer_bench.run_smoke()
    for cfg_name in ("gbt_adult", "rf_adult"):
        after = res["configs"][cfg_name]["after"]
        assert "bucketed" in after and "vectorized" in after
        for ename, a in after.items():
            assert a["allclose"] is True, (cfg_name, ename)
            assert a["us_example"] > 0
    sk = res["configs"].get("sklearn_import")
    if sk is not None:
        assert "bucketed" in sk["strategies"]
        for ename, a in sk["strategies"].items():
            assert a["allclose"] is True, ename
        assert sk["speedup_vs_sklearn"] == max(
            a["speedup_vs_sklearn"] for a in sk["strategies"].values())
