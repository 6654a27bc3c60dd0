"""Device-resident training engine (DESIGN.md §6) + tree-parallel lockstep.

Contracts under test:
  * the "device" engine (jitted level loop, fused hist+gain, tree axis)
    grows the SAME forests as the host "batched" engine at equal seeds —
    identical split structure, allclose leaf values/predictions;
  * RF lockstep blocks (tree_parallelism) are execution-only: bit-identical
    forests against the sequential oracle engine for any block size (keyed
    feature sampling makes the growth schedule semantics-free);
  * the fused kernel's f32-accumulated gain argmax agrees with the f64 numpy
    scan (property-style sweep over random frontiers), and its class score's
    log is within an ulp on any backend;
  * exact_subtraction gating: backends that do not accumulate in f64 are
    never served the parent-minus-sibling subtraction;
  * hist_backend hardening: "auto" pins to numpy on CPU hosts, forcing
    "pallas" without a TPU raises, "pallas_interpret" is the explicit opt-in;
  * CI smoke: one tiny tree through the device engine with the Pallas kernel
    in interpret mode (JAX_PLATFORMS=cpu), so kernel regressions surface in
    tier-1.
"""
import numpy as np
import pytest

from repro.core import GradientBoostedTreesLearner, RandomForestLearner, YdfError
from repro.core.api import Task
from repro.core.binning import BinnedFeatures
from repro.core.grower import GrowthParams, grow_tree, resolve_engine
from repro.core.hist_backend import NumpyHistogramBackend, resolve_backend
from repro.core.splitters import SplitterParams, best_splits
from repro.core.tree import empty_forest
from repro.data.tabular import SUITE, adult_like, make_dataset, train_test_split

STRUCT_KEYS = ["feature", "split_bin", "cat_mask", "left_child", "n_nodes"]
ALL_KEYS = STRUCT_KEYS + ["threshold", "leaf_value"]


def _assert_struct_identical(a, b, msg=""):
    for k in STRUCT_KEYS:
        np.testing.assert_array_equal(getattr(a, k), getattr(b, k),
                                      err_msg=f"{msg}: forest.{k} differs")


def _assert_struct_close(a, b, min_frac=0.995, msg=""):
    """Near-identical structure: f32-vs-f64 rounding can flip the argmax at
    genuine gain ties (e.g. a category mirroring a numeric threshold, or
    adjacent near-equal bins), so a fraction of entries may differ — but only
    a tiny one, or something is actually broken."""
    for k in STRUCT_KEYS:
        x, y = getattr(a, k), getattr(b, k)
        frac = float((x == y).mean())
        assert frac >= min_frac, \
            f"{msg}: forest.{k} agrees on only {frac:.4f} of entries"


@pytest.fixture(scope="module")
def adult():
    return train_test_split(adult_like(900), 0.3, 1)


# ================================================================ device


@pytest.mark.parametrize("hp,strict", [
    (dict(), True),                                         # LOCAL, CART cats
    (dict(categorical_algorithm="ONE_HOT", max_depth=4), True),
    (dict(subsample=0.7, use_hessian_gain=True), False),    # bagging + dups
    (dict(l2_regularization=0.3, max_depth=3), True),
])
def test_gbt_device_matches_batched(adult, hp, strict):
    train, test = adult
    kw = dict(label="income", num_trees=4, validation_ratio=0.0,
              early_stopping="NONE", **hp)
    mb = GradientBoostedTreesLearner(**kw, growth_engine="batched").train(train)
    md = GradientBoostedTreesLearner(**kw, growth_engine="device").train(train)
    assert md.training_logs["growth_engine"] == "device"
    if strict:
        _assert_struct_identical(mb.forest, md.forest, str(hp))
        np.testing.assert_allclose(mb.forest.leaf_value, md.forest.leaf_value,
                                   atol=2e-5)
        np.testing.assert_allclose(mb.predict(test), md.predict(test),
                                   atol=1e-4)
    else:
        # a single f32 gain tie at a shallow node regrows that whole subtree
        # differently (equally good), so the contract here is predictive
        # equivalence, not node-for-node structure
        np.testing.assert_array_equal(mb.forest.n_nodes, md.forest.n_nodes)
        pb, pd = mb.predict(test), md.predict(test)
        assert np.abs(pb - pd).mean() < 2e-3
        assert ((pb > 0.5) == (pd > 0.5)).mean() > 0.99


def test_rf_device_matches_batched_including_sqrt_sampling(adult):
    """Keyed (hash-based) feature sampling is implemented identically in
    numpy and jnp, so the device engine reproduces the host engine's per-node
    feature subsets — SQRT sampling included."""
    train, test = adult
    kw = dict(label="income", num_trees=5, max_depth=7, compute_oob=False)
    mb = RandomForestLearner(**kw, growth_engine="batched").train(train)
    md = RandomForestLearner(**kw, growth_engine="device").train(train)
    assert md.training_logs["growth_engine"] == "device"
    _assert_struct_identical(mb.forest, md.forest, "rf sqrt")
    np.testing.assert_allclose(mb.predict(test), md.predict(test), atol=1e-4)


def test_rf_regression_device_matches_batched():
    train, test = train_test_split(make_dataset(SUITE[7]), 0.3, SUITE[7].seed)
    kw = dict(label="label", task=Task.REGRESSION, num_trees=3, max_depth=6,
              compute_oob=False)
    mb = RandomForestLearner(**kw, growth_engine="batched").train(train)
    md = RandomForestLearner(**kw, growth_engine="device").train(train)
    # moment scores (sum_y^2 / n) hit adjacent-bin f32 ties more often than
    # the other layouts — near-identical structure, close predictions
    _assert_struct_close(mb.forest, md.forest, msg="rf reg")
    pb, pd = mb.predict(test), md.predict(test)
    assert np.abs(pb - pd).mean() < 0.05 * max(1e-9, np.abs(pb).mean())


def test_multiclass_device_close_to_batched():
    """Multiclass switches categorical handling to one-hot (class stats with
    S > 3); entropy scores are more tie-prone under f32, so the contract here
    is allclose predictions rather than identical structure."""
    spec = SUITE[4]                                  # synth_vowel, 11 classes
    train, test = train_test_split(make_dataset(spec), 0.3, spec.seed)
    kw = dict(label="label", num_trees=4, max_depth=5, compute_oob=False)
    mb = RandomForestLearner(**kw, growth_engine="batched").train(train)
    md = RandomForestLearner(**kw, growth_engine="device").train(train)
    pb, pd = mb.predict(test), md.predict(test)
    assert (pb.argmax(1) == pd.argmax(1)).mean() > 0.97


def test_device_fallback_reasons(adult):
    train, _ = adult
    m = GradientBoostedTreesLearner(
        label="income", num_trees=2, growth_engine="device",
        growing_strategy="BEST_FIRST_GLOBAL").train(train)
    assert m.training_logs["growth_engine"] == "batched"
    assert "BEST_FIRST" in m.training_logs["engine_fallback"]
    m = RandomForestLearner(label="income", num_trees=2, compute_oob=False,
                            growth_engine="device",
                            categorical_algorithm="RANDOM").train(train)
    assert m.training_logs["growth_engine"] == "batched"
    assert "RANDOM" in m.training_logs["engine_fallback"]
    with pytest.raises(YdfError, match="growth engine"):
        GradientBoostedTreesLearner(label="income", num_trees=1,
                                    growth_engine="warp").train(train)


# ====================================================== lockstep blocks


def test_rf_lockstep_blocks_are_execution_only(adult):
    """tree_parallelism is semantics-free: any block size produces the same
    forest, and the lockstep batched engine matches the sequential oracle
    bit-for-bit (same keyed subsets, same f64 accumulation order)."""
    train, _ = adult
    kw = dict(label="income", num_trees=7, max_depth=8, compute_oob=False)
    ms = RandomForestLearner(**kw, growth_engine="oracle").train(train)
    for block in (1, 3, 8):
        mb = RandomForestLearner(**kw, growth_engine="batched",
                                 tree_parallelism=block).train(train)
        for k in ALL_KEYS:
            np.testing.assert_array_equal(
                getattr(ms.forest, k), getattr(mb.forest, k),
                err_msg=f"block={block}: forest.{k} differs")


# ==================================== fused kernel: f32 vs f64 argmax


def _random_frontier(seed, n=900, kf=4, n_slots=6, kind="gh"):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, 256, (n, kf)).astype(np.uint8)
    g = rng.normal(size=n)
    w = rng.integers(0, 3, n).astype(np.float64)
    if kind == "gh":
        stats = np.stack([g * w, w, np.abs(g) * w, w], 1)
    elif kind == "class":
        c = rng.integers(0, 2, n)
        stats = np.stack([(c == 0) * w, (c == 1) * w, w], 1)
    else:
        stats = np.stack([g * w, np.square(g) * w, w], 1)
    slot = rng.integers(-1, n_slots, n).astype(np.int32)
    return codes, stats, slot


@pytest.mark.parametrize("kind", ["gh", "class", "moment"])
def test_fused_f32_argmax_matches_f64_scan(kind):
    """Property sweep: across random frontiers, the fused kernel's
    f32-accumulated gain argmax picks the same (feature, split_bin) as the
    f64 numpy histogram + f32 scan used by the host engines. Both fused
    implementations are swept — the jnp oracle on every seed and the actual
    Pallas kernel (interpret mode on CPU) on a subset, so a regression in
    the kernel's scoring/scan for any stat layout fails here, not just in
    the gh-kind end-to-end smoke."""
    from repro.kernels.histogram.ops import fused_best_split

    n_slots = 6
    for seed in range(8):
        codes, stats, slot = _random_frontier(100 * seed + 7, kind=kind)
        kf = codes.shape[1]
        hist64 = NumpyHistogramBackend().build(codes, stats, slot, n_slots)
        binned = BinnedFeatures(
            codes=codes, n_bins=np.full(kf, 256, np.int32),
            is_cat=np.zeros(kf, bool),
            boundaries=[np.arange(255, dtype=np.float32)] * kf,
            names=[f"f{j}" for j in range(kf)])
        sp = SplitterParams(stat_kind=kind, min_examples=5)
        ref = best_splits(hist64.astype(np.float32), binned, sp,
                          np.random.default_rng(0))
        impls = ("ref", "interpret") if seed < 3 else ("ref",)
        for impl in impls:
            gain, feat, sbin = map(np.asarray, fused_best_split(
                codes, stats.astype(np.float32), slot, n_slots,
                kind=kind, l2=0.0, min_examples=5, impl=impl))
            for i, s in enumerate(ref):
                if not s.valid:
                    assert gain[i] <= sp.min_gain or not np.isfinite(gain[i])
                    continue
                assert (feat[i], sbin[i]) == (s.feature, s.split_bin), \
                    (f"seed {seed} impl {impl} slot {i}: f32 argmax "
                     "diverged from f64 scan")


def test_class_score_log_is_within_an_ulp():
    """The class layout's entropy uses ``log_f32``, not the backend's log
    (a TPU's float32 log misses by up to ~1e-4): it must stay within one ulp
    of the float64 log over the probabilities a node can hold, and a pure
    node must score exactly 0."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.histogram.fused import log_f32, score_columns

    rng = np.random.default_rng(0)
    x = np.concatenate([rng.random(100_000), 1 - rng.random(10_000) * 1e-3,
                        np.exp(rng.uniform(-27.6, 0, 10_000)),
                        [1.0, 0.5, 1e-12]]).astype(np.float32)
    x = x[x > 0]
    got = np.asarray(jax.jit(log_f32)(x), np.float64)
    want = np.log(x.astype(np.float64))
    ulp = np.spacing(np.abs(want).astype(np.float32)).astype(np.float64)
    assert (np.abs(got - want) <= np.maximum(ulp, 1e-45)).all()
    pure = [jnp.float32(7.0), jnp.float32(0.0), jnp.float32(7.0)]
    assert float(score_columns(pure, "class", 0.0)) == 0.0


# ============================================ exact_subtraction gating


class _SpyBackend(NumpyHistogramBackend):
    """Numpy-exact accumulation with a configurable exact_subtraction flag
    and a log of how many (node, feature) histograms were built."""

    def __init__(self, exact: bool):
        self.exact_subtraction = exact
        self.built_nodes = 0

    def build(self, codes, stats, node_of, n_nodes, max_bins=256):
        self.built_nodes += int(n_nodes)
        return super().build(codes, stats, node_of, n_nodes, max_bins)


@pytest.mark.parametrize("strategy", ["LOCAL", "BEST_FIRST_GLOBAL"])
def test_exact_subtraction_gating_refuses_f32_backends(strategy):
    """A backend that does not accumulate in f64 must never be served the
    parent-minus-sibling subtraction: the grower rebuilds every histogram
    from scratch (strictly more built nodes), and forests stay identical
    because f64 subtraction is exact through the f32 cast."""
    train, _ = train_test_split(adult_like(3000), 0.3, 1)
    forests = {}
    spies = {}
    for exact in (True, False):
        spy = _SpyBackend(exact)
        m = GradientBoostedTreesLearner(
            label="income", num_trees=2, max_depth=4, validation_ratio=0.0,
            early_stopping="NONE", growing_strategy=strategy,
            histogram_backend=spy).train(train)
        forests[exact] = m.forest
        spies[exact] = spy
    assert spies[False].built_nodes > spies[True].built_nodes, \
        "f32 backend was served the subtraction trick"
    for k in ALL_KEYS:
        np.testing.assert_array_equal(getattr(forests[True], k),
                                      getattr(forests[False], k))


# ================================================= hist_backend guards


def test_auto_backend_pinned_to_numpy_on_cpu_hosts():
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("TPU host: auto resolves to pallas by design")
    assert resolve_backend("auto").name == "numpy"


def test_forced_pallas_without_device_raises():
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("TPU host: forced pallas is legitimate")
    with pytest.raises(YdfError, match="pallas_interpret"):
        resolve_backend("pallas")
    # the explicit opt-in still works (interpret-mode kernel)
    be = resolve_backend("pallas_interpret")
    assert be.name == "pallas" and be.interpret


# ========================================== interpret-mode CI smoke


def _numerical_table(N, F, seed):
    """Raw columns and their 32-bin codes: a numerical-only table."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, F))
    bounds = [np.sort(rng.choice(np.unique(X[:, j]), 31, replace=False))
              for j in range(F)]
    codes = np.stack([np.searchsorted(bounds[j], X[:, j], side="left")
                      for j in range(F)], 1).astype(np.uint8)
    return X, BinnedFeatures(
        codes=codes, n_bins=np.array([32] * F, np.int32),
        is_cat=np.zeros(F, bool),
        boundaries=[b.astype(np.float32) for b in bounds],
        names=[f"f{j}" for j in range(F)])


@pytest.mark.parametrize("N,F,trees,block", [
    (200, 3, 1, None),    # one GBT tree, one example tile
    (1300, 5, 1, None),   # three 512-row tiles, the last one padded
    (700, 4, 3, 4),       # RF, all features: 3 trees in a 4-tree block
])
def test_device_engine_interpret_smoke(N, F, trees, block):
    """Numerical-only trees through the device engine with the fused Pallas
    kernel in interpret mode — the tier-1 canary for kernel regressions on
    CPU-only CI (JAX_PLATFORMS=cpu). Every feature is a candidate, so the
    kernel reads the table's cached lane-major codes: padded past N to whole
    example tiles, and shared by the lockstep block's trees (padded past the
    real ones). Structure and routing equal the batched engine's."""
    from repro.core.grower import grow_trees

    X, binned = _numerical_table(N, F, seed=3)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    if trees == 1:
        kind = "gh"
        stats_list = [np.stack([y, np.ones(N), np.ones(N), np.ones(N)], 1)]
        leaf_fn = lambda s: np.array([s[0] / max(s[-1], 1e-12)], np.float32)
    else:
        kind = "class"
        rng = np.random.default_rng(5)
        counts = [rng.multinomial(N, np.full(N, 1.0 / N)).astype(np.float64)
                  for _ in range(trees)]
        stats_list = [np.stack([(y == 0) * c, (y == 1) * c, c], 1)
                      for c in counts]
        leaf_fn = lambda s: (s[:-1] / max(s[-1], 1e-12)).astype(np.float32)
    actives = [s[:, -1] > 0 for s in stats_list]
    out_dim = len(leaf_fn(stats_list[0][0]))

    def grow(engine, impl="auto"):
        forest = empty_forest(trees, 64, out_dim, feature_names=binned.names)
        gp = GrowthParams(max_depth=3, max_nodes=64,
                          splitter=SplitterParams(stat_kind=kind,
                                                  min_examples=5),
                          engine=engine, device_impl=impl,
                          feature_sampling="keyed", sampling_key=11)
        if trees == 1:
            node_of = grow_tree(forest, 0, binned, X, stats_list[0],
                                actives[0], leaf_fn, gp,
                                np.random.default_rng(0))
        else:
            node_of = grow_trees(
                forest, list(range(trees)), binned, X, stats_list, actives,
                leaf_fn, gp, [np.random.default_rng(t) for t in range(trees)],
                block=block)
        return forest, node_of

    fb, nb = grow("batched")
    fd, nd = grow("device", impl="interpret")
    for k in STRUCT_KEYS:
        np.testing.assert_array_equal(getattr(fb, k), getattr(fd, k),
                                      err_msg=f"forest.{k}")
    np.testing.assert_array_equal(nb, nd)
    np.testing.assert_allclose(fb.leaf_value, fd.leaf_value, atol=1e-5)
    assert (fd.n_nodes > 1).all(), "a tree did not grow"


def test_every_frontier_width_compiles_with_the_first_tree():
    """A first tree that stops at its root still compiles every frontier
    width a tree of depth 4 can reach, so a later, full tree compiles no
    level step (on the chip each such compile stalls training for
    seconds between two trees)."""
    from repro.obs import trace
    N = 333                     # a row count no other test uses: fresh shapes
    X, binned = _numerical_table(N, 3, seed=8)
    gp = GrowthParams(max_depth=4, max_nodes=32,
                      splitter=SplitterParams(stat_kind="gh", min_examples=5),
                      engine="device")
    leaf_fn = lambda s: np.array([-s[0] / (s[2] + 1e-12)], np.float32)
    forest = empty_forest(2, 32, 1, feature_names=binned.names)
    flat = np.stack([np.zeros(N), np.ones(N), np.ones(N), np.ones(N)], 1)
    grow_tree(forest, 0, binned, X, flat, np.ones(N, bool), leaf_fn, gp,
              np.random.default_rng(0))
    assert forest.n_nodes[0] == 1                 # no gain: a root only
    g = X[:, 0] + np.sin(3 * X[:, 1])
    deep = np.stack([g, np.ones(N), np.ones(N), np.ones(N)], 1)
    with trace.capture() as tr:
        grow_tree(forest, 1, binned, X, deep, np.ones(N, bool), leaf_fn, gp,
                  np.random.default_rng(0))
    spans = [s for r in tr.roots for s in r.walk()]
    assert forest.n_nodes[1] > 8                  # reached widths 2, 4, 8
    steps = [s for s in spans if s.name == "grower_device/level_step"]
    assert len(steps) == 4 and not any(s.args["compile"] for s in steps)
    assert all(s.args["routing"] == "dense" for s in steps)
    assert not [s for s in spans if s.name == "jax/compile"
                and "step" in s.args.get("fun_name", "")]


@pytest.mark.parametrize("impl,cat_mode,sample", [
    pytest.param("jnp", "none", False, id="False"),
    pytest.param("jnp", "none", True, id="True"),
    ("interpret", "none", False), ("jnp", "cart", False)])
def test_unsampled_level_step_gathers_no_candidate_codes(impl, cat_mode,
                                                         sample):
    """With every feature a candidate at every node, the level step reads
    the codes in place, and routing reads each example's slot values by a
    one-hot over the frontier slots, its code by a select over features and
    its go-right bit from its slot's split bin or category mask: the
    compiled CPU program holds no gather of N or more elements. (The CART
    scan's gather of the (K, P, F, B, S) histogram in category order,
    917,504 elements here, is per bin and under N.) With per-node sampling
    the per-example (N, kf) candidate gather is still there."""
    from forest_helpers import _gather_sizes, _level_step_program
    from repro.core.sampling import sample_size

    N, F = 1 << 20, 28
    step, args = _level_step_program(impl, sample, N, F, P=32,
                                     cat_mode=cat_mode)
    sizes = _gather_sizes(step.lower(*args).compile().as_text())
    if sample:
        assert N * sample_size(0.5, F) in sizes, sizes
    else:
        assert max(sizes, default=0) < N, sizes


def _route_reference(slot_of, node_of, first, valid, rank, feat, sbin,
                     masks, codes):
    """The level step's routing as per-example gathers (numpy): each
    example's slot, then its code at the slot's split feature, then the
    slot's go-right-by-code table at that code."""
    K, N = slot_of.shape
    slotc = np.maximum(slot_of, 0)
    kk = np.arange(K)[:, None]
    route = (slot_of >= 0) & valid[kk, slotc]
    code = codes[np.arange(N)[None, :], feat[kk, slotc]]
    if masks is None:
        tbl = np.arange(256)[None, None, :] >= sbin[:, :, None]
    else:
        b = np.arange(256)
        tbl = (masks[:, :, b >> 5] >> (b & 31).astype(np.uint32)) & 1
    go = tbl[kk, slotc, code].astype(np.int32)
    left_id = np.where(valid, first[:, None] + 2 * rank, -1)
    return (np.where(route, left_id[kk, slotc] + go, node_of),
            np.where(route, 2 * rank[kk, slotc] + go, -1))


@pytest.mark.parametrize("layout", ["codes", "lane_major"])
@pytest.mark.parametrize("cat", [False, True])
@pytest.mark.parametrize("P", [1, 32, "widest_dense", "gather"])
def test_routing_matches_per_example_gathers(P, cat, layout):
    """The level step's routing gives the same child node and slot, to the
    bit, as per-example gathers: three trees, slots of -1, slots that do not
    split, codes 0 and 255, numerical and categorical splits, on both sides
    of the frontier width where routing turns from dense to gather (the
    widest dense frontier and twice it)."""
    import jax
    import jax.numpy as jnp

    from repro.core import grower_device as gd
    from repro.core.tree import MASK_WORDS
    from repro.kernels.histogram.histogram import lane_major_codes

    widest = gd._DENSE_ROUTING_MAX_P_CAT if cat else gd._DENSE_ROUTING_MAX_P
    P = {"widest_dense": widest, "gather": 2 * widest}.get(P, P)
    assert gd._routing(P, cat) == ("gather" if P > widest else "dense")
    K, N, F = 3, 1100, 5
    rng = np.random.default_rng(P * 4 + 2 * cat + (layout == "codes"))
    codes = rng.integers(0, 256, size=(N, F)).astype(np.int32)
    codes[:8] = 0
    codes[8:16] = 255
    slot_of = rng.integers(-1, P, size=(K, N)).astype(np.int32)
    slot_of[:, :4] = -1
    valid = rng.random((K, P)) < 0.7
    valid[:, 0] = True
    if P > 1:
        valid[:, 1] = False
    vi = valid.astype(np.int32)
    rank = (np.cumsum(vi, axis=1) - vi).astype(np.int32)
    feat = rng.integers(0, F, size=(K, P)).astype(np.int32)
    sbin = rng.integers(0, 257, size=(K, P)).astype(np.int32)
    sbin[:, 0] = 256                        # code 255 goes left
    masks = (rng.integers(0, 2 ** 32, size=(K, P, MASK_WORDS),
                          dtype=np.uint64).astype(np.uint32) if cat else None)
    node_of = rng.integers(0, 50, size=(K, N)).astype(np.int32)
    first = rng.integers(1, 100, size=K).astype(np.int32)

    codes_d = jnp.asarray(codes)
    codes_t = lane_major_codes(codes_d) if layout == "lane_major" else None
    node_d, slot_d = jax.jit(gd._route)(
        jnp.asarray(slot_of), jnp.asarray(node_of), jnp.asarray(first),
        jnp.asarray(valid), jnp.asarray(rank), jnp.asarray(feat),
        jnp.asarray(sbin), None if masks is None else jnp.asarray(masks),
        codes_d, codes_t)
    node_r, slot_r = _route_reference(slot_of, node_of, first, valid, rank,
                                      feat, sbin, masks, codes)
    np.testing.assert_array_equal(np.asarray(slot_d), slot_r)
    np.testing.assert_array_equal(np.asarray(node_d), node_r)
    assert (slot_r >= 0).any() and (slot_r == -1).any()


def test_resolve_engine_reports_fallback():
    gp = GrowthParams(engine="device",
                      splitter=SplitterParams(categorical_algorithm="RANDOM"))
    eng, reason = resolve_engine(gp)
    assert eng == "batched" and "RANDOM" in reason
    gp = GrowthParams(engine="device")
    assert resolve_engine(gp) == ("device", None)
