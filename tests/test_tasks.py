"""Task subsystem (DESIGN.md §12): LambdaMART ranking, uplift trees and
isolation forests through the existing growers and engines.

Pins, in order: hand-computed NDCG@k and Qini/AUUC golden oracles (exact
values on tiny fixed inputs); the device lambda pass against the float64
all-pairs oracle within float32's tolerance; the LambdaMART >= 0.03 NDCG@5
edge over pointwise regression on grouped-relevance data; the isolation
forest's planted-anomaly AUC; wrong-task entry points failing fast with
directions; the CLI --task round trip; and the rank-bench --quick smoke.
"""
import os

import numpy as np
import pytest

from repro.core import GradientBoostedTreesLearner, Model, Task, YdfError
from repro.core.evaluation import evaluate_predictions, ndcg_at_k, qini_curve
from repro.data.tabular import grouped_relevance, planted_anomaly, \
    randomized_treatment
from repro.tasks import (
    IsolationForestLearner,
    UpliftTreesLearner,
    group_aware_split,
    group_layout,
    lambda_grad_device,
    lambda_grad_naive,
)

pytestmark = pytest.mark.tasks


# ------------------------------------------------------------ metric goldens

def test_ndcg_golden_hand_computed():
    """One 4-doc group, k=3, every term written out by hand.

    Scores order the docs [d1, d3, d2, d0] (descending, stable); their
    relevances are [1, 2, 0, 3], gains 2^rel - 1 = [1, 3, 0, 7].
    DCG@3  = 1/log2(2) + 3/log2(3) + 0/log2(4)
    IDCG@3 = 7/log2(2) + 3/log2(3) + 1/log2(4)   (ideal rel order 3,2,1).
    """
    y = np.array([3.0, 1.0, 0.0, 2.0])
    score = np.array([0.1, 0.4, 0.2, 0.3])
    groups = np.zeros(4, np.int64)
    want = (1.0 + 3.0 / np.log2(3)) / (7.0 + 3.0 / np.log2(3) + 0.5)
    assert ndcg_at_k(y, score, groups, k=3) == pytest.approx(want, abs=1e-12)


def test_ndcg_ties_break_by_index_and_zero_groups_score_zero():
    # tie on scores: the FIRST index wins the top rank (stable argsort)
    y = np.array([0.0, 2.0])
    want = (3.0 / np.log2(3)) / 3.0       # rel-2 doc stuck at rank 2
    assert ndcg_at_k(y, np.array([0.5, 0.5]), np.zeros(2, np.int64),
                     k=2) == pytest.approx(want, abs=1e-12)
    # a group with no relevant doc (IDCG = 0) contributes exactly 0
    y2 = np.r_[y, 0.0, 0.0]
    g2 = np.r_[0, 0, 1, 1].astype(np.int64)
    assert ndcg_at_k(y2, np.array([0.5, 0.5, 1.0, 2.0]), g2,
                     k=2) == pytest.approx(want / 2, abs=1e-12)


def test_qini_auuc_golden_hand_computed():
    """4 rows already sorted by score; every cumulative term by hand:
    g = [1-0, 1-1*1/1, 1-1*2/1, 1-2*2/2] = [1, 0, -1, -1]
    auuc = mean(g)/n = -0.0625
    qini = (mean(g) - g[-1]*(n+1)/(2n))/n = (-0.25 + 0.625)/4 = 0.09375.
    """
    score = np.array([4.0, 3.0, 2.0, 1.0])
    treatment = np.array([1, 0, 1, 0], np.int64)
    y = np.array([1.0, 1.0, 0.0, 1.0])
    np.testing.assert_allclose(qini_curve(y, score, treatment),
                               [1.0, 0.0, -1.0, -1.0], atol=1e-15)
    ev = evaluate_predictions(Task.UPLIFT, score, y, treatment=treatment)
    assert ev.metrics["auuc"] == pytest.approx(-0.0625, abs=1e-12)
    assert ev.metrics["qini"] == pytest.approx(0.09375, abs=1e-12)
    assert ev.primary == ev.metrics["qini"]


# ------------------------------------------- device lambda pass vs oracle

def _round_bf16(x):
    """float -> nearest bfloat16 (round half to even), as float64."""
    b = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32).astype(np.float64)


# (sizes, grades, scores, k): heavy-tailed MSLR-like sizes (lognormal,
# clipped to [1, 1251]), a group of 1 (no pairs) beside a group of 1251
# (the largest MSLR query), all-equal grades (zero lambdas), tied scores
# (ties break by row index), and k larger than every group.
LAMBDA_CASES = {
    "lognormal-k5": ("lognormal", "random", "normal", 5),
    "one-and-1251": ("extremes", "random", "normal", 5),
    "all-equal-grades": ("lognormal", "equal", "normal", 5),
    "tied-scores": ("lognormal", "random", "tied", 5),
    "all-tied-at-zero": ("lognormal", "random", "zero", 5),
    "k-above-m": ("small", "random", "normal", 12),
    "k1": ("lognormal", "random", "normal", 1),
    "k3-tied": ("small", "random", "tied", 3),
    "k10-wide-scores": ("lognormal", "random", "wide", 10),
    "mslr-shares": ("lognormal", "mslr", "normal", 5),
    "uniform-small": ("uniform", "random", "normal", 5),
    "singletons": ("ones", "random", "normal", 5),
}


def _lambda_case(name):
    sizes_kind, grades, score_kind, k = LAMBDA_CASES[name]
    rng = np.random.default_rng(sorted(LAMBDA_CASES).index(name))
    if sizes_kind == "lognormal":
        sizes = np.clip(np.rint(rng.lognormal(3.6, 0.9, 40)), 1, 1251)
    elif sizes_kind == "extremes":
        sizes = np.r_[1, 1251, np.rint(rng.lognormal(3.0, 0.6, 10))]
    elif sizes_kind == "small":
        sizes = rng.integers(1, 9, 30)
    elif sizes_kind == "uniform":
        sizes = rng.integers(8, 17, 40)
    else:
        sizes = np.ones(20)
    sizes = sizes.astype(np.int64)
    groups = np.repeat(np.arange(len(sizes)), sizes)
    rng.shuffle(groups)
    n = len(groups)
    rel = {"random": lambda: rng.integers(0, 5, n),
           "equal": lambda: np.full(n, 2),
           "mslr": lambda: rng.choice(5, n, p=[.514, .325, .134, .019,
                                               .008])}[grades]()
    # scores are float32 values, so both passes rank the same numbers
    scores = {"normal": lambda: rng.normal(size=n),
              "tied": lambda: np.round(rng.normal(size=n)),
              "zero": lambda: np.zeros(n),
              "wide": lambda: rng.normal(size=n) * 30.0}[score_kind]()
    return (group_layout(groups), scores.astype(np.float32)
            .astype(np.float64), rel.astype(np.float64), k)


def _within_f32_tolerance(got, want):
    """float32 tolerance of the lambda pass against the float64 oracle.

    float32 rounds each operation to a relative 2^-24 (6e-8). A row's value
    is a sum over at most one bucket width (2048) of pair terms, each a
    handful of roundings deep, summed in a tree of depth <= 11: about 20
    roundings, 1.2e-6 of the largest magnitude. ``atol`` = 4e-6 of the
    case's largest |value| leaves three times that, and ``rtol`` 1e-5
    covers the rows whose value is a single term. bfloat16 rounds to a
    relative 2^-9 (2e-3), which fails both."""
    scale = max(float(np.abs(want).max()), 1e-30)
    return np.allclose(got, want, rtol=1e-5, atol=4e-6 * scale)


@pytest.mark.parametrize("case", sorted(LAMBDA_CASES))
def test_lambda_device_matches_float64_oracle(case):
    """The device pass (float32, top-k pairs only, bucketed layout)
    against the float64 oracle over every pair of each group."""
    layout, scores, rel, k = _lambda_case(case)
    gd, hd = lambda_grad_device(scores, rel, layout, k=k)
    gn, hn = lambda_grad_naive(scores, rel, layout, k=k)
    assert gd.dtype == np.float32 and gd.shape == (layout.n_rows,)
    assert _within_f32_tolerance(gd, gn), np.abs(gd - gn).max()
    assert _within_f32_tolerance(hd, hn), np.abs(hd - hn).max()
    if LAMBDA_CASES[case][1] == "equal" or layout.sizes.max() == 1:
        assert np.all(gd == 0.0) and np.all(hd == 0.0)
    else:
        # the tolerance is tight enough that a bfloat16 pass fails it
        assert not _within_f32_tolerance(_round_bf16(gn), gn)
        assert not _within_f32_tolerance(_round_bf16(hn), hn)


def test_bucketed_layout_round_trip_and_padding_bound():
    """Groups of a heavy-tailed size mix land in power-of-two buckets of
    width >= 8: a group of more than 4 rows is padded to less than twice
    its size, the whole table to 1.4-1.5x at MSLR's size mix, and pad then
    unpad is the identity."""
    rng = np.random.default_rng(5)
    sizes = np.clip(np.rint(rng.lognormal(4.58, 0.64, 2000)), 1,
                    1251).astype(np.int64)
    groups = np.repeat(rng.permutation(len(sizes)), sizes)
    layout = group_layout(groups)
    assert layout.n_groups == len(sizes) and layout.n_rows == sizes.sum()
    assert layout.widths == sorted(set(layout.widths))
    for b in layout.buckets:
        assert b.width >= 8 and b.width & (b.width - 1) == 0
        m = layout.sizes[b.groups]
        assert np.array_equal(b.mask.sum(axis=1), m)
        assert np.all((b.width < 2 * m) | (b.width == 8))
        # within a group, slots follow row order
        assert all(np.all(np.diff(i[k]) > 0) for i, k in zip(b.index, b.mask))
    assert 1.4 <= layout.padded_rows / layout.n_rows <= 1.5
    flat = rng.normal(size=layout.n_rows)
    assert np.array_equal(layout.unpad(layout.pad(flat)), flat)
    rows = np.concatenate(layout.group_rows())
    assert np.array_equal(np.sort(rows), np.arange(layout.n_rows))


def test_group_layout_round_trip_and_split():
    groups = np.array([3, 0, 3, 1, 0, 3], np.int64)
    layout = group_layout(groups)
    flat = np.arange(6, dtype=np.float64)
    assert np.array_equal(layout.unpad(layout.pad(flat)), flat)
    assert layout.n_groups == 3 and layout.widths == [8]
    assert list(layout.sizes) == [2, 1, 3]
    # group-aware validation split keeps every group whole
    gid = np.repeat(np.arange(20), 5)
    tr, va = group_aware_split(gid, 0.25, seed=3)
    assert len(np.intersect1d(gid[tr], gid[va])) == 0
    assert len(tr) + len(va) == len(gid) and len(va) == 25


def test_ranking_group_ids_come_from_the_raw_column():
    """More queries than a categorical dictionary holds (2,048): every
    query keeps its own id, in the column's string order, where the
    dictionary's codes would merge the queries past it into one."""
    from repro.core.models import prepare_train_data
    n_q = 2100
    names = np.array([f"q{i:05d}" for i in range(n_q)], dtype=object)
    data = {"qid": np.repeat(names, 2),
            "x": np.arange(2 * n_q, dtype=np.float64),
            "rel": np.tile([0.0, 2.0], n_q)}
    learner = GradientBoostedTreesLearner(label="rel", task=Task.RANKING,
                                          ranking_group="qid")
    td = prepare_train_data(learner, data)
    assert np.array_equal(td.groups, np.repeat(np.arange(n_q), 2))


# ------------------------------------------------------------ accuracy pins

def test_lambdamart_beats_pointwise_regression_on_ndcg():
    """The acceptance pin: >= 0.03 NDCG@5 over a pointwise-regression GBT
    on grouped-relevance data (observed ~ +0.08). The mechanism: most label
    variance is an unobserved query-level bias that pointwise must regress
    through, while within-group lambda pairs cancel it exactly."""
    ds = grouped_relevance()
    gid = np.asarray([int(v) for v in ds["group"]], np.int64)
    y = np.array([float(v) for v in ds["rel"]])
    tr_idx, te_idx = group_aware_split(gid, 0.3, 99)
    tr = {k: v[tr_idx] for k, v in ds.items()}
    te = {k: v[te_idx] for k, v in ds.items()}
    g_te, y_te = gid[te_idx], y[te_idx]
    lm = GradientBoostedTreesLearner(label="rel", task=Task.RANKING,
                                     num_trees=80, seed=1).train(tr)
    nd_lm = ndcg_at_k(y_te, np.asarray(lm.predict(te)), g_te, 5)
    reg = GradientBoostedTreesLearner(
        label="rel", task=Task.REGRESSION, num_trees=80, seed=1).train(
        {k: v for k, v in tr.items() if k != "group"})
    nd_reg = ndcg_at_k(y_te, np.asarray(reg.predict(te)), g_te, 5)
    assert nd_lm - nd_reg >= 0.03, (nd_lm, nd_reg)
    # the trained ranking model evaluates through the task head end to end
    ev = lm.evaluate(te)
    assert ev.task == Task.RANKING
    assert ev.metrics["ndcg@5"] == pytest.approx(nd_lm, abs=1e-12)


def test_ranking_trains_with_the_device_lambda_pass():
    """task=RANKING on the device grower: the lambda pass runs on the
    device (``training_logs``), no engine fallback, and the ranking spans
    name the layout (once per table), each pass with its pair counts, and
    each NDCG by split."""
    from repro.obs import trace
    ds = grouped_relevance(n_groups=40, seed=7)
    with trace.capture() as tr:
        model = GradientBoostedTreesLearner(
            label="rel", task=Task.RANKING, num_trees=3, max_depth=3,
            growth_engine="device", seed=1).train(ds)
    logs = model.training_logs
    assert logs["ranking_pass"] == "device"
    assert logs["growth_engine"] == "device"
    assert logs["engine_fallback"] is None
    assert logs["ranking_bucket_widths"] == [8, 16]   # groups of 8..16 rows
    spans = [s for r in tr.roots for s in r.walk()]
    layouts = [s for s in spans if s.name == "ranking/layout"]
    assert len(layouts) == 2                           # train and valid
    assert layouts[0].args["widths"] == [8, 16]
    passes = [s for s in spans if s.name == "ranking/lambda"]
    assert len(passes) == 3
    a = passes[0].args
    assert a["rows"] == layouts[0].args["rows"]
    # k = 5 top positions against every slot of each bucket
    assert a["pair_slots"] == 5 * layouts[0].args["padded_rows"]
    assert 0 < a["pairs"] < a["pair_slots"]
    splits = [s.args["split"] for s in spans if s.name == "ranking/ndcg"]
    assert splits.count("train") == 3 and splits.count("valid") == 3


def test_isolation_forest_planted_anomaly_auc():
    da = planted_anomaly()
    m = IsolationForestLearner(label="anomaly", num_trees=100, seed=3).train(da)
    ev = m.evaluate(da)
    assert ev.task == Task.ANOMALY
    assert ev.metrics["auc"] >= 0.9, ev.metrics
    # scores live in (0, 1]: 2^(-E[h]/c(psi))
    p = np.asarray(m.predict(da))
    assert (p > 0).all() and (p <= 1).all()


def test_uplift_trees_positive_qini_on_randomized_treatment():
    du = randomized_treatment()
    m = UpliftTreesLearner(label="outcome", num_trees=20, seed=2).train(du)
    ev = m.evaluate(du)
    assert ev.task == Task.UPLIFT
    assert ev.metrics["qini"] > 0.0, ev.metrics
    # effects are centered-ish differences of probabilities, not scores
    p = np.asarray(m.predict(du))
    assert (np.abs(p) <= 1.0).all()


# ------------------------------------------------------------- task guards

def _tiny_models():
    ds_r = grouped_relevance(n_groups=25, seed=7)
    ds_u = randomized_treatment(n=300, seed=11)
    ds_a = planted_anomaly(n_inlier=120, n_anomaly=8, seed=13)
    return [
        ("ranking", GradientBoostedTreesLearner(
            label="rel", task=Task.RANKING, num_trees=4,
            seed=1).train(ds_r), ds_r, "group"),
        ("uplift", UpliftTreesLearner(
            label="outcome", num_trees=3, seed=2).train(ds_u), ds_u,
         "treatment"),
        ("anomaly", IsolationForestLearner(
            label="anomaly", num_trees=4, seed=3).train(ds_a), ds_a, None),
    ]


def test_predict_class_fails_fast_before_inference():
    """Wrong-task predict_class raises BEFORE touching the dataset: passing
    garbage as the dataset must still produce the directed task error."""
    for name, model, _, _ in _tiny_models():
        with pytest.raises(YdfError, match="classification model"):
            model.predict_class(object())     # would explode if inferred


def test_summary_names_the_task():
    for name, model, _, _ in _tiny_models():
        assert f"Task: {model.task.value}" in model.summary(), name


def test_evaluate_missing_side_column_is_directed():
    for name, model, data, side in _tiny_models():
        if side is None:
            continue
        broken = {k: v for k, v in data.items() if k != side}
        with pytest.raises(YdfError, match=side):
            model.evaluate(broken)


def test_gbt_rejects_uplift_and_anomaly_with_directions():
    ds = grouped_relevance(n_groups=15, seed=7)   # numerical label
    ds["treatment"] = (np.arange(len(ds["rel"])) % 2).astype(object)
    for task, learner_name in ((Task.UPLIFT, "UPLIFT_TREES"),
                               (Task.ANOMALY, "ISOLATION_FOREST")):
        with pytest.raises(YdfError, match=learner_name):
            GradientBoostedTreesLearner(label="rel", task=task,
                                        num_trees=2).train(ds)
    with pytest.raises(YdfError, match="UPLIFT"):
        UpliftTreesLearner(label="outcome", task=Task.CLASSIFICATION)
    with pytest.raises(YdfError, match="ANOMALY"):
        IsolationForestLearner(task=Task.REGRESSION)


def test_ranking_train_requires_group_column():
    ds = grouped_relevance(n_groups=20, seed=7)
    ds.pop("group")
    with pytest.raises(YdfError, match="group"):
        GradientBoostedTreesLearner(label="rel", task=Task.RANKING,
                                    num_trees=2).train(ds)


# ------------------------------------------------------ serving and analysis

def test_task_models_serve_through_bundle_bit_identical():
    from repro.serving.forest import make_forest_server
    for name, model, data, side in _tiny_models():
        bundle = make_forest_server(model, warmup=False)
        feats = {k: v for k, v in data.items() if k != model.label}
        got = np.asarray(bundle.predict(feats))
        want = np.asarray(model.predict(data))
        assert np.array_equal(got, want), name


def test_ranking_analyze_reports_task_metrics():
    ds = grouped_relevance(n_groups=25, seed=7)
    model = GradientBoostedTreesLearner(label="rel", task=Task.RANKING,
                                        num_trees=4, seed=1).train(ds)
    report = model.analyze(ds, permutation_repetitions=1)
    assert report.task == "RANKING"
    assert report.evaluation is not None
    assert "ndcg@5" in report.evaluation.metrics
    kinds = {t.kind for t in report.importances}
    assert "MEAN_INCREASE_RMSE" in kinds      # scalar-proxy permutation VI


# --------------------------------------------------------------- CLI + bench

def test_cli_train_task_round_trip(tmp_path, capsys):
    from repro.cli import main
    from repro.data.io import write_dataset

    cases = [
        ("ranking", grouped_relevance(n_groups=25, seed=7), "rel",
         Task.RANKING, "GradientBoostedTreesModel"),
        ("uplift", randomized_treatment(n=300, seed=11), "outcome",
         Task.UPLIFT, "UpliftModel"),
        ("anomaly", planted_anomaly(n_inlier=120, n_anomaly=8, seed=13),
         "anomaly", Task.ANOMALY, "IsolationForestModel"),
    ]
    for task_arg, data, label, task, model_cls in cases:
        csv_path = f"csv:{tmp_path}/{task_arg}.csv"
        write_dataset(data, csv_path)
        out = str(tmp_path / f"model_{task_arg}")
        main(["train", "--dataset", csv_path, "--label", label,
              "--task", task_arg, "--seed", "7",
              "--hparam", "num_trees=4", "--output", out])
        model = Model.load(out)
        assert model.task == task
        assert type(model).__name__ == model_cls
        pred_path = f"csv:{tmp_path}/pred_{task_arg}.csv"
        main(["predict", "--dataset", csv_path, "--model", out,
              "--output", pred_path])
        assert os.path.exists(pred_path[len("csv:"):])
    capsys.readouterr()


def test_rank_bench_quick_smoke():
    from benchmarks import rank_bench
    res = rank_bench.run_smoke()
    assert res["all_agree_f32"] is True
    assert set(res["configs"]) == {"uniform_small", "uniform_large", "skewed"}
    for cfg in res["configs"].values():
        assert cfg["ms_naive"] > 0 and cfg["ms_device"] > 0
        assert cfg["bucket_widths"] and min(cfg["bucket_widths"]) >= 8
    assert res["headline_speedup"] == max(
        c["speedup"] for c in res["configs"].values())
