"""Learning-to-rank walkthrough: LambdaMART through the stock GBT grower
(DESIGN.md §12; the RANKING task is a loss, not a new engine).

    PYTHONPATH=src python examples/train_ranking.py
"""
import numpy as np

from repro.core import GradientBoostedTreesLearner, Task
from repro.core.evaluation import ndcg_at_k
from repro.data.tabular import grouped_relevance
from repro.serving.forest import MicroBatcher, make_forest_server
from repro.tasks import group_aware_split

# 1. a ranking dataset is a tabular dataset plus a "group" column (the
#    query id). grouped_relevance() plants a group-constant bias in the
#    graded labels that is NOT observable as a feature — pointwise
#    regression must fit through it; pairwise lambdas cancel it.
ds = grouped_relevance(n_groups=150, seed=7)
gid = np.asarray([int(v) for v in ds["group"]], np.int64)
rel = np.array([float(v) for v in ds["rel"]])

# 2. split by GROUP, never by row — a query straddling train/test leaks
tr_idx, te_idx = group_aware_split(gid, ratio=0.3, seed=99)
train = {k: v[tr_idx] for k, v in ds.items()}
test = {k: v[te_idx] for k, v in ds.items()}

# 3. task=RANKING routes the stock GBT grower through LambdaMARTLoss:
#    pairwise |delta-NDCG@k|-weighted gradients computed by one jitted
#    pass over size-bucketed groups, on the pairs that touch the top k
#    (benchmarks/rank_bench.py checks it against the all-pairs oracle)
model = GradientBoostedTreesLearner(label="rel", task=Task.RANKING,
                                    num_trees=80, seed=1).train(train)
print(model.summary())
print("lambda pass:", model.training_logs["ranking_pass"], "buckets",
      model.training_logs["ranking_bucket_widths"])

# 4. evaluate: NDCG@{1,5,10} through the task-aware evaluator, and the
#    same number recomputed directly to show there is no magic
ev = model.evaluate(test)
print(ev.report())
nd5 = ndcg_at_k(rel[te_idx], np.asarray(model.predict(test)),
                gid[te_idx], k=5)
assert abs(ev.metrics["ndcg@5"] - nd5) < 1e-12

# the pin from tests/test_tasks.py: the same trees trained pointwise
# (task=REGRESSION, group column dropped) rank measurably worse
reg = GradientBoostedTreesLearner(
    label="rel", task=Task.REGRESSION, num_trees=80, seed=1).train(
    {k: v for k, v in train.items() if k != "group"})
nd5_reg = ndcg_at_k(rel[te_idx], np.asarray(reg.predict(test)),
                    gid[te_idx], k=5)
print(f"\nNDCG@5: lambdamart={ev.metrics['ndcg@5']:.4f} "
      f"pointwise-regression={nd5_reg:.4f} "
      f"(gap {ev.metrics['ndcg@5'] - nd5_reg:+.4f})\n")

# 5. serve scores through the micro-batching front-end (§5.4): requests
#    carry features only; scores come back bit-identical to predict()
bundle = make_forest_server(model)
batcher = MicroBatcher(bundle, max_batch=256)
features = {k: v for k, v in test.items() if k not in ("rel", "group")}
tickets = [batcher.submit({k: v[i:i + 1] for k, v in features.items()})
           for i in range(32)]
batcher.flush()
served = np.concatenate([batcher.result(t) for t in tickets])
assert np.array_equal(served, np.asarray(model.predict(test))[:32])
print(f"served 32 single-row requests in {batcher.dispatches} padded "
      f"dispatch(es), bit-identical to predict()\n")

# 6. which features drive the ranking? permutation importances run the
#    squared-error scalar proxy over the ranking scores (§12.2)
report = model.analyze(test, permutation_repetitions=2)
top = report.importance("MEAN_INCREASE_RMSE").top(3)
print("top features by permutation importance:",
      [(e.feature, round(e.importance, 4)) for e in top])
