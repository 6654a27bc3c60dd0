"""Paper Tab. 2/6/7: training and inference wall-time per learner (seconds),
averaged over the synthetic suite. CSV output: name,train_s,infer_s."""
from __future__ import annotations

import time

import numpy as np

from repro.core import (
    GradientBoostedTreesLearner,
    LinearLearner,
    RandomForestLearner,
)
from repro.data.tabular import SUITE, make_dataset, train_test_split

NUM_TREES = 30


def learners():
    return {
        "YDF GBT (default hp)": lambda: GradientBoostedTreesLearner(
            label="label", num_trees=NUM_TREES),
        "YDF GBT (benchmark hp)": lambda: GradientBoostedTreesLearner(
            label="label", num_trees=NUM_TREES, template="benchmark_rank1"),
        "YDF RF (default hp)": lambda: RandomForestLearner(
            label="label", num_trees=NUM_TREES, compute_oob=False),
        "YDF RF (benchmark hp)": lambda: RandomForestLearner(
            label="label", num_trees=NUM_TREES, compute_oob=False,
            template="benchmark_rank1"),
        "Linear (default hp)": lambda: LinearLearner(label="label"),
    }


def run(verbose: bool = True) -> dict:
    rows = {}
    datasets = [s for s in SUITE if s.n_classes > 0][:4]
    for lname, make in learners().items():
        t_train = t_inf = 0.0
        for spec in datasets:
            train, test = train_test_split(make_dataset(spec), 0.3, spec.seed)
            t0 = time.perf_counter()
            model = make().train(train)
            t_train += time.perf_counter() - t0
            model.predict(test)  # warm the engine
            t0 = time.perf_counter()
            model.predict(test)
            t_inf += time.perf_counter() - t0
        rows[lname] = (t_train / len(datasets), t_inf / len(datasets))
        if verbose:
            print(f"  {lname:26s} train={rows[lname][0]:7.2f}s "
                  f"infer={rows[lname][1] * 1e3:7.1f}ms", flush=True)
    return rows


def main():
    print("name,train_s,infer_s")
    for n, (tt, ti) in run(verbose=False).items():
        print(f"{n},{tt:.3f},{ti:.4f}")


if __name__ == "__main__":
    from repro.jax_cache import configure_compile_cache
    configure_compile_cache()
    main()
