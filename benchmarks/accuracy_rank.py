"""Paper §5 accuracy benchmark: learners x datasets, k-fold CV with
fold splits SHARED across learners, mean-rank aggregation (Fig. 6) and
pairwise wins/losses (Tab. 3).

Scaled-down stand-in: synthetic suite (see data/tabular.py) instead of the 70
OpenML sets (offline), fewer trees/folds/trials — protocol identical; scale
knobs at the top.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import (
    CartLearner,
    GradientBoostedTreesLearner,
    HyperParameterTuner,
    LinearLearner,
    RandomForestLearner,
)
from repro.core.dataspec import dataset_from_raw
from repro.core.metalearners import kfold_indices
from repro.data.tabular import SUITE, make_dataset

FOLDS = 3
NUM_TREES = 25
TUNER_TRIALS = 4


def learners():
    gbt = lambda **kw: GradientBoostedTreesLearner(num_trees=NUM_TREES, **kw)
    rf = lambda **kw: RandomForestLearner(num_trees=NUM_TREES, **kw)
    return {
        "YDF GBT (default hp)": lambda: gbt(label="label"),
        "YDF GBT (benchmark hp)": lambda: gbt(label="label",
                                              template="benchmark_rank1"),
        "YDF RF (default hp)": lambda: rf(label="label"),
        "YDF RF (benchmark hp)": lambda: rf(label="label",
                                            template="benchmark_rank1"),
        "YDF CART": lambda: CartLearner(label="label"),
        "Linear (default hp)": lambda: LinearLearner(label="label"),
        "YDF Autotuned (opt acc)": lambda: HyperParameterTuner(
            gbt, {"max_depth": [3, 6, 8], "shrinkage": [0.05, 0.1, 0.3]},
            label="label", n_trials=TUNER_TRIALS, metric="accuracy"),
    }


def run(verbose: bool = True) -> dict:
    accs: dict[str, dict[str, list[float]]] = {}
    times: dict[str, float] = {}
    datasets = [s for s in SUITE if s.n_classes > 0][:5]
    for spec in datasets:
        data = make_dataset(spec)
        ds = dataset_from_raw(data)
        folds = kfold_indices(ds.n_rows, FOLDS, seed=spec.seed)  # shared folds
        for lname, make in learners().items():
            fold_accs = []
            t0 = time.perf_counter()
            for tr, va in folds:
                model = make().train(ds.subset(tr))
                fold_accs.append(model.evaluate(ds.subset(va))["accuracy"])
            times[lname] = times.get(lname, 0.0) + time.perf_counter() - t0
            accs.setdefault(spec.name, {})[lname] = fold_accs
            if verbose:
                print(f"  {spec.name:14s} {lname:26s} "
                      f"acc={np.mean(fold_accs):.4f}", flush=True)

    # mean rank over datasets (Fig. 6)
    names = list(learners())
    ranks = {n: [] for n in names}
    for dname, table in accs.items():
        means = np.array([np.mean(table[n]) for n in names])
        order = (-means).argsort().argsort() + 1  # rank 1 = best
        for n, r in zip(names, order):
            ranks[n].append(int(r))
    mean_rank = {n: float(np.mean(r)) for n, r in ranks.items()}

    # pairwise wins/losses over (dataset, fold) cells (Tab. 3)
    wins = {(a, b): 0.0 for a in names for b in names if a != b}
    for table in accs.values():
        for a in names:
            for b in names:
                if a == b:
                    continue
                for fa, fb in zip(table[a], table[b]):
                    wins[(a, b)] += 1.0 if fa > fb else (0.5 if fa == fb else 0.0)
    return {"accs": accs, "mean_rank": mean_rank, "wins": wins,
            "train_time_s": times}


def main():
    out = run()
    print("\n== mean rank (lower is better; Fig. 6 analogue) ==")
    for n, r in sorted(out["mean_rank"].items(), key=lambda kv: kv[1]):
        print(f"  {r:5.2f}  {n}   [train {out['train_time_s'][n]:.1f}s]")
    print("\n== pairwise wins (row beats column; Tab. 3 analogue) ==")
    names = list(out["mean_rank"])
    for a in names:
        row = " ".join(f"{out['wins'][(a, b)]:5.1f}" if a != b else "    -"
                       for b in names)
        print(f"  {a:26s} {row}")


if __name__ == "__main__":
    from repro.jax_cache import configure_compile_cache
    configure_compile_cache()
    main()
