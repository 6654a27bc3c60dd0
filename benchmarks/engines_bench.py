"""Paper App. B.4: inference-engine comparison (us/example per engine) on a
trained GBT and RF — the engine-compilation (§3.7) payoff, CPU edition.
(The pallas engine runs interpret-mode here: correctness path; TPU target.)"""
from __future__ import annotations

import time

import numpy as np

import repro.core.models as M
from repro.core import GradientBoostedTreesLearner, RandomForestLearner
from repro.core.engines import available_engines, compile_model
from repro.data.tabular import adult_like, train_test_split


def run(verbose: bool = True, include_interpret: bool = False) -> dict:
    train, test = train_test_split(adult_like(2000), 0.5, 1)
    out = {}
    for mname, learner in [
        ("GBT", GradientBoostedTreesLearner(label="income", num_trees=30)),
        ("RF", RandomForestLearner(label="income", num_trees=30, max_depth=12)),
    ]:
        model = learner.train(train)
        ds = M._as_vertical(test, model.spec)
        X = M.raw_matrix(ds, model.features)
        for ename in available_engines(model.forest):
            if ename == "pallas" and not include_interpret:
                continue  # interpret-mode timing is not meaningful
            eng = compile_model(model, ename)
            n = X.shape[0] if ename != "naive" else min(200, X.shape[0])
            eng.per_tree(X[:n])  # warm up at the timed shape (§5.1)
            t0 = time.perf_counter()
            eng.per_tree(X[:n])
            dt = time.perf_counter() - t0
            us = dt / n * 1e6
            out[f"{mname}/{ename}"] = us
            if verbose:
                print(f"  {mname:4s} {ename:12s} {us:10.2f} us/example", flush=True)
    return out


def main():
    print("model/engine,us_per_example")
    for k, v in run(verbose=False).items():
        print(f"{k},{v:.2f}")


if __name__ == "__main__":
    from repro.jax_cache import configure_compile_cache
    configure_compile_cache()
    main()
