"""Analysis-throughput benchmark: permutation variable importance on a
300-tree depth-12 Random Forest, the compiled batched-replica path vs a
naive per-feature predict loop. Writes BENCH_analyze.json (the analysis
perf-trajectory baseline, tracked like BENCH_infer.json; DESIGN.md §8).

"naive"   = the per-feature python loop over the SEED per-call path: every
(feature, repetition) replica starts from the raw columns, permutes one of
them, then pays the full per-call pipeline — encode_dataset dataspec walk,
raw_matrix imputation pass, generic lockstep traversal (tree.predict_raw)
— exactly what hand-rolling permutation importance against the seed predict
path costs.
"batched" = analysis.permutation_importances: encode ONCE through the
compiled predictor's BatchEncoder, stack all F x R permuted replicas into
row-budget-bounded batches, and dispatch them through the cached
CompiledPredictor (§5.1 specialized traversal).

Both paths draw each replica's permutation from the same keyed rng
(importance._permutation), and elementwise encoding commutes with row
permutation, so the two score vectors must agree to numerical tolerance —
checked and recorded alongside the timings.

Usage: python benchmarks/analyze_bench.py [--rows N] [--trees T] [--quick]
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np

from repro.analysis.importance import _permutation, permutation_importances
from repro.core import RandomForestLearner
from repro.core.dataspec import encode_dataset, label_values
from repro.core.models import raw_matrix
from repro.core.tree import predict_raw
from repro.data.tabular import adult_like


def _naive_loop(model, data, repetitions: int, seed: int) -> dict[str, float]:
    """Per-feature loop over the seed per-call path; returns feature ->
    mean decrease in accuracy."""
    y = label_values(model, data)
    N = len(y)

    def seed_predict(batch):
        ds = encode_dataset(batch, model.spec)
        X = raw_matrix(ds, model.features)
        return model._finalize(predict_raw(model.forest, X))

    base_acc = float((seed_predict(data).argmax(1) == y).mean())
    out = {}
    for j, name in enumerate(model.features):
        drops = []
        for r in range(repetitions):
            perm = _permutation(seed, j, r, N)
            batch = dict(data)
            batch[name] = np.asarray(data[name], dtype=object)[perm]
            acc = float((seed_predict(batch).argmax(1) == y).mean())
            drops.append(base_acc - acc)
        out[name] = float(np.mean(drops))
    return out


def run(rows: int = 2000, num_trees: int = 300, max_depth: int = 12,
        repetitions: int = 2, reps: int = 2, seed: int = 42,
        row_budget: int | None = None, verbose: bool = True) -> dict:
    import jax
    train = adult_like(max(3000, rows), seed=1)
    data = {k: v[:rows] for k, v in adult_like(rows, seed=9).items()}

    t0 = time.perf_counter()
    model = RandomForestLearner(label="income", num_trees=num_trees,
                                max_depth=max_depth).train(train)
    train_s = time.perf_counter() - t0
    model.predictor()  # compile outside the timed region (paid once, §5.1)

    # interleaved best-of-reps (train_bench protocol): background load on the
    # shared host perturbs both candidates equally
    best_naive = best_batched = np.inf
    naive_scores = batched_table = None
    for _ in range(reps):
        t0 = time.perf_counter()
        naive_scores = _naive_loop(model, data, repetitions, seed)
        best_naive = min(best_naive, time.perf_counter() - t0)
        t0 = time.perf_counter()
        kw = {} if row_budget is None else {"row_budget": row_budget}
        batched_table, _ = permutation_importances(
            model, data, repetitions=repetitions, seed=seed, **kw)
        best_batched = min(best_batched, time.perf_counter() - t0)

    diffs = [abs(naive_scores[f] - batched_table[f])
             for f in model.features]
    n_replicas = len(model.features) * repetitions
    out = {
        "benchmark": "analyze_bench",
        "host": {"platform": platform.platform(), "numpy": np.__version__,
                 "jax_backend": jax.default_backend()},
        "config": {"rows": rows, "num_trees": num_trees,
                   "max_depth": max_depth, "repetitions": repetitions,
                   "n_features": len(model.features),
                   "total_nodes": int(model.forest.n_nodes.sum()),
                   "train_s": round(train_s, 2)},
        "naive_loop_s": round(best_naive, 3),
        "batched_replicas_s": round(best_batched, 3),
        "us_per_replica_row_naive": round(
            best_naive / (n_replicas * rows) * 1e6, 3),
        "us_per_replica_row_batched": round(
            best_batched / (n_replicas * rows) * 1e6, 3),
        "speedup": round(best_naive / best_batched, 3),
        "max_score_diff": float(max(diffs)),
        "scores_match": bool(max(diffs) < 1e-9),
        "top_feature": batched_table.ranking()[0],
    }
    if verbose:
        print(f"  permutation importance ({num_trees} trees, depth "
              f"{max_depth}, {rows} rows x {n_replicas} replicas): "
              f"naive {best_naive:.2f}s, batched {best_batched:.2f}s, "
              f"speedup {out['speedup']:.2f}x, "
              f"match {out['scores_match']}", flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=2000)
    ap.add_argument("--trees", type=int, default=300)
    ap.add_argument("--depth", type=int, default=12)
    ap.add_argument("--repetitions", type=int, default=2)
    ap.add_argument("--reps", type=int, default=2,
                    help="timing repetitions (best-of)")
    ap.add_argument("--quick", action="store_true",
                    help="small configuration for CI smoke")
    ap.add_argument("--out", default="BENCH_analyze.json")
    args = ap.parse_args()
    if args.quick:
        res = run(rows=400, num_trees=30, max_depth=8, repetitions=1, reps=1)
    else:
        res = run(rows=args.rows, num_trees=args.trees, max_depth=args.depth,
                  repetitions=args.repetitions, reps=args.reps)
        with open(args.out, "w") as f:
            json.dump(res, f, indent=2)
        print(f"headline (compiled batched replicas vs naive per-feature "
              f"loop): {res['speedup']:.2f}x -> {args.out}")


if __name__ == "__main__":
    from repro.jax_cache import configure_compile_cache
    configure_compile_cache()
    main()
