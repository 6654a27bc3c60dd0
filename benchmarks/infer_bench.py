"""Inference-throughput benchmark: the compiled serving stack vs the seed
per-call path. Writes BENCH_infer.json (the serving perf-trajectory
baseline, tracked like BENCH_train.json; paper Tab. 2 analogue for
*inference* — see DESIGN.md §5, §10).

"before" = the seed path: every predict call re-walks the dataspec
(encode_dataset), re-imputes into a raw matrix (raw_matrix) and runs the
generic lockstep traversal (tree.predict_raw) — per-call conversion, no
reuse.
"after"  = CompiledPredictor.predict per engine (§5.1/§10): raw→code encode
tables, specialized/device-resident traversal and the output head compiled
once, then reused for every request batch. Every CPU traversal strategy
(vectorized numpy, depth-bucketed XLA scan, forced leaf-path matmul) gets
its own column so the per-strategy trajectory is tracked, not just the
winner.

Every timed pair is checked for allclose predictions (the §2.3 contract).
Engine compile time is reported separately (it is paid once, not per call).

The ``sklearn_import`` config (DESIGN.md §7) times an imported 300-tree
sklearn RandomForest through our compiled predictor against sklearn's own
``predict_proba`` on the same rows — the cross-runtime serving comparison
(Guan et al., 2023 protocol). ``speedup_vs_sklearn`` (the tracked headline)
is the BEST strategy's ratio; per-strategy ratios are recorded alongside.
It runs whenever scikit-learn is installed (an optional dependency) and is
skipped cleanly otherwise.

Usage: python benchmarks/infer_bench.py [--rows N] [--trees T] [--out PATH]
       [--quick]   (tiny smoke sizes; also exercised inside tier-1 tests)
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np

from repro.core import GradientBoostedTreesLearner, RandomForestLearner
from repro.core.dataspec import encode_dataset
from repro.core.models import raw_matrix
from repro.core.tree import predict_raw
from repro.data.tabular import adult_like, train_test_split


def _seed_predict(model, data) -> np.ndarray:
    """The per-call path as it stood at the seed: full conversion + generic
    traversal on every call."""
    ds = encode_dataset(data, model.spec)
    X = raw_matrix(ds, model.features)
    return model._finalize(predict_raw(model.forest, X))


def _best_of(fns: list, reps: int) -> tuple[list[float], list]:
    """Best-of-reps per candidate, reps interleaved so background load
    perturbs every candidate equally (same protocol as train_bench)."""
    best = [np.inf] * len(fns)
    outs = [None] * len(fns)
    for _ in range(reps):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            outs[i] = fn()
            best[i] = min(best[i], time.perf_counter() - t0)
    return best, outs


def _cpu_strategies(forest) -> list[str]:
    """The CPU traversal strategies to column in the report, in preference
    order: every one offered by the engine registry except the oracle."""
    from repro.core.engines import available_engines
    return [e for e in available_engines(forest)
            if e in ("bucketed", "leaf_path", "vectorized")]


def run(rows: int = 100_000, num_trees: int = 30, reps: int = 3,
        verbose: bool = True, include_interpret: bool = False,
        sklearn_trees: int = 300) -> dict:
    import jax

    from repro.core.engines import JIT_ENGINES, compile_predictor
    on_tpu = jax.default_backend() == "tpu"
    train, _ = train_test_split(adult_like(max(2000, min(rows, 4000))), 0.3, 1)
    serve = adult_like(rows, seed=7)
    serve.pop("income")  # serving requests carry features only (§5.1)

    out: dict = {
        "benchmark": "infer_bench",
        "host": {"platform": platform.platform(), "numpy": np.__version__,
                 "jax_backend": jax.default_backend()},
        "rows": rows,
        "num_trees": num_trees,
        "configs": {},
    }
    models = [
        ("gbt_adult", GradientBoostedTreesLearner(
            label="income", num_trees=num_trees).train(train)),
        ("rf_adult", RandomForestLearner(
            label="income", num_trees=max(10, num_trees // 3),
            max_depth=12).train(train)),
    ]
    for name, model in models:
        # the seed path needs every dataspec column present
        seed_batch = dict(serve)
        seed_batch["income"] = np.full(rows, "<=50K", object)

        engines = _cpu_strategies(model.forest) + (["pallas"] if on_tpu else [])
        if include_interpret and not on_tpu:
            engines.append("pallas")
        fns = [lambda m=model, b=seed_batch: _seed_predict(m, b)]
        compile_s = {}
        small = {k: v[:64] for k, v in serve.items()}
        for ename in engines:
            t0 = time.perf_counter()
            pred = compile_predictor(model, ename)
            if ename in JIT_ENGINES:
                # jit'd: the trace/XLA-compile happens on the first call at
                # the timed shape — that IS the one-time compile cost
                pred.predict(serve)
                compile_s[ename] = time.perf_counter() - t0
            else:
                # non-jit: compile cost is the specialization alone; warm
                # the code path untimed on a small slice
                compile_s[ename] = time.perf_counter() - t0
                pred.predict(small)
            fns.append(lambda p=pred: p.predict(serve))
        times, preds = _best_of(fns, reps)
        t_before = times[0]
        row = {"n_rows": rows,
               "us_example_before": round(t_before / rows * 1e6, 3),
               "after": {}}
        for k, ename in enumerate(engines, start=1):
            row["after"][ename] = {
                "us_example": round(times[k] / rows * 1e6, 3),
                "speedup": round(t_before / times[k], 3),
                "compile_s": round(compile_s[ename], 4),
                "allclose": bool(np.allclose(preds[k], preds[0], atol=1e-5)),
            }
        out["configs"][name] = row
        if verbose:
            for ename in engines:
                a = row["after"][ename]
                print(f"  {name:12s} n={rows:<7d} "
                      f"before={row['us_example_before']:8.2f} us/ex  "
                      f"{ename:10s}={a['us_example']:8.2f} us/ex  "
                      f"speedup={a['speedup']:5.2f}x  allclose={a['allclose']}",
                      flush=True)
    sk = _run_sklearn_import(rows=rows, reps=reps, verbose=verbose,
                             n_trees=sklearn_trees)
    if sk is not None:
        out["configs"]["sklearn_import"] = sk
    out["profile"] = _profile_section(models[0][1], serve, verbose)
    out["headline_speedup"] = max(
        a["speedup"] for a in out["configs"]["gbt_adult"]["after"].values())
    return out


def _profile_section(model, serve, verbose: bool) -> dict:
    """Phase breakdown of traced inference (DESIGN.md §13.6): compile vs
    dispatch time for the auto-selected engine, recorded in the BENCH
    trajectory alongside the headline ratios."""
    from repro.core.engines import compile_predictor
    from repro.obs import trace
    from repro.obs.export import profile_dict

    with trace.capture() as tracer:
        pred = compile_predictor(model)
        for _ in range(3):
            pred.predict(serve)
    prof = profile_dict(tracer)
    prof["engine"] = pred.name
    if verbose:
        top = sorted(prof["phases"].items(),
                     key=lambda kv: -kv[1]["total_s"])[:4]
        print("  profile (traced gbt_adult): " + ", ".join(
            f"{n} {d['total_s'] * 1e3:.0f}ms x{d['count']}"
            for n, d in top), flush=True)
    return prof


def _run_sklearn_import(rows: int, reps: int, verbose: bool,
                        n_trees: int = 300) -> dict | None:
    """Imported n_trees-tree sklearn RF through the compiled predictor vs
    sklearn's own predict_proba (both in-process, same rows), one column
    per CPU traversal strategy."""
    try:
        from sklearn.ensemble import RandomForestClassifier
    except ImportError:
        if verbose:
            print("  sklearn_import skipped (scikit-learn not installed)")
        return None
    from repro.core.engines import JIT_ENGINES, compile_predictor
    from repro.interop import from_sklearn

    rng = np.random.default_rng(11)
    F = 10
    X = rng.normal(size=(4000, F)).astype(np.float32)
    y = (X[:, 0] + np.square(X[:, 1]) - X[:, 2] > 0.3).astype(int)
    est = RandomForestClassifier(n_estimators=n_trees, max_depth=12,
                                 random_state=0).fit(X, y)
    model = from_sklearn(est)
    X_serve = rng.normal(size=(rows, F)).astype(np.float32)
    batch = {f"f{i}": X_serve[:, i] for i in range(F)}
    strategies = _cpu_strategies(model.forest)
    fns = [lambda: est.predict_proba(X_serve)]
    compile_s = {}
    for ename in strategies:
        t0 = time.perf_counter()
        pred = compile_predictor(model, ename)
        if ename in JIT_ENGINES:
            pred.predict(batch)                  # trace at the timed shape
        else:
            pred.predict({k: v[:64] for k, v in batch.items()})
        compile_s[ename] = time.perf_counter() - t0
        fns.append(lambda p=pred: p.predict(batch))
    est.predict_proba(X_serve[:64])              # sklearn warm, untimed
    times, outs = _best_of(fns, reps)
    row = {
        "n_rows": rows,
        "n_trees": len(est.estimators_),
        "total_nodes": int(model.forest.n_nodes.sum()),
        "max_depth": int(model.forest.depth),
        "us_example_sklearn": round(times[0] / rows * 1e6, 3),
        "strategies": {},
    }
    for k, ename in enumerate(strategies, start=1):
        row["strategies"][ename] = {
            "us_example": round(times[k] / rows * 1e6, 3),
            "speedup_vs_sklearn": round(times[0] / times[k], 3),
            "compile_s": round(compile_s[ename], 4),
            "allclose": bool(np.allclose(outs[k], outs[0], atol=1e-5)),
        }
    best = max(row["strategies"], key=lambda e:
               row["strategies"][e]["speedup_vs_sklearn"])
    row["best_strategy"] = best
    row["us_example_compiled"] = row["strategies"][best]["us_example"]
    row["speedup_vs_sklearn"] = row["strategies"][best]["speedup_vs_sklearn"]
    row["allclose"] = row["strategies"][best]["allclose"]
    if verbose:
        for ename, a in row["strategies"].items():
            print(f"  sklearn_import n={rows:<7d} "
                  f"sklearn={row['us_example_sklearn']:8.2f} us/ex  "
                  f"{ename:10s}={a['us_example']:8.2f} us/ex  "
                  f"ratio={a['speedup_vs_sklearn']:5.2f}x  "
                  f"allclose={a['allclose']}", flush=True)
    return row


def run_smoke() -> dict:
    """Tiny end-to-end pass over every strategy on real (adult-like +
    sklearn-import) data — exercised inside tier-1 (tests/
    test_traversal_strategies.py) so the benchmark harness itself cannot
    rot between full runs."""
    return run(rows=1500, num_trees=4, reps=1, verbose=False,
               sklearn_trees=25)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=100_000)
    ap.add_argument("--trees", type=int, default=30)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="smoke sizes (1.5k rows, tiny forests)")
    ap.add_argument("--out", default="BENCH_infer.json")
    args = ap.parse_args()
    if args.quick:
        res = run_smoke()
    else:
        res = run(rows=args.rows, num_trees=args.trees, reps=args.reps)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    sk = res["configs"].get("sklearn_import")
    if sk:
        print(f"sklearn_import best={sk['best_strategy']} "
              f"ratio={sk['speedup_vs_sklearn']:.2f}x")
    print(f"headline (gbt_adult, best compiled engine vs seed per-call "
          f"path): {res['headline_speedup']:.2f}x -> {args.out}")


if __name__ == "__main__":
    from repro.jax_cache import configure_compile_cache
    configure_compile_cache()
    main()
