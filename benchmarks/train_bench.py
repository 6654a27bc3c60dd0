"""Training-throughput benchmark: growth engines x histogram backends.
Writes BENCH_train.json (the perf-trajectory baseline; paper Tab. 2 analogue
for *training*).

"before" = growth_engine="oracle": the seed grower — per-node partition
loops, full-N histogram rebuilds, example-major (simple) histogram backend,
trees grown one at a time.
"after" engines:
  * "numpy"  — growth_engine="batched" + numpy histogram backend. For Random
    Forests this includes tree-parallel lockstep blocks with keyed per-node
    feature sampling + gathered sqrt(F)-column histograms (DESIGN.md §6.3).
  * "pallas" — batched + the one-hot-MXU histogram kernel (TPU hosts only;
    resolve_backend refuses interpret mode on the hot path).
  * "device" — growth_engine="device": the device-resident jitted level loop
    (DESIGN.md §6). On CPU hosts XLA's scatter makes it the portability /
    correctness path rather than the fast one — timed on the small configs
    (with compile time split out) so the number is recorded honestly without
    dominating the benchmark wall-clock.

Parity columns: "bit_identical" where the engines promise it (oracle vs
batched at equal seeds — including the tree-parallel RF config, where
lockstep is execution-only), "struct_identical"/"pred_close" for the device
engine (f32 gain ties may regrow an equally-good subtree).

Usage: python benchmarks/train_bench.py [--rows N] [--trees T] [--quick]
       [--no-device] [--out PATH]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import platform
import time

import numpy as np

from repro.core import GradientBoostedTreesLearner, RandomForestLearner
from repro.data.tabular import SUITE, make_dataset, train_test_split

FOREST_KEYS = ["feature", "threshold", "split_bin", "cat_mask", "left_child",
               "leaf_value", "n_nodes"]
STRUCT_KEYS = ["feature", "split_bin", "cat_mask", "left_child", "n_nodes"]


def _forests_identical(a, b, keys=FOREST_KEYS) -> bool:
    return all(np.array_equal(getattr(a, k), getattr(b, k)) for k in keys)


def _time_pair(fns: list, reps: int):
    """Best-of-reps for each candidate, reps interleaved across candidates so
    background load perturbs every candidate equally."""
    best = [np.inf] * len(fns)
    models = [None] * len(fns)
    for _ in range(reps):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            models[i] = fn()
            best[i] = min(best[i], time.perf_counter() - t0)
    return best, models


def _configs(num_trees: int, scaled_rows: int):
    """speed.py-style learner configs on the synthetic suite + a scaled
    dataset where the asymptotics show (the suite datasets are paper-small)."""
    small = SUITE[2]                                     # synth_adult, 2k rows
    scaled = dataclasses.replace(small, n=scaled_rows)
    gbt = lambda **kw: GradientBoostedTreesLearner(
        label="label", num_trees=num_trees, **kw)
    gbt_bf = lambda **kw: GradientBoostedTreesLearner(
        label="label", num_trees=num_trees,
        growing_strategy="BEST_FIRST_GLOBAL", **kw)
    rf = lambda **kw: RandomForestLearner(
        label="label", num_trees=max(10, num_trees // 3), max_depth=12,
        compute_oob=False, **kw)
    rf_par = lambda **kw: RandomForestLearner(
        label="label", num_trees=num_trees, max_depth=12,
        compute_oob=False, **kw)                         # tree_parallelism=8
    return [
        ("gbt_default_small", gbt, small, 4),
        ("gbt_default_scaled", gbt, scaled, 4),
        ("gbt_best_first_scaled", gbt_bf, scaled, 3),
        ("rf_scaled", rf, scaled, 3),
        # the tree-parallel RF headline: a full-size forest where the
        # lockstep blocks + gathered sqrt(F) histograms amortize data prep
        ("rf_parallel_scaled", rf_par, scaled, 3),
    ]


def _device_configs(num_trees: int):
    """Device-engine measurements on suite-sized data. Cold run = compile +
    train; warm run reuses the jit cache (the steady-state number: one
    compiled program per frontier-width bucket, shared across trees)."""
    small = SUITE[2]
    gbt = lambda **kw: GradientBoostedTreesLearner(
        label="label", num_trees=num_trees, **kw)
    rf = lambda **kw: RandomForestLearner(
        label="label", num_trees=max(8, num_trees // 3), max_depth=8,
        compute_oob=False, **kw)
    return [("gbt_device_small", gbt, small),
            ("rf_device_small", rf, small)]


def run(num_trees: int = 30, scaled_rows: int = 100_000, reps_cap: int = 99,
        include_device: bool = True, verbose: bool = True) -> dict:
    import jax
    jb = jax.default_backend()
    backends = ["numpy"] + (["pallas"] if jb == "tpu" else [])
    out: dict = {
        "benchmark": "train_bench",
        "host": {"platform": platform.platform(), "numpy": np.__version__,
                 "jax_backend": jb},
        "num_trees": num_trees,
        "scaled_rows": scaled_rows,
        "configs": {},
    }
    for name, make, spec, reps in _configs(num_trees, scaled_rows):
        reps = min(reps, reps_cap)
        train, _ = train_test_split(make_dataset(spec), 0.3, spec.seed)
        fns = [lambda: make(growth_engine="oracle").train(train)]
        for backend in backends:
            fns.append(lambda backend=backend: make(
                growth_engine="batched",
                histogram_backend=backend).train(train))
        times, models = _time_pair(fns, reps)
        t_before, m_before = times[0], models[0]
        row = {"dataset": spec.name, "n_rows": spec.n, "jax_backend": jb,
               "train_s_before": round(t_before, 4), "after": {}}
        for k, backend in enumerate(backends, start=1):
            row["after"][backend] = {
                "train_s": round(times[k], 4),
                "speedup": round(t_before / times[k], 3),
                "bit_identical": _forests_identical(m_before.forest,
                                                    models[k].forest),
            }
        out["configs"][name] = row
        if verbose:
            a = row["after"]["numpy"]
            print(f"  {name:24s} n={spec.n:<7d} before={t_before:7.2f}s "
                  f"after={a['train_s']:7.2f}s speedup={a['speedup']:5.2f}x "
                  f"bit_identical={a['bit_identical']}", flush=True)

    if include_device:
        for name, make, spec in _device_configs(num_trees):
            train, _ = train_test_split(make_dataset(spec), 0.3, spec.seed)
            t0 = time.perf_counter()
            m_cold = make(growth_engine="device").train(train)
            cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            m_dev = make(growth_engine="device").train(train)
            warm = time.perf_counter() - t0
            t0 = time.perf_counter()
            m_ref = make(growth_engine="batched").train(train)
            host = time.perf_counter() - t0
            pa = np.abs(m_ref.predict(train) - m_dev.predict(train))
            agree = min(float((getattr(m_ref.forest, k)
                               == getattr(m_dev.forest, k)).mean())
                        for k in STRUCT_KEYS)
            out["configs"][name] = {
                "dataset": spec.name, "n_rows": spec.n, "jax_backend": jb,
                "engine": m_dev.training_logs["growth_engine"],
                "train_s_cold": round(cold, 4),
                "train_s_warm": round(warm, 4),
                "compile_s": round(cold - warm, 4),
                "train_s_batched_numpy": round(host, 4),
                # f32 gain ties can regrow an equally-good subtree, so the
                # honest metric is node-level agreement + prediction delta
                "struct_identical": _forests_identical(
                    m_ref.forest, m_dev.forest, STRUCT_KEYS),
                "struct_agreement": round(agree, 5),
                "pred_mean_abs_diff": float(pa.mean()),
            }
            if verbose:
                r = out["configs"][name]
                print(f"  {name:24s} n={spec.n:<7d} warm={warm:7.2f}s "
                      f"compile={r['compile_s']:6.2f}s "
                      f"numpy={host:6.2f}s struct_identical="
                      f"{r['struct_identical']}", flush=True)

    out["checkpoint_overhead"] = _checkpoint_overhead(
        num_trees, reps_cap, verbose)

    out["profile"] = _profile_section(num_trees, verbose)

    out["headline_speedup"] = out["configs"]["gbt_default_scaled"][
        "after"]["numpy"]["speedup"]
    out["rf_headline_speedup"] = out["configs"]["rf_parallel_scaled"][
        "after"]["numpy"]["speedup"]
    return out


def _profile_section(num_trees: int, verbose: bool) -> dict:
    """Phase breakdown of one traced small-config GBT train (DESIGN.md
    §13.6): the BENCH trajectory records where training time GOES — per
    grower phase — not just the headline ratio."""
    from repro.obs import trace
    from repro.obs.export import profile_dict

    small = SUITE[2]
    train, _ = train_test_split(make_dataset(small), 0.3, small.seed)
    with trace.capture() as tracer:
        GradientBoostedTreesLearner(
            label="label", num_trees=num_trees).train(train)
    prof = profile_dict(tracer)
    prof["dataset"] = small.name
    prof["num_trees"] = num_trees
    if verbose:
        top = sorted(prof["phases"].items(),
                     key=lambda kv: -kv[1]["total_s"])[:5]
        print("  profile (traced small GBT): " + ", ".join(
            f"{n} {d['total_s'] * 1e3:.0f}ms x{d['count']}"
            for n, d in top), flush=True)
    return prof


def _checkpoint_overhead(num_trees: int, reps_cap: int, verbose: bool) -> dict:
    """Wall-clock cost of DESIGN.md §11 checkpointing at the default cadence
    (every 10 trees): interleaved best-of timing of train-without vs
    train-with-checkpoints. Acceptance: <= 5% overhead."""
    import shutil
    import tempfile

    from repro.train.checkpoint import CheckpointPolicy

    small = SUITE[2]
    train, _ = train_test_split(make_dataset(small), 0.3, small.seed)
    ckdir = tempfile.mkdtemp(prefix="bench-ck-")
    make = lambda: GradientBoostedTreesLearner(label="label",
                                               num_trees=num_trees)

    def with_ck():
        shutil.rmtree(ckdir, ignore_errors=True)
        return make().train(train, checkpoint=CheckpointPolicy(ckdir))

    try:
        (t_plain, t_ck), (m_plain, m_ck) = _time_pair(
            [lambda: make().train(train), with_ck], min(4, max(2, reps_cap)))
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)
    overhead = t_ck / t_plain - 1.0
    row = {
        "dataset": small.name, "num_trees": num_trees,
        "every_n_trees": 10,
        "train_s_plain": round(t_plain, 4),
        "train_s_checkpointed": round(t_ck, 4),
        "overhead_pct": round(100 * overhead, 2),
        "acceptance_max_pct": 5.0,
        "accepted": bool(overhead <= 0.05),
        "bit_identical": _forests_identical(m_plain.forest, m_ck.forest),
    }
    if verbose:
        print(f"  checkpoint_overhead      every=10 trees: "
              f"plain={t_plain:6.2f}s ck={t_ck:6.2f}s "
              f"overhead={row['overhead_pct']:+.2f}% "
              f"accepted={row['accepted']}", flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=None,
                    help="scaled dataset size (default 100000; 20000 under "
                    "--quick)")
    ap.add_argument("--trees", type=int, default=None,
                    help="trees per GBT config (default 30; 9 under --quick)")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run: 20k rows, 9 trees, single rep, "
                    "no device configs, no JSON overwrite by default "
                    "(explicit --rows/--trees are honored)")
    ap.add_argument("--no-device", action="store_true",
                    help="skip the device-engine configs")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default BENCH_train.json; "
                    "--quick defaults to not writing)")
    args = ap.parse_args()
    rows = args.rows if args.rows is not None else \
        (20_000 if args.quick else 100_000)
    trees = args.trees if args.trees is not None else \
        (9 if args.quick else 30)
    res = run(num_trees=trees, scaled_rows=rows,
              reps_cap=1 if args.quick else 99,
              include_device=not (args.no_device or args.quick))
    out_path = args.out or (None if args.quick else "BENCH_train.json")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(res, f, indent=2)
    print(f"headline GBT {res['headline_speedup']:.2f}x | "
          f"tree-parallel RF {res['rf_headline_speedup']:.2f}x"
          + (f" -> {out_path}" if out_path else ""))


if __name__ == "__main__":
    from repro.jax_cache import configure_compile_cache
    configure_compile_cache()
    main()
