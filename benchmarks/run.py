"""Benchmark entry point: one module per paper table/figure.

  python -m benchmarks.run [--skip accuracy speed ...]
  python -m benchmarks.run --profile   # traced phase breakdowns only:
                                       # re-runs the train/infer headline
                                       # configs with tracing on and writes
                                       # BENCH_profile.json (DESIGN.md §13)

  accuracy_rank   — Fig. 6 mean ranks + Tab. 3 pairwise wins
  speed           — Tab. 2 train/inference seconds
  engines_bench   — App. B.4 per-engine us/example
  infer_bench     — DESIGN.md §5 compiled serving stack vs seed per-call
                    path (BENCH_infer.json when run as a module)
  train_bench     — DESIGN.md §6 growth engines x histogram backends
                    (BENCH_train.json when run as a module; --quick here)
  analyze_bench   — DESIGN.md §8 permutation importance: compiled
                    batched-replica path vs naive per-feature loop
                    (BENCH_analyze.json when run as a module; quick here)
  rank_bench      — DESIGN.md §12 device LambdaMART lambda pass vs
                    per-group loop (BENCH_rank.json when run as a module)
  serve_bench     — DESIGN.md §9 fault-tolerant front-end: p50/p99 latency
                    vs offered QPS, clean vs fault-injected
                    (BENCH_serve.json when run as a module; --quick here)
  distributed_df  — §3.9 traffic scaling
  roofline_report — assignment §Roofline/§Dry-run tables (from results/)
"""
from __future__ import annotations

import argparse
import time


def run_profile(out_path: str = "BENCH_profile.json") -> dict:
    """The --profile sub-mode: the train/infer headline configs re-run
    under the tracer, phase breakdowns written next to the BENCH files."""
    import json

    from benchmarks import infer_bench, train_bench
    from repro.core import GradientBoostedTreesLearner
    from repro.data.tabular import adult_like, train_test_split

    out = {"benchmark": "profile"}
    print("== traced training phases (DESIGN.md §13.6) ==", flush=True)
    out["train"] = train_bench._profile_section(9, verbose=True)
    print("== traced inference phases (DESIGN.md §13.6) ==", flush=True)
    train, _ = train_test_split(adult_like(2000), 0.3, 1)
    model = GradientBoostedTreesLearner(
        label="income", num_trees=10).train(train)
    serve = adult_like(20_000, seed=7)
    serve.pop("income")
    out["infer"] = infer_bench._profile_section(model, serve, verbose=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {out_path}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip", nargs="*", default=[])
    ap.add_argument("--profile", action="store_true",
                    help="only the traced phase-breakdown sub-mode "
                         "(writes BENCH_profile.json)")
    args = ap.parse_args()
    if args.profile:
        run_profile()
        return

    from benchmarks import accuracy_rank, analyze_bench, distributed_df, \
        engines_bench, infer_bench, rank_bench, serve_bench, speed, \
        train_bench

    t_all = time.time()
    if "speed" not in args.skip:
        print("== speed (paper Tab. 2) ==", flush=True)
        speed.run()
    if "train" not in args.skip:
        print("== training engines (DESIGN.md §6) ==", flush=True)
        res = train_bench.run(num_trees=9, scaled_rows=20_000, reps_cap=1,
                              include_device=False)
        print(f"  headline: GBT {res['headline_speedup']:.2f}x, "
              f"tree-parallel RF {res['rf_headline_speedup']:.2f}x vs the "
              "seed grower (full 100k-row run: python -m "
              "benchmarks.train_bench)")
    if "engines" not in args.skip:
        print("== engines (paper App. B.4) ==", flush=True)
        engines_bench.run()
    if "infer" not in args.skip:
        print("== inference serving stack (DESIGN.md §5/§10) ==", flush=True)
        res = infer_bench.run(rows=20_000, reps=2)
        line = (f"  headline: {res['headline_speedup']:.2f}x best compiled "
                "engine vs seed per-call path")
        sk = res["configs"].get("sklearn_import")
        if sk:
            line += (f"; {sk['speedup_vs_sklearn']:.2f}x vs sklearn "
                     f"({sk['best_strategy']})")
        print(line + " (full 100k-row run: python -m benchmarks.infer_bench)")
    if "serve" not in args.skip:
        print("== fault-tolerant serving front-end (DESIGN.md §9) ==",
              flush=True)
        res = serve_bench.run(qps_levels=(200, 800, 2400), duration_s=0.5,
                              num_trees=10)
        top = res["levels"]["2400"]
        print(f"  headline: p99 {top['clean']['p99_ms']} ms clean / "
              f"{top['faults']['p99_ms']} ms under injected faults at "
              "2400 offered qps (full sweep: python -m "
              "benchmarks.serve_bench)")
    if "analyze" not in args.skip:
        print("== model analysis (DESIGN.md §8) ==", flush=True)
        res = analyze_bench.run(rows=400, num_trees=30, max_depth=8,
                                repetitions=1, reps=1)
        print(f"  headline: {res['speedup']:.2f}x batched replicas vs naive "
              "loop at this small config (full 300-tree run: python -m "
              "benchmarks.analyze_bench)")
    if "rank" not in args.skip:
        print("== LambdaMART lambda pass (DESIGN.md §12) ==", flush=True)
        res = rank_bench.run(n_groups=400, reps=2)
        print(f"  headline: {res['headline_speedup']:.2f}x device pass vs "
              f"per-group loop, agreement within float32: "
              f"{res['all_agree_f32']} "
              "(full run: python -m benchmarks.rank_bench)")
    if "distributed" not in args.skip:
        print("== distributed DF traffic (paper §3.9) ==", flush=True)
        distributed_df.run()
    if "accuracy" not in args.skip:
        print("== accuracy ranks (paper Fig. 6 / Tab. 3) ==", flush=True)
        out = accuracy_rank.run(verbose=False)
        for n, r in sorted(out["mean_rank"].items(), key=lambda kv: kv[1]):
            print(f"  rank {r:5.2f}  {n}  [train {out['train_time_s'][n]:.1f}s]")
    if "roofline" not in args.skip:
        try:
            from benchmarks import roofline_report
            cells = roofline_report.load_cells()
            if cells:
                print(f"== roofline ({len(cells)} unrolled cells; full table in "
                      "EXPERIMENTS.md) ==", flush=True)
                worst = sorted(cells, key=lambda d: d["terms"]["roofline_fraction"])
                for d in worst[:3] + worst[-3:]:
                    t = d["terms"]
                    print(f"  {d['arch']:16s} {d['shape']:12s} dominant={t['dominant']:10s} "
                          f"roofline_frac={t['roofline_fraction']:.3f}")
        except Exception as e:
            print(f"  (roofline artifacts unavailable: {e})")
    print(f"\nall benchmarks done in {time.time() - t_all:.0f}s")


if __name__ == "__main__":
    from repro.jax_cache import configure_compile_cache
    configure_compile_cache()
    main()
