"""Ranking-gradient benchmark: the device LambdaMART lambda pass vs a
per-group Python loop. Writes BENCH_rank.json (DESIGN.md §12).

"naive"  = the float64 oracle: one all-pairs `_lambda_pass` call per group
at the group's own (m_g, m_g) size, dominated by Python dispatch and
tiny-kernel overhead.
"device" = the pass the GBT training loop runs each boosting iteration:
groups laid out in power-of-two size buckets and swept by one jitted
float32 program over the pairs that touch the top k (tasks/ranking.py).

The bench checks that the two agree within float32's tolerance (the one
tests/test_tasks.py states) on gradients AND hessians. Its times come from
whatever backend JAX runs here: on a CPU host they are portability checks,
not device speed.

Usage: python -m benchmarks.rank_bench [--groups N] [--reps R] [--out PATH]
       [--quick]   (tiny smoke sizes; also exercised inside tier-1 tests)
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np

from repro.tasks.ranking import group_layout, lambda_grad_device, \
    lambda_grad_naive

# float32 against float64: the tolerance tests/test_tasks.py derives
RTOL, ATOL_OF_SCALE = 1e-5, 4e-6


def _make_groups(n_groups: int, lo: int, hi: int, seed: int):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(lo, hi + 1, n_groups)
    groups = np.repeat(np.arange(n_groups), sizes)
    n = len(groups)
    scores = rng.normal(size=n).astype(np.float32).astype(np.float64)
    rel = rng.integers(0, 5, n).astype(np.float64)
    return groups, scores, rel


def _best_of(fns: list, reps: int) -> tuple[list[float], list]:
    """Best-of-reps, reps interleaved so background load perturbs every
    candidate equally (same protocol as infer_bench)."""
    best = [np.inf] * len(fns)
    outs = [None] * len(fns)
    for _ in range(reps):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            outs[i] = fn()
            best[i] = min(best[i], time.perf_counter() - t0)
    return best, outs


def run(n_groups: int = 1500, reps: int = 3, verbose: bool = True) -> dict:
    out: dict = {
        "benchmark": "rank_bench",
        "host": {"platform": platform.platform(), "numpy": np.__version__},
        "configs": {},
    }
    shapes = [
        ("uniform_small", n_groups, 8, 16),
        ("uniform_large", max(2, n_groups // 4), 32, 64),
        ("skewed", n_groups, 2, 48),
    ]
    for name, g, lo, hi in shapes:
        groups, scores, rel = _make_groups(g, lo, hi, seed=3)
        layout = group_layout(groups)
        k = 5
        fns = [
            lambda: lambda_grad_naive(scores, rel, layout, k=k),
            lambda: lambda_grad_device(scores, rel, layout, k=k),
        ]
        lambda_grad_device(scores, rel, layout, k=k)     # compile
        times, (naive, device) = _best_of(fns, reps)
        agree = all(np.allclose(d, n, rtol=RTOL,
                                atol=ATOL_OF_SCALE * np.abs(n).max())
                    for d, n in zip(device, naive))
        row = {
            "n_groups": layout.n_groups,
            "n_rows": layout.n_rows,
            "max_group": int(layout.sizes.max()),
            "bucket_widths": layout.widths,
            "ms_naive": round(times[0] * 1e3, 3),
            "ms_device": round(times[1] * 1e3, 3),
            "speedup": round(times[0] / times[1], 3),
            "max_abs_diff_grad": float(np.abs(naive[0] - device[0]).max()),
            "max_abs_diff_hess": float(np.abs(naive[1] - device[1]).max()),
            "agree_f32": bool(agree),
        }
        out["configs"][name] = row
        if verbose:
            print(f"  {name:14s} groups={row['n_groups']:<6d} "
                  f"rows={row['n_rows']:<7d} naive={row['ms_naive']:8.2f} ms  "
                  f"device={row['ms_device']:8.2f} ms  "
                  f"speedup={row['speedup']:6.2f}x  "
                  f"agree(f32)={row['agree_f32']}", flush=True)
    out["headline_speedup"] = max(
        c["speedup"] for c in out["configs"].values())
    out["all_agree_f32"] = all(
        c["agree_f32"] for c in out["configs"].values())
    return out


def run_smoke() -> dict:
    """Tiny pass over every shape — exercised inside tier-1 so the bench
    harness cannot rot between full runs."""
    return run(n_groups=40, reps=1, verbose=False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=1500)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="smoke sizes (40 groups)")
    ap.add_argument("--out", default="BENCH_rank.json")
    args = ap.parse_args()
    res = run_smoke() if args.quick else run(n_groups=args.groups,
                                             reps=args.reps)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"headline (device lambda pass vs per-group loop): "
          f"{res['headline_speedup']:.2f}x, agreement within float32: "
          f"{res['all_agree_f32']} -> {args.out}")


if __name__ == "__main__":
    from repro.jax_cache import configure_compile_cache
    configure_compile_cache()
    main()
