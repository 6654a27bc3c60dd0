"""Sustained-throughput serving benchmark (DESIGN.md §9.6): p50/p99 request
latency vs offered QPS through the fault-tolerant front-end, clean and
fault-injected. Writes BENCH_serve.json.

Protocol (the measuring stick is "A Comparison of Decision Forest Inference
Platforms from A Database Perspective": report latency percentiles under
offered load, not just best-case throughput):

* OPEN-LOOP arrival: requests arrive on a fixed schedule (``i / qps``),
  whether or not the server keeps up — so overload shows up as queue depth,
  sheds and deadline misses instead of silently slowing the generator.
* Each request is a small row batch with a deadline; the server micro-
  batches pending requests into padded bucket dispatches on a fixed flush
  interval (and on max_batch pressure).
* The ``faults`` mode replays a SEEDED FaultPlan on the primary engine
  (transient errors, poisoned outputs, latency spikes): the same schedule
  every run. The server must degrade loudly — shed/timeout/fail counters —
  while every ACCEPTED-and-completed request stays bit-identical to a
  direct clean-bundle call (checked on a sample every run).

Usage: python benchmarks/serve_bench.py [--duration S] [--qps q1 q2 ...]
       [--out PATH] [--quick]
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np

from repro.core import GradientBoostedTreesLearner
from repro.data.tabular import adult_like, train_test_split
from repro.serving.faults import FaultPlan
from repro.serving.server import ForestServer, RequestShed, RetryPolicy

DEFAULT_QPS = (250, 1000, 4000)
FAULT_PLAN = dict(transient_rate=0.03, poison_rate=0.01,
                  latency_rate=0.02, latency_s=0.004)


def _drive(model, requests, clean_ref, qps: float, duration_s: float,
           deadline_s: float, fault_seed: int | None,
           flush_interval_s: float = 0.002, equiv_sample: int = 50) -> dict:
    """One sustained-load run at ``qps``; returns the metrics row."""
    srv = ForestServer(model, buckets=(32, 128, 512),
                       default_deadline_s=deadline_s,
                       max_batch=512, max_results=1 << 20,
                       retry=RetryPolicy(max_attempts=3, base_s=5e-4, seed=3),
                       failure_threshold=4, cooldown_s=0.05, warmup=True)
    if fault_seed is not None:
        srv.inject_faults(FaultPlan(seed=fault_seed, **FAULT_PLAN))
    n_req = max(1, int(qps * duration_s))
    tickets: dict[int, int] = {}        # ticket -> request index
    equiv_checked = equiv_ok = 0
    t0 = time.perf_counter()
    last_pump = t0

    def _claim(resolved):
        nonlocal equiv_checked, equiv_ok
        for t in resolved:
            i = tickets.pop(t, None)
            if i is None:
                continue
            try:
                out = srv.result(t)
            except Exception:
                continue                 # typed shed/timeout/fail: counted
            if equiv_checked < equiv_sample:
                equiv_checked += 1
                equiv_ok += int(np.array_equal(out, clean_ref[i]))

    for i in range(n_req):
        t_arr = t0 + i / qps
        now = time.perf_counter()
        if now < t_arr:
            time.sleep(t_arr - now)
        try:
            t = srv.submit(requests[i % len(requests)], pump=False)
            tickets[t] = i % len(requests)
        except RequestShed:
            pass
        now = time.perf_counter()
        if now - last_pump >= flush_interval_s \
                or srv._state(None).pending_rows() >= srv.max_batch:
            _claim(srv.pump())
            last_pump = time.perf_counter()
    _claim(srv.pump())
    wall = time.perf_counter() - t0
    m = srv.metrics.to_dict()
    return {
        "offered_qps": qps,
        "achieved_qps": round(m["submitted"] / wall, 1),
        "completed_qps": round(m["completed"] / wall, 1),
        "wall_s": round(wall, 3),
        "p50_ms": m["latency"]["p50_ms"],
        "p99_ms": m["latency"]["p99_ms"],
        "counters": {k: m[k] for k in (
            "submitted", "accepted", "shed", "timed_out", "completed",
            "failed", "retries", "fallback_dispatches", "poisoned_rejected",
            "circuit_opens", "circuit_closes", "dispatches",
            "rows_dispatched", "rows_padded")},
        "engine_dispatches": m["engine_dispatches"],
        "padding_by_bucket": m["padding_by_bucket"],
        # §13.4 survivorship fix: headline p50/p99 covers COMPLETED
        # requests only; shed/timed-out sojourn times are separate series
        "latency_by_outcome": m["latency_by_outcome"],
        "equiv_checked": equiv_checked,
        "equiv_ok": equiv_ok,
    }


def run(qps_levels=DEFAULT_QPS, duration_s: float = 2.0,
        rows_per_request: int = 4, num_trees: int = 20,
        deadline_ms: float = 50.0, fault_seed: int = 7,
        verbose: bool = True, out_path: str | None = None) -> dict:
    import jax
    train, test = train_test_split(adult_like(3000), 0.3, 1)
    model = GradientBoostedTreesLearner(
        label="income", num_trees=num_trees).train(train)
    feats = {k: v for k, v in test.items() if k != "income"}
    n_test = len(next(iter(feats.values())))
    requests = [{k: v[i:i + rows_per_request] for k, v in feats.items()}
                for i in range(0, n_test - rows_per_request,
                               rows_per_request)]
    # the clean reference: direct bundle calls, no server, no faults
    clean_ref = [model.predict(r) for r in requests]

    res: dict = {
        "benchmark": "serve_bench",
        "host": {"platform": platform.platform(), "numpy": np.__version__,
                 "jax_backend": jax.default_backend()},
        "num_trees": num_trees,
        "rows_per_request": rows_per_request,
        "duration_s": duration_s,
        "deadline_ms": deadline_ms,
        "fault_plan": {"seed": fault_seed, **FAULT_PLAN},
        "levels": {},
    }
    for qps in qps_levels:
        row = {}
        for mode, seed in (("clean", None), ("faults", fault_seed)):
            r = _drive(model, requests, clean_ref, qps, duration_s,
                       deadline_ms / 1e3, seed)
            row[mode] = r
            if verbose:
                c = r["counters"]
                print(f"  {qps:>6.0f} qps [{mode:6s}] p50={r['p50_ms']} ms "
                      f"p99={r['p99_ms']} ms  completed={c['completed']} "
                      f"shed={c['shed']} timed_out={c['timed_out']} "
                      f"failed={c['failed']} retries={c['retries']} "
                      f"fallback={c['fallback_dispatches']} "
                      f"equiv={r['equiv_ok']}/{r['equiv_checked']}",
                      flush=True)
                lo = r["latency_by_outcome"]
                if lo["timed_out"]["n"] or lo["shed"]["n"]:
                    print("           note: headline p50/p99 covers "
                          "completed requests only (survivorship); "
                          f"timed_out p99={lo['timed_out']['p99_ms']} ms "
                          f"(n={lo['timed_out']['n']}), shed est "
                          f"p50={lo['shed']['p50_ms']} ms "
                          f"(n={lo['shed']['n']})", flush=True)
            assert r["equiv_ok"] == r["equiv_checked"], \
                "accepted requests must be bit-identical to clean predictions"
        res["levels"][str(int(qps))] = row
    if out_path:
        with open(out_path, "w") as f:
            json.dump(res, f, indent=1)
        if verbose:
            print(f"wrote {out_path}")
    return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--qps", type=float, nargs="*", default=list(DEFAULT_QPS))
    ap.add_argument("--duration", type=float, default=2.0)
    ap.add_argument("--trees", type=int, default=20)
    ap.add_argument("--quick", action="store_true",
                    help="short sweep for benchmarks/run.py")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    duration = 0.5 if args.quick else args.duration
    run(qps_levels=tuple(args.qps), duration_s=duration,
        num_trees=args.trees, out_path=args.out)


if __name__ == "__main__":
    from repro.jax_cache import configure_compile_cache
    configure_compile_cache()
    main()
