#!/usr/bin/env python3
"""The one-time rate sweep of an online cell: the highest offered rate at
which the server keeps its 99th-percentile latency within the deadline,
sheds nothing, times nothing out, and keeps up: the generator's 99th
percentile lateness stays under a fifth of the deadline, so no backlog
grows through the window.

    python3 bench/knee.py --workload adult_gbt.online --seed 1 \\
        --seconds 6 --rates 500,1000,2000

It builds the cell's server once, as a run's set-up does, then offers each
rate for ``--seconds`` through the cell's own window loop and prints one
JSON line per rate. The knee it finds is written into the cell's file by
hand, with the offered rate at four fifths of it; runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (puts bench/lib, drivers and configs on the path)
from harness import Ctx  # noqa: E402

LATE_SHARE = 0.2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    import program
    program.configure_compile_cache()
    run.check_device(int(spec["entry"]["chips"]))
    import open_loop
    params = {**spec["mix"].get("params", {}),
              **spec["cell"].get("params", {})}
    ctx = Ctx(cell=args.workload, config=spec["config"], params=params,
              limits={}, seed=args.seed, seconds=args.seconds, trace=False,
              t_start=0.0)
    _, srv, _, _ = open_loop.setup(ctx)
    deadline_ms = float(params["deadline_ms"])
    for rate in (float(r) for r in args.rates.split(",")):
        ctx.params = {**params, "rate_per_s": rate}
        _, _, reqs, due = open_loop.requests(ctx)
        out = open_loop.window(ctx, srv, reqs, due)
        lay = out.layer
        ok = (out.metrics["serve_p99_ms"] <= deadline_ms
              and out.failed == 0
              and lay["late_p99_ms"] <= LATE_SHARE * deadline_ms)
        print(json.dumps({"rate_per_s": rate, **out.metrics,
                          "missing": out.failed, "shed": lay["shed"],
                          "timed_out": lay["timed_out"],
                          "late_p99_ms": lay["late_p99_ms"],
                          "rows_per_dispatch": lay["rows_dispatched"]
                          / max(1, lay["dispatches"]),
                          "sustained": ok}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
