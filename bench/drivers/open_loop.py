"""Online serving: an open-loop schedule of requests through
``ForestServer.submit`` and ``pump``.

The schedule is fixed by the mix: ``rate_per_s`` x ``--seconds`` requests,
their row counts drawn from ``sizes`` and their gaps from an exponential
(Poisson arrivals), both from a fixed base seed so that every run seed gets
the same sizes and the same gaps in another order. Rows come from a seeded
pool of the configuration's data. Set-up draws the serving forest, starts
the server (which must put the pallas engine first), warms every padding
bucket a dispatch can reach and serves ``warm_requests`` requests.

The window runs the schedule: a request is submitted when it is due, the
server is pumped every ``flush_ms`` (and whenever ``max_batch`` rows wait),
and each result is claimed as soon as a pump resolves it. A request's
latency runs from its due time to its result in hand; a shed, timed-out or
failed request counts as missing (+inf). ``serve_p50_ms`` and
``serve_p99_ms`` are over all requests due in the window. How late the
generator ran is printed apart.

Correctness: after the window, every completed request's probabilities
against the plain reference's for the same rows.
"""
from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from harness import Check, Outcome, Window
import program
import tabular

_state: dict = {}
BASE_SEED = 20240601          # the schedule's fixed sizes and gaps
WARM_DEADLINE_S = 600.0


def schedule(p: dict, seconds: float, seed_rng: np.random.Generator):
    """(due offsets in s, row counts): fixed multisets, seed-permuted."""
    base = np.random.default_rng(BASE_SEED)
    n = int(round(float(p["rate_per_s"]) * seconds))
    kinds = base.choice(len(p["sizes"]), n,
                        p=[s["p"] for s in p["sizes"]])
    counts = np.array([base.integers(p["sizes"][k]["min"],
                                     p["sizes"][k]["max"] + 1)
                       for k in kinds])
    gaps = base.exponential(1.0 / float(p["rate_per_s"]), n)
    counts = counts[seed_rng.permutation(n)]
    gaps = gaps[seed_rng.permutation(n)]
    due = np.cumsum(gaps) - gaps[0]
    return due, counts


def requests(ctx):
    """(pool of rows, serving forest, requests, due offsets), all from the
    seed."""
    cfg, p = ctx.config, ctx.params
    pool = tabular.make_table(cfg["dataset"], int(cfg["pool_rows"]),
                              ctx.stream_seed(1))
    pool.pop(cfg["dataset"]["label"]["name"])
    forest = tabular.build_forest(cfg["dataset"], cfg["serving_forest"],
                                  ctx.stream_seed(2), pool)
    rng = ctx.rng(3)
    due, counts = schedule(p, ctx.seconds, rng)
    n_pool = len(next(iter(pool.values())))
    reqs = [{k: v[r] for k, v in pool.items()}
            for r in (rng.integers(0, n_pool, c) for c in counts)]
    return pool, forest, reqs, due


def setup(ctx):
    """The server, the window's requests and their schedule, all from the
    seed; every padding bucket a dispatch can reach is warmed through
    ``submit`` and ``pump``."""
    cfg, p = ctx.config, ctx.params
    pool, forest, reqs, due = requests(ctx)
    model = program.servable_model(cfg, forest)
    max_batch = int(p["max_batch"])
    srv = program.forest_server(model, deadline_s=float(p["deadline_ms"])
                                / 1e3, max_batch=max_batch,
                                engine=p.get("engine"))
    # the largest dispatch: a pump once max_batch rows wait, plus the
    # request that crossed it
    top = max_batch - 1 + max(s["max"] for s in p["sizes"])
    # warm-up requests get a deadline that a first dispatch's compile
    # cannot miss; the requests after the buckets settle the server's
    # estimate of its service time, which admission reads
    for b in warm_sizes(p, top):
        srv.result(srv.submit({k: v[:b] for k, v in pool.items()},
                              deadline_s=WARM_DEADLINE_S, pump=False))
    for i in range(int(p["warm_requests"])):
        srv.result(srv.submit(reqs[i % len(reqs)],
                              deadline_s=WARM_DEADLINE_S, pump=False))
    # what set-up built (the pool, the requests, the programs) leaves the
    # collector's view, so a collection in the window scans only what the
    # window allocates
    gc.collect()
    gc.freeze()
    return forest, srv, reqs, due


def warm_sizes(p: dict, top: int) -> list:
    """Row counts that reach every padding bucket up to the one ``top``
    rows need, whatever the server's ladder: all of them."""
    return list(range(1, top + 1))


def run(ctx) -> Outcome:
    forest, srv, reqs, due = setup(ctx)
    out = window(ctx, srv, reqs, due)
    _state.update(forest=forest, reqs=reqs, probs=out.layer.pop("probs"),
                  done=out.layer.pop("done"))
    return out


def window(ctx, srv, reqs, due) -> Outcome:
    """Serve ``reqs`` on their schedule ``due`` (offsets from the window's
    start) and time them."""
    p = ctx.params
    srv.metrics = type(srv.metrics)()   # count the window only
    errors = program.request_errors()
    n = len(reqs)
    rows_of = [len(next(iter(r.values()))) for r in reqs]
    lat = np.full(n, np.inf)
    late = np.zeros(n)
    # each answer is copied into one array allocated here, so the window
    # holds no object per request
    start = np.concatenate([[0], np.cumsum(rows_of)])
    probs = np.zeros((start[-1], 2), np.float32)
    done = np.zeros(n, bool)
    ticket_of: dict = {}
    flush = float(p["flush_ms"]) / 1e3
    max_batch = int(p["max_batch"])
    win = Window(ctx)
    t0 = win.open()
    due_abs = t0 + due
    i = pending = 0
    last_pump = t0

    def claim(resolved):
        for t in resolved:
            j = ticket_of.pop(t, None)
            if j is None:
                continue
            try:
                answer = srv.result(t)
                lat[j] = time.perf_counter() - due_abs[j]
                probs[start[j]:start[j + 1]] = answer
                done[j] = True
            except errors:
                pass

    while i < n or ticket_of:
        now = time.perf_counter()
        while i < n and due_abs[i] <= now and pending < max_batch:
            late[i] = now - due_abs[i]
            try:
                with ctx.span("bench/submit"):
                    ticket_of[srv.submit(reqs[i], pump=False)] = i
                pending += rows_of[i]
            except errors:
                pass
            i += 1
            now = time.perf_counter()
        if pending and (now - last_pump >= flush or pending >= max_batch
                        or i >= n):
            with ctx.span("bench/pump"):
                resolved = srv.pump()
            pending = 0
            claim(resolved)
            last_pump = time.perf_counter()
            continue
        nxt = min(due_abs[i] if i < n else np.inf, last_pump + flush)
        wait = nxt - time.perf_counter()
        if wait > 0:
            time.sleep(min(wait, 1e-3))
    win.close()
    program.check_server_engines(srv, p.get("engine"))
    m = srv.metrics
    missing = int(np.isinf(lat).sum())
    p50, p99 = (float(np.percentile(lat, q)) * 1e3 for q in (50, 99))
    notes = [f"generator lateness ms: p50 {float(np.percentile(late, 50)) * 1e3!r}"
             f" p99 {float(np.percentile(late, 99)) * 1e3!r} max "
             f"{float(late.max()) * 1e3!r}; requests {n}, missing {missing} "
             f"(shed {m.shed}, timed out {m.timed_out}, failed {m.failed})"]
    return Outcome(
        setup_s=t0 - ctx.t_start, window=win,
        metrics={"serve_p50_ms": _finite(p50), "serve_p99_ms": _finite(p99)},
        checks=[], attempted=n, failed=missing,
        layer={"rows_dispatched": m.rows_dispatched,
               "rows_padded": m.rows_padded, "dispatches": m.dispatches,
               "requests": n, "late_p99_ms": float(np.percentile(late, 99))
               * 1e3, "shed": m.shed, "timed_out": m.timed_out,
               "probs": probs, "done": done},
        notes=notes)


def _finite(ms: float) -> float:
    """A percentile that lands on a missing request (+inf) is printed as
    1e7 ms: finite for the result line, and past any bound."""
    return ms if np.isfinite(ms) else 1e7


def joined(reqs, pick) -> dict:
    """The rows of requests ``pick``, one table."""
    return {k: np.concatenate([reqs[j][k] for j in pick])
            for k in reqs[pick[0]]}


def compare(ctx, out: Outcome) -> None:
    from bulk_score import reference_probs
    ref = importlib.import_module(ctx.config["reference"])
    st = {k: _state.pop(k) for k in ("forest", "reqs", "probs", "done")}
    pick = np.flatnonzero(st["done"])
    gap = np.inf
    if len(pick):
        table = joined(st["reqs"], pick)
        n = len(next(iter(table.values())))
        want = reference_probs(ref, ctx.config, st["forest"], table,
                               np.arange(n))
        rows = np.repeat(st["done"], [len(next(iter(r.values())))
                                      for r in st["reqs"]])
        got = st["probs"][rows, 1]
        gap = float(np.max(np.abs(got.astype(np.float64) - want)))
    out.checks.append(Check("prob_gap", gap, ctx.limits["prob_gap"]))
    out.notes.append(f"compared all {len(pick)} completed requests")
