"""The fault-tolerant serving front-end (DESIGN.md §9): admission control,
deadlines, retry, engine degradation, circuit breaking, multi-model routing
— every failure path driven DETERMINISTICALLY by the fault harness
(serving/faults.py) on a virtual clock. No wall-clock sleeps, no flaky
timing: same seeds, same faults, same transitions, every run."""
import asyncio

import numpy as np
import pytest

from repro.core import (
    EngineFailure,
    GradientBoostedTreesLearner,
    RandomForestLearner,
    Task,
    YdfError,
)
from repro.data.tabular import adult_like, train_test_split
from repro.serving.faults import POISON, FakeClock, FaultPlan, FaultyPredictor
from repro.serving.server import (
    AsyncForestServer,
    CircuitBreaker,
    ForestServer,
    RequestFailed,
    RequestShed,
    RequestTimedOut,
    RetryPolicy,
)


@pytest.fixture(scope="module")
def trained():
    train, test = train_test_split(adult_like(900), 0.3, 1)
    gbt = GradientBoostedTreesLearner(label="income", num_trees=6).train(train)
    feats = {k: v for k, v in test.items() if k != "income"}
    return gbt, feats


def make_server(model, clock, **kw):
    kw.setdefault("buckets", (16, 64))
    kw.setdefault("failure_threshold", 2)
    kw.setdefault("cooldown_s", 1.0)
    kw.setdefault("retry", RetryPolicy(max_attempts=2, base_s=0.01, seed=5))
    return ForestServer(model, clock=clock.now, sleep=clock.sleep, **kw)


def req_slice(feats, lo, n=8):
    return {k: v[lo:lo + n] for k, v in feats.items()}


# ------------------------------------------------------------- fault harness

def test_fake_clock_and_fault_plan_are_deterministic():
    clk = FakeClock()
    clk.sleep(0.25)
    clk.advance(0.75)
    assert clk.now() == 1.0
    with pytest.raises(ValueError):
        clk.advance(-1)
    a = FaultPlan(seed=3, transient_rate=0.3, poison_rate=0.2,
                  latency_rate=0.5, latency_s=0.01)
    b = FaultPlan(seed=3, transient_rate=0.3, poison_rate=0.2,
                  latency_rate=0.5, latency_s=0.01)
    rolls = [(a.is_transient(i), a.is_poisoned(i), a.latency_for(i))
             for i in range(200)]
    assert rolls == [(b.is_transient(i), b.is_poisoned(i), b.latency_for(i))
                     for i in range(200)]
    assert any(r[0] for r in rolls) and any(r[1] for r in rolls)
    # a different seed gives a different schedule
    c = FaultPlan(seed=4, transient_rate=0.3)
    assert [a.is_transient(i) for i in range(200)] != \
        [c.is_transient(i) for i in range(200)]
    # explicit schedules
    p = FaultPlan(transient_calls=(2,), poison_calls=(3,),
                  latency_calls={1: 0.5}, dead_from=5, dead_until=7)
    assert not p.is_transient(0) and p.is_transient(2)
    assert p.latency_for(1) == 0.5 and p.latency_for(0) == 0.0
    assert [p.is_dead(i) for i in range(4, 8)] == [False, True, True, False]


def test_faulty_predictor_replays_plan(trained):
    gbt, feats = trained
    clk = FakeClock()
    w = FaultyPredictor(gbt.predictor(), FaultPlan(
        transient_calls=(0,), poison_calls=(2,), latency_calls={1: 0.3},
        dead_from=3, dead_until=4), advance=clk.advance)
    X = w.encode(req_slice(feats, 0))
    with pytest.raises(EngineFailure) as e:
        w.predict_encoded(X)                       # call 0: transient
    assert e.value.transient and e.value.engine == w.name
    out = w.predict_encoded(X)                     # call 1: latency, clean
    assert clk.now() == 0.3
    np.testing.assert_array_equal(out, gbt.predict(req_slice(feats, 0)))
    poisoned = w.predict_encoded(X)                # call 2: poisoned, no raise
    assert np.isnan(poisoned).all() and np.isnan(POISON)
    with pytest.raises(EngineFailure) as e:
        w.predict_encoded(X)                       # call 3: sticky death
    assert not e.value.transient
    w.predict_encoded(X)                           # call 4: revived
    assert w.counts == {"latency": 1, "dead": 1, "transient": 1,
                        "poison": 1, "clean": 2}


def test_compiled_predictor_surfaces_typed_engine_failure(trained):
    gbt, feats = trained
    pred = gbt.predictor()
    X = pred.encode(req_slice(feats, 0))
    bad = type(pred)(engine=type(pred.engine)(
        "vectorized", lambda _: (_ for _ in ()).throw(RuntimeError("boom"))),
        encoder=pred.encoder, finalize=pred.finalize)
    with pytest.raises(EngineFailure, match="vectorized.*boom"):
        bad.predict_encoded(X)


# ------------------------------------------------------------ circuit breaker

def test_circuit_breaker_state_machine():
    br = CircuitBreaker(failure_threshold=2, cooldown_s=1.0)
    assert br.allow(0.0) and br.state == "closed"
    assert not br.record_failure(0.0)
    assert br.record_success() is False            # still closed: no close event
    assert not br.record_failure(1.0)              # consecutive count was reset
    assert br.record_failure(2.0)                  # threshold -> OPEN
    assert br.state == "open" and not br.allow(2.5)
    assert br.allow(3.0) and br.state == "half_open"
    assert br.record_failure(3.0)                  # failed probe -> re-OPEN
    assert br.state == "open"
    assert br.allow(4.0)                           # next probe
    assert br.record_success() and br.state == "closed"


# --------------------------------------------------------------- clean paths

def test_clean_requests_match_direct_predictions(trained):
    gbt, feats = trained
    srv = make_server(gbt, FakeClock())
    for lo in (0, 8, 16):
        out = srv.predict(req_slice(feats, lo))
        np.testing.assert_array_equal(out, gbt.predict(req_slice(feats, lo)))
    m = srv.metrics
    assert m.submitted == m.accepted == m.completed == 3
    assert m.shed == m.timed_out == m.failed == 0
    assert m.engine_dispatches == {"vectorized": 3}


def test_requests_micro_batch_into_one_dispatch(trained):
    gbt, feats = trained
    srv = make_server(gbt, FakeClock())
    tickets = [srv.submit(req_slice(feats, lo), pump=False)
               for lo in (0, 8, 16)]
    assert srv.metrics.dispatches == 0
    srv.pump()
    assert srv.metrics.dispatches == 1
    assert srv.metrics.rows_padded == 64 - 24      # one bucket-64 dispatch
    for t, lo in zip(tickets, (0, 8, 16)):
        np.testing.assert_array_equal(
            srv.result(t), gbt.predict(req_slice(feats, lo)))


def test_result_ticket_validation(trained):
    gbt, feats = trained
    srv = make_server(gbt, FakeClock())
    t = srv.submit(req_slice(feats, 0), pump=False)
    with pytest.raises(KeyError):
        srv.result(999)                            # never issued
    assert srv.metrics.dispatches == 0             # and nothing was flushed
    srv.result(t)
    with pytest.raises(KeyError):
        srv.result(t)                              # already claimed


# ---------------------------------------------------- admission + deadlines

def test_admission_sheds_unmeetable_deadlines(trained):
    gbt, feats = trained
    clk = FakeClock()
    srv = make_server(gbt, clk)
    # teach the EWMA a real service rate: 0.16 s per bucket-16 dispatch
    srv.inject_faults(FaultPlan(latency_calls={0: 0.16}))
    srv.predict(req_slice(feats, 0))
    assert srv._state(None).ewma_row_s == pytest.approx(0.01)
    backlog = srv.submit(req_slice(feats, 8), deadline_s=10.0, pump=False)
    with pytest.raises(RequestShed, match="cannot be met"):
        srv.submit(req_slice(feats, 16), deadline_s=0.01, pump=False)
    assert srv.metrics.shed == 1
    # a meetable deadline is still admitted, and the backlog is unharmed
    ok = srv.submit(req_slice(feats, 16), deadline_s=10.0, pump=False)
    srv.pump()
    np.testing.assert_array_equal(srv.result(backlog),
                                  gbt.predict(req_slice(feats, 8)))
    np.testing.assert_array_equal(srv.result(ok),
                                  gbt.predict(req_slice(feats, 16)))


def test_admission_sheds_on_full_queue(trained):
    gbt, feats = trained
    srv = make_server(gbt, FakeClock(), max_queue_rows=20)
    srv.submit(req_slice(feats, 0, 16), pump=False)
    with pytest.raises(RequestShed, match="queue full"):
        srv.submit(req_slice(feats, 16, 8), pump=False)
    assert srv.metrics.shed == 1


def test_timeout_while_queued_skips_dispatch(trained):
    gbt, feats = trained
    clk = FakeClock()
    srv = make_server(gbt, clk)
    t = srv.submit(req_slice(feats, 0), deadline_s=0.5, pump=False)
    clk.advance(1.0)                               # deadline passes in queue
    before = srv.metrics.dispatches
    srv.pump()
    assert srv.metrics.dispatches == before        # no compute for the dead
    with pytest.raises(RequestTimedOut, match="while queued"):
        srv.result(t)
    assert srv.metrics.timed_out == 1


def test_timeout_during_dispatch_discards_late_result(trained):
    gbt, feats = trained
    clk = FakeClock()
    srv = make_server(gbt, clk)
    srv.inject_faults(FaultPlan(latency_calls={0: 0.5}))
    t = srv.submit(req_slice(feats, 0), deadline_s=0.1, pump=False)
    srv.pump()
    with pytest.raises(RequestTimedOut, match="late result discarded"):
        srv.result(t)
    assert srv.metrics.timed_out == 1 and srv.metrics.completed == 0


# ------------------------------------------------- retry / fallback / breaker

def test_transient_failure_retries_with_seeded_backoff(trained):
    gbt, feats = trained
    clk = FakeClock()
    srv = make_server(gbt, clk)
    w = srv.inject_faults(FaultPlan(transient_calls=(0,)))
    t0 = clk.now()
    out = srv.predict(req_slice(feats, 0))
    np.testing.assert_array_equal(out, gbt.predict(req_slice(feats, 0)))
    assert srv.metrics.retries == 1 and w.counts["transient"] == 1
    # the backoff slept the DETERMINISTIC seeded-jitter delay on our clock
    expected = srv.retry.delay(0, 0)
    assert clk.now() - t0 == pytest.approx(expected)
    assert srv.retry.base_s <= expected <= srv.retry.base_s * 1.5
    # same policy, same counters -> same delay (determinism), jitter varies
    assert RetryPolicy(seed=5).delay(0, 0) == RetryPolicy(seed=5).delay(0, 0)
    assert RetryPolicy(seed=5).delay(0, 0) != RetryPolicy(seed=5).delay(1, 0)


def test_transients_exhaust_retries_then_fall_back(trained):
    gbt, feats = trained
    srv = make_server(gbt, FakeClock())
    w = srv.inject_faults(FaultPlan(transient_calls=(0, 1, 2, 3)))
    out = srv.predict(req_slice(feats, 0))         # 2 attempts, both transient
    np.testing.assert_array_equal(out, gbt.predict(req_slice(feats, 0)))
    assert w.counts["transient"] == 2              # max_attempts on primary
    assert srv.metrics.fallback_dispatches == 1
    # the next chain level takes the dispatch (small CPU model: vectorized
    # primary, the §10 bucketed engine behind it, naive last)
    assert srv.metrics.engine_dispatches.get("bucketed") == 1


def test_sticky_death_opens_circuit_probes_restore(trained):
    gbt, feats = trained
    clk = FakeClock()
    srv = make_server(gbt, clk)
    clean = gbt.predict(req_slice(feats, 0))
    # dead for calls 0..2: two failures open the circuit; the first
    # half-open probe (call 2) fails and re-opens; the second succeeds
    w = srv.inject_faults(FaultPlan(dead_from=0, dead_until=3))
    for _ in range(2):
        np.testing.assert_array_equal(srv.predict(req_slice(feats, 0)), clean)
    assert srv.engine_status()[0]["circuit"] == "open"
    assert srv.metrics.circuit_opens == 1
    # while open the primary is never touched
    frozen = w.calls
    np.testing.assert_array_equal(srv.predict(req_slice(feats, 0)), clean)
    assert w.calls == frozen
    # cooldown -> half-open probe; still dead -> re-open
    clk.advance(1.5)
    np.testing.assert_array_equal(srv.predict(req_slice(feats, 0)), clean)
    assert srv.engine_status()[0]["circuit"] == "open"
    assert srv.metrics.circuit_opens == 2 and w.counts["dead"] == 3
    # cooldown -> probe hits the revived engine -> circuit closes
    clk.advance(1.5)
    np.testing.assert_array_equal(srv.predict(req_slice(feats, 0)), clean)
    assert srv.engine_status()[0]["circuit"] == "closed"
    assert srv.metrics.circuit_closes == 1
    # and stays closed: the next dispatch is primary again, no fallback
    fb = srv.metrics.fallback_dispatches
    np.testing.assert_array_equal(srv.predict(req_slice(feats, 0)), clean)
    assert srv.metrics.fallback_dispatches == fb
    assert w.counts["clean"] == 2


def test_poisoned_outputs_never_escape(trained):
    gbt, feats = trained
    srv = make_server(gbt, FakeClock())
    srv.inject_faults(FaultPlan(poison_calls=(0, 1)))
    out = srv.predict(req_slice(feats, 0))         # poisoned twice -> fallback
    np.testing.assert_array_equal(out, gbt.predict(req_slice(feats, 0)))
    assert np.isfinite(out).all()
    assert srv.metrics.poisoned_rejected == 2
    assert srv.metrics.fallback_dispatches == 1


def test_all_engines_down_fails_loudly(trained):
    gbt, feats = trained
    srv = make_server(gbt, FakeClock(), engines=["vectorized"],
                      failure_threshold=100)
    srv.inject_faults(FaultPlan(dead_from=0))
    t = srv.submit(req_slice(feats, 0), pump=False)
    srv.pump()
    with pytest.raises(RequestFailed, match="all engines failed"):
        srv.result(t)
    assert srv.metrics.failed == 1 and srv.metrics.completed == 0


def test_unknown_model_and_unknown_engine_raise(trained):
    gbt, feats = trained
    srv = make_server(gbt, FakeClock())
    with pytest.raises(YdfError, match="Unknown model"):
        srv.submit(req_slice(feats, 0), model="nope")
    with pytest.raises(YdfError):
        ForestServer(gbt, engines=["warp_drive"]).predict(req_slice(feats, 0))


# ----------------------------------------- equivalence under degradation

LEARNERS = {
    "rf": lambda label, task: RandomForestLearner(
        label=label, task=task, num_trees=4, max_depth=6, seed=3),
    "gbt": lambda label, task: GradientBoostedTreesLearner(
        label=label, task=task, num_trees=4, seed=3),
}


@pytest.mark.parametrize("learner", ["rf", "gbt"])
@pytest.mark.parametrize("task", [Task.CLASSIFICATION, Task.REGRESSION])
def test_accepted_requests_bit_identical_under_faults(learner, task):
    """The §9 contract: with faults hammering the primary engine, every
    ACCEPTED request's prediction is bit-identical to a clean direct call —
    degradation changes latency and counters, never bits."""
    label = "income" if task == Task.CLASSIFICATION else "age"
    train, test = train_test_split(adult_like(700), 0.3, 1)
    model = LEARNERS[learner](label, task).train(train)
    requests = [{k: v[lo:lo + 6] for k, v in test.items() if k != label}
                for lo in range(0, 120, 6)]
    clean = [model.predict(r) for r in requests]
    clk = FakeClock()
    srv = make_server(model, clk)
    w = srv.inject_faults(FaultPlan(
        seed=11, transient_rate=0.25, poison_rate=0.15,
        latency_rate=0.1, latency_s=0.01, dead_from=6, dead_until=9))
    served = failed = 0
    for r, want in zip(requests, clean):
        clk.advance(2.0)      # roll cooldowns so probes fire along the way
        try:
            out = srv.predict(r)
        except YdfError:
            failed += 1       # loud typed failure: acceptable, silent is not
            continue
        served += 1
        np.testing.assert_array_equal(out, want)
    assert served >= 15       # the chain kept almost everything alive
    assert sum(w.counts[k] for k in ("transient", "poison", "dead")) >= 5
    assert srv.metrics.completed == served and srv.metrics.failed == failed


# ------------------------------------------------------- routing + metrics

def test_multi_model_routing(trained):
    gbt, feats = trained
    train, test = train_test_split(adult_like(700), 0.3, 1)
    reg = RandomForestLearner(label="age", task=Task.REGRESSION, num_trees=3,
                              max_depth=5).train(train)
    srv = ForestServer({"income": gbt, "age": reg}, clock=FakeClock().now,
                       sleep=lambda _: None)
    r1 = req_slice(feats, 0)
    r2 = {k: v[:8] for k, v in test.items() if k != "age"}
    np.testing.assert_array_equal(srv.predict(r1, model="income"),
                                  gbt.predict(r1))
    np.testing.assert_array_equal(srv.predict(r2, model="age"),
                                  reg.predict(r2))
    assert sorted(srv.models()) == ["age", "income"]
    # default model = first routed
    np.testing.assert_array_equal(srv.predict(r1), gbt.predict(r1))


def test_metrics_surface(trained):
    gbt, feats = trained
    clk = FakeClock()
    srv = make_server(gbt, clk)
    srv.inject_faults(FaultPlan(latency_calls={0: 0.010, 1: 0.200}))
    srv.predict(req_slice(feats, 0))
    srv.predict(req_slice(feats, 8))
    d = srv.metrics.to_dict()
    assert d["latency"]["n"] == 2
    assert d["latency"]["p50_ms"] == pytest.approx(105.0, abs=1.0)
    assert d["latency"]["p99_ms"] <= 200.0
    assert d["padding_by_bucket"]["16"] == {"dispatches": 2, "pad_rows": 16}
    text = srv.metrics.summary()
    assert "p50" in text and "bucket" in text and "completed=2" in text
    # the latency reservoir is bounded (soak-memory contract, §9.4)
    m = srv.metrics
    m.max_latency_samples = 64
    for _ in range(500):
        m.observe_latency(0.001)
    assert len(m._latencies) <= 64


def test_queue_wait_and_server_spans(trained):
    """Each dispatched request's queue wait is its submit-to-dispatch time,
    exact under the fake clock; while tracing, the spans of a request
    carry its ticket."""
    from repro.obs import trace

    gbt, feats = trained
    clk = FakeClock()
    srv = make_server(gbt, clk)
    with trace.capture(clock=clk.now) as tr:
        a = srv.submit(req_slice(feats, 0), pump=False)
        clk.advance(0.003)
        b = srv.submit(req_slice(feats, 8), pump=False)
        clk.advance(0.002)
        srv.pump()
    wait = srv.metrics.queue_wait
    assert wait.count == 2
    assert sorted(wait.values) == pytest.approx([0.002, 0.005])
    submits = [r for r in tr.roots if r.name == "server/submit"]
    assert [s.args["ticket"] for s in submits] == [a, b]
    (pump,) = [r for r in tr.roots if r.name == "server/pump"]
    assert pump.t0 == pytest.approx(0.005)
    (disp,) = [c for c in pump.children if c.name == "server/dispatch"]
    assert disp.args == {"rows": 16, "padded": 16, "tickets": [a, b]}
    # a shed request's span carries the error, and waits in no queue
    srv2 = make_server(gbt, clk, max_queue_rows=4)
    with trace.capture(clock=clk.now) as tr2:
        with pytest.raises(RequestShed):
            srv2.submit(req_slice(feats, 0))
    (shed,) = tr2.roots
    assert shed.name == "server/submit"
    assert shed.args == {"error": "RequestShed"}
    assert srv2.metrics.queue_wait.count == 0
    # operators read the waits in to_dict() and summary()
    qw = srv.metrics.to_dict()["queue_wait"]
    assert qw["n"] == 2
    assert qw["p50_ms"] == pytest.approx(3.5)
    assert "queue    : wait p50=3.500 ms" in srv.metrics.summary()


# ------------------------------------------------------------ async front-end

def test_async_front_end_micro_batches_and_sheds(trained):
    gbt, feats = trained
    srv = ForestServer(gbt, buckets=(16, 64), max_queue_rows=40)

    async def fan_in():
        async with AsyncForestServer(srv, flush_interval_s=0.001) as a:
            jobs = [a.predict(req_slice(feats, lo))
                    for lo in range(0, 80, 8)]     # 10 x 8 rows > queue cap
            return await asyncio.gather(*jobs, return_exceptions=True)

    results = asyncio.run(fan_in())
    ok = [r for r in results if isinstance(r, np.ndarray)]
    shed = [r for r in results if isinstance(r, RequestShed)]
    assert len(ok) == 5 and len(shed) == 5         # cap admits exactly 40 rows
    for lo, r in zip(range(0, 80, 8), results):
        if isinstance(r, np.ndarray):
            np.testing.assert_array_equal(r, gbt.predict(req_slice(feats, lo)))
    assert srv.metrics.shed == 5 and srv.metrics.completed == 5


# ------------------------------------------------------------------ CLI smoke

def test_cli_serve_smoke(trained, tmp_path, capsys):
    from repro.cli import main
    from repro.data.io import read_dataset, write_dataset
    gbt, feats = trained
    mdir = str(tmp_path / "model")
    gbt.save(mdir)
    csv = "csv:" + str(tmp_path / "req.csv")
    write_dataset({k: v[:40] for k, v in feats.items()}, csv)
    out_csv = "csv:" + str(tmp_path / "preds.csv")
    main(["serve", "--dataset", csv, "--model", mdir, "--request-rows", "8",
          "--deadline-ms", "5000", "--output", out_csv])
    text = capsys.readouterr().out
    assert "engine chain" in text and "shed=0" in text and "p50" in text
    preds = read_dataset(out_csv)
    want = gbt.predict({k: v[:40] for k, v in feats.items()})
    got = np.stack([preds[f"p_{c}"].astype(np.float32)
                    for c in gbt.classes], 1)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_serve_bench_smoke():
    from benchmarks import serve_bench
    res = serve_bench.run(qps_levels=(400,), duration_s=0.25, num_trees=3,
                          verbose=False)
    lvl = res["levels"]["400"]
    for mode in ("clean", "faults"):
        r = lvl[mode]
        assert r["counters"]["submitted"] > 0
        assert r["equiv_ok"] == r["equiv_checked"] > 0
        assert r["p50_ms"] is not None and r["p99_ms"] is not None
    assert res["benchmark"] == "serve_bench"


# ------------------------------------------------------------------ soak

@pytest.mark.slow
def test_soak_mixed_traffic_no_lost_tickets(trained):
    """Sustained mixed traffic + faults on the virtual clock: every accepted
    ticket resolves EXACTLY once (result or typed error), accounting adds
    up, and server memory stays bounded."""
    gbt, feats = trained
    clk = FakeClock()
    srv = make_server(gbt, clk, max_results=64, max_queue_rows=256,
                      default_deadline_s=0.5)
    srv.inject_faults(FaultPlan(
        seed=2, transient_rate=0.1, poison_rate=0.05,
        latency_rate=0.15, latency_s=0.05, dead_from=40, dead_until=48))
    rng = np.random.default_rng(0)
    n_feat_rows = len(next(iter(feats.values())))
    outcomes = {"ok": 0, "shed": 0, "timeout": 0, "failed": 0}
    open_tickets = []
    for step in range(400):
        lo = int(rng.integers(0, n_feat_rows - 8))
        try:
            t = srv.submit(req_slice(feats, lo, int(rng.integers(1, 8))),
                           deadline_s=float(rng.uniform(0.01, 2.0)),
                           pump=False)
            open_tickets.append(t)
        except RequestShed:
            outcomes["shed"] += 1
        clk.advance(float(rng.uniform(0, 0.02)))
        if step % 7 == 0:
            srv.pump()
            while open_tickets:
                t = open_tickets.pop()
                try:
                    srv.result(t)
                    outcomes["ok"] += 1
                except RequestTimedOut:
                    outcomes["timeout"] += 1
                except RequestFailed:
                    outcomes["failed"] += 1
    srv.pump()
    for t in open_tickets:
        try:
            srv.result(t)
            outcomes["ok"] += 1
        except (RequestTimedOut, RequestFailed):
            outcomes["timeout"] += 1
        except KeyError:
            pytest.fail(f"lost ticket {t}")
    # zero lost tickets: every submit is accounted for exactly once
    assert sum(outcomes.values()) == 400
    m = srv.metrics
    assert m.submitted == 400
    assert m.accepted == outcomes["ok"] + outcomes["timeout"] + \
        outcomes["failed"]
    assert m.shed == outcomes["shed"]
    # bounded memory: results map, ticket map and queue all drained/capped
    assert len(srv._done) == 0
    assert len(srv._ticket_model) == 0
    assert srv._state(None).pending_rows() == 0
    assert len(m._latencies) <= m.max_latency_samples
