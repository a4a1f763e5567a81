"""Scheduler tests: simulator determinism, paper-claim validation bands,
metric properties (hypothesis), live-executor behaviour."""
import time

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.configs import workloads
from repro.core import (EvalRequest, Executor, LambdaModel, LoadBalancer,
                        backends, eval_records, metrics, simulate)
from repro.core.metrics import TaskRecord
from repro.core.simulator import Workload


def _run(bench: str, backend: str, q: int, seed: int = 7):
    w = workloads.make_workload(bench)
    recs = simulate(backends.get(backend), w, q, seed=seed)
    return metrics.summarize(bench, backend, eval_records(recs))


# --------------------------------------------------------------------------
# determinism + structural invariants
# --------------------------------------------------------------------------
def test_simulator_deterministic():
    a = _run("eigen-100", "slurm", 2, seed=3)
    b = _run("eigen-100", "slurm", 2, seed=3)
    assert a == b


def test_simulator_respects_queue_depth():
    w = workloads.make_workload("eigen-5000")
    recs = eval_records(simulate(backends.get("slurm"), w, 2, seed=1))
    # at any time at most 2 jobs in flight
    events = sorted([(r.submit_t, 1) for r in recs] +
                    [(r.end_t, -1) for r in recs])
    depth, worst = 0, 0
    for _, d in events:
        depth += d
        worst = max(worst, depth)
    assert worst <= 2


def test_timeout_mechanism():
    spec = backends.get("hq")
    w = Workload("t", runtimes=(10.0, 500.0), time_limit=60.0,
                 hq_alloc=600.0)
    recs = eval_records(simulate(spec, w, 1, seed=0))
    statuses = {r.task_id.split("-")[-1]: r.status for r in recs}
    assert statuses["0"] == "ok" and statuses["1"] == "timeout"
    assert max(r.cpu_time for r in recs) <= 60.0 + 1e-9


# --------------------------------------------------------------------------
# paper-claim validation (tolerance bands; EXPERIMENTS.md §Paper-validation)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("q", [2, 10])
def test_claim_gs2_makespan_reduction_38pct(q):
    s = _run("gs2", "slurm", q)
    h = _run("gs2", "hq", q)
    red = 1 - h.makespan / s.makespan
    assert 0.28 <= red <= 0.48, red          # paper: ~38 % both settings


def test_claim_overhead_three_orders():
    """Median per-job scheduling overhead drops by >= 3 orders of magnitude
    for the long-running workload (and >= ~500x even for eigen-100)."""
    for bench, floor in [("gs2", 1e3), ("eigen-5000", 1e3),
                         ("eigen-100", 300.0)]:
        s = _run(bench, "slurm", 2)
        h = _run(bench, "hq", 2)
        ratio = s.overhead_stats["median"] / max(h.overhead_stats["median"],
                                                 1e-9)
        assert ratio >= floor, (bench, ratio)


def test_claim_eigen100_hq_3x_quicker():
    s = _run("eigen-100", "slurm", 2)
    h = _run("eigen-100", "hq", 2)
    assert 2.0 <= s.makespan / h.makespan <= 6.0   # paper: "roughly 3x"


def test_claim_hq_loses_cpu_time_on_short_tasks():
    """The ~1 s server init makes HQ CPU time WORSE on eigen-100 (the
    paper's reported negative result) but better on GS2."""
    s100, h100 = _run("eigen-100", "slurm", 2), _run("eigen-100", "hq", 2)
    assert h100.total_cpu_time > s100.total_cpu_time
    sgs2, hgs2 = _run("gs2", "slurm", 10), _run("gs2", "hq", 10)
    assert hgs2.total_cpu_time < sgs2.total_cpu_time


def test_claim_slr_ordering():
    """HQ SLR is near the work-conserving bound; SLURM SLR is far above it
    on short tasks (Fig. 4)."""
    s = _run("eigen-100", "slurm", 2)
    h = _run("eigen-100", "hq", 2)
    assert h.slr < 2.0
    assert s.slr > 2.0 * h.slr


def test_claim_umb_slurm_no_gain():
    """Appendix A: the UM-Bridge SLURM backend is no better than naive."""
    for q in (2, 10):
        s = _run("gs2", "slurm", q)
        u = _run("gs2", "umb-slurm", q)
        assert u.makespan >= 0.95 * s.makespan


def test_hq_finishes_first_in_most_benchmarks():
    wins = 0
    cells = [(b, q) for b in workloads.BENCHMARKS for q in (2, 10)]
    for bench, q in cells:
        if _run(bench, "hq", q).makespan < _run(bench, "slurm", q).makespan:
            wins += 1
    assert wins >= 7, wins                     # paper: 'majority finished first'


# --------------------------------------------------------------------------
# metric properties (hypothesis)
# --------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.floats(0, 100), st.floats(0.01, 50),
                          st.floats(0, 10)), min_size=1, max_size=40))
def test_metrics_invariants(raw):
    recs = []
    for i, (submit, compute, ovh) in enumerate(raw):
        start = submit + ovh
        recs.append(TaskRecord(task_id=str(i), submit_t=submit,
                               start_t=start, end_t=start + compute,
                               cpu_time=compute, compute_t=compute))
    assert metrics.makespan(recs) >= 0
    assert metrics.scheduling_overhead(recs) >= 0
    assert all(r.overhead >= 0 for r in recs)
    s = metrics.summarize("x", "y", recs)
    assert s.total_cpu_time == pytest.approx(sum(r.cpu_time for r in recs))
    # makespan >= the longest single task
    assert s.makespan >= max(r.end_t - r.submit_t for r in recs) - 1e-9


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), q=st.sampled_from([1, 2, 5, 10]))
def test_simulator_records_are_consistent(seed, q):
    w = workloads.make_workload("eigen-100")
    recs = simulate(backends.get("hq"), w, q, seed=seed)
    for r in recs:
        assert r.end_t >= r.start_t >= r.submit_t - 1e-9
        assert r.cpu_time >= 0 and r.compute_t >= 0
        assert r.end_t - r.start_t == pytest.approx(r.cpu_time, abs=1e-6)


# --------------------------------------------------------------------------
# live executor
# --------------------------------------------------------------------------
def _toy_factory():
    time.sleep(0.02)
    return LambdaModel("toy", lambda p, c: [[float(p[0][0]) * 2]], 1, 1)


def test_executor_correct_values():
    with Executor({"toy": _toy_factory}, n_workers=4) as ex:
        res = ex.run_all([EvalRequest("toy", [[i]]) for i in range(30)])
        assert [r.value[0][0] for r in res] == [2.0 * i for i in range(30)]
        assert all(r.status == "ok" for r in res)


def test_executor_persistent_vs_fresh_init_cost():
    with Executor({"toy": _toy_factory}, n_workers=2) as ex:
        res = ex.run_all([EvalRequest("toy", [[i]]) for i in range(20)])
        hq_init = sum(r.init_t for r in res)
    with Executor({"toy": _toy_factory}, n_workers=2,
                  persistent_servers=False) as ex:
        res = ex.run_all([EvalRequest("toy", [[i]]) for i in range(20)])
        slurm_init = sum(r.init_t for r in res)
    assert slurm_init > 5 * hq_init


def test_executor_retry_and_fail():
    with Executor({"toy": _toy_factory}, n_workers=2, max_attempts=3) as ex:
        ok = ex.run_all([EvalRequest("toy", [[1]],
                                     config={"fail_attempts": 2})])[0]
        assert ok.status == "ok" and ok.attempts == 3
        bad = ex.run_all([EvalRequest("toy", [[1]],
                                      config={"fail_attempts": 99})])[0]
        assert bad.status == "failed"


def test_executor_counts_swallowed_hook_errors():
    """A failing predictor does not fail the task it rode on, and does not
    vanish either: each swallowed error is counted per site, in the
    executor's metrics and in the registry."""
    from repro.obs import MetricsRegistry
    from repro.sched.predictor import QuantileEstimator

    class Broken(QuantileEstimator):
        def observe(self, req, compute_t):
            raise RuntimeError("device lost")

    reg = MetricsRegistry()
    with Executor({"toy": _toy_factory}, n_workers=2, predictor=Broken(),
                  metrics_registry=reg) as ex:
        res = ex.run_all([EvalRequest("toy", [[i]]) for i in range(5)])
        swallowed = ex.metrics()["swallowed_errors"]
    assert all(r.status == "ok" for r in res)
    assert swallowed["predictor.observe"] == (5, "RuntimeError: device lost")
    assert reg.counters["swallowed_errors{site=predictor.observe}"] == 5


def test_executor_worker_death_requeues():
    def slow():
        return LambdaModel("s", lambda p, c: (time.sleep(0.2), [[1.0]])[1],
                           1, 1)
    with Executor({"s": slow}, n_workers=2) as ex:
        ids = [ex.submit(EvalRequest("s", [[i]])) for i in range(6)]
        time.sleep(0.05)
        ex.kill_worker(0)
        res = [ex.result(t, timeout=30) for t in ids]
        assert all(r.status == "ok" for r in res)
        assert ex.n_workers() == 1


def test_executor_dependencies_order():
    order = []

    def dep():
        return LambdaModel(
            "d", lambda p, c: (order.append(p[0][0]), [[p[0][0]]])[1], 1, 1)
    with Executor({"d": dep}, n_workers=2) as ex:
        a = EvalRequest("d", [[1]])
        b = EvalRequest("d", [[2]], depends_on=(a.task_id,))
        c = EvalRequest("d", [[3]], depends_on=(b.task_id,))
        for r in (c, b, a):
            ex.submit(r)
        ex.result(c.task_id, 10)
    assert order == [1, 2, 3]


def test_executor_autoscale_and_snapshot():
    def slowcall():
        return LambdaModel(
            "toy", lambda p, c: (time.sleep(0.05), [[float(p[0][0])]])[1],
            1, 1)
    with Executor({"toy": slowcall}, n_workers=1, autoscale_backlog=3,
                  max_workers=4) as ex:
        ids = [ex.submit(EvalRequest("toy", [[i]])) for i in range(25)]
        [ex.result(t, 30) for t in ids]
        assert ex.n_workers() > 1
    with Executor({"toy": _toy_factory}, n_workers=1) as ex:
        ids = [ex.submit(EvalRequest("toy", [[i]])) for i in range(10)]
        ex.result(ids[0], 10)
        snap = ex.snapshot()
    ex2 = Executor.restore(snap, {"toy": _toy_factory}, n_workers=2)
    try:
        res = [ex2.result(t, 30) for t in ids]
        assert all(r.status == "ok" for r in res)
    finally:
        ex2.shutdown()


def test_executor_straggler_speculation():
    def var():
        return LambdaModel(
            "v", lambda p, c: (time.sleep(p[0][0]), [[1.0]])[1], 1, 1)
    with Executor({"v": var}, n_workers=3, straggler_factor=3.0,
                  straggler_min_completed=5) as ex:
        reqs = [EvalRequest("v", [[0.02]]) for _ in range(15)]
        reqs.append(EvalRequest("v", [[1.0]]))
        res = ex.run_all(reqs, timeout=60)
        assert all(r.status == "ok" for r in res)


def test_balancer_readiness_and_health():
    with LoadBalancer("hq", n_workers=2) as lb:
        info = lb.register_model("toy", _toy_factory)
        assert info.probes_run == 5
        assert lb.evaluate("toy", [[21]])[0][0] == 42.0
        assert lb.health_check("toy", [[1]])
        with pytest.raises(KeyError):
            lb.submit(EvalRequest("nope", [[1]]))
