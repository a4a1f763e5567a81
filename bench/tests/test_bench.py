"""Tests of the benchmark under `bench/`, on the CPU at small sizes:

    python3 -m pytest bench/tests -q

The harness itself refuses the CPU; the end-to-end tests here run it in a
child process that skips only that look for a chip, at a size a test run
can hold."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench.harness import cell as cell_mod
from bench.harness import roofline, sampling
from bench.reference import gp as ref_gp
from bench.reference import gs2 as ref_gs2
from bench.trace import reduce as trace_reduce

REPO = pathlib.Path(__file__).resolve().parents[2]
PROBE_TRACE = REPO / "bench" / "trace" / "testdata" / "probe.xplane.pb"
CHILD_TIMEOUT_S = 600


def config(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json")
                      .read_text())


# -- refusals ---------------------------------------------------------------
def _bench(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gp-hq.depth10",
         "--seed", "3000000000", "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True,
        timeout=CHILD_TIMEOUT_S)


def test_refuses_a_cpu_backend():
    p = _bench(REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _bench(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# -- metric arithmetic against a hand count ---------------------------------
class _Rec:
    def __init__(self, end_t, status="ok", worker="worker-0", compute_t=0.0):
        self.task_id = f"t{end_t}"
        self.end_t, self.status, self.worker = end_t, status, worker
        self.compute_t = compute_t


def _run_with(records=(), client=()):
    run = cell_mod.Run({}, {}, {}, None, 0, 10.0, False, 0.0)
    run.t_open, run.t_close = 10.0, 20.0
    run.records, run.client = list(records), list(client)
    return run


def _metric(name, run):
    from bench.harness.main import load_module
    return load_module(REPO, "metrics", name).read(run)


def test_tasks_per_s_counts_ok_tasks_that_ended_in_the_window():
    recs = ([_Rec(10.0 + i) for i in range(7)]            # 7 ok inside
            + [_Rec(12.5, status="timeout")]               # not ok
            + [_Rec(9.99), _Rec(20.0)])                    # outside
    assert _metric("tasks_per_s", _run_with(recs)) == pytest.approx(0.7)


def _client(t_submit, turnaround_ms, compute_ms, init_ms=0.0):
    t_done = t_submit + turnaround_ms / 1e3
    return cell_mod.ClientTask("c", t_submit, t_submit + 1e-4, t_done,
                               init_ms / 1e3, compute_ms / 1e3)


def test_turnaround_p95_is_the_nearest_rank_over_every_window_task():
    # turnarounds 1..20 ms inside the window; one slow task outside
    tasks = [_client(11.0, ms, 0.5) for ms in range(1, 21)]
    tasks.append(_client(21.0, 500.0, 0.5))
    # nearest rank: the ceil(0.95 * 20) = 19th smallest
    assert _metric("turnaround_p95_ms", _run_with(client=tasks)) \
        == pytest.approx(19.0)


def test_overhead_is_a_sum_over_a_count_each_at_least_zero():
    tasks = [_client(11.0, ms, 0.5) for ms in range(1, 21)]
    tasks.append(_client(11.0, 1.0, 3.0))      # compute > turnaround -> 0
    tasks.append(_client(11.0, 10.0, 2.0, init_ms=3.0))   # 10 - 5 = 5
    want = (sum(ms - 0.5 for ms in range(1, 21)) + 0.0 + 5.0) / 22
    assert _metric("overhead_ms", _run_with(client=tasks)) \
        == pytest.approx(want)


def test_per_layer_readers_return_nothing_where_nothing_is_read():
    run = _run_with()
    for name in ("gp_predict_roofline", "device_idle_share",
                 "queue_wait_ms", "submit_ms", "task_compute_ms",
                 "turnaround_p95_ms", "overhead_ms"):
        assert _metric(name, run) is None, name


# -- plain references against the program ----------------------------------
def _gs2_limit():
    return config("gs2-hq")["checks"]["limits"]["gs2_growth_gap"]


def test_gs2_reference_operator_matches_the_program():
    import jax.numpy as jnp
    from repro.uq import gs2_proxy
    th = sampling.latin_hypercube(4, [5, 2])
    for t in th:
        got = np.asarray(gs2_proxy.build_operator(jnp.asarray(t, jnp.float32)),
                         np.float64)
        want = ref_gs2.operators(t[None])[0]
        assert np.max(np.abs(got - want)) <= 1e-5 * np.max(np.abs(want))


def test_gs2_program_within_limit_and_control_beyond_it():
    import jax
    import jax.numpy as jnp
    from repro.uq import gs2_proxy
    th = sampling.latin_hypercube(12, [5, 2])
    g, _, _ = jax.vmap(gs2_proxy.solve)(jnp.asarray(th, jnp.float32))
    want = ref_gs2.growth_rate(th)
    control = ref_gs2.growth_rate(th, precision="bfloat16")
    limit = _gs2_limit()
    assert np.max(np.abs(np.asarray(g, np.float64) - want)) <= limit
    assert np.max(np.abs(control - want)) > limit


def test_benchmark_labels_follow_the_reference():
    from bench.harness import labels
    th = sampling.latin_hypercube(12, [5, 2])
    got = labels.labels(th)
    assert got.shape == (12, 2)
    assert np.max(np.abs(got[:, 0] - ref_gs2.growth_rate(th))) \
        <= _gs2_limit()


def _gp_case(n=128, q=16):
    import jax.numpy as jnp
    from bench.harness import labels
    from repro.uq import gp
    hyper = config("gp-hq")["surrogate"]["hyperparameters"]
    x = sampling.latin_hypercube(n, [5, 1])
    y = labels.labels(x)
    params = gp.GPParams(jnp.asarray(hyper["log_lengthscale"]),
                         jnp.asarray(hyper["log_variance"]),
                         jnp.asarray(hyper["log_noise"]))
    post = gp.recondition(gp.GPPosterior(params, None, None, None, None,
                                         None, None), x, y)
    xq = sampling.latin_hypercube(q, [5, 3])
    return x, y, hyper, post, xq


def test_gp_program_within_limits_and_control_beyond_them():
    from bench.tasks import gp_predict
    import jax.numpy as jnp
    from repro.uq import gp
    x, y, hyper, post, xq = _gp_case()
    mean, var = gp.predict(post, jnp.asarray(xq, jnp.float32))
    values = [[np.concatenate([np.asarray(mean)[i], np.asarray(var)[i]])
               .tolist()] for i in range(len(xq))]
    data = cell_mod.Data(x, y, hyper, post)
    cfg = config("gp-hq")
    limits = cfg["checks"]["limits"]
    got = gp_predict.compare(values, xq, cfg, data)
    assert all(got[k] <= limits[k] for k in got), got
    # the control: the reference in bfloat16 in the program's place
    ctl = ref_gp.Posterior(x, y, hyper, "bfloat16")
    cm, csd = ctl.predict(xq)
    cvar = (csd[:, None] * ctl.y_std[None, :]) ** 2
    cvalues = [[np.concatenate([cm[i], cvar[i]]).tolist()]
               for i in range(len(xq))]
    bad = gp_predict.compare(cvalues, xq, cfg, data)
    assert any(bad[k] > limits[k] for k in bad), bad


# -- trace reduction and roofline -------------------------------------------
def test_union_and_gaps_by_hand():
    busy = trace_reduce.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert trace_reduce.gaps(busy, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                                  (4.0, 5.0)]


def test_reduce_averages_busy_over_chips_and_names_gaps_by_host():
    devices = {"/device:TPU:0": [("a", 0.0, 1.0), ("b", 0.5, 2.0)],
               "/device:TPU:1": [("a", 1.0, 2.0)]}
    host = [("bench.submit", 2.0, 3.5), ("PjitFunction(f)", 3.5, 4.0)]
    r = trace_reduce.reduce_events((0.0, 4.0), devices, host)
    assert r.window_s == 4.0
    assert r.busy_s == pytest.approx((2.0 + 1.0) / 2)
    assert r.kernel_s("a") == pytest.approx(2.0)
    assert r.breakdown["idle_gaps"][0] == ["bench.submit", 2.0]


def test_reduce_reads_the_recorded_chip_trace():
    window, devices, host = trace_reduce.read_xplane(
        PROBE_TRACE, window_span="bench.probe")
    r = trace_reduce.reduce_events(window, devices, host)
    assert list(devices) == ["/device:TPU:0"]
    assert 0.0 < r.busy_s < r.window_s
    # ten launches of the fused predict: five at (2048, 64), five at
    # (288, 1024), 202.025 us in all on the device
    assert r.kernel_s(roofline.GP_PREDICT_KERNEL) == pytest.approx(
        202.025e-6, rel=1e-6)
    names = [n for n, _ in r.breakdown["device_ops"]]
    assert roofline.GP_PREDICT_KERNEL in names
    assert all(label.startswith(trace_reduce.HOST_ACTIVITY) or label == "none"
               for label, _ in r.breakdown["idle_gaps"])
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    least = roofline.least_seconds_gp_predict(
        {(2048, 64): 5, (288, 1024): 5}, peaks, "TPU v5 lite")
    share = least / r.kernel_s(roofline.GP_PREDICT_KERNEL)
    assert 0.0 < share < 1.0


def test_roofline_reader_on_the_recorded_trace():
    window, devices, host = trace_reduce.read_xplane(
        PROBE_TRACE, window_span="bench.probe")
    run = _run_with()
    run.trace_result = trace_reduce.reduce_events(window, devices, host)
    run.launches = {(2048, 64): 5, (288, 1024): 5}
    run.peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    run.device_kind = "TPU v5 lite"
    share = _metric("gp_predict_roofline", run)
    assert 0.0 < share < 100.0
    idle = _metric("device_idle_share", run)
    assert 0.0 < idle < 100.0


def test_gp_predict_cost_by_hand():
    flops, nbytes = roofline.gp_predict_cost(4, 2, d=3, m=1)
    # cross 2*2*4*3 = 48, triangle 2*4*5 = 40, mean 2*2*4*1 = 16
    assert flops == 104.0
    # x 12 + q 6 + alpha 4 + triangle 10 + out 4 words of 4 bytes
    assert nbytes == 4.0 * 36
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    with pytest.raises(KeyError):
        roofline.least_seconds_gp_predict({(4, 2): 1}, peaks, "no such chip")


# -- whole runs on the CPU, chip look skipped ---------------------------------
CHILD = """
import sys, time
t0 = time.monotonic()
root = sys.argv[1]
sys.path[:0] = [root, root + "/src"]
import jax
from bench.harness import device, main
device.require_tpu = lambda chips: jax.devices()[:chips]
fault = sys.argv[2]
if fault == "answer":
    from repro.uq import gs2_proxy
    make = gs2_proxy.make_solver
    def broken(m=96):
        solve = make(m)
        return lambda theta: (lambda g, f: (g + 10.0, f))(*solve(theta))
    gs2_proxy.make_solver = broken
elif fault == "answer_gp":
    from repro.uq import gp
    predict = gp.predict
    gp.predict = lambda post, x: (lambda m, v: (m + 1.0, v))(*predict(post, x))
elif fault == "answer_surrogate":
    from repro.sched import offload
    evaluate = offload.SurrogateOffload.evaluate
    offload.SurrogateOffload.evaluate = lambda self, parameters: [
        [v + 1.0 for v in row] for row in evaluate(self, parameters)]
elif fault == "gate_open":
    # from the window's opening (the service has warmed up on real
    # completions), the gate trusts every input it looks at
    from bench.harness import cell
    from repro.sched import offload
    enter, opened = cell._Window.__enter__, []
    cell._Window.__enter__ = lambda self: opened.append(1) or enter(self)
    decide = offload.SurrogateOffload._decide
    def trusting(self, req, cost):
        if opened:
            self.sd_threshold = 1e9
        return decide(self, req, cost)
    offload.SurrogateOffload._decide = trusting
elif fault == "set_foreign":
    from repro.sched import offload
    observe = offload.SurrogateOffload.observe
    offload.SurrogateOffload.observe = lambda self, parameters, value, **kw: \
        observe(self, parameters, [[v + 1.0 for v in value[0]]], **kw)
elif fault == "served_twice":
    from repro.core import executor
    complete = executor.Executor._complete
    again = set()
    def complete_and_rerun(self, req, res):
        complete(self, req, res)
        if req.task_id not in again and not res.worker.endswith("-surrogate"):
            again.add(req.task_id)
            self._push(req, 2)
    executor.Executor._complete = complete_and_rerun
    executor.Executor._already_done = lambda self, task_id: False
elif fault == "lost":
    from repro.core import executor
    submit = executor.Executor.submit
    seen = []
    def dropping(self, req):
        seen.append(req.task_id)
        if len(seen) % 50 == 0:
            return req.task_id
        return submit(self, req)
    executor.Executor.submit = dropping
sys.exit(main.main(sys.argv[3:], t0, __import__("pathlib").Path(root)))
"""


def _tiny_root(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    os.symlink(REPO / "src", root / "src")
    for f in (root / "bench" / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        c["surrogate"]["n_train"] = 128
        c["service"]["predictor"].update(max_points=32, refit_every=16,
                                         fit_steps=5)
        if "offload" in c["surrogate"]:
            # about half the inputs trusted at this size
            c["surrogate"]["offload"].update(max_points=128,
                                             sd_threshold=0.18,
                                             condition_every=8)
        f.write_text(json.dumps(c))
    for name, key, value in (("backlog-100k", "tasks", 400),
                             ("depth10", "inputs", 4000)):
        f = root / "bench" / "traffic" / f"{name}.json"
        t = json.loads(f.read_text())
        t[key] = value
        t["warm_observations"] = 40
        f.write_text(json.dumps(t))
    (root / "child.py").write_text(CHILD)
    return root


def _run_child(root, cell, fault="none", seconds="2", trace="0"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(root / "child.py"), str(root), fault,
         "--workload", cell, "--seed", str(2 ** 31 + 12345),
         "--seconds", seconds, "--trace", trace],
        cwd=root, env=env, capture_output=True, text=True,
        timeout=CHILD_TIMEOUT_S)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_a_new_cell_and_metric_are_found_by_name(tmp_path):
    root = _tiny_root(tmp_path)
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(
        {"pattern": "closed_loop", "clients": 4, "inputs": 1000,
         "result_timeout_s": 60, "warm_observations": 40}))
    (root / "bench" / "metrics" / "completed_count.py").write_text(
        "def read(run):\n    return len(run.completed())\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "gp-hq.tiny", "config": "gp-hq",
                              "traffic": "tiny", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "completed_count", "unit": "tasks",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["gp-hq.tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = _run_child(root, "gp-hq.tiny", seconds="4")
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["completed_count"]["value"] == out["attempted"] > 0
    assert set(out["metrics"]) == {"tasks_per_s", "setup_s",
                                   "completed_count"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell,fault,number", [
    ("gs2-hq.depth10", "answer", "gs2_growth_gap"),
    ("gp-hq.depth10", "answer_gp", "gp_mean_gap")])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        tmp_path, cell, fault, number):
    root = _tiny_root(tmp_path)
    sound = _run_child(root, cell)
    assert sound["correct"] is True, sound["checks"]
    broken = _run_child(root, cell, fault=fault)
    assert broken["correct"] is False
    gap = broken["checks"][number]
    assert gap["value"] > gap["limit"]


def test_a_lost_task_is_not_correct(tmp_path):
    # the backlog mix, kept as data for a later cell, at a tiny size
    root = _tiny_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "gp-hq.backlog", "config": "gp-hq",
                              "traffic": "backlog-100k", "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = _run_child(root, "gp-hq.backlog", fault="lost")
    assert out["correct"] is False
    assert out["checks"]["tasks_lost"]["value"] > 0


@pytest.mark.parametrize("fault,number", [
    ("answer_surrogate", "surrogate_mean_gap"),
    ("gate_open", "gate_wrong"),
    ("set_foreign", "surrogate_set_foreign"),
    ("served_twice", "tasks_served_twice")])
def test_a_gate_or_surrogate_fault_is_not_correct(tmp_path, fault, number):
    root = _tiny_root(tmp_path)
    out = _run_child(root, "gs2-hq.depth10", fault=fault, seconds="4")
    assert out["correct"] is False
    assert out["checks"][number]["value"] > out["checks"][number]["limit"]


def test_gate_numbers_pass_the_program_and_fail_the_control():
    """The gate's numbers on a small surrogate: the program's f32
    posterior within the limits, the bfloat16 control beyond one."""
    import jax.numpy as jnp
    from bench import control
    from bench.harness import checks
    from repro.uq import gp
    x, y, hyper, post, xq = _gp_case(q=64)
    cfg = config("gs2-hq")
    thr = cfg["surrogate"]["offload"]["sd_threshold"]
    mean, var = gp.predict(post, jnp.asarray(xq, jnp.float32))
    sd = np.sqrt(np.asarray(var)[:, 0]) / np.asarray(post.y_std)[0]
    g = checks.Gathered(
        [], [], {}, [(xq[i], np.asarray(mean, np.float64)[i], 0)
                     for i in range(len(xq))],
        [(xq[i], float(sd[i]), 0, bool(sd[i] <= thr))
         for i in range(len(xq))],
        {0: (np.float32(x), np.float32(y))})
    run = cell_mod.Run({}, cfg, {}, None, 0, 10.0, False, 0.0)
    run.thetas = {}
    data = cell_mod.Data(x, y, hyper, post)
    limits = cfg["checks"]["limits"]
    got = checks.compare_gate(run, data, g)
    assert all(v["value"] <= v["limit"] for v in got.values()), got
    bad = checks.compare_gate(run, data,
                              control.control_gathered(run, data, g,
                                                       real=False))
    assert any(bad[k]["value"] > limits[k]
               for k in ("surrogate_mean_gap", "trust_sd_gap")), bad
