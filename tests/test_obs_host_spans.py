"""Host spans on the live service's hot path (`Tracer.scope`, the traced
dispatch lock) and the benchmark's readers of them.

A traced `ServiceBroker` must account for each task from the start of
`svc.submit` to the end of `task.handoff` with its own spans, keep the
dispatch lock's holds mutually exclusive across `Condition.wait`, mirror
its spans into the JAX profiler's host plane, and leave sim/live parity's
`span_sequence` as it was.  Untraced, the executor keeps a plain RLock and
records nothing.  The readers under `bench/metrics/` are loaded by path,
as the benchmark loads them."""
import importlib.util
import pathlib
import sys
import threading
import time

import pytest

from repro.core import EvalRequest
from repro.core.task import LambdaModel
from repro.obs import (HOST_PID, TracedLock, Tracer, read_jsonl, span_sequence,
                       validate_chrome_trace)
from repro.sched.predictor import GPRuntimePredictor
from repro.service import ServiceBroker

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:                  # `bench` for the readers
    sys.path.insert(0, str(REPO))

N_WORKERS, N_CLIENTS, PER_CLIENT = 4, 6, 12
SLEEP_S = 0.005


def _sleepy():
    def fn(p, c):
        time.sleep(SLEEP_S)
        return [[float(p[0][0])]]
    return LambdaModel("toy", fn, 1, 1)


def _service(tracer, **kw):
    # a GP runtime predictor that never fits: every cost is a
    # `predictor.predict` call on its quantile fallback, no JAX compile
    return ServiceBroker({"toy": _sleepy},
                         predictor=GPRuntimePredictor(min_fit=10 ** 9),
                         inner_policy="sjf", n_workers=N_WORKERS,
                         tracer=tracer, **kw)


def _drive(svc, n_clients=N_CLIENTS, per_client=PER_CLIENT):
    errors = []

    def client(c):
        try:
            for i in range(per_client):
                req = EvalRequest("toy", [[float(c * 100 + i)]],
                                  time_request=1.0, time_limit=30.0)
                svc.submit(req)
                assert svc.result(req.task_id, timeout=60).status == "ok"
        except BaseException as e:         # noqa: BLE001 — re-raised
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    if errors:
        raise errors[0]


def _stop(svc):
    svc.shutdown(final_checkpoint=False)
    for t in [*svc._ex.workers, svc._ex._monitor]:
        if t is not None:
            t.join(5.0)


def _host(events, name=None):
    return [e for e in events if e[3] == HOST_PID and e[1] == "X"
            and (name is None or e[2] == name)]


def _uncovered(lo, hi, intervals):
    covered, t = 0.0, lo
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        a = max(a, t)
        if b > a:
            covered += b - a
            t = b
    return (hi - lo) - covered


def test_spans_tile_each_task_from_submit_to_handoff():
    tracer = Tracer(capacity=1 << 20)
    svc = _service(tracer)
    try:
        _drive(svc)
    finally:
        _stop(svc)
    events = tracer.events()
    assert tracer.n_dropped == 0
    by_task = {}
    for ts, ph, name, pid, tid, dur, args in events:
        if ph == "X" and args and "task" in args:
            by_task.setdefault(args["task"], []).append((name, ts, ts + dur))
    done = [r.task_id for r in svc.records() if r.status == "ok"]
    assert len(done) == N_CLIENTS * PER_CLIENT
    for task in done:
        spans = by_task[task]
        names = {n for n, _, _ in spans}
        assert {"svc.submit", "task.queued", "task.run", "task.complete",
                "task.handoff", "predictor.predict",
                "predictor.observe"} <= names, names
        lo = min(a for n, a, _ in spans if n == "svc.submit")
        hi = max(b for n, _, b in spans if n == "task.handoff")
        gap = _uncovered(lo, hi, [(a, b) for _, a, b in spans])
        assert gap < 1e-3, (task, gap, sorted(spans, key=lambda s: s[1]))


def test_lock_holds_nest_across_condition_wait():
    tracer = Tracer(capacity=1 << 20)
    svc = _service(tracer)
    try:
        _drive(svc, per_client=6)
    finally:
        _stop(svc)
    events = tracer.events()
    held = sorted((ts, ts + dur, tid)
                  for ts, _, _, _, tid, dur, _ in _host(events, "lock.held"))
    waits = _host(events, "lock.wait")
    assert held and waits
    # one holder at a time, across every thread
    for (a0, b0, _), (a1, _, _) in zip(held, held[1:]):
        assert a1 >= b0
    # on each thread, a hold nests inside or lies apart from every other
    # host span it shares the track with (task.handoff starts on the
    # worker's clock reading, so it is left out)
    others = [(ts, ts + dur, tid) for ts, _, name, _, tid, dur, _
              in _host(events) if name not in ("lock.held", "task.handoff")]
    for a, b, tid in held:
        for c, d, t2 in others:
            if t2 != tid or d <= a or c >= b:
                continue
            assert (c <= a and b <= d) or (a <= c and d <= b), \
                ((a, b), (c, d))
    # each hold opens at the clock reading that closed a wait of its own
    # thread (ts + dur rounds that reading by an ulp or so)
    ends = {}
    for ts, _, _, _, tid, dur, _ in waits:
        ends.setdefault(tid, []).append(ts + dur)
    for a, _, tid in held:
        assert any(abs(a - e) < 1e-9 for e in ends[tid])


def test_traced_lock_under_a_condition_by_hand():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    tracer = Tracer(clock=clock)
    lock = TracedLock(tracer)
    cv = threading.Condition(lock)
    with cv:
        with lock:                             # re-entry: one hold
            cv.wait(0.001)                     # releases both levels
            assert lock._is_owned()
        assert lock._is_owned()
    assert not lock._is_owned()
    names = [(name, ts, dur) for ts, _, name, _, _, dur, _ in
             sorted(tracer.events(), key=lambda e: e[0])]
    assert [n for n, _, _ in names] == ["lock.wait", "lock.held",
                                        "lock.wait", "lock.held"]
    (_, w0, wd0), (_, h0, hd0), (_, w1, wd1), (_, h1, _) = names
    assert h0 == w0 + wd0 and w1 >= h0 + hd0 and h1 == w1 + wd1
    with pytest.raises(RuntimeError):
        lock.release()


def test_scopes_from_many_threads_lose_no_count():
    n_threads, per_thread = 16, 500
    tracer = Tracer(capacity=n_threads * per_thread)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def spans():
            for _ in range(per_thread):
                with tracer.scope("svc.submit"):
                    pass

        threads = [threading.Thread(target=spans) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert tracer.buf.n_seen == n_threads * per_thread
    assert tracer.n_dropped == 0
    per_tid = {}
    for e in tracer.events():
        per_tid[e[4]] = per_tid.get(e[4], 0) + 1
    # one track per thread, each holding that thread's spans
    assert sorted(per_tid) == list(range(n_threads))
    assert set(per_tid.values()) == {per_thread}


def test_host_spans_export_to_jsonl_and_chrome(tmp_path):
    tracer = Tracer(capacity=1 << 20)
    svc = _service(tracer)
    try:
        _drive(svc, n_clients=2, per_client=3)
    finally:
        _stop(svc)
    path = tmp_path / "trace.jsonl"
    tracer.write_jsonl(str(path))
    assert read_jsonl(str(path)) == tracer.events()
    chrome = tracer.to_chrome()
    assert validate_chrome_trace(chrome) == []
    meta = [e for e in chrome["traceEvents"]
            if e["ph"] == "M" and e["pid"] == HOST_PID]
    assert {"name": "process_name", "ph": "M", "pid": HOST_PID, "tid": 0,
            "args": {"name": "host"}} in meta
    # one named track per thread that recorded a host span
    tids = {e[4] for e in _host(tracer.events())}
    assert {e["tid"] for e in meta if e["name"] == "thread_name"} == tids


def test_untraced_executor_keeps_a_plain_rlock_and_records_nothing(
        monkeypatch):
    from repro.obs import trace
    # threads an earlier test left behind may still enter scopes of
    # their own tracers: only this service's threads count
    before = set(threading.enumerate())
    entered = []
    enter = trace._Scope.__enter__

    def spy(self):
        if threading.current_thread() not in before:
            entered.append(self._name)
        return enter(self)

    monkeypatch.setattr(trace._Scope, "__enter__", spy)
    svc = _service(None)
    try:
        assert type(svc._ex._lock) is type(threading.RLock())
        _drive(svc, n_clients=2, per_client=4)
    finally:
        _stop(svc)
    assert entered == []
    assert svc._ex._stored_t == {}


def test_profiler_host_plane_holds_the_program_spans(tmp_path):
    import jax
    tracer = Tracer(capacity=1 << 20)
    # the two clocks are read a few instructions apart; a long switch
    # interval keeps the interpreter from handing another thread the GIL
    # in between (a thread that blocks still lets the others run)
    old = sys.getswitchinterval()
    sys.setswitchinterval(0.5)
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc = _service(tracer, monitor_interval=None)
        try:
            _drive(svc, n_clients=3, per_client=4)
        finally:
            _stop(svc)
    finally:
        jax.profiler.stop_trace()
        sys.setswitchinterval(old)
    files = sorted(tmp_path.rglob("*.xplane.pb"))
    assert files
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    prof = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    prof.setdefault(e.name, []).append(e.duration_ns * 1e-9)
    events = tracer.events()
    for name in ("svc.submit", "task.complete", "lock.wait",
                 "predictor.predict"):
        mine = sorted(dur for *_, dur, _ in _host(events, name))
        theirs = sorted(prof.get(name, []))
        assert mine and len(theirs) == len(mine), (name, len(theirs),
                                                   len(mine))
        # sorted pairing: within 1 ms if any pairing is
        assert max(abs(a - b) for a, b in zip(mine, theirs)) < 1e-3, name


def test_live_parity_span_sequence_ignores_host_spans():
    from repro.cluster import bimodal_trace, run_parity
    from repro.core import backends
    sim, live = Tracer(), Tracer()
    rep = run_parity(backends.get("hq"), bimodal_trace(n=20, seed=9),
                     tracers=(sim, live), n_workers=3, seed=9)
    assert rep.ok, rep.divergences
    assert _host(live.events(), "lock.held")   # the live lock was traced
    assert span_sequence(sim) == span_sequence(live)
    assert all(e[3] != HOST_PID for e in span_sequence(live))


def test_predictor_refit_and_condition_nest_in_observe():
    tracer = Tracer()
    tracer.bind_clock(time.monotonic)
    pred = GPRuntimePredictor(min_fit=4, refit_every=100,
                              condition_every=2, fit_steps=2)
    pred.tracer = tracer
    for i in range(6):
        pred.observe(EvalRequest("toy", [[0.1 * i, 1.0 - 0.1 * i]],
                                 task_id=f"t{i}"), 0.01 * (i + 1))
    spans = sorted(_host(tracer.events()), key=lambda e: e[0])
    observe = [e for e in spans if e[2] == "predictor.observe"]
    assert [e[6]["task"] for e in observe] == [f"t{i}" for i in range(6)]
    for inner, outer_task in (("predictor.refit", "t3"),
                              ("predictor.condition", "t5")):
        (s,) = [e for e in spans if e[2] == inner]
        (o,) = [e for e in observe if e[6]["task"] == outer_task]
        assert o[0] <= s[0] and s[0] + s[5] <= o[0] + o[5]
        assert s[4] == o[4]                     # the same thread's track


# -- the benchmark's readers, on synthetic events ----------------------------
class _Run:
    def __init__(self, events, lo=10.0, hi=20.0):
        self.tracer_events = events
        self.t_open, self.t_close = lo, hi

    def in_window(self, t):
        return self.t_open <= t < self.t_close

    @property
    def window_s(self):
        return self.t_close - self.t_open


def _x(name, start, end, tid=0, pid=HOST_PID, **args):
    return (start, "X", name, pid, tid, end - start, args or None)


def _metric(name, events):
    path = REPO / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(_Run(events))


def test_ingest_lock_wait_sums_waits_inside_each_window_submit():
    events = [
        _x("svc.submit", 11.0, 11.010, tid=1, task="a"),
        _x("lock.wait", 11.001, 11.004, tid=1),   # 3 ms
        _x("lock.wait", 11.005, 11.006, tid=1),   # 1 ms
        _x("lock.wait", 11.002, 11.009, tid=2),   # another thread
        _x("svc.submit", 12.0, 12.002, tid=2, task="b"),   # no wait
        _x("svc.submit", 9.99, 10.5, tid=3, task="c"),     # began before
        _x("lock.wait", 10.0, 10.4, tid=3),
    ]
    assert _metric("ingest_lock_wait_ms", events) == pytest.approx(2.0)
    assert _metric("ingest_lock_wait_ms", []) is None


def test_cost_predicts_per_submit_counts_nested_predicts():
    events = [
        _x("svc.submit", 11.0, 11.010, tid=1, task="a"),
        _x("predictor.predict", 11.001, 11.002, tid=1, task="a", n=1),
        _x("predictor.predict", 11.003, 11.004, tid=1, task="a", n=1),
        _x("predictor.predict", 11.005, 11.006, tid=1, task="a", n=1),
        _x("predictor.predict", 11.003, 11.004, tid=0, task="w", n=1),
        _x("svc.submit", 12.0, 12.002, tid=2, task="b"),
        _x("predictor.predict", 12.0005, 12.001, tid=2, task="b", n=1),
        _x("predictor.predict", 12.001, 12.003, tid=2, task="b", n=1),
    ]
    # a: 3; b: 1 (the second ends after its submit)
    assert _metric("cost_predicts_per_submit", events) == pytest.approx(2.0)
    assert _metric("cost_predicts_per_submit", []) is None


def test_dispatch_lock_busy_share_is_the_union_of_holds_in_window():
    events = [
        _x("lock.held", 9.0, 11.0, tid=1),     # 1 s inside the window
        _x("lock.held", 12.0, 13.0, tid=2),
        _x("lock.held", 12.5, 13.5, tid=3),    # overlaps: union 1.5 s
        _x("lock.held", 19.5, 21.0, tid=1),    # 0.5 s inside
        _x("lock.held", 25.0, 26.0, tid=1),    # outside
    ]
    assert _metric("dispatch_lock_busy_share", events) == pytest.approx(30.0)
    assert _metric("dispatch_lock_busy_share", []) is None


def test_completion_ms_averages_real_completions_closing_in_window():
    events = [
        _x("task.complete", 11.0, 11.004, task="a", offloaded=False),
        _x("task.complete", 12.0, 12.002, task="b", offloaded=False),
        _x("task.complete", 12.0, 12.5, task="c", offloaded=True),
        _x("task.complete", 19.9, 20.1, task="d", offloaded=False),
    ]
    assert _metric("completion_ms", events) == pytest.approx(3.0)
    assert _metric("completion_ms", []) is None


def test_handoff_ms_averages_handoffs_closing_in_window():
    events = [
        _x("task.handoff", 9.99, 10.01, task="a"),
        _x("task.handoff", 11.0, 11.003, task="b"),
        _x("task.handoff", 19.99, 20.5, task="c"),
        _x("task.queued", 11.0, 11.5, pid=0, task="b"),
    ]
    assert _metric("handoff_ms", events) == pytest.approx(
        (20.0 + 3.0) / 2)
    assert _metric("handoff_ms", []) is None


def test_readers_take_spans_with_equal_times():
    # a coarse clock stamps two spans of one thread alike; their args
    # (dicts) must never be compared
    events = [
        _x("svc.submit", 11.0, 11.010, tid=1, task="a"),
        _x("predictor.predict", 11.002, 11.002, tid=1, task="a", n=1),
        _x("predictor.predict", 11.002, 11.002, tid=1, task="a", n=1),
        _x("lock.wait", 11.004, 11.004, tid=1, task="a"),
        _x("lock.wait", 11.004, 11.004, tid=1, task="b"),
    ]
    assert _metric("cost_predicts_per_submit", events) == 2.0
    assert _metric("ingest_lock_wait_ms", events) == 0.0
