"""One run of one cell: build the service a configuration states, drive
the traffic mix through `ServiceBroker.submit`, open the measured window
once the service is warm, and collect what the window produced.

Everything here is the same for every configuration and traffic mix; what
differs between them is read from their files."""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench.harness import labels, sampling

# seed streams, one per use of the run's seed
SEED_TRAIN, SEED_TRAFFIC, SEED_SAMPLE = 1, 2, 3
WARM_TIMEOUT_S = 240.0
DRAIN_TIMEOUT_S = 60.0


@dataclasses.dataclass
class ClientTask:
    """One request as its client saw it (host clock, seconds)."""
    task_id: str
    t_submit: float          # the submit call began
    t_submitted: float       # the submit call returned
    t_done: float            # the result was in the client's hands
    init_t: float
    compute_t: float


@dataclasses.dataclass
class Data:
    """What set-up makes from the seed before the service starts."""
    x_train: np.ndarray      # [n, 7] surrogate training inputs
    y_train: np.ndarray      # [n, 2] their labels (made by the benchmark)
    hyper: Dict[str, Any]    # the configuration's GP hyperparameters
    posterior: Any           # the program's GPPosterior on (x, y)


def make_data(cfg: dict, seed) -> Data:
    from repro.uq import gp
    import jax.numpy as jnp
    sur = cfg["surrogate"]
    x = sampling.latin_hypercube(sur["n_train"], [int(seed), SEED_TRAIN])
    y = labels.labels(x)
    h = sur["hyperparameters"]
    params = gp.GPParams(jnp.asarray(h["log_lengthscale"], jnp.float32),
                         jnp.asarray(h["log_variance"], jnp.float32),
                         jnp.asarray(h["log_noise"], jnp.float32))
    stub = gp.GPPosterior(params=params, x=None, y=None, y_mean=None,
                          y_std=None, chol=None, alpha=None, kind="rbf")
    post = gp.recondition(stub, x, y)
    return Data(x, y, h, post)


class Run:
    """State of one run, read by the metric readers and the checks."""

    def __init__(self, cell: dict, cfg: dict, traffic: dict, task, seed,
                 seconds: float, trace: bool, t_start: float):
        self.cell, self.cfg, self.traffic, self.task = cell, cfg, traffic, task
        self.seed, self.seconds, self.trace = int(seed), seconds, trace
        self.t_start = t_start
        self.t_open = self.t_close = None
        self.setup_s = None
        self.thetas: Dict[str, np.ndarray] = {}
        self.client: List[ClientTask] = []
        self.records = []                  # the executor's TaskRecords
        self.launches: Dict[tuple, int] = {}
        self.programs_in_window = 0
        self.tracer_events = None          # the program's Tracer, traced runs
        self.trace_result = None           # bench.trace.reduce.Reduction
        self.peaks: Dict[str, Any] = {}    # bench/peaks.json
        self.device_kind: Optional[str] = None
        self.notes: Dict[str, Any] = {}
        self.served: List[bytes] = []      # input_key of every evaluation
        self.ended: List[str] = []         # task id of every stored result
        self.gate = None                   # bench.harness.gatelog.GateLog
        self._lock = threading.Lock()

    # -- what the window holds -----------------------------------------
    def in_window(self, t: float) -> bool:
        return self.t_open <= t < self.t_close

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def completed(self, real_only: bool = False):
        """TaskRecords of tasks that ended in the window, any status."""
        out = [r for r in self.records if self.in_window(r.end_t)]
        if real_only:
            out = [r for r in out if not r.worker.endswith("-surrogate")]
        return out

    def client_completed(self) -> List[ClientTask]:
        return [c for c in self.client if self.in_window(c.t_done)]


def build_service(run: Run, data: Data, tracer=None):
    from repro.sched.predictor import GPRuntimePredictor
    from repro.sched.offload import SurrogateOffload
    from repro.service import ServiceBroker
    from bench.harness.gatelog import GateLog
    svc_cfg = run.cfg["service"]
    predictor = GPRuntimePredictor(**svc_cfg["predictor"])

    def served(parameters):
        run.served.append(sampling.input_key(parameters))

    svc = ServiceBroker(run.task.factories(run.cfg, data, served),
                        predictor=predictor,
                        inner_policy=svc_cfg["inner_policy"],
                        n_workers=run.cfg["n_workers"],
                        persistent_servers=svc_cfg["persistent_servers"],
                        tracer=tracer)
    on_result = svc._ex.on_result

    def stored(req, res):
        # called once for each result the executor stores, under its lock
        run.ended.append(req.task_id)
        on_result(req, res)

    svc._ex.on_result = stored
    gate = run.cfg["surrogate"].get("offload")
    if gate is not None:
        offload = SurrogateOffload(
            data.posterior, model_name=run.task.MODEL,
            runtime_budget_s=gate["runtime_budget_s"],
            sd_threshold=gate["sd_threshold"],
            condition_every=gate["condition_every"],
            max_points=gate["max_points"], backend="exact")
        run.gate = GateLog(offload, served)
        svc.broker.attach_surrogate(offload)
        deadline = time.monotonic() + 60.0
        while not any(a.virtual and a.open
                      for a in svc.broker.allocations()):
            if time.monotonic() > deadline:
                raise TimeoutError("the surrogate allocation never opened")
            time.sleep(0.01)
    return svc


def log(run: Run, msg: str) -> None:
    """A set-up line on standard error, as it happens."""
    print(f"bench: {time.monotonic() - run.t_start:.1f} s: {msg}",
          file=sys.stderr, flush=True)


def _wait_warm(run: Run, svc):
    """Serve until the runtime predictor has seen the traffic's
    `warm_observations` completions (its size cycle and the shapes its
    conditioning races make), so that the window finds the service in its
    steady state."""
    want = int(run.traffic["warm_observations"])
    deadline = time.monotonic() + WARM_TIMEOUT_S
    while svc.broker.predictor.n_observed() < want:
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"warm-up: {svc.broker.predictor.n_observed()} of {want} "
                f"completions after {WARM_TIMEOUT_S} s")
        time.sleep(0.02)
    log(run, f"warm: {want} completions observed")


def _counts(counter) -> collections.Counter:
    """A copy of a counter that worker threads may be updating."""
    while True:
        try:
            return collections.Counter(dict(counter))
        except RuntimeError:               # changed size while copying
            continue


class _Window:
    """Opens and closes the measured window: host clock, the program's
    launch counter, JAX's program counter and, when traced, the profiler."""

    def __init__(self, run: Run, programs, trace_dir):
        self.run, self.programs, self.trace_dir = run, programs, trace_dir

    def __enter__(self):
        from repro.uq import gp
        import jax
        run = self.run
        if run.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        self._launches0 = _counts(gp.predict_batch_shapes)
        self._programs0 = self.programs.n
        self._misses0 = self.programs.misses
        run.notes["setup_programs"] = self.programs.n
        run.notes["setup_cache_hits"] = self.programs.hits
        run.notes["setup_cache_misses"] = self.programs.misses
        run.notes["setup_compile_s"] = self.programs.seconds
        run.t_open = time.monotonic()
        run.setup_s = run.t_open - run.t_start
        self._ann = jax.profiler.TraceAnnotation("bench.window") \
            if run.trace else contextlib.nullcontext()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        from repro.uq import gp
        import jax
        run = self.run
        run.t_close = time.monotonic()
        self._ann.__exit__(*exc)
        run.programs_in_window = self.programs.n - self._programs0
        run.notes["window_cache_misses"] = self.programs.misses \
            - self._misses0
        now = _counts(gp.predict_batch_shapes)
        run.launches = {k: v - self._launches0.get(k, 0)
                        for k, v in now.items()
                        if v - self._launches0.get(k, 0) > 0}
        if run.trace:
            jax.profiler.stop_trace()
        return False


def drive_backlog(run: Run, svc, programs, trace_dir) -> None:
    """The whole backlog is submitted from one client thread before the
    worker pool takes work, as a job array is submitted ahead of its
    allocation; then the pool serves, and the window opens once the
    service is warm.  The backlog cannot drain in the window."""
    n = int(run.traffic["tasks"])
    thetas = sampling.latin_hypercube(n, [run.seed, SEED_TRAFFIC])
    svc._ex.scale_to(0)
    t0 = time.monotonic()
    for th in thetas:
        req = run.task.request(th, run.cfg)
        run.thetas[req.task_id] = th
        svc.submit(req)
    t1 = time.monotonic()
    log(run, f"submitted {n} tasks in {t1 - t0:.1f} s")
    svc._ex.scale_to(int(run.cfg["n_workers"]))
    _wait_warm(run, svc)
    run.notes["setup_submit_s"] = t1 - t0
    run.notes["setup_warm_wait_s"] = time.monotonic() - t1
    with _Window(run, programs, trace_dir):
        time.sleep(run.seconds)


def drive_closed_loop(run: Run, svc, programs, trace_dir) -> None:
    """`clients` client threads, each keeping one task in flight: submit,
    wait for the result, submit the next, each with a new input from the
    seed."""
    import jax
    n_clients = int(run.traffic["clients"])
    pool = sampling.latin_hypercube(int(run.traffic["inputs"]),
                                    [run.seed, SEED_TRAFFIC])
    order = itertools.count()
    stop = threading.Event()
    errors: List[BaseException] = []
    timeout = float(run.traffic["result_timeout_s"])

    def client():
        try:
            while not stop.is_set():
                th = pool[next(order) % len(pool)]
                req = run.task.request(th, run.cfg)
                with run._lock:
                    run.thetas[req.task_id] = th
                ann = jax.profiler.TraceAnnotation("bench.submit") \
                    if run.trace else contextlib.nullcontext()
                t0 = time.monotonic()
                with ann:
                    svc.submit(req)
                t1 = time.monotonic()
                res = svc.result(req.task_id, timeout=timeout)
                t2 = time.monotonic()
                with run._lock:
                    run.client.append(ClientTask(req.task_id, t0, t1, t2,
                                                 res.init_t, res.compute_t))
        except BaseException as e:         # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=client, name=f"bench-client-{i}",
                                daemon=True) for i in range(n_clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    try:
        _wait_warm(run, svc)
        run.notes["setup_warm_s"] = time.monotonic() - t0
        with _Window(run, programs, trace_dir):
            time.sleep(run.seconds)
    finally:
        stop.set()
        for t in threads:
            t.join(DRAIN_TIMEOUT_S + timeout)
    if errors:
        raise errors[0]
    if any(t.is_alive() for t in threads):
        raise TimeoutError("a client did not finish its last task")


DRIVERS = {"backlog": drive_backlog, "closed_loop": drive_closed_loop}


def stop_service(svc) -> None:
    """Shut the service down and wait until every worker thread has
    ended (each finishes the task it holds)."""
    svc.shutdown()
    deadline = time.monotonic() + DRAIN_TIMEOUT_S
    for w in svc._ex.workers:
        w.join(max(deadline - time.monotonic(), 0.0))
    if any(w.is_alive() for w in svc._ex.workers):
        raise TimeoutError("a worker thread did not stop")
