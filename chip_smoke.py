"""Smoke test of the UQ service path on one TPU chip.

    python chip_smoke.py

Drives the main path once through the entry points a user calls:

  1. checks the Pallas GP kernels against the jnp references on the chip;
  2. labels 2,048 seeded Latin-hypercube points over the paper's Table II
     ranges with the GS2 proxy (m = 96) and fits the GP surrogate
     (`gp.fit`) on them;
  3. starts a `ServiceBroker` with the online GP runtime predictor
     (`predictor="gp"`), attaches the surrogate-offload gate on that
     posterior (`Broker.attach_surrogate`), submits a 10,000-task GS2-proxy
     backlog, and serves until 256 tasks have run on the real path;
  4. re-costs the whole backlog through `gp.predict_batch` (1,024-query
     buckets) and checks the compiled re-cost program holds the kernel;
  5. checks every served task ended ok, served results against direct
     `gs2_proxy.evaluate` calls, the surrogate's training-point error, and
     that no best-effort hook swallowed an error.

Timings, the device and the counts go to earlier lines; the last line is
one JSON object naming the device.  Exits non-zero, without that line,
when JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

SEED = 0
N_TRAIN = 2048                     # GP training set (GS2 proxy labels)
N_BACKLOG = 10_000                 # queued GS2-proxy requests
N_SERVE = 256                      # real-path tasks to serve (>= 200)
N_WORKERS = 4
GS2_TIME_REQUEST_S = 900.0         # Table III: HQ time request, GS2
SERVE_TIMEOUT_S = 600.0
RECOST_BUCKET = 1024

# tolerances of the repo's own tests: tests/test_kernels.py (covariance),
# tests/test_offload.py (batched predict vs the triangular-solve predict)
KMAT_TOL = 2e-5
PREDICT_TOL = 1e-4
# re-cost vs the per-task predict, in log-seconds: both are f32 sums over
# alpha, which grows as 1/noise on the runtime GP's small, nearly noiseless
# training sets, so the two orders of summation may differ by ~1e-3
RECOST_TOL = 1e-2
# served vs direct GS2-proxy evaluation: the served solver is a fresh
# compile of the same program with its input shifted and shifted back
# (`gs2_proxy.make_solver`), so theta may differ in its last bits
SERVED_TOL = 1e-3
# surrogate error at its own training points: per output, the RMS in
# original units must stay under this fraction of the output's standard
# deviation, plus an absolute floor (the proxy's mode frequency is zero up
# to rounding, so its standard deviation sits at the 1e-8 floor)
TRAIN_RMS_FRAC = 0.25
TRAIN_RMS_FLOOR = 1e-6


class Smoke:
    def __init__(self):
        self.failures = []
        self.compiles = 0
        self.compile_s = 0.0

    def check(self, name, ok, detail=""):
        print(f"check {name}: {'PASS' if ok else 'FAIL'} {detail}".rstrip(),
              flush=True)
        if not ok:
            self.failures.append(name)

    def on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def phase(self, name, t0, compiles0, **counts):
        extra = " ".join(f"{k}={v}" for k, v in counts.items())
        print(f"phase {name}: seconds={time.perf_counter() - t0} "
              f"compiles={self.compiles - compiles0} {extra}".rstrip(),
              flush=True)


def _max_err(got, want):
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))


def kernel_checks(smoke, x, post):
    """Pallas covariance and fused predict vs the jnp references, on the
    surrogate's own operands."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import gp_kernel, ref
    from repro.uq import gp

    ls = jnp.exp(jnp.clip(post.params.log_lengthscale, -5.0, 5.0))
    var = jnp.exp(jnp.clip(post.params.log_variance, -8.0, 8.0))
    k_pl = gp_kernel.gp_kernel_matrix(x, x, ls, var)
    k_ref = ref.gp_kernel_matrix(x, x, ls, var)
    err = _max_err(k_pl, k_ref)
    smoke.check("gp_kernel_matrix_vs_ref", err <= KMAT_TOL,
                f"n={x.shape[0]} max_err={err} tol={KMAT_TOL}")
    linv = gp.ensure_linv(post)
    xq = x[:RECOST_BUCKET] + 0.01
    m_pl, q_pl = gp_kernel.gp_predict(x, xq, ls, var, post.alpha, linv)
    m_ref, q_ref = ref.gp_predict(x, xq, ls, var, post.alpha, linv)
    jax.block_until_ready((m_pl, q_pl, m_ref, q_ref))
    err_m, err_q = _max_err(m_pl, m_ref), _max_err(q_pl, q_ref)
    smoke.check("gp_predict_vs_ref", max(err_m, err_q) <= PREDICT_TOL,
                f"n={x.shape[0]} s={xq.shape[0]} mean_err={err_m} "
                f"qf_err={err_q} tol={PREDICT_TOL}")


def gs2_factory():
    import numpy as np
    from repro.core import LambdaModel
    from repro.uq import gs2_proxy

    solver = gs2_proxy.make_solver()          # m = 96, per-server compile

    def fn(parameters, config):
        g, f = solver(np.asarray(parameters[0], np.float32))
        return [[g, f]]

    return LambdaModel("gs2", fn, 7, 2,
                       warmup_fn=lambda: solver(np.full(7, 0.5, np.float32)))


def run(smoke, *, n_train=N_TRAIN, n_backlog=N_BACKLOG, n_serve=N_SERVE,
        fit_steps=200, check_kernel_in_hlo=True):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import EvalRequest
    from repro.sched.offload import SurrogateOffload
    from repro.service import ServiceBroker
    from repro.uq import gp, gs2_proxy, sampling

    # -- surrogate: label and fit --------------------------------------
    t0, c0 = time.perf_counter(), smoke.compiles
    x_train = jnp.asarray(sampling.latin_hypercube(n_train, seed=SEED),
                          jnp.float32)
    g, f, _ = jax.vmap(gs2_proxy.solve)(x_train)
    y_train = jnp.stack([g, f], axis=1)
    jax.block_until_ready(y_train)
    smoke.phase("label", t0, c0, n=n_train, m=gs2_proxy.DEFAULT_RESOLUTION)
    smoke.check("labels_finite", bool(jnp.all(jnp.isfinite(y_train))))

    t0, c0 = time.perf_counter(), smoke.compiles
    post = gp.fit(x_train, y_train, steps=fit_steps)
    jax.block_until_ready(post.alpha)
    smoke.phase("fit", t0, c0, n=n_train, steps=fit_steps)
    smoke.check("fit_finite", bool(
        all(bool(jnp.all(jnp.isfinite(a)))
            for a in jax.tree.leaves(post.params.tree()))
        and bool(jnp.all(jnp.isfinite(post.alpha)))))

    t0, c0 = time.perf_counter(), smoke.compiles
    kernel_checks(smoke, x_train, post)
    mean, _ = gp.predict_batch(post, x_train)
    rms = np.sqrt(np.mean((np.asarray(mean, np.float64)
                           - np.asarray(y_train, np.float64)) ** 2, axis=0))
    bound = TRAIN_RMS_FRAC * np.asarray(post.y_std) + TRAIN_RMS_FLOOR
    smoke.check("surrogate_training_error", bool(np.all(rms <= bound)),
                f"rms={rms.tolist()} bound={bound.tolist()}")
    smoke.phase("kernel_checks", t0, c0)

    # -- the service: backlog, offload gate, serving --------------------
    t0, c0 = time.perf_counter(), smoke.compiles
    svc = ServiceBroker({"gs2": gs2_factory}, predictor="gp",
                        inner_policy="sjf", n_workers=N_WORKERS)
    offload = SurrogateOffload(post, model_name="gs2", max_points=None,
                               condition_every=64)
    svc.broker.attach_surrogate(offload)
    deadline = time.monotonic() + 60.0
    while not any(a.virtual and a.open for a in svc.broker.allocations()):
        if time.monotonic() > deadline:
            raise TimeoutError("surrogate allocation never opened")
        time.sleep(0.01)
    thetas = sampling.latin_hypercube(n_backlog, seed=SEED + 1)
    backlog = [EvalRequest("gs2", [th.tolist()],
                           time_request=GS2_TIME_REQUEST_S)
               for th in thetas]
    theta_of = {}
    for req, th in zip(backlog, thetas):
        theta_of[svc.submit(req)] = th
    smoke.phase("submit", t0, c0, tasks=n_backlog)

    t0, c0 = time.perf_counter(), smoke.compiles
    deadline = time.monotonic() + SERVE_TIMEOUT_S
    while True:
        recs = svc.records()
        real = [r for r in recs if not r.worker.endswith("-surrogate")]
        if sum(r.status == "ok" for r in real) >= n_serve:
            break
        if any(r.status != "ok" for r in recs) or \
                time.monotonic() > deadline:
            break
        time.sleep(0.1)
    svc.shutdown()
    # tasks already running finish, and feed the predictor, after this
    deadline = time.monotonic() + 120.0
    while svc.metrics()["running"] and time.monotonic() < deadline:
        time.sleep(0.05)
    metrics = svc.metrics()
    recs = svc.records()
    real = [r for r in recs if not r.worker.endswith("-surrogate")]
    predictor = svc.broker.predictor
    stats = offload.stats()
    smoke.phase("serve", t0, c0, served=len(recs), served_real=len(real),
                served_offloaded=len(recs) - len(real),
                refits=predictor.n_fits, offloads=stats.n_offloaded,
                offload_decisions=stats.n_considered)
    smoke.check("served_enough", len(real) >= n_serve,
                f"real={len(real)} want>={n_serve}")
    bad = sorted({r.status for r in recs} - {"ok"})
    smoke.check("served_all_ok", not bad, f"statuses={bad}")
    smoke.check("predictor_refit", predictor.n_fits >= 1,
                f"refits={predictor.n_fits}")
    smoke.check("offload_gate_ran", stats.n_considered >= n_backlog,
                f"decisions={stats.n_considered}")
    swallowed = metrics["swallowed_errors"]
    smoke.check("no_swallowed_errors", not swallowed, f"{swallowed}")

    # -- served results vs direct evaluation -----------------------------
    t0, c0 = time.perf_counter(), smoke.compiles
    worst = 0.0
    for r in real:
        got = svc.result(r.task_id, timeout=1.0).value[0]
        want = gs2_proxy.evaluate(theta_of[r.task_id])
        worst = max(worst, _max_err(got, want))
    smoke.phase("direct_evaluate", t0, c0, tasks=len(real))
    smoke.check("served_vs_direct", worst <= SERVED_TOL,
                f"tasks={len(real)} max_err={worst} tol={SERVED_TOL}")

    # -- re-cost the backlog through the batched predict -----------------
    launches0 = sum(v for k, v in gp.predict_batch_shapes.items()
                    if k[-1] == RECOST_BUCKET)
    for rep in range(2):
        t0, c0 = time.perf_counter(), smoke.compiles
        costs = svc.broker.costs(backlog)
        launches = sum(v for k, v in gp.predict_batch_shapes.items()
                       if k[-1] == RECOST_BUCKET) - launches0
        smoke.phase(f"recost_{rep}", t0, c0, tasks=len(backlog),
                    bucket_launches=launches)
        launches0 += launches
    # reference: the per-task predict (triangular solve, no kernel)
    want = [predictor.predict(req) for req in backlog[:64]]
    err = _max_err(np.log(costs[:64]), np.log(want))
    post_rt = predictor._post
    smoke.check("recost_vs_predict", err <= RECOST_TOL and
                bool(np.all(np.isfinite(costs))),
                f"n_train={post_rt.x.shape[0]} max_err={err} "
                f"tol={RECOST_TOL}")
    if check_kernel_in_hlo:
        hlo = jax.jit(lambda q: gp.predict_batch(post_rt, q)).lower(
            jnp.zeros((RECOST_BUCKET, post_rt.x.shape[1]), jnp.float32)
        ).compile().as_text()
        smoke.check("recost_program_has_kernel", "tpu_custom_call" in hlo)


def main() -> int:
    root = pathlib.Path(__file__).resolve().parent
    if not (root / "src" / "repro").is_dir():
        print("chip_smoke: the repro package is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(root / "src"))
    import jax
    from repro.compile_cache import enable_compile_cache

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend {backend!r})",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device {json.dumps(device)} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)
    smoke = Smoke()
    jax.monitoring.register_event_duration_secs_listener(smoke.on_event)
    t0 = time.perf_counter()
    run(smoke)
    print(f"total seconds={time.perf_counter() - t0} "
          f"compiles={smoke.compiles} compile_seconds={smoke.compile_s}",
          flush=True)
    if smoke.failures:
        print(f"chip_smoke: failed checks: {smoke.failures}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
