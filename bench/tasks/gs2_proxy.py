"""Task kind `gs2_proxy`: one GS2-proxy solve per task (`uq/gs2_proxy.py`)
on a persistent model server, and its check against the plain reference.

The served answer is [[growth rate, mode frequency]].  The check compares
the growth rate with the dominant eigenvalue of the reference's float64
propagator, the value the power iteration converges to.  The frequency is
v.(A - A^T)v / 2 for a real v, zero in exact arithmetic, so it carries
only rounding and is not compared."""
from __future__ import annotations

import numpy as np

from bench.reference import gs2 as ref

MODEL = "gs2"


def factories(cfg, data, served):
    """`served(parameters)` is called with every input the model server
    evaluates, so that the checks can count how often each task ran."""
    from repro.core import LambdaModel
    from repro.uq import gs2_proxy
    m = int(cfg["task"]["resolution"])

    def factory():
        solver = gs2_proxy.make_solver(m)

        def fn(parameters, config):
            served(parameters)
            g, f = solver(np.asarray(parameters[0], np.float32))
            return [[g, f]]

        return LambdaModel(MODEL, fn, 7, 2, warmup_fn=lambda: solver(
            np.full(7, 0.5, np.float32)))

    return {MODEL: factory}


def request(theta, cfg):
    from repro.core import EvalRequest
    t = cfg["task"]
    return EvalRequest(MODEL, [np.asarray(theta).tolist()],
                       time_request=float(t["time_request_s"]),
                       time_limit=float(t["time_limit_s"]))


def compare(values, thetas, cfg, data, precision: str = "float64"):
    """Served answers vs the reference at the same inputs: the largest
    absolute growth-rate gap."""
    got = np.array([v[0][0] for v in values], np.float64)
    want = ref.growth_rate(np.asarray(thetas), m=int(cfg["task"]["resolution"]),
                           precision=precision)
    return {"gs2_growth_gap": float(np.max(np.abs(got - want)))}
