"""Task kind `gp_predict`: each task evaluates the configuration's GP
surrogate of the GS2 proxy at its input, a single-query `gp.predict`
(the paper's GP benchmark), and its check against the plain reference.

The served answer is [[mean growth, mean frequency, var growth, var
frequency]] in original units.  The check rebuilds the posterior in
float64 from the same training inputs, labels and hyperparameters, and
compares the means (in units of each output's standard deviation) and the
latent sd the outputs share."""
from __future__ import annotations

import numpy as np

from bench.reference import gp as ref

MODEL = "gp"


def factories(cfg, data, served):
    """`served(parameters)` is called with every input the model server
    evaluates, so that the checks can count how often each task ran."""
    from repro.core import LambdaModel
    from repro.uq import gp
    post = data.posterior

    def fn(parameters, config):
        served(parameters)
        mean, var = gp.predict(post, np.asarray(parameters, np.float32))
        return [np.concatenate([np.asarray(mean)[0],
                                np.asarray(var)[0]]).tolist()]

    def factory():
        return LambdaModel(MODEL, fn, 7, 4, warmup_fn=lambda: gp.predict(
            post, np.full((1, 7), 0.5, np.float32)))

    return {MODEL: factory}


def request(theta, cfg):
    from repro.core import EvalRequest
    t = cfg["task"]
    return EvalRequest(MODEL, [np.asarray(theta).tolist()],
                       time_request=float(t["time_request_s"]),
                       time_limit=float(t["time_limit_s"]))


def compare(values, thetas, cfg, data, precision: str = "float64"):
    got = np.asarray([v[0] for v in values], np.float64)      # [S, 4]
    post = ref.Posterior(data.x_train, data.y_train, data.hyper, precision)
    mean, sd = post.predict(np.asarray(thetas))
    mean_gap = np.abs(got[:, :2] - mean) / post.y_std[None, :]
    sd_got = np.sqrt(np.maximum(got[:, 2], 0.0)) / post.y_std[0]
    return {"gp_mean_gap": float(np.max(mean_gap)),
            "gp_sd_gap": float(np.max(np.abs(sd_got - sd)))}
