"""The control of the comparison that decides `correct`: the plain
reference, computed in bfloat16 (every product with bfloat16 operands),
put in the program's place, at a cell's own size.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed it makes one run of the cell in this process, with a
window of `--seconds`, and takes what that run's checks compared: the
sampled real-path answers and, where the configuration attaches the
offload gate, the sampled offloaded answers and trust launches with the
conditioning sets they used.  It prints one JSON line per seed with the
numbers the program reads there (`program`) and those the control reads
on the same inputs and sets (`control`), each beside its limit.  The
control has to read above a limit on at least one number for the
comparison to be worth anything.  The benchmark's own runs never run it."""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import numpy as np


def _gp_values(post, thetas) -> list:
    mean, sd = post.predict(np.asarray(thetas))
    var = (sd[:, None] * post.y_std[None, :]) ** 2
    return [[np.concatenate([mean[i], var[i]]).tolist()]
            for i in range(len(thetas))]


def control_gathered(run, data, g, real: bool = True):
    """`g` with every answer the program gave replaced by the control's
    answer at the same input, on the same conditioning set (with
    ``real=False``, the gate's and the surrogate's answers only)."""
    from bench.reference import gp, gs2
    values = g.values
    if real:
        thetas = np.stack([run.thetas[r.task_id] for r in g.sample])
        if run.cfg["task"]["kind"] == "gs2_proxy":
            grow = gs2.growth_rate(thetas,
                                   m=int(run.cfg["task"]["resolution"]),
                                   precision="bfloat16")
            values = [[[float(v), 0.0]] for v in grow]
        else:
            values = _gp_values(gp.Posterior(data.x_train, data.y_train,
                                             data.hyper, "bfloat16"), thetas)
    posts = {s: gp.Posterior(x, y, data.hyper, "bfloat16")
             for s, (x, y) in g.sets.items()}
    offloaded = []
    for th, _, s in g.offloaded:
        mean, _ = posts[s].predict(np.asarray(th)[None])
        offloaded.append((th, mean[0], s))
    trust = []
    if g.trust:
        thr = float(run.cfg["surrogate"]["offload"]["sd_threshold"])
        for th, _, s, _ in g.trust:
            _, sd = posts[s].predict(np.asarray(th)[None])
            trust.append((th, float(sd[0]), s, bool(sd[0] <= thr)))
    return dataclasses.replace(g, values=values, offloaded=offloaded,
                               trust=trust)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="bench/control.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    root = pathlib.Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(root), str(root / "src")]
    from bench.harness import checks, main as bench_main

    def on_gathered(run, data, g):
        program = checks.compare(run, data, g)
        control = checks.compare(run, data, control_gathered(run, data, g))
        print(json.dumps({"workload": run.cell["name"], "seed": run.seed,
                          "program": program, "control": control}),
              flush=True)

    for seed in args.seeds:
        rc = bench_main.main(
            ["--workload", args.workload, "--seed", str(seed),
             "--seconds", str(args.seconds), "--trace", "0"],
            time.monotonic(), root, on_gathered=on_gathered)
        if rc:
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
