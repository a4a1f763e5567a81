"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Emits CSV blocks per benchmark plus the derived headline numbers that
EXPERIMENTS.md §Paper-validation quotes.
"""
from __future__ import annotations

import argparse
import sys


def _csv(rows, keys=None):
    if not rows:
        print("(no rows)")
        return
    keys = keys or list(rows[0].keys())
    print(",".join(keys))
    for r in rows:
        vals = []
        for k in keys:
            v = r.get(k, "")
            vals.append(f"{v:.6g}" if isinstance(v, float) else str(v))
        print(",".join(vals))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer evaluations / smaller sizes")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    n_evals = 30 if args.quick else 100
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    if want("table3"):
        from repro.configs.workloads import resource_table
        print("\n== Table III: resource requests ==")
        rows = [{"bench": k, **v} for k, v in resource_table().items()]
        _csv(rows)

    if want("scheduler"):
        from benchmarks import scheduler_comparison
        print("\n== Fig. 3: scheduler comparison (makespan / CPU / overhead) ==")
        rows = scheduler_comparison.run(n_evals=n_evals)
        _csv(rows)
        print("\n-- derived headline numbers --")
        for k, v in scheduler_comparison.derived(rows).items():
            print(f"{k},{v:.4g}")

    if want("slr"):
        from benchmarks import slr
        print("\n== Fig. 4: SLR ==")
        _csv(slr.run(n_evals=n_evals))

    if want("umb"):
        from benchmarks import umb_slurm
        print("\n== Figs. 5-6 (Appendix A): UM-Bridge SLURM backend ==")
        _csv(umb_slurm.run(n_evals=n_evals))

    if want("gp"):
        from benchmarks import gp_throughput
        print("\n== GP surrogate throughput ==")
        _csv(gp_throughput.run(sizes=(128, 512) if args.quick
                               else (128, 512, 1024)))

    if want("live"):
        from benchmarks import executor_live
        print("\n== Live executor: real JAX tasks (GS2 proxy + GP) ==")
        _csv(executor_live.run(n_tasks=12 if args.quick else 24))

    if want("roofline"):
        from benchmarks import roofline
        print("\n== Roofline table (from dry-run artifacts) ==")
        rows = roofline.run()
        if rows:
            _csv(rows)
            print("\n-- summary --")
            for k, v in roofline.summary(rows).items():
                print(f"{k},{v}")
        else:
            print("(run `python -m repro.launch.dryrun --all --mesh both` first)")


if __name__ == "__main__":
    main()
