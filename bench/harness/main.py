"""`python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`: one run of one cell of `BENCHMARK.json`.

The cell names a configuration (`bench/configs/<config>.json`) and a
traffic mix (`bench/traffic/<traffic>.json`); the configuration names its
task kind (`bench/tasks/<kind>.py`); each metric is read by
`bench/metrics/<metric>.py`.  All are found by name, so a cell, a mix or a
metric is added by adding files and entries.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), `device`, with ``--trace 1``
`breakdown`, and last `checks`, each compared number beside its limit.
Exits 1, printing no result, when JAX finds no TPU or too few chips."""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import pathlib
import shutil
import sys
import time

RUN_DIR = pathlib.Path(".bench_run")


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def parse(argv):
    p = argparse.ArgumentParser(prog="bench/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def metric_specs(spec: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or with tracing its per-layer ones:
    every entry whose `workloads` lists the cell, or that has no list."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_module(root: pathlib.Path, group: str, name: str):
    """`bench/<group>/<name>.py` of this checkout, found by name."""
    path = root / "bench" / group / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.{group}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_metrics(run, root: pathlib.Path, specs: list) -> dict:
    out = {}
    for m in specs:
        value = load_module(root, "metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv, t_start: float, root: pathlib.Path, on_gathered=None) -> int:
    """One run; `on_gathered(run, data, gathered)`, where given, is handed
    what the checks compared (`bench/control.py` puts its own answers in
    the program's place there)."""
    args = parse(argv)
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        print(f"bench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    src = root / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program is not in {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    from bench.harness import cell as cell_mod, checks, device
    try:
        devices = device.require_tpu(int(cell["chips"]))
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    device.enable_compile_cache(root)
    programs = device.ProgramCounter().install()

    cfg = load_json(root / "bench" / "configs" / f"{cell['config']}.json")
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    task = load_module(root, "tasks", cfg["task"]["kind"])
    run = cell_mod.Run(cell, cfg, traffic, task, args.seed, args.seconds,
                       bool(args.trace), t_start)
    run.peaks = load_json(root / "bench" / "peaks.json")
    run_dir = (root / RUN_DIR).resolve()
    trace_dir = run_dir / f"trace-{cell['name']}"
    shutil.rmtree(trace_dir, ignore_errors=True)

    tracer = None
    if run.trace:
        from repro.obs import Tracer
        tracer = Tracer(capacity=1 << 22)
    t0 = time.monotonic()
    data = cell_mod.make_data(cfg, args.seed)
    run.notes["setup_data_s"] = time.monotonic() - t0
    cell_mod.log(run, "data made")
    svc = cell_mod.build_service(run, data, tracer)
    try:
        cell_mod.DRIVERS[traffic["pattern"]](run, svc, programs, trace_dir)
    finally:
        cell_mod.stop_service(svc)
    run.records = svc.records()
    run.notes.update(checks.service_notes(svc))
    dev = device.describe(devices)
    dev["memory_peak_bytes"] = device.memory_peak_bytes(devices)
    run.device_kind = dev["kind"]
    if tracer is not None:
        run.tracer_events = tracer.events()
    if run.trace:
        from bench.trace import reduce as trace_reduce
        run.trace_result = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev["busy_s"] = run.trace_result.busy_s
        dev["window_s"] = run.trace_result.window_s

    # the answers: read back, then the program's state is let go before
    # the reference runs
    gathered = checks.gather(run, svc, data)
    run.notes.update(gathered.notes)
    del svc
    gc.collect()
    compared = checks.compare(run, data, gathered)
    if on_gathered is not None:
        on_gathered(run, data, gathered)

    metrics = read_metrics(run, root,
                           metric_specs(spec, cell["name"], run.trace))
    window = run.completed()
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    run.notes["setup_s"] = run.setup_s
    run.notes["programs_in_window"] = run.programs_in_window
    run.notes["completed_in_window"] = len(window)
    for k, v in run.notes.items():
        print(f"note {k}: {v}", flush=True)
    result = {"correct": correct, "attempted": len(window),
              "failed": sum(r.status != "ok" for r in window),
              "metrics": metrics, "device": dev}
    if run.trace:
        result["breakdown"] = run.trace_result.breakdown
    result["checks"] = compared
    for name, c in compared.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
