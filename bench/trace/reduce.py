"""From a JAX profiler trace (`*.xplane.pb`) to the numbers the benchmark
reports: device busy time over the window, per-kernel time, and the
`breakdown` of the device's top operations and longest idle gaps, each gap
named by what the host was doing in it.

The window is the span of the benchmark's own `bench.window` annotation
on the host, so host and device are read on the trace's one clock."""
from __future__ import annotations

import collections
import dataclasses
import pathlib
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# host events that say what the host was doing: the benchmark's own
# annotations and JAX's dispatch of a jitted function
HOST_ACTIVITY = ("bench.", "PjitFunction(")
TOP = 10

Interval = Tuple[float, float]               # seconds on the trace clock


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                            # averaged over the chips
    op_s: Dict[str, float]                   # device op name -> seconds
    breakdown: dict

    def kernel_s(self, name: str) -> float:
        """Seconds of the device ops named `name` (see `op_name`)."""
        return self.op_s.get(name, 0.0)


def op_name(hlo: str) -> str:
    """'%gp_predict_experts.1 = (f32[...]) custom-call(...)' ->
    'gp_predict_experts': the instruction's name without its number."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    base, dot, num = name.rpartition(".")
    return base if dot and num.isdigit() else name


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def host_label(gap: Interval, host: List[Tuple[str, float, float]]) -> str:
    """The host activity that overlaps `gap` the most, or "none"."""
    best, best_s = "none", 0.0
    for name, a, b in host:
        s = min(b, gap[1]) - max(a, gap[0])
        if s > best_s:
            best, best_s = name, s
    return best


def reduce_events(window: Interval,
                  devices: Dict[str, List[Tuple[str, float, float]]],
                  host: List[Tuple[str, float, float]]) -> Reduction:
    """`devices`: per chip, (op name, start s, end s); `host`: (name,
    start s, end s) of host activity other than the window span."""
    lo, hi = window
    busy_total = 0.0
    op_s: Dict[str, float] = collections.Counter()
    gap_list: List[Interval] = []
    for ops in devices.values():
        inside = [(n, max(a, lo), min(b, hi)) for n, a, b in ops
                  if b > lo and a < hi]
        for n, a, b in inside:
            op_s[n] += b - a
        busy = union([(a, b) for _, a, b in inside])
        busy_total += sum(b - a for a, b in busy)
        gap_list += gaps(busy, lo, hi)
    n_dev = max(len(devices), 1)
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gap_list, key=lambda g: g[0] - g[1])[:TOP]
    breakdown = {
        "device_ops": [[n, s] for n, s in top_ops],
        "idle_gaps": [[host_label(g, host), g[1] - g[0]] for g in top_gaps],
    }
    return Reduction(hi - lo, busy_total / n_dev, dict(op_s), breakdown)


def read_xplane(path: pathlib.Path, window_span: str = WINDOW_SPAN):
    """(window, device ops per chip, host activity) from one trace file."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    window: Optional[Interval] = None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != DEVICE_OPS_LINE:
                    continue
                for e in line.events:
                    a = e.start_ns * 1e-9
                    ops.append((op_name(e.name), a,
                                a + e.duration_ns * 1e-9))
        elif plane.name.startswith(HOST_PLANE):
            for line in plane.lines:
                for e in line.events:
                    a = e.start_ns * 1e-9
                    b = a + e.duration_ns * 1e-9
                    if e.name == window_span:
                        window = (a, b)
                    elif b > a and e.name.startswith(HOST_ACTIVITY):
                        host.append((e.name, a, b))
    if window is None:
        raise ValueError(f"{path}: no {window_span!r} span on the host")
    return window, devices, host


def reduce_dir(trace_dir: pathlib.Path) -> Reduction:
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return reduce_events(*read_xplane(files[-1]))
