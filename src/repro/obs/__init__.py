"""`repro.obs`: tracing, metrics, attribution, calibration, replay.

One observability layer for both execution paths: because the spans and
counters are instrumented at the shared `LifecycleStepper` / `Broker`
choke points and timestamped by the injected clock, a seeded parity run
produces identical span sequences from `simulate_cluster` and the live
`Executor` (see `tests/test_parity.py`).  Everything is opt-in:
``tracer=None`` / ``registry=None`` defaults keep the hot paths free of
even the tuple-append cost.

Given a tracer, the live service also records where its host time goes:
`Tracer.scope` spans on a ``host`` track per thread (``svc.submit``,
``predictor.*``, ``offload.trust``, ``task.complete``,
``surrogate.observe``, ``task.handoff``) and the dispatch lock's
``lock.wait`` / ``lock.held`` (`TracedLock`), each mirrored into the JAX
profiler's host plane under the same name.  `span_sequence` leaves them
out, so sim/live parity compares scheduling events only.

On top of the recording layer sit the consumers that close the
sim-to-reality gap: `repro.obs.calib` fits per-phase overhead
distributions from a trace into a drop-in `CalibratedBackendSpec` and
watches for drift online (`CalibrationMonitor`), and `repro.obs.replay`
re-runs a recorded workload — bitwise-exactly for sim-recorded traces —
through `simulate_cluster` (`TraceReplay` / `replay_cluster`).
"""
from repro.obs.attribution import (OverheadBreakdown, attribute_overhead,
                                   capacity_intervals, format_breakdown)
from repro.obs.calib import (SACCT_DEFAULT_FIELDS, CalibratedBackendSpec,
                             CalibrationMonitor, PhaseFit, calibrate,
                             extract_phase_samples, fit_lognormal,
                             fit_phase, hlo_runtime_prior, ks_lognormal,
                             parse_slurm_duration, parse_slurm_time,
                             prior_fit, read_sacct, sacct_to_jsonl)
from repro.obs.registry import DEFAULT_EDGES, Histogram, MetricsRegistry
from repro.obs.replay import (ReplayBackendSpec, TraceReplay,
                              replay_cluster)
from repro.obs.trace import (HOST_PID, RingBuffer, TraceEvent, TracedLock,
                             Tracer, read_jsonl, span_sequence,
                             validate_chrome_trace, validate_jsonl_row)

__all__ = [
    "DEFAULT_EDGES",
    "HOST_PID",
    "CalibratedBackendSpec",
    "CalibrationMonitor",
    "Histogram",
    "MetricsRegistry",
    "OverheadBreakdown",
    "PhaseFit",
    "ReplayBackendSpec",
    "RingBuffer",
    "TraceEvent",
    "TraceReplay",
    "TracedLock",
    "Tracer",
    "attribute_overhead",
    "calibrate",
    "capacity_intervals",
    "extract_phase_samples",
    "fit_lognormal",
    "fit_phase",
    "format_breakdown",
    "hlo_runtime_prior",
    "ks_lognormal",
    "parse_slurm_duration",
    "parse_slurm_time",
    "prior_fit",
    "read_jsonl",
    "read_sacct",
    "replay_cluster",
    "sacct_to_jsonl",
    "SACCT_DEFAULT_FIELDS",
    "span_sequence",
    "validate_chrome_trace",
    "validate_jsonl_row",
]
