"""The comparison that decides `correct`.

Each number has its own limit (the readings each limit was set from are
in PERF.md):

* accounting, exact (limit 0): every task the run finished ended `ok`
  (`tasks_not_ok`); every submitted task is finished or still queued when
  the service stops (`tasks_lost`); no task was evaluated, by a model
  server or by the surrogate, more than once, and no result was stored
  twice (`tasks_served_twice`);
* real-path answers: a sample of the answers due in the window, drawn
  from the seed with the longest-running task in it, read back once the
  window has closed and compared with the plain reference by the task
  kind's `compare`;
* where the configuration attaches the offload gate, against the plain
  GP reference (`bench/reference/gp.py`) built on the conditioning set
  that the program used, read back from it (`bench/harness/gatelog.py`),
  on up to `MAX_SETS` of the window's sets drawn from the seed:
  - `surrogate_mean_gap`: a sample of the window's offloaded answers, the
    largest |served mean - reference mean| in units of each output's sd
    (none where the window offloaded nothing: `gate_wrong` then says
    whether it should have);
  - `trust_sd_gap`: a sample of the window's trust launches, the largest
    |the gate's latent sd - the reference's| (a window with no trust
    launch is not correct: `runtime_budget_s` is set so that about half
    the tasks reach it);
  - `gate_wrong` (exact): of that sample, tasks routed against the
    reference's decision by more than `trust_sd_gap`'s limit: offloaded
    where the reference's sd is above `sd_threshold`, or sent to the real
    path where it is below;
  - `surrogate_set_foreign` (exact): rows of those conditioning sets that
    are neither one of the benchmark's labels nor a real-path answer that
    this run served at that input, and rows held twice."""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.harness import sampling
from bench.harness.cell import SEED_SAMPLE

SURROGATE = "-surrogate"
MAX_SETS = 8          # conditioning sets the gate's numbers are read on


def service_notes(svc) -> dict:
    """Counts the run prints on earlier lines."""
    notes = {"predictor_fits": svc.broker.predictor.n_fits,
             "swallowed_errors": svc.metrics()["swallowed_errors"]}
    sur = svc.broker.surrogate
    if sur is not None:
        st = sur.stats()
        notes["offload_decisions"] = st.n_considered
        notes["offloaded"] = st.n_offloaded
        notes["surrogate_evals"] = st.n_surrogate_evals
    return notes


def _sample(items: list, k: int, rng, first: Optional[int] = None) -> list:
    """Up to `k` of `items` drawn by `rng`, always with `items[first]`."""
    if len(items) <= k:
        return list(items)
    rest = [i for i in range(len(items)) if i != first]
    pick = sorted(rng.choice(len(rest), size=k - (first is not None),
                             replace=False))
    head = [items[first]] if first is not None else []
    return head + [items[rest[i]] for i in pick]


@dataclasses.dataclass
class Gathered:
    """What the checks read back from the service before it is let go.
    The control puts its own answers in the program's place here."""
    sample: list                       # TaskRecords of real-path answers
    values: list                       # their answers
    accounting: Dict[str, float]
    # offloaded answers: (input, served [M], conditioning set index)
    offloaded: List[Tuple[np.ndarray, np.ndarray, int]]
    # trust launches: (input, the gate's sd, set index, offloaded?)
    trust: List[Tuple[np.ndarray, float, int, bool]]
    sets: Dict[int, Tuple[np.ndarray, np.ndarray]]
    set_foreign: int = 0
    notes: dict = dataclasses.field(default_factory=dict)


def _accounting(run, svc) -> dict:
    finished = {r.task_id: r.status for r in run.records}
    queued = {req.task_id for req, _ in svc.broker.pending()}
    lost = set(run.thetas) - set(finished) - queued
    keys = {sampling.input_key(th) for th in run.thetas.values()}
    twice = sum(n > 1 for k, n in collections.Counter(run.served).items()
                if k in keys)
    twice += sum(n > 1 for n in collections.Counter(run.ended).values())
    return {"tasks_not_ok": sum(s != "ok" for s in finished.values()),
            "tasks_lost": len(lost), "tasks_served_twice": twice}


def _real_sample(run):
    """Up to `sample` of the window's real-path answers that ended ok,
    drawn from the seed, always with the longest-running one."""
    done = sorted((r for r in run.completed(real_only=True)
                   if r.status == "ok"), key=lambda r: r.task_id)
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: done[i].compute_t)
    return _sample(done, int(run.cfg["checks"]["sample"]),
                   sampling.stream(run.seed, SEED_SAMPLE), longest)


def _flat(value) -> np.ndarray:
    return np.asarray(value, np.float64).reshape(-1)


def _gate(run, svc, data, g: Gathered) -> None:
    """The offloaded answers and trust launches of the window, a sample of
    each, the conditioning sets they used, and the check of those sets
    against what the run fed them."""
    log, k = run.gate, int(run.cfg["checks"]["sample"])
    rng = sampling.stream(run.seed, SEED_SAMPLE + 1)
    window = {r.task_id: r for r in run.completed() if r.status == "ok"}
    by_key = {sampling.input_key(th): tid for tid, th in run.thetas.items()}
    # the last trust launch of each task decided its route
    decided = {}
    for key, sd, s in log.trust:
        decided[key] = (sd, s)
    trust = []
    for key in sorted(decided):
        tid = by_key.get(key)
        if tid in window and decided[key][1] is not None:
            offloaded = window[tid].worker.endswith(SURROGATE)
            trust.append((run.thetas[tid], decided[key][0], decided[key][1],
                          offloaded))
    answered = {key: s for key, s in log.answers}
    offl = sorted(tid for tid, r in window.items()
                  if r.worker.endswith(SURROGATE))
    offl = [tid for tid in offl
            if answered.get(sampling.input_key(run.thetas[tid])) is not None]
    g.notes["trust_launches_in_window"] = len(trust)
    g.notes["offloaded_in_window"] = len(offl)
    g.notes["unattributed"] = (
        sum(s is None for _, _, s in log.trust)
        + sum(s is None for _, s in log.answers))
    # the reference factors each set it reads anew: sample from a few
    sets = sorted({answered[sampling.input_key(run.thetas[tid])]
                   for tid in offl} | {t[2] for t in trust})
    keep = set(_sample(sets, MAX_SETS, rng))
    offl = [tid for tid in offl
            if answered[sampling.input_key(run.thetas[tid])] in keep]
    trust = [t for t in trust if t[2] in keep]
    for tid in _sample(offl, k, rng):
        th = run.thetas[tid]
        g.offloaded.append((th, _flat(svc.result(tid, timeout=1.0).value),
                            answered[sampling.input_key(th)]))
    g.trust = _sample(trust, k, rng)
    used = {s for *_, s in g.offloaded} | {t[2] for t in g.trust}
    g.sets = {s: log.sets[s] for s in sorted(used)}
    # what each row of a set may be: a label, or a real-path answer
    fed = {sampling.input_key(x): np.float32(y).tobytes()
           for x, y in zip(data.x_train, data.y_train)}
    for r in run.records:
        if r.status == "ok" and not r.worker.endswith(SURROGATE):
            th = run.thetas[r.task_id]
            fed[sampling.input_key(th)] = np.asarray(
                svc.result(r.task_id, timeout=1.0).value,
                np.float32).reshape(-1).tobytes()
    foreign = 0
    logged = {s for *_, s in log.trust} | {s for _, s in log.answers}
    for s in sorted(logged - {None}):
        x, y = log.sets[s]
        keys = [sampling.input_key(row) for row in x]
        foreign += len(keys) - len(set(keys))
        foreign += sum(fed.get(kx) != row.tobytes()
                       for kx, row in zip(keys, y))
    g.set_foreign = foreign


def gather(run, svc, data) -> Gathered:
    """Read back, while the service still holds them, every answer and
    count the checks compare."""
    sample = _real_sample(run)
    values = [svc.result(r.task_id, timeout=1.0).value for r in sample]
    g = Gathered(sample, values, _accounting(run, svc), [], [], {})
    if run.gate is not None:
        _gate(run, svc, data, g)
    return g


def compare(run, data, g: Gathered) -> dict:
    limits = run.cfg["checks"]["limits"]
    out = {k: {"value": float(v), "limit": 0.0}
           for k, v in g.accounting.items()}
    if not g.sample:
        out["answers_missing"] = {"value": 1.0, "limit": 0.0}
        return out
    thetas = np.stack([run.thetas[r.task_id] for r in g.sample])
    gaps = run.task.compare(g.values, thetas, run.cfg, data)
    for name, v in gaps.items():
        out[name] = {"value": v, "limit": float(limits[name])}
    if run.gate is not None:
        out.update(compare_gate(run, data, g))
    return out


def compare_gate(run, data, g: Gathered) -> dict:
    from bench.reference import gp as ref
    limits = run.cfg["checks"]["limits"]
    thr = float(run.cfg["surrogate"]["offload"]["sd_threshold"])
    band = float(limits["trust_sd_gap"])
    refs = {s: ref.Posterior(x, y, data.hyper) for s, (x, y) in g.sets.items()}
    out = {}
    if g.offloaded:
        gaps = []
        for th, served, s in g.offloaded:
            mean, _ = refs[s].predict(np.asarray(th)[None])
            gaps.append(np.max(np.abs(served - mean[0]) / refs[s].y_std))
        out["surrogate_mean_gap"] = {"value": float(max(gaps)),
                                     "limit": float(limits[
                                         "surrogate_mean_gap"])}
    if g.trust:
        gap, wrong = 0.0, 0
        for th, sd, s, offloaded in g.trust:
            _, want = refs[s].predict(np.asarray(th)[None])
            want = float(want[0])
            gap = max(gap, abs(sd - want))
            wrong += (want > thr + band) if offloaded else (want < thr - band)
        out["trust_sd_gap"] = {"value": gap, "limit": band}
        out["gate_wrong"] = {"value": float(wrong), "limit": 0.0}
    else:
        out["trust_launches_missing"] = {"value": 1.0, "limit": 0.0}
    out["surrogate_set_foreign"] = {"value": float(g.set_foreign),
                                    "limit": 0.0}
    return out
