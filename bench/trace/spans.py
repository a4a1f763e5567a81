"""The program's host spans, read from the events of its `repro.obs`
Tracer (`run.tracer_events`): ``X`` spans by name, and the spans of one
name that lie inside each span of another on the same thread's track.
A program without such spans gives empty lists, never an error."""
from __future__ import annotations

import bisect
import collections
from typing import Iterable, List, Tuple

# (start s, end s, (pid, tid), args) on the executor's clock
Span = Tuple[float, float, Tuple[int, int], dict]


def named(events: Iterable, name: str) -> List[Span]:
    return [(ts, ts + dur, (pid, tid), args or {})
            for ts, ph, n, pid, tid, dur, args in events or ()
            if ph == "X" and n == name]


def inside(outer: List[Span], inner: List[Span]) -> List[List[Span]]:
    """For each span of `outer`, the spans of `inner` it contains on its
    own track."""
    tracks = collections.defaultdict(list)
    for s in inner:
        tracks[s[2]].append(s)
    starts = {}
    for track, spans in tracks.items():
        spans.sort(key=lambda s: s[:2])        # args are dicts: no order
        starts[track] = [s[0] for s in spans]
    out = []
    for a, b, track, _ in outer:
        spans = tracks.get(track, [])
        got = []
        i = bisect.bisect_left(starts.get(track, []), a)
        while i < len(spans) and spans[i][0] <= b:
            if spans[i][1] <= b:
                got.append(spans[i])
            i += 1
        out.append(got)
    return out


def union_in(spans: List[Span], lo: float, hi: float) -> float:
    """Seconds of [lo, hi) that at least one span covers."""
    total, t = 0.0, lo
    for a, b in sorted((max(s[0], lo), min(s[1], hi)) for s in spans):
        a = max(a, t)
        if b > a:
            total += b - a
            t = b
    return total
