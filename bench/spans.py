#!/usr/bin/env python3
"""The program's own host spans in a profiler trace, beside the chip's
operations.

The program marks its phases as host annotations named ``union.*``
(``repro.runtime.spans``): the mapping engine's setup, candidate
generation, dedup, admission, materialisation, offers and device syncs,
and the wave server's cache initializer, loops and per-step dispatch,
pull and emit. They are recorded in the same ``.xplane.pb`` as the
device's operations, on the same clock. This module reduces them:

- ``span_s`` and ``span_n``: self time and count per ``union.*`` name
  inside the traced window (``bench.window``);
- ``gaps``: the first chip's idle gaps, each named by the innermost
  ``bench.*`` or ``union.*`` annotation around its midpoint;
- ``idle_by_span``: the first chip's idle time split exactly by the
  innermost annotation open over each part of each gap;
- :func:`span_metrics`: the per-query and per-step host times the spans
  give (see ``METRICS``).

``bench/trace.py`` keeps only the benchmark's own ``bench.*`` spans and
deletes the trace after reducing it, so the benchmark's result line does
not read these yet. Run on a trace kept by ``BENCH_KEEP_TRACE``:

    BENCH_KEEP_TRACE=<dir> python3 bench/run.py --workload <cell> --seed <n> --seconds 50 --trace 1
    python3 bench/spans.py <dir>/<file>.xplane.pb
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace as tr  # noqa: E402

HOST_PREFIXES = ("bench.", "union.")  # the benchmark's and the program's annotations
SPAN_PREFIX = "union."

Segment = Tuple[int, int, str]


@dataclass
class SpanReduction:
    window_s: float
    idle_s: float  # the first chip's idle time in the window
    span_s: Dict[str, float]  # self time per union.* name
    span_n: Dict[str, int]  # count per union.* name
    spans: List[tr.Event]  # the union.* events, clipped to the window
    gaps: List[Tuple[str, float]]  # the first chip's idle gaps, longest first
    idle_by_span: Dict[str, float]  # idle seconds by the innermost annotation

    def self_s(self, names: Iterable[str]) -> Optional[float]:
        """Summed self time of the named spans; None where none occurs."""
        names = list(names)
        if not any(n in self.span_n for n in names):
            return None
        return sum(self.span_s.get(n, 0.0) for n in names)

    def outside(self, outer: str, inner: Iterable[str]) -> float:
        """Seconds of the ``outer`` spans covered by none of ``inner``."""
        names = set(inner)
        cover = tr.union((e.start, e.end) for e in self.spans if e.name in names)
        own = tr.union((e.start, e.end) for e in self.spans if e.name == outer)
        return tr.measure(tr.subtract(own, cover)) / 1e9

    def idle_share_in(self, prefix: str, exclude: Iterable[str] = ()) -> float:
        """Share of the chip's idle time that falls inside a span whose
        name starts with ``prefix``, other than ``exclude``."""
        skip = set(exclude)
        inside = sum(s for n, s in self.idle_by_span.items()
                     if n.startswith(prefix) and n not in skip)
        return inside / self.idle_s if self.idle_s > 0 else float("nan")


def host_events(profile) -> List[tr.Event]:
    """The host's ``bench.*`` and ``union.*`` annotations."""
    out: List[tr.Event] = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(e for e in tr._events(line) if e.name.startswith(HOST_PREFIXES))
    return out


def innermost(host: List[tr.Event], lo: int, hi: int) -> List[Segment]:
    """``[lo, hi)`` cut into ``(start, end, name)`` segments, each named
    by the innermost annotation open over it ("host" where none is)."""
    out: List[Segment] = []
    stack: List[tr.Event] = []
    cur = lo

    def upto(t: int) -> None:
        nonlocal cur
        t = min(t, hi)
        if t > cur:
            out.append((cur, t, stack[-1].name if stack else "host"))
            cur = t

    for ev in sorted(host, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1].end <= ev.start:
            upto(stack[-1].end)
            stack.pop()
        upto(ev.start)
        stack.append(ev)
    while stack:
        upto(stack[-1].end)
        stack.pop()
    upto(hi)
    return out


def overlaps(segments: List[Segment], gaps: List[tr.Interval]) -> Iterator[Segment]:
    """The parts of the sorted ``segments`` inside the sorted, disjoint
    ``gaps``, as ``(start, end, name)``."""
    j = 0
    for s, e, name in segments:
        while j < len(gaps) and gaps[j][1] <= s:
            j += 1
        k = j
        while k < len(gaps) and gaps[k][0] < e:
            a, b = max(s, gaps[k][0]), min(e, gaps[k][1])
            if b > a:
                yield a, b, name
            k += 1


def reduce_spans(profile) -> SpanReduction:
    devices, _ = tr.read_planes(profile)
    if not devices:
        raise ValueError("the trace holds no device operations")
    host = host_events(profile)
    windows = [e for e in host if e.name == tr.WINDOW]
    if windows:
        lo, hi = windows[0].start, windows[0].end
    else:
        lo = min(e.start for d in devices.values() for e in d.ops)
        hi = max(e.end for d in devices.values() for e in d.ops)
    host = [e for e in host if e.name != tr.WINDOW]
    first = devices[min(devices, key=lambda n: int(n.rsplit(":", 1)[1]))]
    busy = tr.union(tr.clip(((e.start, e.end) for e in first.ops), lo, hi))
    gaps = tr.subtract([(lo, hi)], busy)
    spans = [
        tr.Event(e.name, max(e.start, lo), min(e.end, hi))
        for e in host
        if e.name.startswith(SPAN_PREFIX) and e.end > lo and e.start < hi
    ]
    span_n: Dict[str, int] = defaultdict(int)
    for e in spans:
        span_n[e.name] += 1
    idle_by_span: Dict[str, float] = defaultdict(float)
    for s, e, name in overlaps(innermost(host, lo, hi), gaps):
        idle_by_span[name] += (e - s) / 1e9
    return SpanReduction(
        window_s=(hi - lo) / 1e9,
        idle_s=tr.measure(gaps) / 1e9,
        span_s=tr.self_times(spans),
        span_n=dict(span_n),
        spans=spans,
        gaps=sorted(((tr._host_doing(host, (s + e) // 2), (e - s) / 1e9) for s, e in gaps),
                    key=lambda g: -g[1]),
        idle_by_span=dict(idle_by_span),
    )


# ------------------------------------------------------------------ #
# what the spans give per query and per step (ms)
# ------------------------------------------------------------------ #
QUERY = "union.map.query"
WAVE = "union.serve.wave"
DISPATCH = "union.serve.dispatch"
STEP_PARTS = (DISPATCH, "union.serve.pull", "union.serve.emit")

# per-query self time of these spans, over the query spans
PER_QUERY = {
    "map.setup_ms_per_query": ("union.map.setup",),
    "map.mapper_ms_per_query": ("union.map.generate", "union.map.offer"),
    "map.engine_host_ms_per_query": ("union.map.dedup", "union.map.admit", "union.map.materialize"),
    "map.sync_ms_per_query": ("union.map.sync",),
}
METRICS = tuple(PER_QUERY) + ("serve.host_ms_per_step", "serve.wave_edge_ms")


def span_metrics(red: SpanReduction) -> Dict[str, float]:
    """The per-query and per-step host times the trace's spans give; a
    number whose spans are absent is left out."""
    out: Dict[str, float] = {}
    queries = red.span_n.get(QUERY, 0)
    for name, spans in PER_QUERY.items():
        s = red.self_s(spans)
        if queries and s is not None:
            out[name] = 1e3 * s / queries
    steps = red.span_n.get(DISPATCH, 0)
    if steps:  # dispatch and emit per step: one dispatch a step
        out["serve.host_ms_per_step"] = 1e3 * red.self_s((DISPATCH, "union.serve.emit")) / steps
    waves = red.span_n.get(WAVE, 0)
    if waves:  # a wave's time outside its steps' dispatch, pull and emit
        out["serve.wave_edge_ms"] = 1e3 * red.outside(WAVE, STEP_PARTS) / waves
    return out


def summary(red: SpanReduction, top: int = 10) -> Dict:
    return {
        "window_s": red.window_s,
        "idle_s": red.idle_s,
        "metrics": span_metrics(red),
        "span_s": dict(sorted(red.span_s.items(), key=lambda kv: -kv[1])),
        "span_n": red.span_n,
        "idle_gaps": red.gaps[:top],
        "idle_by_span": sorted(red.idle_by_span.items(), key=lambda kv: -kv[1])[:top],
        "idle_share_in_map_phases": red.idle_share_in("union.map.", exclude=(QUERY,)),
    }


def main(argv=None) -> int:
    paths = sys.argv[1:] if argv is None else argv
    if not paths:
        print(__doc__, file=sys.stderr)
        return 2
    for p in paths:
        print(json.dumps(dict(summary(reduce_spans(tr.load(Path(p)))), trace=str(p))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
