"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy and idle time, device time per operation and per
compiled program, collective time left exposed, and the longest idle gaps
named by what the host was doing.

The traced window is the span of the host annotation ``bench.window``,
which :class:`Tracer` puts around the traced work. Every device plane
(``/device:TPU:<n>``) counts; busy time is the union of the intervals in
which an operation ran, averaged over the chips.
"""

from __future__ import annotations

import os
import re
import shutil
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"send|recv|psum|ppermute", re.I,
)
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")

Interval = Tuple[int, int]


@dataclass
class Event:
    name: str
    start: int  # ns
    end: int  # ns


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: List[Event]) -> Dict[str, float]:
    """Seconds per operation name, each event counted without the events
    nested inside it (a loop's body is not counted again in the loop)."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    totals: Dict[str, float] = defaultdict(float)
    stack: List[List] = []  # [event, child ns]

    def pop() -> None:
        ev, child = stack.pop()
        totals[ev.name] += max(0, ev.end - ev.start - child) / 1e9
        if stack:
            stack[-1][1] += ev.end - ev.start

    for ev in evs:
        while stack and stack[-1][0].end <= ev.start:
            pop()
        stack.append([ev, 0])
    while stack:
        pop()
    return dict(totals)


@dataclass
class Device:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    op_s: Dict[str, float]  # self time per operation, summed over chips
    module_s: Dict[str, float]  # device time per compiled program
    module_n: Dict[str, int]  # executions per compiled program
    collective_s: float  # per chip, mean
    collective_exposed_s: float  # per chip, mean: no compute alongside
    gaps: List[Tuple[str, float]]  # idle gaps of the first chip, longest first
    chips: int

    def top_ops(self, n: int) -> List[Tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int) -> List[Tuple[str, float]]:
        return self.gaps[:n]

    def ops_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_s.items() if rx.search(name))

    def modules_matching(self, pattern: str) -> Tuple[int, float]:
        rx = re.compile(pattern)
        n = sum(c for name, c in self.module_n.items() if rx.search(name))
        s = sum(t for name, t in self.module_s.items() if rx.search(name))
        return n, s

    @property
    def idle_share(self) -> float:
        return max(0.0, 1.0 - self.busy_s / self.window_s) if self.window_s > 0 else float("nan")


def short_name(name: str) -> str:
    """An HLO op's instruction name (``%fusion.12 = bf16[...] ...`` ->
    ``fusion.12``); other names as they are."""
    head = name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _events(line) -> List[Event]:
    return [
        Event(short_name(e.name), int(e.start_ns), int(e.start_ns + e.duration_ns))
        for e in line.events
    ]


def read_planes(profile) -> Tuple[Dict[str, Device], List[Event]]:
    """Device planes by name, and the host's ``bench.*`` annotations."""
    devices: Dict[str, Device] = {}
    host: List[Event] = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = Device()
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = _events(line)
                elif line.name == MODULES_LINE:
                    dev.modules = _events(line)
            if dev.ops:
                devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line) if e.name.startswith("bench."))
    return devices, host


def reduce_profile(profile) -> Reduction:
    devices, host = read_planes(profile)
    if not devices:
        raise ValueError("the trace holds no device operations")
    windows = [e for e in host if e.name == WINDOW]
    if windows:
        lo, hi = windows[0].start, windows[0].end
    else:
        lo = min(e.start for d in devices.values() for e in d.ops)
        hi = max(e.end for d in devices.values() for e in d.ops)
    busy, coll, exposed = [], [], []
    op_s: Dict[str, float] = defaultdict(float)
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, int] = defaultdict(int)
    first_busy: Optional[List[Interval]] = None
    for name in sorted(devices, key=lambda n: int(n.rsplit(":", 1)[1])):
        dev = devices[name]
        ops = [e for e in dev.ops if e.end > lo and e.start < hi]
        u = union(clip(((e.start, e.end) for e in ops), lo, hi))
        busy.append(measure(u))
        if first_busy is None:
            first_busy = u
        c = union(clip(((e.start, e.end) for e in ops if COLLECTIVE.search(e.name)), lo, hi))
        other = union(clip(((e.start, e.end) for e in ops if not COLLECTIVE.search(e.name)), lo, hi))
        coll.append(measure(c))
        exposed.append(measure(subtract(c, other)))
        for k, v in self_times(ops).items():
            op_s[k] += v
        for e in dev.modules:
            if e.end > lo and e.start < hi:
                module_s[e.name] += (e.end - e.start) / 1e9
                module_n[e.name] += 1
    n = len(devices)
    gaps = subtract([(lo, hi)], first_busy or [])
    named = sorted(
        ((_host_doing(host, (s + e) // 2), (e - s) / 1e9) for s, e in gaps),
        key=lambda g: -g[1],
    )
    return Reduction(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / n / 1e9,
        op_s=dict(op_s),
        module_s=dict(module_s),
        module_n=dict(module_n),
        collective_s=sum(coll) / n / 1e9,
        collective_exposed_s=sum(exposed) / n / 1e9,
        gaps=named,
        chips=n,
    )


def _host_doing(host: List[Event], t: int) -> str:
    """The innermost ``bench.*`` host annotation around time ``t``."""
    around = [e for e in host if e.start <= t < e.end and e.name != WINDOW]
    if not around:
        return "host"
    return min(around, key=lambda e: e.end - e.start).name


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: Path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


class Tracer:
    """Profile the work inside ``with``, annotated as ``bench.window``;
    :meth:`reduce` reads the trace and deletes it."""

    def __init__(self, log_dir: Path) -> None:
        self.log_dir = Path(log_dir)
        self._ann = None

    def __enter__(self) -> "Tracer":
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        self.log_dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no Python call events: they bloat the trace
        opts.host_tracer_level = 1  # annotations only, not every runtime event
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        import jax

        self._ann.__exit__(*exc)
        jax.profiler.stop_trace()

    def reduce(self) -> Reduction:
        path = find_xplane(self.log_dir)
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            Path(keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(path, Path(keep) / path.name)
        try:
            return reduce_profile(load(path))
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)


def annotate(name: str):
    """A host span that names what the host does in the idle gaps."""
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")
