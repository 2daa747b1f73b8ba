"""The benchmark's data-driven core: find a cell's files by name, check the
chip, run the cell's driver, and print the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

- ``bench/configs/<config>.json``  sizes of the model as it is run
- ``bench/traffic/<mix>.json``     ``{"driver": <name>, ...parameters}``
- ``bench/drivers/<driver>.py``    one driver per entry point, ``run(ctx)``
- ``bench/metrics/<metric>.py``    one reader per per-layer metric,
  ``read(ctx) -> float | None``

A later cell, mix or metric is therefore new files and new entries only.
"""

from __future__ import annotations

import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class BenchError(RuntimeError):
    """A cell that cannot be run as specified (missing file, no chip)."""


def load_json(path: Path) -> Any:
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    """Import one file of the benchmark by its path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_dyn.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: Path


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT, bench_dir: Path = BENCH_DIR) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    w = by_name[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=load_json(root / conf["file"]),
        traffic=load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
        bench_dir=bench_dir,
    )


# ------------------------------------------------------------------ #
# the device
# ------------------------------------------------------------------ #
def require_chips(count: int):
    """The TPU devices the cell asks for; raise when there are fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU, found platform {devices[0].platform!r}")
    if len(devices) < count:
        raise BenchError(f"needs {count} TPU chips, found {len(devices)}")
    return devices[:count]


def device_info(devices) -> Dict[str, Any]:
    """Platform, kind, count and the peak bytes in use on the fullest chip."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks) if peaks else 0,
    }


def peaks_for(kind: str, bench_dir: Path = BENCH_DIR) -> Dict[str, float]:
    table = load_json(bench_dir / "peaks.json")
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


# ------------------------------------------------------------------ #
# what a driver hands back
# ------------------------------------------------------------------ #
@dataclass
class Check:
    """One number compared with its limit: sound when ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class RunContext:
    """What a driver gets, and what it fills in for the metric readers."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float  # process start on the host clock (perf_counter)
    devices: List[Any]
    interpret: bool = False  # Pallas interpret mode (CPU tests only)
    work_dir: Path = BENCH_DIR / ".runs"
    # filled by the driver
    setup_s: float = 0.0
    end_to_end: Dict[str, float] = field(default_factory=dict)
    counters: Dict[str, Any] = field(default_factory=dict)
    checks: List[Check] = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    trace_reduction: Any = None  # bench.trace.Reduction of the traced window
    device: Dict[str, Any] = field(default_factory=dict)
    peaks: Dict[str, float] = field(default_factory=dict)


def enable_compile_cache() -> None:
    """The program's persistent compile cache (in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` says otherwise), keeping every program,
    so that only a cell's first run in a checkout compiles."""
    import jax

    from repro.runtime import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def seconds_since(t0: float) -> float:
    return time.perf_counter() - t0


def run_context(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: Optional[float] = None,
    root: Path = ROOT,
    bench_dir: Path = BENCH_DIR,
    devices=None,
    interpret: bool = False,
    before_run: Optional[Callable[[RunContext], None]] = None,
) -> RunContext:
    """Run one cell; the returned context holds what it measured.

    ``devices`` skips the look for a chip (CPU tests pass the CPU
    devices); the benchmark's own runs leave it None."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(workload, root, bench_dir)
    peaks: Dict[str, float] = {}
    if devices is None:
        devices = require_chips(cell.chips)
        peaks = peaks_for(devices[0].device_kind, bench_dir)
        enable_compile_cache()
    ctx = RunContext(
        cell=cell, seed=int(seed), seconds=float(seconds), trace=bool(trace),
        t_start=t_start, devices=list(devices), interpret=interpret,
        work_dir=bench_dir / ".runs", peaks=peaks,
    )
    if before_run is not None:
        before_run(ctx)
    driver = load_module(bench_dir / "drivers" / f"{cell.traffic['driver']}.py",
                         f"drivers.{cell.traffic['driver']}")
    driver.run(ctx)
    return ctx


def run_cell(workload: str, seed: int, seconds: float, trace: bool, **kw) -> Dict[str, Any]:
    """Run one cell and return its result object (not yet printed)."""
    return assemble(run_context(workload, seed, seconds, trace, **kw))


def assemble(ctx: RunContext) -> Dict[str, Any]:
    cell = ctx.cell
    metrics: Dict[str, Dict[str, Any]] = {}
    if not ctx.trace:
        values = dict(ctx.end_to_end, setup_s=ctx.setup_s)
        for m in cell.end_to_end:
            v = values.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            reader = load_module(cell.bench_dir / "metrics" / f"{m['name']}.py",
                                 f"metrics.{m['name']}")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    correct = bool(ctx.checks) and all(c.ok for c in ctx.checks)
    out: Dict[str, Any] = {
        "correct": correct,
        "attempted": int(ctx.attempted),
        "failed": int(ctx.failed),
        "metrics": metrics,
        "device": ctx.device,
    }
    red = ctx.trace_reduction
    if ctx.trace and red is not None:
        out["device"] = dict(ctx.device, busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in red.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in red.top_gaps(10)],
        }
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in ctx.checks}
    return out


def print_result(result: Dict[str, Any], counters: Optional[Dict[str, Any]] = None) -> None:
    """Counters and each compared number on standard error, its last
    lines; the result object as the last line of standard output."""
    if counters:
        scalars = {k: v for k, v in counters.items() if not isinstance(v, (list, tuple))}
        print("counters " + json.dumps(scalars, default=str), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
