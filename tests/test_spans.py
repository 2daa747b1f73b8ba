"""The program's named host spans (``repro.runtime.spans``): what they
time, that a profile of a mapping query and of a served wave holds them,
and that the numpy mapping path still imports no jax."""

import glob
import subprocess
import sys
from collections import Counter
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

from repro.runtime.spans import span, step_span

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _profiled(tmp_path, fn):
    """Run ``fn`` under the profiler; its result and the ``union.*`` host
    events of the trace as (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events
                    if e.name.startswith("union.")
                ]
    return out, events


def test_a_span_with_a_timer_adds_its_seconds():
    stats = NS(admit_s=0.5)
    with span("map.admit", (stats, "admit_s")):
        pass
    assert stats.admit_s > 0.5
    with pytest.raises(KeyError):  # the time is added when the block raises too
        with span("map.admit", (stats, "admit_s")):
            raise KeyError
    with span("x"), step_span("y", 3):
        pass


def test_the_numpy_mapping_path_imports_no_jax():
    code = (
        "import sys\n"
        "from repro.core.optimizer import union_opt\n"
        "from repro.core.architecture import cloud_accelerator\n"
        "from repro.core.problem import Problem\n"
        "sol = union_opt(Problem.gemm(64, 128, 256, word_bytes=1), cloud_accelerator(),\n"
        "                mapper='random', samples=256, seed=1)\n"
        "assert sol.mapping is not None\n"
        "print(sorted(m for m in sys.modules if m == 'jax' or m.startswith('jax.')))\n"
    )
    p = subprocess.run(
        [sys.executable, "-c", code], env={"PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "[]"


def test_a_mapping_query_on_the_jax_engine_emits_its_spans(tmp_path):
    from repro.core.architecture import cloud_accelerator
    from repro.core.optimizer import union_opt
    from repro.core.problem import Problem

    def query():
        return union_opt(
            Problem.gemm(64, 128, 256, word_bytes=1), cloud_accelerator(),
            mapper="random", engine_backend="jax", samples=1024, seed=3,
        )

    sol, events = _profiled(tmp_path, query)
    n = Counter(name for name, _, _ in events)
    assert n["union.map.query"] == 1
    assert n["union.map.setup"] == 2  # the build, and the engine's close
    for phase in ("generate", "dedup", "admit", "materialize", "offer"):
        assert n[f"union.map.{phase}"] >= 1, phase
    assert sol.search.device_syncs >= 1
    assert n["union.map.sync"] == sol.search.device_syncs
    # every phase lies inside the query
    (q,) = [(s, e) for name, s, e in events if name == "union.map.query"]
    assert all(q[0] <= s and e <= q[1] for _, s, e in events)


def test_a_served_wave_emits_its_spans(tmp_path):
    from repro.configs.base import get_config
    from repro.launch.serve import Request, WaveServer
    from repro.models import init_params

    cfg = get_config("qwen3-0.6b_smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    server = WaveServer(cfg, params, batch_slots=2, max_len=32)
    rng = np.random.default_rng(0)
    wave = [Request(i, rng.integers(0, cfg.vocab, size=k).tolist(), 4) for i, k in enumerate((5, 3))]
    server.run_wave([Request(9, [1, 2], 2), Request(8, [3], 2)])  # compiles the step

    steps, events = _profiled(tmp_path, lambda: server.run_wave(wave))
    n = Counter(name for name, _, _ in events)
    prefill = max(len(r.prompt) for r in wave)
    assert steps == 3  # the first token comes from the prefill
    assert n["union.serve.dispatch"] == prefill + steps  # one a step
    assert n["union.serve.step"] == steps
    assert n["union.serve.pull"] == steps + 1
    for name in ("wave", "init_cache", "prefill", "decode"):
        assert n[f"union.serve.{name}"] == 1, name
    assert all(len(r.out) == 4 for r in wave)
