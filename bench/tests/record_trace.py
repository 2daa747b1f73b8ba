#!/usr/bin/env python3
"""Record the chip fixture ``data/spans.xplane.pb`` that
``test_span_reduction.py`` reads: under :class:`bench.trace.Tracer`, one
wave of a two-layer qwen3-0.6b ``WaveServer`` (four slots, one prefill and
two decode steps) and then one small ``union_opt`` query on the jax
engine, both warmed up first.

    python3 bench/tests/record_trace.py --out <file>.xplane.pb

Runs only where a TPU is attached. The trace is then cut to what the
reductions read (:func:`slim`): the engine's float64 cores alone bring
megabytes of HLO into it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

SLOTS, MAX_LEN, LAYERS = 4, 64, 2


def record(out: Path, work: Path) -> dict:
    import jax

    from bench import spans
    from bench import trace as tr
    from bench.drivers.serve import model_config
    from bench.weights import make_params
    from repro import kernels
    from repro.core.architecture import cloud_accelerator
    from repro.core.optimizer import union_opt
    from repro.core.problem import Problem
    from repro.launch.serve import Request, WaveServer

    cfg_file = json.loads((ROOT / "bench/configs/qwen3-0.6b.json").read_text())
    cfg = dataclasses.replace(model_config(cfg_file), n_layers=LAYERS)
    kernels.enable_pallas(True)
    params = make_params(cfg, 7)
    server = WaveServer(cfg, params, batch_slots=SLOTS, max_len=MAX_LEN)

    def wave(max_new):  # one-token prompts: one prefill step
        return [Request(i, [11 + i], max_new) for i in range(SLOTS)]

    def query(seed):
        return union_opt(Problem.gemm(1024, 1024, 1024, word_bytes=1), cloud_accelerator(),
                         mapper="random", engine_backend="jax", samples=256, seed=seed)

    server.run_wave(wave(2))  # compiles the step and the cache's initializer
    query(1)  # compiles the engine's cores
    with tr.Tracer(work):
        steps = server.run_wave(wave(3))
        sol = query(2)
    shutil.copy(tr.find_xplane(work), out)
    shutil.rmtree(work, ignore_errors=True)
    return dict(
        spans.summary(spans.reduce_spans(tr.load(out)), top=5),
        decode_steps=steps,
        device_syncs=sol.search.device_syncs,
        platform=jax.devices()[0].platform,
    )


def slim(path: Path) -> None:
    """Cut the recorded trace down to what the reductions read, in place:
    the programs' HLO (the metadata plane), the operations' statistics,
    and each operation's name after its instruction name
    (``%fusion.12 = f64[...] ...`` -> ``%fusion.12``, as
    :func:`bench.trace.short_name` reads it). Events and times stay as
    the chip recorded them."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(path.read_bytes())
    for plane in space.planes:
        if plane.name == "/host:metadata":
            plane.event_metadata.clear()
        if plane.name.startswith("/device:"):
            for md in plane.event_metadata.values():
                md.name = md.name.split(" = ", 1)[0]
                del md.stats[:]
            for line in plane.lines:
                for ev in line.events:
                    del ev.stats[:]
    path.write_bytes(space.SerializeToString())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)
    from bench import harness

    harness.require_chips(1)
    harness.enable_compile_cache()
    args.out.parent.mkdir(parents=True, exist_ok=True)
    found = record(args.out, ROOT / "bench/.runs/record")
    slim(args.out)
    print(json.dumps(dict(found, bytes=args.out.stat().st_size), default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
