#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's numbers and the
control's, over many seeds in one process (the benchmark's own runs never
run the control).

    python3 bench/calibrate.py --workload <cell> --seeds <n> [<n> ...]

Serving cells: per seed, the weights are made from the seed and the cell's
first wave is served at its own slot count; the sampled requests then go
through the float32 reference, which reads the program's widest gap, and
the float8 control (the reference with every matrix product's operands
rounded to float8 e4m3), which reads the gap of the tokens it would have
served at the same positions.

Mapping cells: per seed, the sampled number of queries is answered with
the jax engine; each answer's cost is read against the float64 reference
(the program's gap), the float32 reference against the float64 one (the
control's gap), and the host search of the same query.

One JSON line per seed; the last line holds the largest sound reading and
the smallest control reading of each number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def serve_readings(ctx, seed: int):
    import numpy as np

    from bench import gen
    from bench.drivers import serve as sd
    from bench.reference import dense
    from bench.weights import make_params
    from repro import kernels
    from repro.launch.serve import Request, WaveServer

    mix = ctx.cell.traffic
    cfg = sd.model_config(ctx.cell.config)
    kernels.enable_pallas(True, interpret=ctx.interpret)
    ctx.seed = seed
    params = make_params(cfg, seed)
    server = WaveServer(cfg, params, batch_slots=int(mix["slots"]),
                        max_len=int(mix["max_len"]), pad_token=int(mix["pad_token"]))
    loop = sd.Loop(server, Request, gen.ClosedLoop(mix, cfg.vocab, seed), int(mix["slots"]))
    loop.serve_wave()
    del server
    sound, control = [], []
    for rec in sd.sample_finished(ctx, loop):
        tokens, targets = sd.reference_inputs(rec, int(mix["pad_token"]), int(mix["max_len"]))
        g, c = dense.served_gaps(ctx.cell.config, params, tokens, targets, control=True)
        sound.append(float(np.max(g[targets >= 0])))
        control.append(float(np.max(c[targets >= 0])))
    return {"served_logit_gap": (max(sound), max(control))}


def map_readings(ctx, seed: int):
    import numpy as np

    from bench import gen
    from bench.drivers import map as md
    from bench.reference import timeloop

    mix = ctx.cell.traffic
    arch = md.architecture(mix["arch"])
    qs = md.gemms(ctx)
    n = int(mix["check"]["queries"])
    order = gen.query_order(len(qs), seed, n)
    ref_gap = ctl_gap = host_gap = 0.0
    for k, idx in enumerate(order):
        q = qs[idx]
        s = (seed * 1000003 + k) % md.SEED_MOD
        sol = md.query(q, arch, mix, s, "jax")
        m = sol.mapping.to_dict()
        wb = int(mix["word_bytes"])
        r64 = timeloop.cost(q["dims"], mix["arch"], m, wb)["edp"]
        r32 = timeloop.cost(q["dims"], mix["arch"], m, wb, dtype=np.float32)["edp"]
        host = md.query(q, arch, mix, s, "numpy").cost.edp
        ref_gap = max(ref_gap, abs(sol.cost.edp - r64) / r64)
        ctl_gap = max(ctl_gap, abs(r32 - r64) / r64)
        host_gap = max(host_gap, (sol.cost.edp - host) / host)
    return {"edp_gap_vs_reference": (ref_gap, ctl_gap),
            "edp_gap_vs_host_search": (host_gap, None)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    ctx = harness.RunContext(cell=cell, seed=0, seconds=0.0, trace=False,
                             t_start=time.perf_counter(), devices=devices)
    read = {"serve": serve_readings, "map": map_readings}[cell.traffic["driver"]]
    worst_sound, least_control = {}, {}
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = read(ctx, seed)
        print(json.dumps({"seed": seed, "readings": r, "s": time.perf_counter() - t0}), flush=True)
        for k, (sound, control) in r.items():
            worst_sound[k] = max(worst_sound.get(k, 0.0), sound)
            if control is not None:
                least_control[k] = min(least_control.get(k, float("inf")), control)
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds),
                      "lower": worst_sound, "upper": least_control,
                      "device": harness.device_info(devices)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
