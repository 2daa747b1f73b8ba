#!/usr/bin/env python3
"""Smoke run of Union's two device paths on one TPU chip.

    python chip_smoke.py               # engine phase + serve phase, one chip
    python chip_smoke.py --four-chips  # sharded MoE training, four chips

Engine phase: one qwen3-0.6b GEMM from ``opstream`` is mapped by the
random, genetic and exhaustive mappers through
``union_opt(engine_backend="jax")`` and again on the numpy backend with
the same seeds. The jax runs must not fall back (``backend_fallbacks ==
0``), must run the device-resident loop (``device_syncs > 0``), and must
find the same best mappings with the same costs, bit for bit. (The chip's
float64 differs from the host's in the last bits, so a search on the chip
states its best mapping's cost as the host computes it.)

Serve phase: ``launch.serve.WaveServer`` serves a few waves of requests
with qwen3-0.6b at its published widths (28 layers, d_model 1024, vocab
151936, bf16), its attention on compiled Pallas flash attention with
blocks from ``codesign.plan``. The planner must not fall back, the decode
step must hold the kernel (``tpu_custom_call``), and one step's logits
must agree with the jnp reference attention on the same params and cache
-- more closely than two deliberately wrong attentions (the valid cache
length off by one either way) do, which the tolerance must reject.

Four chips (``--four-chips``): ``launch.train`` runs qwen2-moe-a2.7b at
its published widths, cut to 2 of its 24 layers, on a (data=1, model=4)
mesh for 3 steps under GSPMD and again with shard_map expert parallelism;
the first-step losses must agree, and each chip's memory is printed.

Each phase prints its numbers as JSON lines; the last line is the result
``{"ok": true, "device": {...}}``. On any platform but a TPU, or if any
assertion fails, the script exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.runtime.compile_cache import use_compile_cache  # noqa: E402

ENGINE_MAPPERS = {
    "random": {"samples": 2048, "seed": 0},
    "genetic": {"generations": 8, "seed": 0},
    "exhaustive": {"max_mappings": 2048},
}
# max |pallas - reference| over max |reference|: on a v5e the sound kernel
# reads 0.0138 and the nearer wrong attention (one empty cache slot
# attended) 0.0676, so the tolerance sits between them
LOGITS_TOL = 0.03
LOSS_TOL = 0.02  # |EP - GSPMD| first-step loss, relative


def emit(**fields) -> None:
    print(json.dumps(fields, sort_keys=True), flush=True)


def require_tpu(count: int):
    """The device list, or exit non-zero naming the platform found."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform {devices[0].platform!r}")
    if len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found {len(devices)}")
    return devices


def engine_phase(model: str = "qwen3-0.6b", shape: str = "prefill_32k") -> None:
    import numpy as np

    from repro.core.architecture import cloud_accelerator
    from repro.core.opstream import build_opstream
    from repro.core.optimizer import union_opt

    entry = next(
        e for e in build_opstream(model, shape).mappable_entries() if e.role == "mlp"
    )
    problem, arch = entry.problem, cloud_accelerator()
    for mapper, kw in ENGINE_MAPPERS.items():
        t0 = time.perf_counter()
        dev = union_opt(problem, arch, mapper=mapper, engine_backend="jax", **kw)
        dev_s = time.perf_counter() - t0
        host = union_opt(problem, arch, mapper=mapper, engine_backend="numpy", **kw)
        s = dev.search
        got, want = _cost_fields(dev.cost), _cost_fields(host.cost)
        emit(
            phase="engine",
            problem=problem.name,
            dims=problem.dims,
            mapper=mapper,
            backend_fallbacks=s.backend_fallbacks,
            fused_dispatches=s.fused_dispatches,
            n_traces=s.n_traces,
            device_syncs=s.device_syncs,
            evaluated=s.evaluated,
            best_edp=host.cost.edp,
            bit_identical=bool(np.array_equal(got, want)),
            max_abs_cost_diff=float(np.max(np.abs(got - want))),
            setup_and_search_s=dev_s,
        )
        assert s.backend_fallbacks == 0, f"{mapper}: jax engine fell back"
        assert s.fused_dispatches > 0, f"{mapper}: no fused dispatch ran"
        assert s.device_syncs > 0, f"{mapper}: the device loop never ran"
        assert dev.mapping.to_dict() == host.mapping.to_dict(), (
            f"{mapper}: best mapping differs from the numpy run"
        )
        assert np.array_equal(got, want), f"{mapper}: cost off the numpy run"


def _cost_fields(cost):
    """A Cost's numbers, breakdown included, in a fixed order."""
    import numpy as np

    bd = sorted(cost.breakdown.items())
    return np.array(
        [cost.latency_cycles, cost.energy_pj, cost.utilization]
        + [float(v) for _, v in bd],
        dtype=np.float64,
    )


def serve_phase(
    arch: str = "qwen3-0.6b",
    requests: int = 8,
    batch: int = 4,
    max_len: int = 256,
    max_new: int = 16,
    seed: int = 0,
) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import kernels
    from repro.codesign import planner_stats, reset_planner_stats
    from repro.configs.base import get_config
    from repro.launch.serve import Request, WaveServer
    from repro.models import decode_step, init_cache, init_params

    cfg = get_config(arch)
    kernels.enable_pallas(True, interpret=False)
    reset_planner_stats()
    params = init_params(cfg, jax.random.PRNGKey(seed))
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    server = WaveServer(cfg, params, batch_slots=batch, max_len=max_len)

    # compile the decode step once, as set-up, and look for the kernel
    cache = init_cache(cfg, batch, max_len)
    tok = jnp.zeros((batch, 1), jnp.int32)
    t0 = time.perf_counter()
    hlo = server._decode.lower(params, cache, tok, jnp.int32(0)).compile().as_text()
    compile_s = time.perf_counter() - t0
    assert "tpu_custom_call" in hlo, "decode step holds no Pallas kernel"

    rng = np.random.default_rng(seed)
    for rid in range(requests):
        prompt = rng.integers(0, cfg.vocab, size=int(rng.integers(4, 12))).tolist()
        server.submit(Request(rid, prompt, max_new))
    t0 = time.perf_counter()
    done = server.run()
    serve_s = time.perf_counter() - t0
    tokens = sum(len(r.out) for r in done)
    stats = planner_stats()

    # one step's logits, Pallas against the jnp reference attention, on the
    # same params and the same cache (filled with 16 random tokens)
    fill = jnp.asarray(rng.integers(0, cfg.vocab, size=(batch, 16)), jnp.int32)
    step_pallas = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i))
    for i in range(fill.shape[1]):
        _, cache = step_pallas(params, cache, fill[:, i : i + 1], jnp.int32(i))
    pos = jnp.int32(fill.shape[1])
    nxt = fill[:, -1:]
    got = np.asarray(step_pallas(params, cache, nxt, pos)[0], np.float32)
    kernels.enable_pallas(False)
    try:
        want = _reference_logits(cfg, params, cache, nxt, pos)
        # controls: wrong attentions the tolerance must reject
        controls = {
            name: _rel_diff(
                _reference_logits(cfg, params, cache, nxt, pos, kv_len_shift=d),
                want,
            )
            for name, d in (("drops_newest_key", -1), ("attends_empty_slot", 1))
        }
    finally:
        kernels.enable_pallas(True)
    rel = _rel_diff(got, want)
    emit(
        phase="serve",
        arch=cfg.name,
        layers=cfg.n_layers,
        d_model=cfg.d_model,
        vocab=cfg.vocab,
        params=n_params,
        requests=len(done),
        tokens_served=tokens,
        decode_compile_s=compile_s,
        serve_wall_s=serve_s,
        plan_fallbacks=stats["plan_fallbacks"],
        plan_searches=stats["plan_searches"],
        interpret=kernels.interpret_default(),
        logits_max_rel_diff=rel,
        logits_tol=LOGITS_TOL,
        control_rel_diff=controls,
        argmax_agree=float(np.mean(got.argmax(-1) == want.argmax(-1))),
    )
    assert len(done) == requests and tokens == requests * max_new
    assert stats["plan_fallbacks"] == 0, "the attention planner fell back"
    assert stats["plan_searches"] > 0, "no attention block was planned"
    assert kernels.interpret_default() is False
    assert np.all(np.isfinite(got)) and got.shape == (batch, cfg.vocab)
    assert rel <= LOGITS_TOL, f"Pallas logits off the reference by {rel}"
    for name, c in controls.items():
        assert c > LOGITS_TOL, f"the tolerance does not reject {name} ({c})"


def _rel_diff(got, want) -> float:
    """max |got - want| over max |want|."""
    import numpy as np

    return float(np.max(np.abs(got - want)) / max(1e-6, np.max(np.abs(want))))


def _reference_logits(cfg, params, cache, tok, pos, kv_len_shift: int = 0):
    """One decode step's logits on the jnp reference attention (Pallas must
    be off); ``kv_len_shift`` moves the valid cache length the attention
    masks with, to make a deliberately wrong attention."""
    import jax
    import numpy as np

    from repro.models import decode_step, layers

    sound = layers.mha

    def shifted(*args, kv_len=None, **kw):
        if kv_len is not None:
            kv_len = kv_len + kv_len_shift
        return sound(*args, kv_len=kv_len, **kw)

    layers.mha = shifted
    try:
        step = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i))
        return np.asarray(step(params, cache, tok, pos)[0], np.float32)
    finally:
        layers.mha = sound


def four_chip_phase(layers: int = 2, steps: int = 3) -> None:
    from repro.launch import train

    argv = [
        "--arch", "qwen2-moe-a2.7b", "--layers", str(layers), "--mesh", "1,4",
        "--steps", str(steps), "--batch", "8", "--seq", "512",
        "--warmup", "1", "--log-every", "1",
    ]
    runs = {}
    for name, extra in (("gspmd", []), ("ep_shardmap", ["--ep-shardmap"])):
        out = train.main(argv + extra)
        runs[name] = out
        emit(
            phase="four_chips",
            sharding=name,
            layers_kept=f"{layers} of 24",
            params=out["params"],
            losses=out["losses"],
            bytes_in_use=out["bytes_in_use"],
        )
        assert out["steps"] == steps
        live = [b for b in out["bytes_in_use"] if b]
        assert len(live) == 4 and min(live) > 0.5 * max(live), (
            "training state is not spread over the four chips"
        )
    a, b = runs["gspmd"]["first_loss"], runs["ep_shardmap"]["first_loss"]
    emit(phase="four_chips", first_loss_gspmd=a, first_loss_ep=b,
         rel_diff=abs(a - b) / abs(a), tol=LOSS_TOL)
    assert abs(a - b) <= LOSS_TOL * abs(a), "EP and GSPMD first-step losses differ"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip sharded training path only")
    args = ap.parse_args(argv)
    use_compile_cache()
    devices = require_tpu(4 if args.four_chips else 1)
    if args.four_chips:
        four_chip_phase()
    else:
        engine_phase()
        serve_phase()
    emit(ok=True, device={
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
