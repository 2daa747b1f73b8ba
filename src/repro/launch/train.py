"""Production training driver.

Wires every substrate layer together: config registry -> model -> sharded
step (pjit over the mesh) -> deterministic data pipeline -> checkpoint
manager (atomic, async, elastic) -> fault-tolerant runner (retry /
restore / straggler watchdog).

CPU-scale example (the examples/ scripts call this):

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b_smoke \\
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/run1

On a real TPU slice the same entry point runs with --mesh pod,data,model
dimensions; the step function and shardings are identical to the ones the
multi-pod dry-run compiles for 512 chips.
"""

from __future__ import annotations

import argparse
import logging
import math
import time
from dataclasses import replace
from pathlib import Path

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs.base import SHAPES, ShapeConfig, get_config
from repro.data import DataConfig, SyntheticLM, TokenFileDataset, make_pipeline
from repro.launch import steps as steps_mod
from repro.launch.specs import batch_struct, state_struct
from repro.optim.optimizers import adamw, lion
from repro.optim.schedules import cosine_schedule
from repro.runtime import FaultTolerantRunner, RunnerConfig, use_compile_cache
from repro.sharding.hints import hints_from_mesh
from repro.sharding.specs import ShardingRules, batch_specs, named, state_specs

log = logging.getLogger("repro.train")


def build_mesh(spec: str | None) -> Mesh | None:
    if not spec:
        return None
    dims = [int(x) for x in spec.split(",")]
    names = ("pod", "data", "model")[-len(dims):]
    devs = np.array(jax.devices()[: math.prod(dims)]).reshape(dims)
    return Mesh(devs, names)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--optimizer", choices=["adamw", "lion"], default="adamw")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="", help="e.g. '2,16,16' or '1,4'")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", default="synthetic", help="'synthetic' or a token file path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--step-timeout", type=float, default=None)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep only this many layers (0: the config's own)")
    ap.add_argument("--ep-shardmap", action="store_true",
                    help="route MoE layers through shard_map expert parallelism")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    use_compile_cache()
    cfg = get_config(args.arch)
    if args.layers:
        log.info("%s: keeping %d of its %d layers", cfg.name, args.layers, cfg.n_layers)
        cfg = replace(cfg, n_layers=args.layers)
    mesh = build_mesh(args.mesh)
    rules = ShardingRules(ep_shardmap=args.ep_shardmap)
    if mesh is not None:
        hints_from_mesh(mesh, rules)

    lr = cosine_schedule(args.lr, args.warmup, args.steps)
    optimizer = {"adamw": adamw, "lion": lion}[args.optimizer](lr)
    step_fn = steps_mod.make_train_step(
        cfg, optimizer, remat=not args.no_remat, microbatches=args.microbatches
    )

    # ---- init / restore ------------------------------------------------ #
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    init = steps_mod.make_init_state(cfg, optimizer)
    if mesh is not None:
        st_specs = state_specs(state_struct(cfg, optimizer), cfg, mesh, rules)
        st_sh = named(st_specs, mesh)
        b_specs = batch_specs(cfg, shape, mesh, rules)
        with mesh:
            state = jax.jit(init, out_shardings=st_sh)(jax.random.PRNGKey(args.seed))
            jit_step = jax.jit(
                step_fn, in_shardings=(st_sh, named(b_specs, mesh)),
                out_shardings=(st_sh, None), donate_argnums=(0,),
            )
    else:
        st_sh = None
        b_specs = None
        state = jax.jit(init)(jax.random.PRNGKey(args.seed))
        jit_step = jax.jit(step_fn, donate_argnums=(0,))

    start_step = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(Path(args.ckpt_dir), every=args.ckpt_every)
        try:
            state, start_step, _ = ckpt.restore_latest(state, shardings=st_sh)
            log.info("restored checkpoint at step %d", start_step)
        except FileNotFoundError:
            pass

    # ---- data ----------------------------------------------------------- #
    if args.data == "synthetic":
        source = SyntheticLM(cfg.vocab, seed=args.seed)
    else:
        source = TokenFileDataset(args.data, cfg.vocab, seed=args.seed)
    pipe = make_pipeline(
        source, args.batch, args.seq, mesh=mesh, specs=b_specs,
        start_step=start_step, data_cfg=DataConfig(seed=args.seed),
    )

    def restore_fn():
        assert ckpt is not None
        st, step, _ = ckpt.restore_latest(state, shardings=st_sh)
        return st, step

    runner = FaultTolerantRunner(
        jit_step,
        RunnerConfig(step_timeout_s=args.step_timeout),
        checkpoint_manager=ckpt,
        restore_fn=restore_fn if ckpt else None,
    )

    # ---- loop ------------------------------------------------------------ #
    losses = []
    t0 = time.time()
    ctx = mesh if mesh is not None else _nullcontext()
    with ctx:
        for step in range(start_step, args.steps):
            batch = next(pipe)
            state, metrics = runner.run_step(state, batch, step)
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % args.log_every == 0 or step == args.steps - 1:
                dt = time.time() - t0
                log.info("step %-5d loss %.4f  (%.2f s/step avg)",
                         step, loss, dt / max(1, step - start_step + 1))
            if ckpt is not None and ckpt.should_save(step + 1):
                ckpt.save(step + 1, state, extra={"loss": loss})
    if not losses:  # resumed at/after the target step: nothing to do
        return {"first_loss": float("nan"), "last_loss": float("nan"), "steps": 0}
    if ckpt is not None:
        ckpt.save(args.steps, state, extra={"loss": losses[-1]})
        ckpt.wait()
    return {
        "first_loss": losses[0],
        "last_loss": losses[-1],
        "steps": len(losses),
        "losses": losses,
        "params": sum(int(np.prod(a.shape)) for a in jax.tree.leaves(state["params"])),
        # per device while the state is live (None where the backend has
        # no memory stats)
        "bytes_in_use": [
            (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()
        ],
    }


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    out = main()
    print(f"train done: loss {out['first_loss']:.4f} -> {out['last_loss']:.4f} "
          f"over {out['steps']} steps")
