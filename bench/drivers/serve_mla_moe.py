"""Serving driver for a latent-attention, routed-expert model (DeepSeek-V2
style): the closed loop of ``bench/drivers/serve.py`` against
``launch.serve.WaveServer``, at one chip's share of an expert-parallel
deployment.

The configuration file holds the model's published ``config.json`` keys
and the share this chip holds (``experts_held`` routed experts from
``first_expert``); the program's ``ModelConfig`` is built from them. The
loop, the window, the traced wave and the end-to-end numbers are those of
the dense serving driver, imported from it.

``correct``: a sample of finished requests, drawn from the seed and with
the longest in it, goes through the plain float32 reference of MLA and
the held experts (``bench/reference/mla_moe.py``) over the same
left-padded prompt and the served tokens; the widest gap by which a served
token's logit lies below the reference's best is held to the cell's limit.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict

import numpy as np

from bench import gen, harness
from bench import trace as tr
from bench.drivers.serve import Loop, _end_to_end, _traced_wave, reference_inputs, sample_finished
from bench.reference import mla_moe
from bench.weights import count_params, make_params

# what the program implements of the published config, key by key
IMPLEMENTS = {"hidden_act": "silu", "scoring_func": "softmax", "topk_method": "greedy",
              "n_group": 1, "topk_group": 1, "moe_layer_freq": 1, "routed_scaling_factor": 1,
              "q_lora_rank": None, "attention_bias": False}


def model_config(c: Dict):
    """The program's ``ModelConfig`` from the published keys and the share."""
    differ = {k: c.get(k) for k, v in IMPLEMENTS.items() if c.get(k) != v}
    if differ or c["rope_scaling"].get("type") != "yarn":
        raise harness.BenchError(f"the program does not implement {differ or c['rope_scaling']}")
    try:
        from repro.configs.base import YarnScaling, get_config
    except ImportError as e:  # a program without YaRN rope or the expert share
        raise harness.BenchError(f"the program cannot build this model: {e}") from e

    rs = c["rope_scaling"]
    return dataclasses.replace(
        get_config(c["model"]),
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        tie_embeddings=c["tie_word_embeddings"], rms_eps=c["rms_norm_eps"],
        rope_theta=float(c["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(rs["factor"]),
            original_max_position=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"])),
        use_mla=True, kv_lora_rank=c["kv_lora_rank"], q_lora_rank=0,
        nope_head_dim=c["qk_nope_head_dim"], rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"],
        first_k_dense=c["first_k_dense_replace"], d_expert=c["moe_intermediate_size"],
        n_routed_experts=c["n_routed_experts"], n_shared_experts=c["n_shared_experts"],
        top_k=c["num_experts_per_tok"], norm_topk_prob=c["norm_topk_prob"],
        experts_held=c["experts_held"], first_expert=c["first_expert"],
    )


def run(ctx: harness.RunContext) -> None:
    import jax

    from repro import kernels
    from repro.codesign import planner_stats, reset_planner_stats
    from repro.launch.serve import Request, WaveServer

    cell, mix = ctx.cell, ctx.cell.traffic
    cfg = model_config(cell.config)
    slots, max_len = int(mix["slots"]), int(mix["max_len"])
    if int(mix["clients"]) != slots:
        raise harness.BenchError("a closed loop of waves needs one client per slot")
    kernels.enable_pallas(True, interpret=ctx.interpret)
    reset_planner_stats()
    params = make_params(cfg, ctx.seed)
    jax.block_until_ready(params)
    ctx.counters["params"] = count_params(params)
    server = WaveServer(cfg, params, batch_slots=slots, max_len=max_len,
                        pad_token=int(mix["pad_token"]))
    # warm-up: one wave at the cell's slot count and cache length compiles
    # the decode step and the cache's initializer, the only programs run
    for c in range(slots):
        server.submit(Request(-1 - c, [int(mix["token_min"])] * 2, 2))
    server.run()
    ctx.counters["planner"] = dict(planner_stats())

    loop = Loop(server, Request, gen.ClosedLoop(mix, cfg.vocab, ctx.seed), slots)
    ctx.setup_s = harness.seconds_since(ctx.t_start)
    if ctx.trace:
        with tr.Tracer(ctx.work_dir / "trace") as tracer:
            loop.serve_wave()
        ctx.trace_reduction = tracer.reduce()
        _traced_wave(ctx, loop)
    else:
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < ctx.seconds:
            loop.serve_wave()
        _end_to_end(ctx, loop)
    ctx.device = harness.device_info(ctx.devices)
    del server
    _check(ctx, params, loop)


def _check(ctx, params, loop: Loop) -> None:
    mix = ctx.cell.traffic
    gc.collect()  # the server's cache goes before the reference runs
    picked = sample_finished(ctx, loop)
    gaps = []
    for rec in picked:
        tokens, targets = reference_inputs(rec, int(mix["pad_token"]), int(mix["max_len"]))
        g, _ = mla_moe.served_gaps(ctx.cell.config, params, tokens, targets)
        gaps.append(float(np.max(g[targets >= 0])))
    limits = harness.load_json(ctx.cell.bench_dir / "limits" / f"{ctx.cell.name}.json")
    ctx.counters["checked_requests"] = len(picked)
    ctx.counters["checked_tokens"] = sum(len(r.out) for r in picked)
    ctx.checks.append(harness.Check(
        "served_logit_gap", max(gaps) if gaps else float("inf"),
        float(limits["served_logit_gap"]),
    ))
