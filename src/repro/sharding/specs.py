"""PartitionSpec rules for every architecture family.

These rules ARE a Union mapping projected onto the mesh levels: the
spatial tile at the 'pod'/'data' levels is the batch split (DP), the
spatial tile at the 'model' level is the head/expert/ff split (TP/EP),
and FSDP shards the weight's remaining big dim over 'data' (ZeRO-3).
``repro/sharding/auto.py`` produces the same structures from an explicit
Union ``Mapping`` found by a mapper; this module encodes the
paper-faithful defaults used as the §Perf baseline.

Divisibility-guarded: any dim not divisible by its mesh axis size falls
back to replication (e.g. starcoder2's 4 KV heads on a 16-way model axis
-> KV cache shards over sequence instead).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


@dataclass(frozen=True)
class ShardingRules:
    """Knobs for the sharding strategy (hillclimbed in §Perf)."""

    fsdp: bool = True  # shard params' non-TP dim over 'data' (train)
    fsdp_min_elems: int = 65536  # replicate small tensors
    # weight-gathered serving: at inference, ALSO shard weights over 'data'
    # when the TP-sharded weights alone would exceed this budget (qwen2-moe's
    # 60 experts cannot shard over a 16-way model axis; qwen1.5-110b's
    # TP-sharded weights are 13.75 GB before any KV cache). Costs an
    # all-gather per layer -- decode is bandwidth-bound anyway.
    inference_weight_budget: int = 8 * (1 << 30)
    # Megatron-style sequence parallelism on the residual stream: the
    # per-layer remat carries shard over 'model', which is what lets the
    # 110B train cell fit (86 GB -> 5.4 GB of saved activations per chip).
    seq_shard_activations: bool = True
    shard_cache_heads: bool = True  # prefer head-sharding of KV caches
    expert_axis: str = "model"  # EP axis
    tp_axis: str = "model"
    dp_over_pod: bool = True  # batch also split over 'pod'
    # pure-FSDP (ZeRO-3) mode: the 'model' axis joins DATA parallelism and
    # TP is disabled. Trades the per-layer TP activation all-reduces for
    # per-unit parameter all-gathers -- wins when 2*act_bytes*layers >
    # 3*param_bytes (the qwen1.5-110b train_4k hillclimb, SPerf).
    fsdp_only: bool = False
    # explicit expert parallelism: route MoE layers through the shard_map
    # all-to-all dispatch (models/moe_ep.py) instead of GSPMD scatters --
    # the SPerf MoE hillclimb. Default off = paper-faithful GSPMD baseline.
    ep_shardmap: bool = False
    # remat policy for the scanned unit stack: 'full' (recompute all,
    # collectives included) or 'save_block_outputs' (keep the all-reduced
    # per-block residual contributions; bwd recompute skips collectives)
    remat_policy: str = "full"


# dense-param orientation sets (keys are the owning module names)
_COL = {
    "wq", "wk", "wv", "gate", "up", "in_z", "in_x", "in_dt", "lm_head",
    "kv_up", "kv_down", "w_i", "w_f", "wx", "ffn_up", "l1",
}
_ROW = {"wo", "down", "out_proj", "ffn_down", "l2", "frontend_proj"}
_REPL = {"router", "in_B", "in_C"}


def _axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh: Mesh, rules: ShardingRules):
    pool = ("pod", "data", "model") if rules.fsdp_only else ("pod", "data")
    axes = [a for a in pool if a in mesh.axis_names]
    if not rules.dp_over_pod:
        axes = [a for a in axes if a != "pod"]
    return tuple(axes)


def _maybe(axis: Optional[str], dim: int, sizes: Dict[str, int]) -> Optional[str]:
    if axis is None or axis not in sizes:
        return None
    return axis if dim % sizes[axis] == 0 else None


def _maybe_dp(axes: Tuple[str, ...], dim: int, sizes: Dict[str, int]):
    if not axes:
        return None
    n = int(np.prod([sizes[a] for a in axes]))
    return axes if dim % n == 0 else None


def _maybe_any(ax, dim: int, sizes: Dict[str, int]):
    """_maybe for either a single axis name or a tuple of axes."""
    if ax is None:
        return None
    if isinstance(ax, tuple):
        return _maybe_dp(ax, dim, sizes)
    return _maybe(ax, dim, sizes)


# --------------------------------------------------------------------- #
# parameter specs
# --------------------------------------------------------------------- #
def param_specs(
    params_shape,  # pytree of ShapeDtypeStruct (or arrays)
    cfg: ModelConfig,
    mesh: Mesh,
    rules: ShardingRules = ShardingRules(),
    for_training: bool = True,
) -> Dict:
    sizes = _axis_sizes(mesh)
    tp = None if rules.fsdp_only else rules.tp_axis
    fsdp_ax = "data" if (rules.fsdp and for_training and "data" in sizes) else None
    if rules.fsdp_only:
        fsdp_ax = tuple(a for a in ("data", "model") if a in sizes) or None
    if not for_training and "data" in sizes:
        # weight-gathered serving for models whose TP-sharded weights
        # exceed the per-chip budget (see ShardingRules). Expert banks
        # whose expert count does not divide the model axis (qwen2-moe's
        # 60 on a 16-way axis) stay REPLICATED under pure TP -- account
        # for that when estimating per-chip weight residency.
        tp_n = max(1, sizes.get(tp, 1))
        e = cfg.n_routed_experts
        expert_p = (
            (cfg.n_layers - cfg.first_k_dense) * e * 3 * cfg.d_model * cfg.d_expert
            if e else 0
        )
        dense_p = cfg.num_params() - expert_p
        eff = dense_p / tp_n + expert_p / (tp_n if (e and e % tp_n == 0) else 1)
        if 2 * eff > rules.inference_weight_budget:
            fsdp_ax = "data"

    def leaf_spec(path, leaf) -> P:
        keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        shape = leaf.shape
        stacked = keys and keys[0] == "units"  # leading unit axis from scan
        off = 1 if stacked else 0
        body = shape[off:]
        name = keys[-1]
        owner = keys[-2] if name in ("w", "b") and len(keys) >= 2 else name

        def wrap(*spec_body):
            return P(*([None] * off), *spec_body)

        big = leaf.size >= rules.fsdp_min_elems

        # ---- embeddings & head ---------------------------------------- #
        if name == "embed":
            return wrap(_maybe(tp, body[0], sizes), _maybe_any(fsdp_ax, body[1], sizes) if big else None)
        # ---- norm scales / small vectors ------------------------------- #
        if len(body) == 1:
            if owner in _COL and name == "b":
                return wrap(_maybe(tp, body[0], sizes))
            if name in ("A_log", "D", "dt_bias", "conv_x_b"):
                return wrap(_maybe(tp, body[0], sizes))
            return wrap(None)
        # ---- MoE expert banks (E, d, de) / (E, de, d) ------------------- #
        if owner in ("w_gate", "w_up", "w_down") or name in ("w_gate", "w_up", "w_down"):
            e_ax = (None if rules.fsdp_only
                    else _maybe(rules.expert_axis, body[0], sizes))
            d_ax = _maybe_any(fsdp_ax, body[1], sizes) if big else None
            return wrap(e_ax, d_ax, None)
        # ---- depthwise convs (W, C) ------------------------------------ #
        if name.startswith("conv_") and name.endswith("_w"):
            ch_ax = _maybe(tp, body[1], sizes) if name == "conv_x_w" else None
            return wrap(None, ch_ax)
        if name == "conv_w":
            return wrap(None, _maybe(tp, body[1], sizes))
        # ---- sLSTM recurrent (4, nh, hd, hd) ---------------------------- #
        if name == "r":
            return wrap(None, None, _maybe(tp, body[2], sizes), None)
        # ---- dense weights ---------------------------------------------- #
        if owner in _COL:
            col = _maybe(tp, body[-1], sizes)
            row = _maybe_any(fsdp_ax, body[0], sizes) if (big and col != fsdp_ax) else None
            return wrap(row, *([None] * (len(body) - 2)), col)
        if owner in _ROW:
            row = _maybe(tp, body[0], sizes)
            col = _maybe_any(fsdp_ax, body[-1], sizes) if (big and row != fsdp_ax) else None
            return wrap(row, *([None] * (len(body) - 2)), col)
        if owner in _REPL:
            return wrap(*([None] * len(body)))
        # default: replicate
        return wrap(*([None] * len(body)))

    return jax.tree_util.tree_map_with_path(leaf_spec, params_shape)


# --------------------------------------------------------------------- #
# batch / cache / state specs
# --------------------------------------------------------------------- #
def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                rules: ShardingRules = ShardingRules()) -> Dict:
    dp = dp_axes(mesh, rules)
    seq_ax = (rules.tp_axis if rules.seq_shard_activations else None)
    if rules.fsdp_only:
        seq_ax = None  # 'model' already consumed by the batch axis
    sizes = _axis_sizes(mesh)
    # divisibility guard: when the global batch cannot split over the full
    # dp pool (fsdp_only prefill: batch 32 on 256 chips), keep batch on
    # (pod, data) and move 'model' back to the sequence axis
    if _maybe_dp(dp, shape.global_batch, sizes) is None:
        narrower = tuple(a for a in dp if a != rules.tp_axis)
        if rules.fsdp_only and _maybe_dp(narrower, shape.global_batch, sizes):
            dp, seq_ax = narrower, rules.tp_axis
        else:
            dp = None

    def tok_spec(ndim: int) -> P:
        extra = [None] * (ndim - 2)
        return P(dp if dp else None, seq_ax, *extra)

    specs: Dict = {}
    if cfg.frontend == "audio_stub":
        specs["frames"] = tok_spec(3)
        specs["labels"] = tok_spec(2)
    else:
        specs["tokens"] = tok_spec(2)
        if cfg.frontend == "vision_stub" and shape.kind in ("train", "prefill"):
            specs["patch_embeds"] = tok_spec(3)
    return specs


def cache_specs(cache_shape, cfg: ModelConfig, mesh: Mesh,
                rules: ShardingRules = ShardingRules()) -> Dict:
    sizes = _axis_sizes(mesh)
    tp = None if rules.fsdp_only else rules.tp_axis
    dp = dp_axes(mesh, rules)

    def leaf_spec(path, leaf) -> P:
        keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        shape = leaf.shape
        stacked = keys and keys[0] == "units"
        off = 1 if stacked else 0
        body = shape[off:]
        name = keys[-1]

        def wrap(*spec_body):
            return P(*([None] * off), *spec_body)

        bdp = _maybe_dp(dp, body[0], sizes)
        # batch-1 long-context decode: the batch axis cannot shard, so the
        # cache SEQUENCE axis takes the dp axes instead (sequence parallelism
        # over the ring) -- this is what keeps the 500k cells per-chip small
        s_ax = 2 if name in ("k", "v") else 1
        seq_dp = None if bdp else _maybe_dp(dp, body[s_ax] if len(body) > s_ax else 0, sizes)
        if name in ("k", "v"):
            # (b, hkv, S, hd), the flash kernel's layout: heads over model if
            # divisible, else sequence
            if rules.shard_cache_heads and body[1] % sizes.get(tp, 1) == 0:
                return wrap(bdp, tp, seq_dp, None)
            return wrap(bdp, None, seq_dp or _maybe(tp, body[2], sizes), None)
        if name in ("ckv", "krope"):
            return wrap(bdp, seq_dp or _maybe(tp, body[1], sizes), None)
        if name in ("conv", "conv_x", "conv_B", "conv_C"):
            return wrap(bdp, None, _maybe(tp, body[2], sizes))
        if name == "state":  # (b, nh, hp, n)
            return wrap(bdp, _maybe(tp, body[1], sizes), None, None)
        if name == "C":  # (b, nh, dk, dv)
            if body[1] % sizes.get(tp, 1) == 0:
                return wrap(bdp, tp, None, None)
            return wrap(bdp, None, _maybe(tp, body[2], sizes), None)
        if name in ("n", "c", "h"):  # (b, nh, dk)
            if body[1] % sizes.get(tp, 1) == 0:
                return wrap(bdp, tp, None)
            return wrap(bdp, None, _maybe(tp, body[2], sizes))
        if name == "m":  # (b, nh)
            return wrap(bdp, _maybe(tp, body[1], sizes))
        return wrap(bdp, *([None] * (len(body) - 1)))

    return jax.tree_util.tree_map_with_path(leaf_spec, cache_shape)


def state_specs(state_shape, cfg: ModelConfig, mesh: Mesh,
                rules: ShardingRules = ShardingRules()) -> Dict:
    """Train-state specs: optimizer moments/master mirror the param specs."""
    pspecs = param_specs(state_shape["params"], cfg, mesh, rules, for_training=True)
    out = {"params": pspecs, "opt": {}}
    for k, sub in state_shape["opt"].items():
        if k == "step":
            out["opt"][k] = P()
        else:
            out["opt"][k] = param_specs(sub, cfg, mesh, rules, for_training=True)
    return out


def named(tree_specs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
