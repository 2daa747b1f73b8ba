"""Model assembly: embedding/frontends + repeating-unit block stack + head.

Layers are stacked per *repeating unit* (cfg.block_pattern) and iterated
with ``jax.lax.scan`` over stacked parameters, so the HLO contains ONE
copy of the unit regardless of depth -- this is what keeps 80-layer
dry-run compiles tractable and is also the production-correct structure
for pipelining. ``first_k_dense`` prefix layers (DeepSeek) live outside
the scan.

Public API:
  init_params(cfg, key)                 -> params pytree (eval_shape-able)
  forward(cfg, params, batch)           -> (logits, aux_loss)
  loss_fn(cfg, params, batch)           -> scalar loss
  init_cache(cfg, batch, max_len)       -> decode cache pytree
  decode_step(cfg, params, cache, tok, pos) -> (logits, new_cache)
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.sharding.hints import shard_hint
from repro.models.layers import (
    DTYPE,
    attention_apply,
    dense,
    init_attention,
    init_dense,
    init_mla,
    init_mlp,
    mla_apply,
    mlp_apply,
    rms_norm,
)

Params = Dict

# Scan-unroll knob for the unit stack. Production leaves this at 1 (one
# HLO copy of the unit; compile time O(1) in depth). The dry-run's
# structure-corrected cost pass sets it to the unit count on SMALL unit
# counts so ``compiled.cost_analysis()`` -- which counts a while-loop body
# ONCE, not x trip-count -- sees every unit (see launch/dryrun.py).
_SCAN_UNROLL = 1


def set_scan_unroll(n: int) -> None:
    global _SCAN_UNROLL
    _SCAN_UNROLL = max(1, int(n))


# ===================================================================== #
# init
# ===================================================================== #
def _init_block(key, cfg: ModelConfig, kind: str, moe_ffn: bool) -> Params:
    ks = jax.random.split(key, 4)
    if kind == "attn":
        p: Params = {"ln1": jnp.ones((cfg.d_model,), DTYPE)}
        p["attn"] = init_mla(ks[0], cfg) if cfg.use_mla else init_attention(ks[0], cfg)
        if cfg.d_ff or moe_ffn:
            p["ln2"] = jnp.ones((cfg.d_model,), DTYPE)
            if moe_ffn:
                p["moe"] = moe_mod.init_moe(ks[1], cfg)
            else:
                p["ffn"] = init_mlp(ks[1], cfg)
        return p
    if kind == "mamba2":
        return {"ln": jnp.ones((cfg.d_model,), DTYPE), "core": ssm_mod.init_mamba2(ks[0], cfg)}
    if kind == "mlstm":
        return {"ln": jnp.ones((cfg.d_model,), DTYPE), "core": ssm_mod.init_mlstm(ks[0], cfg)}
    if kind == "slstm":
        return {"ln": jnp.ones((cfg.d_model,), DTYPE), "core": ssm_mod.init_slstm(ks[0], cfg)}
    raise ValueError(f"unknown block kind {kind!r}")


def _unit_moe(cfg: ModelConfig) -> bool:
    return cfg.n_routed_experts > 0


def init_params(cfg: ModelConfig, key) -> Params:
    keys = jax.random.split(key, 8)
    params: Params = {}
    d = cfg.d_model
    if cfg.frontend == "audio_stub":
        params["frontend_proj"] = init_dense(keys[0], cfg.d_frontend, d)
    else:
        params["embed"] = (
            jax.random.normal(keys[0], (cfg.vocab, d), jnp.float32) * 0.02
        ).astype(DTYPE)
        if cfg.frontend == "vision_stub":
            k1, k2 = jax.random.split(keys[1])
            params["frontend_proj"] = {
                "l1": init_dense(k1, cfg.d_frontend, d),
                "l2": init_dense(k2, d, d),
            }
    # prefix (dense) layers outside the scan
    n_prefix = cfg.first_k_dense
    if n_prefix:
        pks = jax.random.split(keys[2], n_prefix)
        params["prefix"] = [
            _init_block(pks[i], cfg, "attn", moe_ffn=False) for i in range(n_prefix)
        ]
    # scanned units
    n_scanned = cfg.n_layers - n_prefix
    assert n_scanned % len(cfg.block_pattern) == 0
    n_units = n_scanned // len(cfg.block_pattern)

    def init_unit(k):
        uks = jax.random.split(k, len(cfg.block_pattern))
        return {
            f"b{i}": _init_block(uks[i], cfg, kind, moe_ffn=_unit_moe(cfg))
            for i, kind in enumerate(cfg.block_pattern)
        }

    params["units"] = jax.vmap(init_unit)(jax.random.split(keys[3], n_units))
    params["final_norm"] = jnp.ones((d,), DTYPE)
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(keys[4], d, cfg.vocab)
    return params


# ===================================================================== #
# block application
# ===================================================================== #
def apply_block(
    cfg: ModelConfig,
    kind: str,
    p: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cache: Optional[Params],
    cache_len,
    layer=None,
) -> Tuple[jnp.ndarray, Optional[Params], jnp.ndarray]:
    """One block. In decode, ``cache`` is this block's cache, or with
    ``layer`` its cache stacked over the scanned units: the block then
    updates its own entry of the stack and returns the whole stack."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "attn":
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        fn = mla_apply if cfg.use_mla else attention_apply
        a, new_cache = fn(p["attn"], cfg, h, positions, cache, cache_len, layer)
        x = x + checkpoint_name(a, "block_out")
        if "moe" in p:
            h2 = rms_norm(x, p["ln2"], cfg.rms_eps)
            from repro.models import moe_ep
            from repro.sharding import hints as _h
            if (cache is None and _h._STATE.get("ep_shardmap")
                    and moe_ep.ep_available(cfg, h2)):
                f, aux = moe_ep.moe_apply_ep(p["moe"], cfg, h2)
            else:
                # decode (cache present) routes droplessly: capacity dropping
                # is a training-throughput tradeoff, not a serving behavior
                f, aux = moe_mod.moe_apply(p["moe"], cfg, h2,
                                           dropless=cache is not None)
            x = x + checkpoint_name(f, "block_out")
        elif "ffn" in p:
            h2 = rms_norm(x, p["ln2"], cfg.rms_eps)
            x = x + checkpoint_name(mlp_apply(p["ffn"], cfg, h2), "block_out")
        return x, new_cache, aux
    h = rms_norm(x, p["ln"], cfg.rms_eps)
    stacked = cache is not None and layer is not None
    state = (
        jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False), cache)
        if stacked else cache
    )
    if kind == "mamba2":
        y, new_cache = ssm_mod.mamba2_apply(p["core"], cfg, h, state)
    elif kind == "mlstm":
        y, new_cache = ssm_mod.mlstm_apply(p["core"], cfg, h, state)
    elif kind == "slstm":
        y, new_cache = ssm_mod.slstm_apply(p["core"], cfg, h, state)
    else:
        raise ValueError(kind)
    if stacked:  # the recurrent state is small: replace the layer's entry
        new_cache = jax.tree.map(
            lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, layer, 0), cache, new_cache
        )
    return x + checkpoint_name(y, "block_out"), new_cache, aux


# ===================================================================== #
# embedding / frontends
# ===================================================================== #
def embed_inputs(cfg: ModelConfig, params: Params, batch: Dict) -> Tuple[jnp.ndarray, int]:
    """Returns (x, text_start): x (b, S, d); text_start = index where text
    tokens begin (for VLM loss masking)."""
    if cfg.frontend == "audio_stub":
        x = dense(params["frontend_proj"], batch["frames"].astype(DTYPE))
        return x, 0
    tok = params["embed"][batch["tokens"]]  # (b, s_text, d)
    if cfg.frontend == "vision_stub" and "patch_embeds" in batch:
        fp = params["frontend_proj"]
        img = dense(fp["l2"], jax.nn.gelu(dense(fp["l1"], batch["patch_embeds"].astype(DTYPE))))
        x = jnp.concatenate([img, tok], axis=1)
        return x, img.shape[1]
    return tok, 0


def lm_logits(cfg: ModelConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = dense(params["lm_head"], x)
    # keep the vocab dim model-sharded: the single biggest activation
    return shard_hint(logits, "dp", None, "tp")


# ===================================================================== #
# forward / loss
# ===================================================================== #
_REMAT_POLICIES = {
    # full remat: save only the scan carry; bwd re-runs the whole unit
    # forward INCLUDING its TP collectives
    "full": None,
    # save each block's residual contribution (the all-reduced tensors):
    # bwd recompute re-runs matmuls but NOT the collectives that produced
    # the saved outputs -- the SPerf 110B hillclimb. Costs 2 x (tokens x d)
    # bf16 per unit of saved activations.
    "save_block_outputs": "save_block_outputs",
}


def forward(
    cfg: ModelConfig,
    params: Params,
    batch: Dict,
    *,
    remat: bool = True,
    remat_policy: str = "full",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    x, _ = embed_inputs(cfg, params, batch)
    S = x.shape[1]
    positions = jnp.arange(S)
    aux = jnp.zeros((), jnp.float32)
    x = shard_hint(x, "dp", "sp", None)
    for blk in params.get("prefix", []):
        x, _, a = apply_block(cfg, "attn", blk, x, positions, None, None)
        aux = aux + a

    def unit_fn(carry, unit_params):
        x, aux = carry
        for i, kind in enumerate(cfg.block_pattern):
            x, _, a = apply_block(cfg, kind, unit_params[f"b{i}"], x, positions, None, None)
            x = shard_hint(x, "dp", "sp", None)
            aux = aux + a
        return (x, aux), None

    if remat and remat_policy == "save_block_outputs":
        from jax.ad_checkpoint import checkpoint_policies as _cp

        body = jax.checkpoint(
            unit_fn, policy=_cp.save_only_these_names("block_out")
        )
    elif remat:
        body = jax.checkpoint(unit_fn)
    else:
        body = unit_fn
    (x, aux), _ = jax.lax.scan(body, (x, aux), params["units"], unroll=_SCAN_UNROLL)
    return lm_logits(cfg, params, x), aux


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict, *, remat: bool = True,
            remat_policy: str = "full") -> jnp.ndarray:
    logits, aux = forward(cfg, params, batch, remat=remat, remat_policy=remat_policy)
    if cfg.frontend == "audio_stub" or cfg.encoder_only:
        labels = batch["labels"]
        lg = logits
    else:
        x0 = logits.shape[1] - batch["tokens"].shape[1]  # text start (VLM prefix)
        lg = logits[:, x0:-1]
        labels = batch["tokens"][:, 1:]
    lg32 = shard_hint(lg.astype(jnp.float32), "dp", None, "tp")
    lse = jax.scipy.special.logsumexp(lg32, axis=-1)
    tgt = jnp.take_along_axis(lg32, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt) + aux


# ===================================================================== #
# decode
# ===================================================================== #
def _init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int) -> Params:
    if kind == "attn":
        if cfg.use_mla:
            return {
                "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), DTYPE),
                "krope": jnp.zeros((batch, max_len, cfg.rope_head_dim), DTYPE),
            }
        # (b, hkv, S, hd): the layout the flash kernel reads
        return {
            "k": jnp.zeros((batch, cfg.n_kv_heads, max_len, cfg.head_dim), DTYPE),
            "v": jnp.zeros((batch, cfg.n_kv_heads, max_len, cfg.head_dim), DTYPE),
        }
    if kind == "mamba2":
        return ssm_mod.init_mamba2_cache(cfg, batch)
    if kind == "mlstm":
        return ssm_mod.init_mlstm_cache(cfg, batch)
    if kind == "slstm":
        return ssm_mod.init_slstm_cache(cfg, batch)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    n_prefix = cfg.first_k_dense
    n_units = (cfg.n_layers - n_prefix) // len(cfg.block_pattern)
    unit_cache = {
        f"b{i}": _init_block_cache(cfg, kind, batch, max_len)
        for i, kind in enumerate(cfg.block_pattern)
    }
    cache: Params = {
        # stack per-unit caches PRESERVING init values: recurrent caches are
        # not all-zero (the m-stabilizers of sLSTM/mLSTM start at -1e30, and
        # zeroing them silently shifts the exp-gating floor)
        "units": jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_units,) + a.shape).astype(a.dtype),
            unit_cache,
        )
    }
    if n_prefix:
        cache["prefix"] = [
            _init_block_cache(cfg, "attn", batch, max_len) for _ in range(n_prefix)
        ]
    return cache


def decode_step(
    cfg: ModelConfig,
    params: Params,
    cache: Params,
    tokens: jnp.ndarray,  # (b, 1) int32
    pos,  # scalar int32: number of tokens already in the cache
) -> Tuple[jnp.ndarray, Params]:
    assert cfg.supports_decode, f"{cfg.name} is encoder-only"
    x = params["embed"][tokens]
    positions = pos + jnp.arange(1)
    new_cache: Params = {}
    if "prefix" in cache:
        new_prefix = []
        for blk, c in zip(params["prefix"], cache["prefix"]):
            x, nc, _ = apply_block(cfg, "attn", blk, x, positions, c, pos)
            new_prefix.append(nc)
        new_cache["prefix"] = new_prefix

    # the stacked unit cache is the scan's carry: each layer writes its new
    # token into the stack, which the donated serving step updates in place.
    # Scanned as an input with a new stack as output, the whole cache would
    # be sliced out and restacked every step
    def unit_fn(carry, pu_l):
        x, cu = carry
        pu, l = pu_l
        cu = dict(cu)
        for i, kind in enumerate(cfg.block_pattern):
            x, cu[f"b{i}"], _ = apply_block(
                cfg, kind, pu[f"b{i}"], x, positions, cu[f"b{i}"], pos, layer=l
            )
        return (x, cu), None

    n_units = jax.tree.leaves(params["units"])[0].shape[0]
    (x, new_cache["units"]), _ = jax.lax.scan(
        unit_fn, (x, cache["units"]), (params["units"], jnp.arange(n_units)),
        unroll=_SCAN_UNROLL,
    )
    logits = lm_logits(cfg, params, x)
    return logits[:, 0], new_cache
