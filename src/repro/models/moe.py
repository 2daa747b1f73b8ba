"""Mixture-of-Experts FFN: shared + routed experts, top-k gating,
capacity-based dispatch (GShard/Switch-style) with load-balance aux loss.

Dispatch is index-based (cumsum positions + scatter into an (E, C, d)
buffer) rather than a dense (T, E, C) one-hot einsum, so the biggest
intermediate is (T, E) -- this is what keeps the 1M-token train_4k cells
compilable. Experts carry a leading E axis so expert parallelism is plain
GSPMD sharding of that axis over the 'model' mesh axis.

Under expert parallelism across chips a layer may hold a share of the
routed experts (``cfg.experts_held`` from ``cfg.first_expert``): the
router keeps all ``n_routed_experts`` outputs and its top-k, assignments
to held experts are dispatched to the local buffer, and the rest add
nothing here. Nothing stands in for the absent chips or their all-to-all.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import DTYPE, act_fn, dense, init_dense
from repro.runtime.records import TraceRecord
from repro.sharding.hints import shard_hint

Params = Dict[str, jnp.ndarray]


class MoeCall(NamedTuple):
    """The shape of one traced routed-expert layer call."""

    tokens: int
    top_k: int
    n_routed_experts: int
    experts_held: int


_MOE_RECORD = TraceRecord()


def moe_record() -> Dict[MoeCall, Dict[str, int]]:
    """Per traced call: ``buffer_rows``, the dispatch buffer's rows the
    held experts multiply (held experts x capacity)."""
    return _MOE_RECORD.snapshot()


def reset_moe_record() -> None:
    _MOE_RECORD.reset()


def top_k_gates(cfg: ModelConfig, probs: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(T, E) router probabilities -> the top-k gates and experts (T, k);
    the gates sum to 1 only where the config renormalizes them."""
    gates, eidx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, eidx


def init_moe(key, cfg: ModelConfig) -> Params:
    d, e, de = cfg.d_model, cfg.n_routed_experts, cfg.d_expert
    held = cfg.n_experts_held
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": init_dense(ks[0], d, e),
        # routed experts held here: stacked (held, d, de) / (held, de, d)
        "w_gate": (jax.random.normal(ks[1], (held, d, de), jnp.float32) * scale).astype(DTYPE),
        "w_up": (jax.random.normal(ks[2], (held, d, de), jnp.float32) * scale).astype(DTYPE),
        "w_down": (jax.random.normal(ks[3], (held, de, d), jnp.float32) / math.sqrt(de)).astype(DTYPE),
    }
    if cfg.n_shared_experts:
        dsh = cfg.n_shared_experts * de
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {
            "gate": init_dense(kss[0], d, dsh),
            "up": init_dense(kss[1], d, dsh),
            "down": init_dense(kss[2], dsh, d),
        }
    return p


def moe_apply(
    p: Params, cfg: ModelConfig, x: jnp.ndarray, *, dropless: bool = False
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (b, s, d) -> (y, aux_loss).

    ``dropless=True`` sizes the expert buffers at T*k so no assignment is
    ever dropped -- the decode/serving path uses this (capacity dropping
    is a training-throughput tradeoff; dropping tokens at decode would
    corrupt generation)."""
    b, s, d = x.shape
    e, k = cfg.n_routed_experts, cfg.top_k
    held = cfg.n_experts_held
    T = b * s
    xt = x.reshape(T, d)
    logits = dense(p["router"], xt).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eidx = top_k_gates(cfg, probs)  # (T, k)

    # ---- load-balance auxiliary loss (Switch): E * sum_e f_e * P_e ----- #
    onehot_top1 = jax.nn.one_hot(eidx[:, 0], e, dtype=jnp.float32)
    f_e = jnp.mean(onehot_top1, axis=0)
    P_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * P_e) * cfg.router_aux_coef

    # ---- capacity-based dispatch -------------------------------------- #
    if dropless:
        C = T * k
    else:
        C = max(1, int(math.ceil(T * k * cfg.capacity_factor / e)))
    _MOE_RECORD.put(MoeCall(T, k, e, held), {"buffer_rows": held * C})
    # held expert of each assignment (row-major: all k slots of token 0,
    # then token 1, ...); an assignment to an expert held elsewhere parks
    flat_e = eidx.reshape(T * k) - cfg.first_expert
    here = (flat_e >= 0) & (flat_e < held)
    flat_e = jnp.where(here, flat_e, 0)
    flat_g = gate_vals.reshape(T * k)
    tok_of = jnp.repeat(jnp.arange(T), k)
    onehot = jax.nn.one_hot(flat_e, held, dtype=jnp.int32) * here[:, None]  # (T*k, held)
    pos = jnp.cumsum(onehot, axis=0) - 1  # position within expert
    slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]  # (T*k,)
    keep = here & (slot < C)
    slot_c = jnp.where(keep, slot, C)  # overflow or elsewhere -> parked slot C

    buf = jnp.zeros((held, C + 1, d), x.dtype)
    buf = buf.at[flat_e, slot_c].add(xt[tok_of])
    buf = buf[:, :C]  # (held, C, d)
    buf = shard_hint(buf, "tp", None, None)  # expert-parallel dispatch buffer

    # ---- expert computation (EP-shardable einsums over leading E) ------ #
    f = act_fn(cfg.act)
    h = f(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", buf, p["w_up"]
    )
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])  # (held, C, d)

    # ---- combine -------------------------------------------------------- #
    out_padded = jnp.concatenate([out, jnp.zeros((held, 1, d), out.dtype)], axis=1)
    gathered = out_padded[flat_e, slot_c]  # (T*k, d); parked slot reads zeros
    weighted = gathered * (flat_g * keep.astype(jnp.float32)).astype(gathered.dtype)[:, None]
    y = jnp.zeros((T, d), x.dtype).at[tok_of].add(weighted)

    if "shared" in p:
        sh = p["shared"]
        y = y + dense(sh["down"], f(dense(sh["gate"], xt)) * dense(sh["up"], xt))
    return y.reshape(b, s, d), aux
