"""Expert-parallel MoE via shard_map + all-to-all (the SPerf MoE hillclimb).

WHY: the baseline moe_apply relies on GSPMD to shard the dispatch
scatter/gather. GSPMD cannot reason about data-dependent scatters onto an
expert-sharded buffer, so it REPLICATES the dispatch buffer and the expert
einsums on every chip -- the dry-run measured ~50-100x the active FLOPs on
the MoE cells (EXPERIMENTS.md SPerf). The production pattern -- explicit
all-to-all between token-sharded and expert-sharded layouts -- cannot be
expressed as sharding constraints; it needs per-device code. This module
is that pattern in jax-native form (shard_map + lax.all_to_all), exactly
the "map the paper's communication pattern onto jax constructs" adaptation
called for in DESIGN.md.

Layout contract (matches the activation sharding the launcher installs):
  tokens: batch over the dp axes, sequence over the tp axis
  experts: padded to a multiple of tp_n, sharded over the tp axis
Per device: route local tokens -> bucket by owning device (fixed capacity)
-> all_to_all -> local-expert capacity dispatch -> compute -> all_to_all
back -> gate-weighted combine. Empty slots carry zeros and are harmless
(gateless SwiGLU maps 0 -> 0). Capacity drops occur (a) into each
destination bucket and (b) within the owner's local dispatch -- same
semantics class as the baseline's single capacity rule.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import act_fn, dense
from repro.models.moe import top_k_gates
from repro.sharding import hints as hints_mod

Params = Dict[str, jnp.ndarray]


def ep_available(cfg: ModelConfig, x: jnp.ndarray) -> bool:
    if cfg.n_experts_held != cfg.n_routed_experts:  # a chip's share: no mesh here
        return False
    st = hints_mod._STATE
    if not (st.get("enabled") and st.get("tp") and st.get("mesh") is not None):
        return False
    sizes = st["sizes"]
    tp_n = sizes.get(st["tp"], 1)
    dp = st.get("dp") or ()
    dp_n = math.prod(sizes.get(a, 1) for a in (dp if isinstance(dp, tuple) else (dp,)))
    b, s, _ = x.shape
    return tp_n > 1 and b % max(1, dp_n) == 0 and s % tp_n == 0


def _capacity_dispatch(xt, eids, n_buckets, cap):
    """Assign slot-within-bucket for each row; returns (buf, slot, keep).

    xt: (N, d) rows; eids: (N,) bucket ids. buf: (n_buckets, cap, d);
    overflow rows park at slot==cap (dropped).
    """
    N, d = xt.shape
    onehot = jax.nn.one_hot(eids, n_buckets, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.take_along_axis(pos, eids[:, None], axis=1)[:, 0]
    keep = slot < cap
    slot_c = jnp.where(keep, slot, cap)
    buf = jnp.zeros((n_buckets, cap + 1, d), xt.dtype)
    buf = buf.at[eids, slot_c].add(xt)
    return buf[:, :cap], slot_c, keep


def moe_apply_ep(
    p: Params, cfg: ModelConfig, x: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in for moe_apply (same params pytree, same (y, aux) contract)."""
    st = hints_mod._STATE
    mesh = st["mesh"]
    tp = st["tp"]
    sizes = st["sizes"]
    tp_n = sizes[tp]
    dp = st.get("dp") or ()
    dp = dp if isinstance(dp, tuple) else (dp,)
    all_axes = tuple(a for a in mesh.axis_names)

    e, k = cfg.n_routed_experts, cfg.top_k
    e_pad = (e + tp_n - 1) // tp_n * tp_n
    e_loc = e_pad // tp_n
    b, s, d = x.shape
    f = act_fn(cfg.act)

    # pad the expert banks so E divides the tp axis (extra experts receive
    # -inf router logits and therefore no tokens)
    def pad_e(w):
        return jnp.pad(w, ((0, e_pad - e),) + ((0, 0),) * (w.ndim - 1))

    wg, wu, wd = pad_e(p["w_gate"]), pad_e(p["w_up"]), pad_e(p["w_down"])
    wr = p["router"]["w"]

    T_loc = (b * s) // (math.prod(sizes.get(a, 1) for a in dp) * tp_n)
    cap_send = max(1, int(math.ceil(T_loc * k * cfg.capacity_factor / tp_n)))
    cap_own = max(1, int(math.ceil(tp_n * cap_send * cfg.capacity_factor / e_loc)))

    def body(x_blk, wr_, wg_, wu_, wd_):
        b_l, s_l, _ = x_blk.shape
        T = b_l * s_l
        xt = x_blk.reshape(T, d)
        logits = (xt @ wr_).astype(jnp.float32)  # (T, e) real experts only
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eidx = top_k_gates(cfg, probs)  # (T, k) over REAL experts

        # aux loss over the GLOBAL batch (pmean across every mesh axis)
        onehot_top1 = jax.nn.one_hot(eidx[:, 0], e, dtype=jnp.float32)
        f_e = jnp.mean(onehot_top1, axis=0)
        P_e = jnp.mean(probs, axis=0)
        for ax in all_axes:
            f_e = jax.lax.pmean(f_e, ax)
            P_e = jax.lax.pmean(P_e, ax)
        aux = e * jnp.sum(f_e * P_e) * cfg.router_aux_coef

        flat_e = eidx.reshape(T * k)
        flat_g = gates.reshape(T * k)
        tok_of = jnp.repeat(jnp.arange(T), k)
        dest = flat_e // e_loc  # owning device along tp
        local_e = flat_e % e_loc

        # bucket rows by destination device (capacity cap_send each)
        send_x, slot1, keep1 = _capacity_dispatch(
            xt[tok_of], dest, tp_n, cap_send
        )
        # ship the local-expert id per slot the same way (as f32 payload)
        ebuf = jnp.zeros((tp_n, cap_send + 1), jnp.int32)
        ebuf = ebuf.at[dest, slot1].max(
            jnp.where(keep1, local_e, 0).astype(jnp.int32)
        )
        send_e = ebuf[:, :cap_send]

        recv_x = jax.lax.all_to_all(send_x, tp, 0, 0, tiled=False)
        recv_e = jax.lax.all_to_all(send_e, tp, 0, 0, tiled=False)
        T_r = tp_n * cap_send
        rx = recv_x.reshape(T_r, d)
        re = recv_e.reshape(T_r)

        # local-expert capacity dispatch + expert FFNs
        buf, slot2, keep2 = _capacity_dispatch(rx, re, e_loc, cap_own)
        h = f(jnp.einsum("ecd,edf->ecf", buf, wg_)) * jnp.einsum(
            "ecd,edf->ecf", buf, wu_
        )
        out = jnp.einsum("ecf,efd->ecd", h, wd_)  # (e_loc, cap_own, d)

        # route results back to the original rows
        out_pad = jnp.concatenate(
            [out, jnp.zeros((e_loc, 1, d), out.dtype)], axis=1
        )
        back = out_pad[re, slot2]  # (T_r, d); dropped rows read zeros
        back = back.reshape(tp_n, cap_send, d)
        ret = jax.lax.all_to_all(back, tp, 0, 0, tiled=False)
        ret_pad = jnp.concatenate(
            [ret, jnp.zeros((tp_n, 1, d), ret.dtype)], axis=1
        )
        vals = ret_pad[dest, slot1]  # (T*k, d); parked slots read zeros
        w = (flat_g * keep1.astype(jnp.float32)).astype(vals.dtype)
        y = jnp.zeros((T, d), x_blk.dtype).at[tok_of].add(vals * w[:, None])
        return y.reshape(b_l, s_l, d), aux

    x_spec = P(dp if dp else None, tp, None)
    y, aux = shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), P(tp, None, None),
                  P(tp, None, None), P(tp, None, None)),
        out_specs=(x_spec, P()),
        check_rep=False,
    )(x, wr, wg, wu, wd)

    # shared experts: plain dense compute outside the shard_map (token-
    # sharded GEMMs that GSPMD handles well)
    if "shared" in p:
        sh = p["shared"]
        xt = x.reshape(b * s, d)
        y = y + dense(sh["down"], f(dense(sh["gate"], xt)) * dense(sh["up"], xt)).reshape(b, s, d)
    return y, aux
