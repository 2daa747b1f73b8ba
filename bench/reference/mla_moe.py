"""Plain float32 reference of a DeepSeek-V2 decoder: latent attention (MLA)
with YaRN rope, a dense leading layer, then routed experts with shared
ones, at one chip's share of the routed experts.

It imports nothing of the program: the sizes come from the benchmark's
configuration file, under the published ``config.json`` keys, and the
weights are the arrays the benchmark made, read by their names. Attention
is causal over the whole sequence, with no latent cache, no absorption and
no kernel: each position's latent is up-projected to per-head keys and
values. The router scores all ``n_routed_experts`` and keeps the top
``num_experts_per_tok`` greedily; only the experts held here
(``experts_held`` from ``first_expert``) add their part, each computed for
every position and weighted by its gate (0 where it was not chosen). Every
matrix product runs at ``HIGHEST`` precision, layer by layer under a scan
so that one layer's float32 weights are live at a time.

``quant="fp8"`` is the control: every matrix product's operands are
rounded to float8 e4m3 (absmax scaled per tensor), the precision below the
bfloat16 the configuration serves in.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference.dense import HI, _chunks, _mm, _rms


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, base: float, rs: Dict):
    """The published ``DeepseekV2YarnRotaryEmbedding`` frequencies."""

    def correction_dim(num_rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    freq_extra = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    freq_inter = 1.0 / (rs["factor"] * base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def _rope(x, pos, base, rs):
    """Rotate-half YaRN rope on (S, heads, dim)."""
    dim = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * yarn_inv_freq(dim, base, rs)  # (S, dim/2)
    m = yarn_get_mscale(rs["factor"], rs["mscale"]) / yarn_get_mscale(rs["factor"],
                                                                       rs["mscale_all_dim"])
    cos, sin = (jnp.cos(ang) * m)[:, None, :], (jnp.sin(ang) * m)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def softmax_scale(c: Dict) -> float:
    """``(nope + rope)^-0.5``, times YaRN's mscale^2 over all dims."""
    rs = c["rope_scaling"]
    m = yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]) if rs["mscale_all_dim"] else 1.0
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * m * m


def hidden(c: Dict, params, tokens, quant: Optional[str] = None):
    """Final-normed hidden states (S, d) of one sequence ``tokens`` (S,)."""
    mm = _mm(quant)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    S = tokens.shape[0]
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    eps, base, rs = c["rms_norm_eps"], c["rope_theta"], c["rope_scaling"]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    x = f32(params["embed"][tokens])

    def attention(a, x):
        q = mm("sd,de->se", x, f32(a["wq"]["w"])).reshape(S, h, dn + dr)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, base, rs)], axis=-1)
        down = mm("sd,de->se", x, f32(a["kv_down"]["w"]))
        latent = _rms(down[:, :r], f32(a["latent_norm"]), eps)
        k_rope = _rope(down[:, None, r:], pos, base, rs)  # one rope key for every head
        kv = mm("sr,re->se", latent, f32(a["kv_up"]["w"])).reshape(S, h, dn + dv)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (S, h, dr))], axis=-1)
        s = mm("qhd,khd->hqk", q, k) * softmax_scale(c)
        w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = mm("hqk,khd->qhd", w, kv[..., dn:]).reshape(S, h * dv)
        return mm("sd,de->se", o, f32(a["wo"]["w"]))

    def swiglu(f, x):
        gate = jax.nn.silu(mm("sd,df->sf", x, f32(f["gate"]["w"])))
        return mm("sf,fd->sd", gate * mm("sd,df->sf", x, f32(f["up"]["w"])), f32(f["down"]["w"]))

    def experts(m, x):
        probs = jax.nn.softmax(mm("sd,de->se", x, f32(m["router"]["w"])), axis=-1)
        gates, chosen = lax.top_k(probs, c["num_experts_per_tok"])
        if c["norm_topk_prob"]:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        held = c["first_expert"] + jnp.arange(c["experts_held"])
        weight = jnp.sum(jnp.where(chosen[:, :, None] == held, gates[:, :, None], 0.0), axis=1)
        act = jax.nn.silu(mm("sd,edf->sef", x, f32(m["w_gate"]))) * mm(
            "sd,edf->sef", x, f32(m["w_up"]))
        out = mm("sef,efd->sed", act, f32(m["w_down"]))  # (S, held, d)
        routed = jnp.sum(weight[:, :, None] * out, axis=1)
        return routed * c["routed_scaling_factor"] + swiglu(m["shared"], x)

    def layer(x, p):
        x = x + attention(p["attn"], _rms(x, f32(p["ln1"]), eps))
        h2 = _rms(x, f32(p["ln2"]), eps)
        return x + (experts(p["moe"], h2) if "moe" in p else swiglu(p["ffn"], h2)), None

    for blk in params["prefix"]:
        x, _ = layer(x, blk)
    x, _ = lax.scan(layer, x, params["units"]["b0"])
    return _rms(x, f32(params["final_norm"]), eps)


@partial(jax.jit, static_argnames=("c_items", "control", "chunk"))
def _gaps(params, tokens, targets, c_items, control, chunk):
    c = dict(c_items)
    c["rope_scaling"] = dict(c["rope_scaling"])
    head = params["lm_head"]["w"].astype(jnp.float32)
    h = hidden(c, params, tokens)
    hq = hidden(c, params, tokens, quant="fp8") if control else h
    mm8 = _mm("fp8")

    def one(args):
        hs, hqs, tg = args
        ref = jnp.einsum("sd,dv->sv", hs, head, precision=HI)
        best = jnp.max(ref, axis=-1)
        served = jnp.take_along_axis(ref, jnp.maximum(tg, 0)[:, None], axis=-1)[:, 0]
        gap = jnp.where(tg >= 0, best - served, 0.0)
        if not control:
            return gap, gap
        pick = jnp.argmax(mm8("sd,dv->sv", hqs, head), axis=-1)
        chosen = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        return gap, jnp.where(tg >= 0, best - chosen, 0.0)

    gap, ctrl = lax.map(one, (_chunks(h, chunk), _chunks(hq, chunk), _chunks(targets, chunk)))
    return gap.reshape(-1), ctrl.reshape(-1)


REF_KEYS = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rms_norm_eps", "rope_theta", "num_experts_per_tok", "norm_topk_prob",
            "experts_held", "first_expert", "routed_scaling_factor")


def config_items(c: Dict):
    """The sizes the reference reads, hashable (the jit's static key)."""
    return tuple((k, c[k]) for k in REF_KEYS) + (
        ("rope_scaling", tuple(sorted(c["rope_scaling"].items()))),)


def served_gaps(c: Dict, params, tokens, targets, control: bool = False, chunk: int = 128):
    """Per position of ``tokens`` (S,), the gap by which the logit of
    ``targets`` (S,; -1 where nothing was served) lies below the
    reference's best logit. With ``control`` also the gap of the token the
    float8 reference puts first. Returns host arrays (gap, control_gap)."""
    import numpy as np

    if c["tie_word_embeddings"]:
        raise ValueError("the reference reads an untied head")
    chunk = min(chunk, len(tokens))
    gap, ctrl = _gaps(params, jnp.asarray(tokens, jnp.int32), jnp.asarray(targets, jnp.int32),
                      config_items(c), bool(control), int(chunk))
    return np.asarray(gap), np.asarray(ctrl)
