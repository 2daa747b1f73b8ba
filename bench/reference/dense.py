"""Plain float32 reference of a dense decoder-only LM (GQA, optional
qk-norm and QKV bias, SwiGLU, RoPE, tied or untied head).

It imports nothing of the program: the sizes come from the benchmark's
configuration file, the weights are the arrays the benchmark made, read by
their names. Attention is causal over the whole sequence, with no cache
and no kernel; every matrix product runs at ``HIGHEST`` precision, layer
by layer under a scan so that one layer's float32 weights are live at a
time.

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

HI = lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _round_fp8(x):
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(quant: Optional[str]):
    rnd = _round_fp8 if quant == "fp8" else (lambda a: a)

    def mm(spec, a, b):
        return jnp.einsum(spec, rnd(a), rnd(b), precision=HI)

    return mm


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate-half RoPE on (S, heads, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def hidden(c: Dict, params, tokens, quant: Optional[str] = None):
    """Final-normed hidden states (S, d) of one sequence ``tokens`` (S,)."""
    mm = _mm(quant)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    S = tokens.shape[0]
    hq, hkv, hd = c["n_heads"], c["n_kv_heads"], c["d_head"]
    eps = c["rms_eps"]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    x = f32(params["embed"][tokens])

    def proj(p, h):
        y = mm("sd,de->se", h, f32(p["w"]))
        return y + f32(p["b"]) if "b" in p else y

    def layer(x, p):
        a = p["attn"]
        h = _rms(x, f32(p["ln1"]), eps)
        q = proj(a["wq"], h).reshape(S, hq, hd)
        k = proj(a["wk"], h).reshape(S, hkv, hd)
        v = proj(a["wv"], h).reshape(S, hkv, hd)
        if c["qk_norm"]:
            q = _rms(q, f32(a["q_norm"]), eps)
            k = _rms(k, f32(a["k_norm"]), eps)
        q = _rope(q, pos, c["rope_theta"])
        k = _rope(k, pos, c["rope_theta"])
        g = hq // hkv
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        s = mm("qhd,khd->hqk", q, k) / math.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = mm("hqk,khd->qhd", w, v).reshape(S, hq * hd)
        x = x + mm("sd,de->se", o, f32(a["wo"]["w"]))
        f = p["ffn"]
        h2 = _rms(x, f32(p["ln2"]), eps)
        gate = jax.nn.silu(mm("sd,df->sf", h2, f32(f["gate"]["w"])))
        up = mm("sd,df->sf", h2, f32(f["up"]["w"]))
        x = x + mm("sf,fd->sd", gate * up, f32(f["down"]["w"]))
        return x, None

    x, _ = lax.scan(layer, x, params["units"]["b0"])
    return _rms(x, f32(params["final_norm"]), eps)


def _head(c: Dict, params):
    if c["tie_embeddings"]:
        return params["embed"].astype(jnp.float32).T
    return params["lm_head"]["w"].astype(jnp.float32)


def _chunks(x, size):
    S = x.shape[0]
    return x.reshape(S // size, size, *x.shape[1:])


@partial(jax.jit, static_argnames=("c_items", "control", "chunk"))
def _gaps(params, tokens, targets, c_items, control, chunk):
    c = dict(c_items)
    head = _head(c, params)
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
        low = mm8("sd,dv->sv", hqs, head)
        pick = jnp.argmax(low, axis=-1)
        chosen = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        return gap, jnp.where(tg >= 0, best - chosen, 0.0)

    gap, ctrl = lax.map(one, (_chunks(h, chunk), _chunks(hq, chunk), _chunks(targets, chunk)))
    return gap.reshape(-1), ctrl.reshape(-1)


REF_KEYS = ("n_heads", "n_kv_heads", "d_head", "rms_eps", "qk_norm",
            "rope_theta", "tie_embeddings")


def served_gaps(c: Dict, params, tokens, targets, control: bool = False, chunk: int = 128):
    """Per position of ``tokens`` (S,), the gap by which the logit of
    ``targets`` (S,; -1 where nothing was served) lies below the
    reference's best logit. With ``control`` also the gap of the token the
    float8 reference puts first. Returns host arrays (gap, control_gap)."""
    import numpy as np

    items = tuple((k, c[k]) for k in REF_KEYS)
    chunk = min(chunk, len(tokens))
    gap, ctrl = _gaps(params, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(targets, jnp.int32), items, bool(control), int(chunk))
    return np.asarray(gap), np.asarray(ctrl)
