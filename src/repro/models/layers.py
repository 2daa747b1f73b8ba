"""Core layers: norms, RoPE, dense, chunked attention (GQA + MLA), MLP.

Everything is functional: ``init_*`` builds a params pytree, ``*_apply``
consumes it. Attention uses a q-chunked online-softmax-free formulation
(full softmax per q-chunk against all keys) so 32k-sequence cells never
materialize an SxS score tensor; the Pallas flash-attention kernel
(repro/kernels/flash_attention) is the TPU fast path for the same math and
is validated against these references.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, YarnScaling
from repro.runtime.records import TraceRecord

Params = Dict[str, jnp.ndarray]
DTYPE = jnp.bfloat16


# --------------------------------------------------------------------- #
# primitives
# --------------------------------------------------------------------- #
def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def init_dense(key, d_in: int, d_out: int, bias: bool = False, scale: float = None) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(DTYPE)}
    if bias:
        p["b"] = jnp.zeros((d_out,), DTYPE)
    return p


def dense(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# --------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------- #
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature for a context stretched ``factor``
    times (DeepSeek-V2's ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, ys: YarnScaling) -> jnp.ndarray:
    """YaRN's inverse frequencies: rope's own above the fast correction
    dim, divided by ``factor`` below the slow one, and a linear ramp
    between (DeepSeek-V2's ``yarn_find_correction_range`` and
    ``yarn_linear_ramp_mask``)."""

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(ys.original_max_position / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(ys.beta_fast)), 0)
    high = min(math.ceil(correction_dim(ys.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    return extra / ys.factor * ramp + extra * (1 - ramp)


def rope_cos_sin(positions: jnp.ndarray, dim: int, theta: float,
                 scaling: Optional[YarnScaling] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions: (..., S) int32 -> cos/sin (..., S, dim//2) f32; with
    ``scaling``, YaRN's frequencies and its cos/sin magnitude."""
    if scaling is None:
        inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
        m = 1.0
    else:
        inv = yarn_inv_freq(dim, theta, scaling)
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (b, S, h, d); cos/sin: (b, S, d//2) or (S, d//2)."""
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    if cos.ndim == 2:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------- #
# chunked multi-head attention with GQA grouping
# --------------------------------------------------------------------- #
def _auto_q_chunk(B: int, Sq: int, Skv: int, hq: int,
                  budget: int = 1 << 31) -> int:
    """Pick the q-chunk (the chip-level temporal tile of the attention
    Problem's q dim) so the f32 score chunk fits the HBM budget PER CHIP --
    Union legality rule R3 applied at the HBM cluster level. Matters when
    heads cannot shard over 'model' (llava's 56 heads on a 16-way axis):
    the fallback keeps heads unsharded and shrinks the temporal tile
    instead."""
    from repro.sharding import hints as _h

    st = _h._STATE
    qc = 1024
    if not st["enabled"]:
        return qc
    sizes = st["sizes"]
    dp = st["dp"] or ()
    dpn = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dpn *= sizes.get(a, 1)
    tpn = sizes.get(st["tp"], 1) if st["tp"] else 1
    hq_loc = hq // tpn if hq % tpn == 0 else hq
    b_loc = B // dpn if B % dpn == 0 else B
    while qc > 128 and b_loc * qc * Skv * hq_loc * 4 > budget:
        qc //= 2
    return qc


def mha(
    q: jnp.ndarray,  # (b, Sq, hq, d)
    k: jnp.ndarray,  # (b, Skv, hkv, d), or (b, hkv, Skv, d) if kv_heads_major
    v: jnp.ndarray,  # (b, Skv, hkv, dv), or (b, hkv, Skv, dv) if kv_heads_major
    *,
    causal: bool,
    q_offset=0,  # int or scalar array: global position of q[0]
    kv_len: Optional[jnp.ndarray] = None,  # valid cache length (decode)
    q_chunk: Optional[int] = None,
    sm_scale: Optional[float] = None,
    kv_heads_major: bool = False,  # k/v in the decode cache's layout
    layer=None,  # k/v stacked over layers (the decode cache): this layer
) -> jnp.ndarray:
    b, Sq, hq, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    from repro import kernels as _k
    if _k.pallas_enabled():
        from repro.kernels.flash_attention import flash_attention
        return flash_attention(
            q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len,
            sm_scale=scale, kv_heads_major=kv_heads_major, layer=layer,
        )
    if layer is not None:
        k, v = (jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False) for a in (k, v))
    if kv_heads_major:
        k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
    _, Skv, hkv, dv = v.shape
    g = hq // hkv
    if q_chunk is None:
        q_chunk = _auto_q_chunk(b, Sq, Skv, hq)
    # GQA: repeat KV heads to hq so the head axis stays flat and GSPMD can
    # shard it over 'model' even when hkv < mesh size (e.g. starcoder2 kv=4
    # on a 16-way TP axis). The repeat is sharded and cheap; the Pallas
    # flash kernel avoids it natively on TPU.
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    kpos = jnp.arange(Skv)

    def attend(qc: jnp.ndarray, qpos: jnp.ndarray) -> jnp.ndarray:
        # qc: (b, c, hq, d); qpos: (c,) global positions
        s = jnp.einsum("bchd,bkhd->bhck", qc, k, preferred_element_type=jnp.float32)
        s = s * scale
        mask = jnp.ones((qc.shape[1], Skv), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if kv_len is not None:
            mask &= kpos[None, :] < kv_len
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhck,bkhd->bchd", p, v)

    if Sq <= q_chunk:
        out = attend(q, q_offset + jnp.arange(Sq))
    else:
        assert Sq % q_chunk == 0, f"Sq={Sq} must divide q_chunk={q_chunk}"
        nq = Sq // q_chunk
        qs = q.reshape(b, nq, q_chunk, hq, d).transpose(1, 0, 2, 3, 4)

        def body(_, qi_i):
            qi, i = qi_i
            pos = q_offset + i * q_chunk + jnp.arange(q_chunk)
            return None, attend(qi, pos)

        _, outs = jax.lax.scan(body, None, (qs, jnp.arange(nq)))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(b, Sq, hq, dv)
    return out.reshape(b, Sq, hq, dv)


# --------------------------------------------------------------------- #
# decode cache writes
# --------------------------------------------------------------------- #
def cache_write(c: jnp.ndarray, new: jnp.ndarray, pos, seq_axis: int,
                layer=None) -> jnp.ndarray:
    """Write ``new`` into the cache leaf ``c`` at ``pos`` along its sequence
    axis; returns the updated leaf. ``c`` is one layer's leaf, or with
    ``layer`` the stack of every scanned layer's leaves, whose leading axis
    is the layer: the token is then written into the stack, which the
    donated decode step updates in place."""
    new = new.astype(c.dtype)
    idx = [0] * c.ndim
    if layer is None:
        idx[seq_axis] = pos
        return jax.lax.dynamic_update_slice(c, new, idx)
    idx[0], idx[seq_axis + 1] = layer, pos
    return jax.lax.dynamic_update_slice(c, new[None], idx)


# --------------------------------------------------------------------- #
# GQA attention layer (with optional qk-norm, bias, KV cache)
# --------------------------------------------------------------------- #
def init_attention(key, cfg: ModelConfig) -> Params:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": init_dense(ks[0], d, hq * hd, cfg.qkv_bias),
        "wk": init_dense(ks[1], d, hkv * hd, cfg.qkv_bias),
        "wv": init_dense(ks[2], d, hkv * hd, cfg.qkv_bias),
        "wo": init_dense(ks[3], hq * hd, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), DTYPE)
        p["k_norm"] = jnp.ones((hd,), DTYPE)
    return p


def attention_apply(
    p: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,  # (b, S, d)
    positions: jnp.ndarray,  # (S,) global positions of x
    cache: Optional[Params] = None,  # {"k","v"}: (b, hkv, Smax, hd); decode only
    cache_len=None,  # filled length of the cache before this call
    layer=None,  # cache leaves stacked over layers: this layer's index
) -> Tuple[jnp.ndarray, Optional[Params]]:
    b, S, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(p["wq"], x).reshape(b, S, hq, hd)
    k = dense(p["wk"], x).reshape(b, S, hkv, hd)
    v = dense(p["wv"], x).reshape(b, S, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta, cfg.rope_scaling)
    if not cfg.encoder_only:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    new_cache = None
    if cache is not None:
        # decode: write new k/v at cache_len, attend over the whole cache,
        # which is kept in the flash kernel's (b, hkv, S, hd) layout; a
        # stacked cache goes to mha whole, with the layer to read
        ck = cache_write(cache["k"], jnp.swapaxes(k, 1, 2), cache_len, 2, layer)
        cv = cache_write(cache["v"], jnp.swapaxes(v, 1, 2), cache_len, 2, layer)
        new_cache = {"k": ck, "v": cv}
        out = mha(q, ck, cv, causal=False, q_offset=cache_len,
                  kv_len=cache_len + S, kv_heads_major=True, layer=layer)
    else:
        out = mha(q, k, v, causal=not cfg.encoder_only, q_offset=0)
    y = dense(p["wo"], out.reshape(b, S, hq * hd))
    return y, new_cache


# --------------------------------------------------------------------- #
# MLA attention (DeepSeek-V2): latent-compressed KV cache
# --------------------------------------------------------------------- #
class MlaCall(NamedTuple):
    """The shape of one traced MLA call that reads the decode cache."""

    batch: int
    q_seq: int
    cache_len: int


_MLA_RECORD = TraceRecord()


def mla_record() -> Dict[MlaCall, Dict[str, int]]:
    """Per traced decode call: ``upproj_rows``, the latent rows the call
    up-projects to per-head keys and values."""
    return _MLA_RECORD.snapshot()


def reset_mla_record() -> None:
    _MLA_RECORD.reset()


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(q/k head dim), times YaRN's mscale^2 where the config scales
    rope (DeepSeek-V2's ``softmax_scale``)."""
    scale = 1.0 / math.sqrt(cfg.nope_head_dim + cfg.rope_head_dim)
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        scale *= yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
    return scale


def init_mla(key, cfg: ModelConfig) -> Params:
    d, h = cfg.d_model, cfg.n_heads
    r, dr, dn, dv = cfg.kv_lora_rank, cfg.rope_head_dim, cfg.nope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": init_dense(ks[0], d, h * (dn + dr)),
        "kv_down": init_dense(ks[1], d, r + dr),  # latent + shared rope key
        "kv_up": init_dense(ks[2], r, h * (dn + dv)),
        "wo": init_dense(ks[3], h * dv, d),
        "latent_norm": jnp.ones((r,), DTYPE),
    }


def mla_apply(
    p: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cache: Optional[Params] = None,  # {"ckv": (b,Smax,r), "krope": (b,Smax,dr)}
    cache_len=None,
    layer=None,  # as in attention_apply
) -> Tuple[jnp.ndarray, Optional[Params]]:
    b, S, d = x.shape
    h = cfg.n_heads
    r, dr, dn, dv = cfg.kv_lora_rank, cfg.rope_head_dim, cfg.nope_head_dim, cfg.v_head_dim
    q = dense(p["wq"], x).reshape(b, S, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    down = dense(p["kv_down"], x)
    ckv, k_rope = down[..., :r], down[..., r:]
    ckv = rms_norm(ckv, p["latent_norm"], cfg.rms_eps)
    cos, sin = rope_cos_sin(positions, dr, cfg.rope_theta, cfg.rope_scaling)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope.reshape(b, S, 1, dr), cos, sin)

    new_cache = None
    if cache is not None:
        c_ckv = cache_write(cache["ckv"], ckv, cache_len, 1, layer)
        c_kr = cache_write(cache["krope"], k_rope.reshape(b, S, dr), cache_len, 1, layer)
        new_cache = {"ckv": c_ckv, "krope": c_kr}
        ckv, kr = c_ckv, c_kr
        if layer is not None:
            ckv, kr = (jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
                       for a in (c_ckv, c_kr))
        k_rope = kr.reshape(b, -1, 1, dr)
        _MLA_RECORD.put(MlaCall(b, S, ckv.shape[1]), {"upproj_rows": b * ckv.shape[1]})
        kv_len = cache_len + S
        q_offset = cache_len
        causal = False
    else:
        kv_len = None
        q_offset = 0
        causal = True
    # up-project latents to per-head keys/values
    kv = dense(p["kv_up"], ckv).reshape(b, ckv.shape[1], h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    # concat nope+rope parts; rope key is shared across heads (hkv=1 for it)
    k_full = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, ckv.shape[1], h, dr))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = mha(q_full, k_full, v, causal=causal, q_offset=q_offset, kv_len=kv_len,
              sm_scale=mla_softmax_scale(cfg))
    y = dense(p["wo"], out.reshape(b, S, h * dv))
    return y, new_cache


# --------------------------------------------------------------------- #
# MLPs
# --------------------------------------------------------------------- #
def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    ff = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act in ("silu",):  # gated (SwiGLU)
        return {
            "gate": init_dense(ks[0], d, ff),
            "up": init_dense(ks[1], d, ff),
            "down": init_dense(ks[2], ff, d),
        }
    return {"up": init_dense(ks[0], d, ff), "down": init_dense(ks[1], ff, d)}


def mlp_apply(p: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    f = act_fn(cfg.act)
    if "gate" in p:
        return dense(p["down"], f(dense(p["gate"], x)) * dense(p["up"], x))
    return dense(p["down"], f(dense(p["up"], x)))
