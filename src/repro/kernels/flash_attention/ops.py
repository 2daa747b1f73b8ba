"""Public flash-attention op: (b,s,h,d) layout adapter, padding, decode path.

Block sizes come from the shared co-design layer (docs/codesign.md):
:class:`FlashAttentionSpace` registers the per-head attention score
Problem (einsum ``qd,kd->qk``) with ``repro.codesign``, and
``plan_blocks`` is a thin wrapper over the single ``codesign.plan`` path.
The C1 temporal tile (bq, bk) must satisfy rule R3 with the f32 score
block + q/k/v/acc blocks resident -- same legality machinery (and now the
same planner, plan cache, and VMEM-budget convention) as the matmul
kernel.

Gradients: forward runs the Pallas kernel; backward recomputes through the
jnp oracle (ref.py) under ``jax.vjp`` -- numerically identical math. A
fused backward kernel is a further TPU optimization left on the table and
recorded in EXPERIMENTS.md SPerf.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import codesign
from repro import kernels as _cfg
from repro.codesign import (
    KernelSpace,
    fit_tiles,
    pipeline_vmem_bytes,
    repair_tile,
    round_up,
)
from repro.core.constraints import mxu_aligned
from repro.core.problem import Problem
from repro.kernels.flash_attention.flash_attention import (  # noqa: F401
    flash_attention_pallas,
    kernel_record,
    reset_kernel_record,
)
from repro.kernels.flash_attention.ref import attention_ref


def flash_vmem_bytes(blocks, D: int) -> int:
    """VMEM the compiler allocates for ``flash_attention_pallas`` at (bq,
    bk) and head dim D: double-buffered bf16 q, k, v and out blocks, the
    f32 max/denominator/accumulator scratch, and the f32 score block."""
    bq, bk = blocks
    return pipeline_vmem_bytes(
        [2 * n * D for n in (bq, bk, bk, bq)],
        [4 * bq * 128, 4 * bq * 128, 4 * bq * D, 4 * bq * bk],
    )


class FlashAttentionSpace(KernelSpace):
    """Co-design space of the flash-attention kernel: shape =
    (Sq, Skv, D) per head, BlockConfig = (bq, bk)."""

    name = "flash_attention"
    decode_dims = ("q", "k")
    search_budget = 200

    def problem(self, shape):
        Sq, Skv, D = shape
        return Problem.from_einsum(
            "attn_scores", "qd,kd->qk", {"q": Sq, "k": Skv, "d": D}, "GEMM"
        )

    def constraints(self, shape):
        return mxu_aligned(["q", "k"], 128)

    def legalize(self, config, shape, vmem_budget=None):
        bq, bk = config
        Sq, Skv, D = shape
        # blocks above 1024 blow the f32 score block past rule R3 even
        # when the mapper's coarser model admits them: cap, repair into
        # divisor tiles, then hold the compiler's VMEM within the budget
        blocks = (
            repair_tile(bq, Sq, 512, cap=1024),
            repair_tile(bk, Skv, 512, cap=1024),
        )
        return fit_tiles(
            blocks,
            (Sq, Skv),
            lambda b: flash_vmem_bytes(b, D),
            int(vmem_budget or self.vmem_budget),
        )

    def example_inputs(self, shape, seed: int = 0):
        Sq, Skv, D = shape
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
        return (
            jax.random.normal(kq, (1, Sq, 1, D), jnp.float32),
            jax.random.normal(kk, (1, Skv, 1, D), jnp.float32),
            jax.random.normal(kv, (1, Skv, 1, D), jnp.float32),
        )

    def run(self, inputs, config, interpret: bool = True):
        q, k, v = inputs
        return flash_attention(
            q, k, v, causal=False, blocks=tuple(config), interpret=interpret
        )


FLASH_ATTENTION_SPACE = codesign.register_space(FlashAttentionSpace())


@functools.lru_cache(maxsize=256)
def plan_blocks(Sq: int, Skv: int, D: int) -> Tuple[int, int]:
    """Plan the per-head score GEMM (Sq x Skv x D) via ``codesign.plan``;
    return (bq, bk)."""
    return codesign.plan(FLASH_ATTENTION_SPACE, (Sq, Skv, D)).config


# ------------------------------------------------------------------ #
# custom-vjp core over the padded (B, H, S, D) layout, or K/V stacked over
# layers (L, B, H, S, D).  ``meta`` is a float32 (3,) array [kv_len,
# q_offset, layer] so traced decode positions stay differentiable-dtype
# (zero cotangent) without being static; the layer is read only when K/V
# are stacked.
# ------------------------------------------------------------------ #
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _fa(q, k, v, meta, causal, scale, blocks, interpret):
    bq, bk = blocks
    return flash_attention_pallas(
        q, k, v,
        causal=causal, scale=scale,
        q_offset=meta[1].astype(jnp.int32), kv_len=meta[0].astype(jnp.int32),
        layer=meta[2].astype(jnp.int32) if k.ndim == 5 else None,
        bq=bq, bk=bk, interpret=interpret,
    )


def _fa_fwd(q, k, v, meta, causal, scale, blocks, interpret):
    return _fa(q, k, v, meta, causal, scale, blocks, interpret), (q, k, v, meta)


def _fa_bwd(causal, scale, blocks, interpret, res, g):
    q, k, v, meta = res
    kvl = meta[0].astype(jnp.int32)
    qo = meta[1].astype(jnp.int32)
    layer = meta[2].astype(jnp.int32)

    def f(q, k, v):
        if k.ndim == 5:  # stacked: only the layer read gets a cotangent
            k, v = (jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
                    for a in (k, v))
        return attention_ref(
            q, k, v, causal=causal, scale=scale, q_offset=qo, kv_len=kvl
        )

    _, vjp = jax.vjp(f, q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, jnp.zeros_like(meta)


_fa.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(
    q: jnp.ndarray,  # (b, Sq, hq, d) -- model layout (see models/layers.py)
    k: jnp.ndarray,  # (b, Skv, hkv, d), or (b, hkv, Skv, d) if kv_heads_major
    v: jnp.ndarray,  # (b, Skv, hkv, dv), or (b, hkv, Skv, dv) if kv_heads_major
    *,
    causal: bool,
    q_offset=0,
    kv_len: Optional[jnp.ndarray] = None,
    sm_scale: Optional[float] = None,
    blocks: Optional[Tuple[int, int]] = None,
    interpret: Optional[bool] = None,
    kv_heads_major: bool = False,
    layer=None,
) -> jnp.ndarray:
    """Drop-in for models.layers.mha's math on TPU. Handles GQA natively
    and pads Sq/Skv up to the block grid (padded KV is masked via kv_len).
    K and V already in the kernel's (b, h, S, d) layout, as the decode cache
    holds them, pass with ``kv_heads_major`` and are read without a swap.

    With ``layer`` (int or traced scalar), K and V are that layout stacked
    over layers, (L, b, hkv, Skv, d), as the decode cache's scanned layers
    hold them: the kernel reads the layer's blocks out of the stack, and
    nothing is copied, when Skv fills whole KV blocks. Otherwise the layer
    is sliced out and padded, as a single layer is."""
    interpret = _cfg.interpret_default() if interpret is None else interpret
    b, Sq, hq, d = q.shape
    Skv = v.shape[-2] if kv_heads_major else v.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    bq, bk = blocks or plan_blocks(round_up(Sq, 128), round_up(Skv, 128), d)
    bq, bk = min(bq, round_up(Sq, 8)), min(bk, round_up(Skv, 8))
    Sqp, Skvp = round_up(Sq, bq), round_up(Skv, bk)
    if layer is not None:
        assert kv_heads_major, "a stacked cache is held heads-major"
        if Skvp != Skv:  # padding the stack would copy every layer
            k, v = (jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
                    for a in (k, v))
            layer = None
    qt = jnp.swapaxes(q, 1, 2)  # (b, hq, Sq, d)
    if kv_heads_major:
        kt, vt = k, v
    else:
        kt, vt = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
    if Sqp != Sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    if Skvp != Skv:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, Skvp - Skv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, Skvp - Skv), (0, 0)))
    meta = jnp.stack(
        [
            jnp.asarray(Skv if kv_len is None else kv_len, jnp.float32),
            jnp.asarray(q_offset, jnp.float32),
            jnp.asarray(0 if layer is None else layer, jnp.float32),
        ]
    )
    out = _fa(qt, kt, vt, meta, causal, scale, (bq, bk), interpret)
    return jnp.swapaxes(out[:, :, :Sq], 1, 2)  # (b, Sq, hq, dv)
