"""Flash attention (causal / bidirectional, GQA-native) Pallas TPU kernel.

Layout: q (B, Hq, Sq, D), k/v (B, Hkv, Skv, D), or k/v stacked over
layers (L, B, Hkv, Skv, D) with a traced ``layer``: the decode cache is
such a stack, and the K/V BlockSpecs pick the layer's blocks straight out
of it, so no layer is copied out before the call. Grid = (B, Hq, Sq/bq,
Skv/bk) with the KV axis innermost; online-softmax running max / sum /
accumulator live in VMEM scratch across the KV sweep.

GQA is native: the K/V BlockSpec index_map folds the q-head onto its KV
group (``h // group``), so KV heads are never materialized ``Hq/Hkv``
times in HBM (the jnp reference path must ``jnp.repeat``; see
models/layers.py).

Causality is handled two ways, in Union mapping terms both at the C2
grid level: fully-masked KV blocks are skipped via ``pl.when`` (no MXU
work), and only diagonal blocks pay the element mask. One scalar-prefetch
operand ``[kv_len, q_offset, layer]`` (int32, in SMEM before the grid
runs) masks the valid cache prefix for decode and feeds the K/V index
maps the layer.

Each call records, as it is built (at trace time, never per step), the
bytes its grid and BlockSpecs move between HBM and VMEM: the pipeline
copies a block in at the first grid step and again at each step whose
block index differs from the step before, and writes an output block back
likewise. ``kernel_record()`` returns them by call shape. The layer is
constant within a call, so a stacked call moves what the same call handed
one layer moves.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime.records import TraceRecord

NEG_INF = -1e30


class FlashCall(NamedTuple):
    """The shape of one ``union_flash_attention`` call (padded lengths)."""

    batch: int
    q_heads: int
    kv_heads: int
    q_seq: int
    kv_seq: int
    head_dim: int
    bq: int
    bk: int
    kv_layers: int  # depth of the K/V operand's layer axis; 0: one layer


_RECORD = TraceRecord()


def kernel_record() -> Dict[FlashCall, Dict[str, int]]:
    """Bytes one call moves, by call shape: ``q``, ``k``, ``v`` copied in
    and ``o`` written back, for every call shape built in this process."""
    return _RECORD.snapshot()


def reset_kernel_record() -> None:
    _RECORD.reset()


def block_copies(grid: Tuple[int, ...], index_map, chunk: int = 1 << 20) -> int:
    """Copies of a BlockSpec's blocks over ``grid``: one at the first grid
    step and one at each step whose block index differs from the step
    before (steps in row-major order, the last axis innermost). The index
    map is evaluated with numpy over the grid, ``chunk`` steps at a time."""
    n = math.prod(grid)
    copies, prev = 0, None
    for start in range(0, n, chunk):
        steps = np.arange(start, min(n, start + chunk))
        idx = np.stack([np.broadcast_to(np.asarray(x), steps.shape)
                        for x in index_map(*np.unravel_index(steps, grid))])
        if prev is None:  # block indices are never negative: the first step copies
            prev = np.full_like(idx[:, :1], -1)
        copies += int(np.any(np.diff(idx, axis=1, prepend=prev) != 0, axis=0).sum())
        prev = idx[:, -1:]
    return copies


def _record(call: FlashCall, grid, specs) -> None:
    """Record the bytes moved per call for ``specs``: {name: (BlockSpec,
    dtype)}. The index maps take the scalar-prefetch operand last; the
    copies are counted with the layer fixed, as it is within a call."""
    meta = np.zeros(3, np.int64)
    moved = {
        name: block_copies(grid, lambda *g, f=spec.index_map: f(*g, meta))
        * math.prod(spec.block_shape) * jnp.dtype(dtype).itemsize
        for name, (spec, dtype) in specs.items()
    }
    _RECORD.put(call, moved)


def _fa_kernel(
    meta_ref,  # (3,) SMEM: [valid KV prefix, q position offset, layer]
    q_ref,  # (1, 1, bq, d)
    k_ref,  # (1, 1, 1, bk, d)
    v_ref,  # (1, 1, 1, bk, dv)
    o_ref,  # (1, 1, bq, dv)
    m_ref,  # (bq, 128) f32 scratch -- running max (broadcast over lanes)
    l_ref,  # (bq, 128) f32 scratch -- running denominator
    acc_ref,  # (bq, dv) f32 scratch
    *,
    scale: float,
    causal: bool,
    bq: int,
    bk: int,
    n_kv: int,
):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = meta_ref[0]
    q_offset = meta_ref[1]
    q_pos0 = q_offset + i * bq  # global position of this q block's first row

    # Skip KV blocks that are entirely masked: block start beyond both the
    # causal frontier and the valid cache prefix.
    causal_live = (q_pos0 + bq - 1 >= j * bk) if causal else True
    live = jnp.logical_and(causal_live, j * bk < kv_len)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0]  # (bq, d)
        k = k_ref[0, 0, 0]  # (bk, d)
        v = v_ref[0, 0, 0]  # (bk, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        qpos = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]  # (bq,)
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(s, axis=1)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)  # rescale factor for old state
        p = jnp.exp(s - m_next[:, None])  # (bq, bk)
        l_next = l_prev * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_next[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_next[:, None], l_ref.shape)

    @pl.when(j == n_kv - 1)
    def _flush():
        l = l_ref[:, 0]
        # fully-masked rows (decode padding) produce l == 0 -> emit zeros
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,  # (B, Hq, Sq, D)
    k: jnp.ndarray,  # (B, Hkv, Skv, D), or (L, B, Hkv, Skv, D) with layer
    v: jnp.ndarray,  # (B, Hkv, Skv, Dv), or (L, B, Hkv, Skv, Dv) with layer
    *,
    causal: bool,
    scale: float,
    q_offset=0,  # int or traced scalar: global position of q[0]
    kv_len: Optional[jnp.ndarray] = None,  # scalar int32; None => Skv
    layer=None,  # int or traced scalar: the layer of stacked k/v to read
    bq: int = 512,
    bk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    kv_layers = 0 if layer is None else k.shape[0]
    if layer is None:  # one layer is a stack of one: a free reshape
        k, v, layer = k[None], v[None], 0
    B, Hq, Sq, D = q.shape
    _, _, Hkv, Skv, Dv = v.shape
    assert Hq % Hkv == 0, f"GQA heads {Hq} % {Hkv} != 0"
    group = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (
        f"seq ({Sq},{Skv}) not divisible by blocks ({bq},{bk}); pad in ops"
    )
    grid = (B, Hq, Sq // bq, Skv // bk)
    meta = jnp.stack(
        [
            jnp.asarray(Skv if kv_len is None else kv_len, jnp.int32),
            jnp.asarray(q_offset, jnp.int32),
            jnp.asarray(layer, jnp.int32),
        ]
    )
    kernel = functools.partial(
        _fa_kernel,
        scale=scale,
        causal=causal,
        bq=bq,
        bk=bk,
        n_kv=grid[3],
    )
    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j, m: (b, h, i, 0))
    k_spec = pl.BlockSpec((1, 1, 1, bk, D),
                          lambda b, h, i, j, m: (m[2], b, h // group, j, 0))
    v_spec = pl.BlockSpec((1, 1, 1, bk, Dv),
                          lambda b, h, i, j, m: (m[2], b, h // group, j, 0))
    o_spec = pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j, m: (b, h, i, 0))
    _record(FlashCall(B, Hq, Hkv, Sq, Skv, D, bq, bk, kv_layers), grid,
            {"q": (q_spec, q.dtype), "k": (k_spec, k.dtype),
             "v": (v_spec, v.dtype), "o": (o_spec, q.dtype)})
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[q_spec, k_spec, v_spec],
            out_specs=o_spec,
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, Dv), q.dtype),
        interpret=interpret,
        name="union_flash_attention",
    )(meta, q, k, v)
