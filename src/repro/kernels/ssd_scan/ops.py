"""Public chunked-SSD op: Pallas intra-chunk kernel + jnp inter-chunk scan.

Signature matches models.ssm._ssd_chunked so the model can swap it in on
TPU. Chunk sizing goes through the shared co-design layer
(docs/codesign.md): :class:`SsdScanSpace` registers the intra-chunk score
GEMM with ``repro.codesign`` and ``plan_chunk`` is a thin wrapper over
the single ``codesign.plan`` path. The space's ``legalize`` is BINDING --
it holds the VMEM the compiler allocates for the kernel
(:func:`ssd_vmem_bytes`, the rule the matmul and flash-attention spaces
also follow) within the unified budget and picks the largest power-of-two
chunk that fits, regardless of what the mapper proposed, so the policy
"maximize the chunk under R3" stays exact.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import codesign
from repro import kernels as _cfg
from repro.codesign import (
    DEFAULT_VMEM_BUDGET,
    KernelSpace,
    pipeline_vmem_bytes,
    round_up,
)
from repro.core.problem import Problem
from repro.kernels.ssd_scan.ssd_scan import ssd_intra_chunk_pallas

#: proxy extent of the chunk dims in the search Problem = the largest
#: chunk ``legalize`` can pick, so every candidate tile is a divisor
_MAX_CHUNK = 1024


def ssd_vmem_bytes(cl: int, hp: int, n: int) -> int:
    """VMEM the compiler allocates for ``ssd_intra_chunk_pallas`` at chunk
    cl: its f32 in blocks (x, the decay row, two decay columns, B, C) and
    out blocks (y, the chunk state), each padded to whole (8, 128) tiles
    and double-buffered, plus the (cl, cl) score and decay-mask values."""

    def block(rows: int, cols: int) -> int:
        return 4 * round_up(rows, 8) * round_up(cols, 128)

    ins = [block(cl, hp), block(1, cl), block(cl, 1), block(cl, 1)]
    ins += [block(cl, n), block(cl, n)]
    outs = [block(cl, hp), block(n, hp)]
    return pipeline_vmem_bytes(ins + outs, [block(cl, cl), block(cl, cl)])


class SsdScanSpace(KernelSpace):
    """Co-design space of the chunked-SSD kernel: shape = (hp, n),
    BlockConfig = (cl,) -- the chunk length."""

    name = "ssd_scan"
    decode_dims = ("l",)
    search_budget = 200

    def problem(self, shape):
        hp, n = shape
        # intra-chunk score GEMM C . B^T over the state dim: the chunk
        # appears as both free dims of the cl x cl score block
        return Problem.from_einsum(
            "ssd_scores",
            "ln,mn->lm",
            {"l": _MAX_CHUNK, "m": _MAX_CHUNK, "n": n},
            "GEMM",
        )

    def legalize(self, config, shape, vmem_budget=None):
        """BINDING repair: largest power-of-two chunk cl whose compiler
        VMEM allocation (:func:`ssd_vmem_bytes`) fits the budget. The
        mapper's proposal is intentionally ignored (the policy is
        maximize-chunk-under-R3, not argmin of a model)."""
        hp, n = shape
        budget = int(vmem_budget or self.vmem_budget)
        cl = _MAX_CHUNK
        while cl > 64 and ssd_vmem_bytes(cl, hp, n) > budget:
            cl //= 2
        return (cl,)

    def block_tiles(self, shape, config):
        # the chunk is BOTH free dims of the score block (n stays full)
        (cl,) = config
        return {"l": cl, "m": cl}

    def example_inputs(self, shape, seed: int = 0):
        hp, n = shape
        b, l, nh = 1, 256, 1
        kx, ka, kb, kc = jax.random.split(jax.random.PRNGKey(seed), 4)
        return (
            jax.random.normal(kx, (b, l, nh, hp), jnp.float32),
            -jnp.abs(jax.random.normal(ka, (b, l, nh), jnp.float32)) * 0.1,
            jax.random.normal(kb, (b, l, nh, n), jnp.float32),
            jax.random.normal(kc, (b, l, nh, n), jnp.float32),
        )

    def run(self, inputs, config, interpret: bool = True):
        x, dA, B, C = inputs
        (cl,) = config
        chunk = min(int(cl), x.shape[1])
        return ssd_chunked(x, dA, B, C, chunk=chunk, interpret=interpret)


SSD_SCAN_SPACE = codesign.register_space(SsdScanSpace())


@functools.lru_cache(maxsize=64)
def plan_chunk(hp: int, n: int, vmem_budget: int = DEFAULT_VMEM_BUDGET) -> int:
    """Plan the chunk length via ``codesign.plan`` (legalize is binding:
    largest power-of-two cl whose working set fits ``vmem_budget``)."""
    return codesign.plan(
        SSD_SCAN_SPACE, (hp, n), vmem_budget=vmem_budget
    ).config[0]


def ssd_chunked(
    x: jnp.ndarray,  # (b, l, nh, hp) dt-scaled inputs (f32 or bf16)
    dA: jnp.ndarray,  # (b, l, nh)
    B: jnp.ndarray,  # (b, l, nh, n)
    C: jnp.ndarray,  # (b, l, nh, n)
    chunk: Optional[int] = None,
    init_state: Optional[jnp.ndarray] = None,  # (b, nh, hp, n)
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (b,l,nh,hp) f32, final_state (b,nh,hp,n) f32).

    Differentiable: forward runs the Pallas intra-chunk kernel; backward
    recomputes through the jnp oracle (ref.py) under ``jax.vjp``.
    """
    interpret = _cfg.interpret_default() if interpret is None else interpret
    b, l, nh, hp = x.shape
    n = B.shape[-1]
    chunk = chunk or min(plan_chunk(hp, n), l)
    s0 = (
        init_state
        if init_state is not None
        else jnp.zeros((b, nh, hp, n), jnp.float32)
    )
    return _ssd(x, dA, B, C, s0, chunk, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dA, B, C, s0, chunk, interpret):
    return _ssd_impl(x, dA, B, C, s0, chunk, interpret)


def _ssd_fwd(x, dA, B, C, s0, chunk, interpret):
    return _ssd(x, dA, B, C, s0, chunk, interpret), (x, dA, B, C, s0)


def _ssd_bwd(chunk, interpret, res, g):
    from repro.kernels.ssd_scan.ref import ssd_chunked_ref

    x, dA, B, C, s0 = res
    _, vjp = jax.vjp(
        lambda *a: ssd_chunked_ref(*a[:4], chunk=chunk, init_state=a[4]),
        x, dA, B, C, s0,
    )
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def _ssd_impl(
    x, dA, B, C, init_state, chunk, interpret
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, l, nh, hp = x.shape
    n = B.shape[-1]
    chunk = chunk or min(plan_chunk(hp, n), l)
    assert l % chunk == 0, f"seq {l} % chunk {chunk} != 0"
    nc = l // chunk

    # (b, l, nh, *) -> (b, nh, nc, cl, *): head-major so each grid step is
    # one contiguous (cl, *) VMEM block
    def to_blocks(t, feat):
        t = t.astype(jnp.float32)
        if feat:
            return t.reshape(b, nc, chunk, nh, -1).transpose(0, 3, 1, 2, 4)
        return t.reshape(b, nc, chunk, nh).transpose(0, 3, 1, 2)

    xb, dab = to_blocks(x, True), to_blocks(dA, False)
    bb, cb = to_blocks(B, True), to_blocks(C, True)

    cum = jnp.cumsum(dab, axis=-1)  # (b, nh, nc, cl) in-chunk log decay
    y_diag, S_c = ssd_intra_chunk_pallas(xb, cum, bb, cb, interpret=interpret)
    dte = jnp.exp(cum)  # in-chunk growth factors

    # inter-chunk recurrence (cheap, O(nc) elementwise+add)
    chunk_decay = dte[:, :, :, -1]  # (b, nh, nc) = exp(full-chunk decay)
    s0 = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((b, nh, hp, n), jnp.float32)
    )

    def step(S_prev, inp):
        S_new, dec = inp  # (b, nh, n, hp), (b, nh)
        S_next = S_prev * dec[:, :, None, None] + S_new
        return S_next, S_prev  # emit the state ENTERING this chunk

    xs = (S_c.transpose(2, 0, 1, 3, 4), chunk_decay.transpose(2, 0, 1))
    final_nhp, S_in = jax.lax.scan(step, s0.transpose(0, 1, 3, 2), xs)
    S_in = S_in.transpose(1, 2, 0, 3, 4)  # (b, nh, nc, n, hp)

    # inter-chunk contribution: y_off[l] = (C_l . S_in) * exp(cum_l)
    y_off = jnp.einsum("bhcln,bhcnp,bhcl->bhclp", cb, S_in, dte)
    y = (y_diag + y_off).transpose(0, 2, 3, 1, 4).reshape(b, l, nh, hp)
    return y, final_nhp.transpose(0, 1, 3, 2)
