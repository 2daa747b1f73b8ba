"""Public matmul op: padding + unified co-design planning + custom vjp.

Tile planning goes through the shared co-design layer (docs/codesign.md):
:class:`MatmulSpace` registers the GEMM ``Problem``, MXU-aligned
``Constraints``, and the ``legalize`` repair with
``repro.codesign``, and ``plan_tiles`` is a thin wrapper over the single
``codesign.plan`` path (heuristic mapper x Timeloop-like cost model over
the ``tpu_chip()`` hierarchy, C1/VMEM temporal tile read back as the
BlockSpec -- the paper's mapping IS the program, DESIGN.md Sec. 2).
Finished plans are cached in the planner's ResultStore, so warm queries
skip the mapper search entirely.
"""

from __future__ import annotations

import functools
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
from repro.core.mapping import Mapping
from repro.core.problem import Problem
from repro.kernels.matmul.matmul import matmul_pallas


def tiles_from_mapping(mapping: Mapping, problem: Problem) -> Tuple[int, int, int]:
    """Read (bm, bn, bk) from the innermost (VMEM) level temporal tile."""
    leaf = mapping.levels[-1]
    return leaf.tt("m"), leaf.tt("n"), leaf.tt("k")


def matmul_vmem_bytes(tiles) -> int:
    """VMEM the compiler allocates for ``matmul_pallas`` at (bm, bn, bk):
    double-buffered bf16 x, y and out blocks plus the f32 accumulator."""
    bm, bn, bk = tiles
    return pipeline_vmem_bytes([2 * bm * bk, 2 * bk * bn, 2 * bm * bn], [4 * bm * bn])


class MatmulSpace(KernelSpace):
    """Co-design space of the tiled GEMM kernel: shape = (M, N, K),
    BlockConfig = (bm, bn, bk)."""

    name = "matmul"
    decode_dims = ("m", "n", "k")
    search_budget = 400

    def problem(self, shape):
        M, N, K = shape
        return Problem.gemm(M, N, K)

    def constraints(self, shape):
        return mxu_aligned(["m", "n", "k"], 128)

    def legalize(self, config, shape, vmem_budget=None):
        bm, bn, bk = config
        M, N, K = shape
        # safe MXU-aligned defaults if the mapper degenerated (e.g.
        # trivial mapping with tile 1): clamp into [128, dim]; then hold
        # the compiler's VMEM allocation within the budget
        tiles = (
            repair_tile(bm, M, 256),
            repair_tile(bn, N, 256),
            repair_tile(bk, K, 512),
        )
        return fit_tiles(
            tiles, shape, matmul_vmem_bytes, int(vmem_budget or self.vmem_budget)
        )

    def example_inputs(self, shape, seed: int = 0):
        M, N, K = shape
        kx, ky = jax.random.split(jax.random.PRNGKey(seed))
        return (
            jax.random.normal(kx, (M, K), jnp.float32),
            jax.random.normal(ky, (K, N), jnp.float32),
        )

    def run(self, inputs, config, interpret: bool = True):
        x, y = inputs
        return matmul(x, y, tiles=tuple(config), interpret=interpret)


MATMUL_SPACE = codesign.register_space(MatmulSpace())


@functools.lru_cache(maxsize=512)
def plan_tiles(
    M: int, N: int, K: int, *, mapper: str = "heuristic", budget: int = 400
) -> Tuple[int, int, int]:
    """Plan the GEMM (M,N,K) via ``codesign.plan``; return (bm, bn, bk)."""
    return codesign.plan(
        MATMUL_SPACE, (M, N, K), mapper=mapper, budget=budget
    ).config


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _matmul(x, y, tiles, out_dtype, interpret):
    bm, bn, bk = tiles
    return matmul_pallas(
        x, y, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype, interpret=interpret
    )


def _matmul_fwd(x, y, tiles, out_dtype, interpret):
    return _matmul(x, y, tiles, out_dtype, interpret), (x, y)


def _matmul_bwd(tiles, out_dtype, interpret, res, g):
    x, y = res
    g = g.astype(x.dtype)
    # dX = g @ Y^T ; dY = X^T @ g -- re-plan tiles for the transposed shapes
    M, K = x.shape
    _, N = y.shape
    tx = plan_tiles(M, K, N)
    ty = plan_tiles(K, N, M)
    dx = _matmul(g, y.T, tx, x.dtype, interpret)
    dy = _matmul(x.T, g, ty, y.dtype, interpret)
    return dx, dy


_matmul.defvjp(_matmul_fwd, _matmul_bwd)


def matmul(
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    tiles: Optional[Tuple[int, int, int]] = None,
    out_dtype=None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Union-tiled matmul for arbitrary (even non-128-aligned) shapes.

    Leading batch dims of ``x`` are flattened into M. Pads M/N/K up to
    the tile grid and slices the result back.
    """
    interpret = _cfg.interpret_default() if interpret is None else interpret
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    M = 1
    for s in lead:
        M *= s
    K = x.shape[-1]
    K2, N = y.shape
    assert K == K2, f"matmul inner dim mismatch {K} vs {K2}"
    x2 = x.reshape(M, K)
    tiles = tiles or plan_tiles(round_up(M, 128), round_up(N, 128), round_up(K, 128))
    bm, bn, bk = tiles
    Mp, Np, Kp = round_up(M, bm), round_up(N, bn), round_up(K, bk)
    if (Mp, Kp) != (M, K):
        x2 = jnp.pad(x2, ((0, Mp - M), (0, Kp - K)))
    yp = jnp.pad(y, ((0, Kp - K), (0, Np - N))) if (Kp, Np) != (K, N) else y
    out = _matmul(x2, yp, (bm, bn, bk), out_dtype, interpret)
    return out[:M, :N].reshape(*lead, N)
