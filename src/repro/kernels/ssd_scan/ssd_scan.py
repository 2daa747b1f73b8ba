"""Mamba-2 chunked SSD intra-chunk Pallas kernel.

The SSD (state-space dual) algorithm splits the sequence into chunks; the
quadratic *intra-chunk* work (the decay-masked C.B^T score matmul and the
chunk-state outer product) dominates compute and is MXU-shaped -- that is
the kernel. The O(nc) inter-chunk recurrence and the rank-1 elementwise
decay algebra are cheap and stay in jnp (ops.py), mirroring how the paper
keeps the coarse-grained schedule outside the accelerator cost model.

Per grid step (b, h, c) the kernel computes, entirely in VMEM, from the
chunk's cumulative log decay ``cum`` (a cheap cumsum done outside):
  L     = exp(segsum(dA_chunk))               (cl, cl) lower-triangular
  scores = (C @ B^T) * L                      (cl, cl)
  y_diag = scores @ x                         (cl, hp)
  S_c    = B^T @ (x * exp(cum_end - cum))     (n, hp)   chunk-final state

Union mapping view: chunk length `cl` is the C1 temporal tile of the
sequence dim; rule R3 (cl*cl f32 scores + operands <= VMEM) bounds it,
which is why ops.plan_chunk consults the same legality machinery.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(
    x_ref,  # (1, 1, 1, cl, hp) f32  -- dt-scaled inputs
    cr_ref,  # (1, 1, 1, 1, cl) f32  -- in-chunk cumulative log decay, row
    cc_ref,  # (1, 1, 1, cl, 1) f32  -- the same, as a column
    de_ref,  # (1, 1, 1, cl, 1) f32  -- exp(cum_end - cum), as a column
    b_ref,  # (1, 1, 1, cl, n)  f32
    c_ref,  # (1, 1, 1, cl, n)  f32
    y_ref,  # (1, 1, 1, cl, hp) f32  out: intra-chunk output
    s_ref,  # (1, 1, 1, n, hp)  f32  out: chunk-final state contribution
):
    x = x_ref[0, 0, 0]  # (cl, hp)
    cum_row = cr_ref[0, 0, 0]  # (1, cl)
    cum_col = cc_ref[0, 0, 0]  # (cl, 1)
    B = b_ref[0, 0, 0]  # (cl, n)
    C = c_ref[0, 0, 0]  # (cl, n)
    cl = x.shape[0]

    # segsum: L[i, j] = exp(sum_{k=j+1..i} dA_k) for j <= i else 0
    diff = cum_col - cum_row
    ii = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 1)
    L = jnp.where(ii >= jj, jnp.exp(diff), 0.0)

    scores = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (cl, cl) = C @ B^T
    y_ref[0, 0, 0] = jax.lax.dot(
        scores * L, x, preferred_element_type=jnp.float32
    )

    s_ref[0, 0, 0] = jax.lax.dot_general(
        B, x * de_ref[0, 0, 0],
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (n, hp) = B^T @ (x * dte)


def ssd_intra_chunk_pallas(
    x: jnp.ndarray,  # (b, nh, nc, cl, hp) f32, dt-scaled
    cum: jnp.ndarray,  # (b, nh, nc, cl) f32 in-chunk cumsum of dA
    B: jnp.ndarray,  # (b, nh, nc, cl, n) f32
    C: jnp.ndarray,  # (b, nh, nc, cl, n) f32
    *,
    interpret: bool = False,
):
    """Returns (y_diag (b, nh, nc, cl, hp), S_c (b, nh, nc, n, hp)).

    The cumulative decay enters precomputed, as a row and as a column
    block, with the decay to the chunk's end: the TPU kernel language has
    no cumsum, a (cl,) vector block would break the (8, 128) block
    tiling, and slicing the chunk's last element needs an unsupported
    layout."""
    b, nh, nc, cl, hp = x.shape
    n = B.shape[-1]
    grid = (b, nh, nc)
    idx5 = lambda i, h, c: (i, h, c, 0, 0)
    return pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, cl, hp), idx5),
            pl.BlockSpec((1, 1, 1, 1, cl), idx5),
            pl.BlockSpec((1, 1, 1, cl, 1), idx5),
            pl.BlockSpec((1, 1, 1, cl, 1), idx5),
            pl.BlockSpec((1, 1, 1, cl, n), idx5),
            pl.BlockSpec((1, 1, 1, cl, n), idx5),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, cl, hp), idx5),
            pl.BlockSpec((1, 1, 1, n, hp), idx5),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, nc, cl, hp), jnp.float32),
            jax.ShapeDtypeStruct((b, nh, nc, n, hp), jnp.float32),
        ],
        interpret=interpret,
        name="union_ssd_intra_chunk",
    )(
        x,
        cum[:, :, :, None, :],
        cum[..., None],
        jnp.exp(cum[..., -1:] - cum)[..., None],
        B,
        C,
    )
