"""The flash kernel's record of the bytes each call moves: counted from the
grid and BlockSpecs it hands to ``pallas_call``, at trace time, one copy
per grid step whose block index changes."""

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.kernels.flash_attention import ops
from repro.kernels.flash_attention.flash_attention import block_copies

BF16 = 2


def _decode_record(batch, hq, hkv, max_len=1024, d=128, blocks=None):
    """Build (trace, not run) one decode call of the serving step's shape:
    a single-token query against the whole stacked-layout cache."""
    ops.reset_kernel_record()
    q = jax.ShapeDtypeStruct((batch, 1, hq, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((batch, hkv, max_len, d), jnp.bfloat16)
    jax.eval_shape(
        lambda q, k, v: ops.flash_attention(
            q, k, v, causal=False, q_offset=jnp.int32(7), kv_len=jnp.int32(8),
            blocks=blocks, interpret=True, kv_heads_major=True),
        q, kv, kv)
    (rec,) = ops.kernel_record().items()
    return rec


def test_block_copies_counts_each_change_of_block_index():
    grid = (2, 4, 1, 3)  # (b, h, i, j), j innermost
    per_kv_block = pl.BlockSpec((1, 1, 8, 8), lambda b, h, i, j: (b, h // 2, j, 0))
    assert block_copies(grid, per_kv_block.index_map) == 2 * 4 * 3
    per_head = pl.BlockSpec((1, 1, 8, 8), lambda b, h, i, j: (b, h // 2, 0, 0))
    assert block_copies(grid, per_head.index_map) == 2 * 2
    assert block_copies(grid, lambda b, h, i, j: (0, 0)) == 1
    # chunked evaluation counts the same as one pass
    assert block_copies(grid, per_head.index_map, chunk=5) == 2 * 2


@pytest.mark.parametrize("group", [8, 1])
def test_two_kv_blocks_a_head_fetch_k_and_v_once_per_query_head(group):
    """With the cache split into two KV blocks, the block index changes at
    every grid step, so every query head fetches its group's K and V
    again: group times the bytes the K and V inputs hold."""
    batch, hkv = 4, 2
    call, moved = _decode_record(batch, group * hkv, hkv, blocks=(128, 512))
    assert (call.q_seq, call.bk) == (8, 512)
    held = batch * hkv * 1024 * 128 * BF16
    assert moved["k"] == moved["v"] == group * held
    assert moved["q"] == moved["o"] == batch * group * hkv * 8 * 128 * BF16


def test_one_kv_block_a_head_is_fetched_once_for_the_group():
    """At the planned decode blocks (one KV block spans the cache) the
    group's consecutive query heads keep the block index, so K and V are
    fetched once per KV head: what the inputs hold, at group 8 too."""
    batch, hkv = 4, 4
    call, moved = _decode_record(batch, 8 * hkv, hkv)
    assert call.bk == 1024
    assert moved["k"] == moved["v"] == batch * hkv * 1024 * 128 * BF16


def test_a_call_records_once_per_shape_and_not_per_step():
    ops.reset_kernel_record()
    _decode_record(2, 4, 2)
    assert len(ops.kernel_record()) == 1
    ops.reset_kernel_record()
    assert ops.kernel_record() == {}
