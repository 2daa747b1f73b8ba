"""The flash kernel reads K and V straight out of a cache stacked over
layers: the layer index rides in the scalar-prefetch operand and the K/V
index maps pick that layer's blocks, so no layer is sliced out first. A
stacked call must give what the same call handed the one layer gives, bit
for bit, move the same bytes, and record the depth of the stack it read."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops

N_LAYERS, BATCH, HKV, D, BK = 3, 2, 2, 64, 64


def _inputs(group, skv, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (BATCH, 1, group * HKV, D), jnp.float32).astype(jnp.bfloat16)
    shape = (N_LAYERS, BATCH, HKV, skv, D)
    k = jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.float32).astype(jnp.bfloat16)
    return q, k, v


def _call(q, k, v, kv_len, layer=None):
    return ops.flash_attention(
        q, k, v, causal=False, q_offset=kv_len - 1, kv_len=kv_len, blocks=(8, BK),
        interpret=True, kv_heads_major=True, layer=layer)


@pytest.mark.parametrize("n_blocks", [1, 2], ids=["one-kv-block", "two-kv-blocks"])
@pytest.mark.parametrize("group", [1, 2, 8])
def test_a_stacked_call_reads_what_the_layers_own_call_reads(group, n_blocks):
    skv = n_blocks * BK
    kv_len = jnp.int32(skv - 27)  # a valid prefix that ends inside the last block
    q, k, v = _inputs(group, skv)
    ops.reset_kernel_record()
    stacked = jax.jit(lambda q, k, v, layer: _call(q, k, v, kv_len, layer))
    got = [stacked(q, k, v, jnp.int32(layer)) for layer in range(N_LAYERS)]
    (call, moved), = ops.kernel_record().items()  # recorded once, as traced
    ops.reset_kernel_record()
    single = jax.jit(lambda q, k, v: _call(q, k, v, kv_len))
    want = [single(q, k[layer], v[layer]) for layer in range(N_LAYERS)]
    (single_call, single_moved), = ops.kernel_record().items()
    for layer in range(N_LAYERS):
        np.testing.assert_array_equal(np.asarray(got[layer]), np.asarray(want[layer]))
    assert (call.kv_layers, single_call.kv_layers) == (N_LAYERS, 0)
    assert call._replace(kv_layers=0) == single_call
    assert moved == single_moved


def test_an_unaligned_stack_is_sliced_and_padded_as_one_layer():
    """Where the cache does not fill whole KV blocks, padding the stack
    would copy every layer: the layer is sliced out and padded instead."""
    q, k, v = _inputs(2, BK + 36)  # pads to two blocks of 64
    ops.reset_kernel_record()
    got = _call(q, k, v, jnp.int32(90), layer=jnp.int32(1))
    (call, _), = ops.kernel_record().items()
    want = _call(q, k[1], v[1], jnp.int32(90))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (call.kv_seq, call.kv_layers) == (2 * BK, 0)


def test_the_gradient_of_a_stacked_call_lands_in_its_layer():
    q, k, v = _inputs(2, BK)
    kv_len, layer = jnp.int32(40), 2

    def loss(q, k, v, layer=None):
        return jnp.sum(_call(q, k, v, kv_len, layer).astype(jnp.float32) ** 2)

    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, jnp.int32(layer))
    wq, wk, wv = jax.grad(loss, argnums=(0, 1, 2))(q, k[layer], v[layer])
    np.testing.assert_array_equal(np.asarray(dq), np.asarray(wq))
    for got, want in ((dk, wk), (dv, wv)):
        assert got.shape == k.shape
        np.testing.assert_array_equal(np.asarray(got[layer]), np.asarray(want))
        others = np.delete(np.asarray(got, np.float32), layer, axis=0)
        assert not others.any()
