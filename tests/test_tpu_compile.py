"""Ahead-of-time compiles for a described TPU v5e, at real widths.

Nothing here runs on a chip: each test lowers a kernel (or the mapping
engine's fused core) for one chip of a described ``v5e:2x2`` topology and
compiles it with the TPU compiler, which refuses what the chip would
refuse -- a block that overflows the scoped VMEM limit, an unaligned slice.
Every kernel compiles with the planner's own choice of tiles or chunk.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and every
test worker imports this file.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import get_config  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prior)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


@pytest.mark.parametrize("mnk", [(4096, 3072, 1024), (4096, 1024, 3072)])
def test_matmul_planned_tiles_compile(one_chip, mnk):
    from repro.kernels.matmul import matmul, plan_tiles
    from repro.kernels.matmul.ops import matmul_vmem_bytes
    from repro.codesign import DEFAULT_VMEM_BUDGET, SCOPED_VMEM_LIMIT

    M, N, K = mnk
    tiles = plan_tiles(M, N, K)
    assert matmul_vmem_bytes(tiles) <= DEFAULT_VMEM_BUDGET <= SCOPED_VMEM_LIMIT
    x = _spec((M, K), jnp.bfloat16, one_chip)
    y = _spec((K, N), jnp.bfloat16, one_chip)
    _, text = _compile(lambda a, b: matmul(a, b), x, y)
    assert "tpu_custom_call" in text


def test_flash_attention_prefill_compiles_at_qwen3_widths(one_chip):
    from repro.kernels.flash_attention import flash_attention

    cfg = get_config("qwen3-0.6b")
    S, hq, hkv, d = 2048, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _spec((1, S, hq, d), jnp.bfloat16, one_chip)
    kv = _spec((1, S, hkv, d), jnp.bfloat16, one_chip)
    _, text = _compile(lambda a, b, c: flash_attention(a, b, c, causal=True), q, kv, kv)
    assert "tpu_custom_call" in text


def test_flash_attention_decode_compiles_at_qwen3_widths(one_chip):
    from repro.kernels.flash_attention import flash_attention

    cfg = get_config("qwen3-0.6b")
    B, Smax, hq, hkv, d = 4, 2048, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _spec((B, 1, hq, d), jnp.bfloat16, one_chip)
    kv = _spec((B, Smax, hkv, d), jnp.bfloat16, one_chip)
    pos = _spec((), jnp.int32, one_chip)

    def step(a, b, c, p):
        return flash_attention(a, b, c, causal=False, q_offset=p, kv_len=p + 1)

    _, text = _compile(step, q, kv, kv, pos)
    assert "tpu_custom_call" in text


def test_ssd_scan_compiles_at_zamba2_widths(one_chip):
    from repro.codesign import DEFAULT_VMEM_BUDGET
    from repro.kernels.ssd_scan.ops import plan_chunk, ssd_chunked, ssd_vmem_bytes

    cfg = get_config("zamba2-2.7b")
    hp, n = cfg.ssm_head_dim, cfg.ssm_state
    nh = cfg.ssm_expand * cfg.d_model // hp
    L = 2048
    cl = plan_chunk(hp, n)
    assert L % cl == 0 and ssd_vmem_bytes(cl, hp, n) <= DEFAULT_VMEM_BUDGET
    x = _spec((1, L, nh, hp), jnp.bfloat16, one_chip)
    dA = _spec((1, L, nh), jnp.float32, one_chip)
    bc = _spec((1, L, nh, n), jnp.bfloat16, one_chip)
    _, text = _compile(lambda *a: ssd_chunked(*a), x, dA, bc, bc)
    assert "tpu_custom_call" in text


def test_shape_generic_engine_core_compiles_under_x64(one_chip):
    from repro.core.architecture import cloud_accelerator
    from repro.core.cost import EvaluationEngine, TimeloopLikeModel
    from repro.core.cost.analysis import x64
    from repro.core.opstream import build_gemm

    cfg = get_config("qwen3-0.6b")
    problem = build_gemm(2048, cfg.d_ff, cfg.d_model, name="qwen3_mlp_up")
    arch = cloud_accelerator()
    eng = EvaluationEngine(
        TimeloopLikeModel(), problem, arch, metric="edp", backend="jax"
    )
    runner = eng._get_fused_runner()
    B, n, D = 256, arch.n_levels, len(problem.dims)
    with x64():
        mats = [_spec((B, n, D), jnp.int64, one_chip) for _ in range(3)]
        inc = _spec((), jnp.float64, one_chip)
        params = {
            k: _spec(np.shape(v), np.asarray(v).dtype, one_chip)
            for k, v in runner._params.items()
        }
        compiled = runner._core.lower(*mats, inc, params).compile()
    out = compiled.out_info
    latency = out[2]
    assert latency.shape == (B,) and latency.dtype == jnp.float64
    assert "f64" in compiled.as_text()
