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


@pytest.mark.parametrize("name,slots,n_layers", [
    ("qwen3-0.6b", 32, None),  # serve_chat: 28 layers
    ("codeqwen1.5-7b", 64, 8),  # serve_code: one 8-layer pipeline stage
], ids=["qwen3-0.6b", "codeqwen1.5-7b"])
def test_serve_step_updates_the_full_width_cache_in_place(one_chip, name, slots, n_layers):
    """A serving cell's donated step at its widths (max_len 1024, Pallas
    on): its output aliases the whole cache, nothing but an in-place
    dynamic-update-slice writes a whole stacked cache leaf, and the flash
    kernel reads K and V out of the stack itself: no instruction produces
    one layer's K or V, and the step needs no temporary of that size."""
    import dataclasses
    import re

    from repro import kernels
    from repro.launch.steps import make_serve_step
    from repro.models import init_cache, init_params

    cfg = get_config(name)
    if n_layers:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    B, max_len = slots, 1024

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, B, max_len)))
    tok = _spec((B, 1), jnp.int32, one_chip)
    pos = _spec((), jnp.int32, one_chip)
    prior = kernels.pallas_enabled(), kernels.interpret_default()
    kernels.enable_pallas(True, interpret=False)
    try:
        step = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
        compiled = step.lower(params, cache, tok, pos).compile()
    finally:
        kernels.enable_pallas(prior[0], interpret=prior[1])
    stats = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert stats.alias_size_in_bytes == cache_bytes
    assert stats.temp_size_in_bytes < 8e6  # one layer's K or V is 67 MB

    def hlo_shape(dims):
        return "bf16[" + ",".join(map(str, dims)) + "]"

    leaf = jax.tree.leaves(cache["units"])[0]
    stack, layer = hlo_shape(leaf.shape), hlo_shape(leaf.shape[1:])
    text = compiled.as_text()
    writers = re.findall(
        r"%\S+ = " + re.escape(stack) + r"\{[^}]*\} ([\w-]+)\(", text
    )
    assert "dynamic-update-slice" in writers
    # parameters and loop-tuple elements pass the leaf through; nothing
    # else (a copy, a fusion restacking it) may produce it
    assert set(writers) <= {"parameter", "get-tuple-element", "dynamic-update-slice"}
    assert not re.search(r"= " + re.escape(layer) + r"\{", text)
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    args = re.search(r"custom-call\(([^)]*)\)", call).group(1).split(", ")
    shapes = dict(re.findall(r"(%\S+) = (bf16\[[\d,]*\])", text))
    # [kv_len, q_offset, layer], q, then K and V: the stacks themselves
    assert [shapes.get(a) for a in args[2:]] == [stack, stack]


def test_serve_step_compiles_at_deepseek_v2_lite_widths(one_chip):
    """``deepseek-v2-lite.serve_mla_chat``'s donated step at published
    widths, 8 of 64 experts held, 16 slots, max_len 1024, Pallas on: the
    flash kernel takes MLA's q/k head dim of 192 and value head dim of 128
    (the prefix layer's call and the scanned layers' call), the output
    aliases the whole latent cache, and weights, cache and temporaries fit
    the chip."""
    import dataclasses

    from repro import kernels
    from repro.launch.steps import make_serve_step
    from repro.models import init_cache, init_params

    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b"), experts_held=8)
    B, max_len = 16, 1024

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, B, max_len)))
    tok = _spec((B, 1), jnp.int32, one_chip)
    pos = _spec((), jnp.int32, one_chip)
    prior = kernels.pallas_enabled(), kernels.interpret_default()
    kernels.enable_pallas(True, interpret=False)
    try:
        step = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
        compiled = step.lower(params, cache, tok, pos).compile()
    finally:
        kernels.enable_pallas(prior[0], interpret=prior[1])
    stats = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert cache_bytes == B * max_len * 27 * (512 + 64) * 2
    assert stats.alias_size_in_bytes == cache_bytes
    assert stats.argument_size_in_bytes + stats.temp_size_in_bytes < 16 * 2**30
    assert compiled.as_text().count("tpu_custom_call") == 2
