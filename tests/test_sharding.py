"""Sharding rules: divisibility guards, family-specific layouts, and the
Union-mapping <-> PartitionSpec correspondence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import SHAPES, get_config
from repro.launch.specs import input_specs
from repro.sharding.hints import clear_hints, hints, shard_hint
from repro.sharding.specs import (
    ShardingRules,
    _maybe,
    _maybe_dp,
    batch_specs,
    cache_specs,
    dp_axes,
    param_specs,
)

SIZES = {"pod": 2, "data": 16, "model": 16}


class _FakeMesh:
    """Shape-only stand-in: spec builders only read axis_names/devices.shape."""

    def __init__(self, sizes):
        self.axis_names = tuple(sizes)
        import numpy as _np

        self.devices = _np.empty(tuple(sizes.values()), object)


MESH = _FakeMesh(SIZES)


def test_maybe_divisibility_guard():
    assert _maybe("model", 64, SIZES) == "model"
    assert _maybe("model", 56, SIZES) is None  # llava's 56 heads on 16-way
    assert _maybe("model", 4, SIZES) is None   # starcoder2's 4 kv heads
    assert _maybe(None, 64, SIZES) is None
    assert _maybe_dp(("pod", "data"), 64, SIZES) == ("pod", "data")
    assert _maybe_dp(("pod", "data"), 1, SIZES) is None  # batch-1 long ctx


def test_param_specs_dense():
    cfg = get_config("qwen3-0.6b")
    ps = jax.eval_shape(
        lambda: {"units": {"b0": {"attn": {
            "wq": {"w": jnp.zeros((8, cfg.d_model, cfg.n_heads * cfg.head_dim), jnp.bfloat16)},
            "wo": {"w": jnp.zeros((8, cfg.n_heads * cfg.head_dim, cfg.d_model), jnp.bfloat16)},
        }}},
            "embed": jnp.zeros((cfg.vocab, cfg.d_model), jnp.bfloat16)}
    )
    specs = param_specs(ps, cfg, MESH, ShardingRules())
    wq = specs["units"]["b0"]["attn"]["wq"]["w"]
    assert wq[0] is None            # stacked-unit axis never sharded
    assert wq[-1] == "model"        # column-parallel
    assert wq[1] == "data"          # FSDP on the other big dim
    wo = specs["units"]["b0"]["attn"]["wo"]["w"]
    assert wo[1] == "model"         # row-parallel
    emb = specs["embed"]
    assert emb[0] == "model"        # vocab-sharded embedding


def test_param_specs_inference_disables_fsdp():
    cfg = get_config("qwen3-0.6b")
    ps = jax.eval_shape(lambda: {"attn": {"wq": {"w": jnp.zeros((1024, 2048), jnp.bfloat16)}}})
    sp = param_specs(ps, cfg, MESH, ShardingRules(), for_training=False)
    assert sp["attn"]["wq"]["w"][0] is None


def test_cache_specs_head_vs_sequence_fallback():
    rules = ShardingRules()
    # qwen3: kv=8 NOT divisible by 16 -> sequence-sharded over model
    cfg = get_config("qwen3-0.6b")
    cs = jax.eval_shape(lambda: {"units": {"b0": {
        "k": jnp.zeros((8, 128, cfg.n_kv_heads, 32768, cfg.head_dim), jnp.bfloat16)}}})
    sp = cache_specs(cs, cfg, MESH, rules)["units"]["b0"]["k"]
    assert sp[2] is None and sp[3] == "model"
    # an MHA-shaped kv=32 (divisible by 16) -> head-sharded
    cfg2 = dataclasses.replace(get_config("codeqwen1.5-7b"), n_kv_heads=32)
    cs2 = jax.eval_shape(lambda: {"units": {"b0": {
        "k": jnp.zeros((8, 128, cfg2.n_kv_heads, 32768, cfg2.head_dim), jnp.bfloat16)}}})
    sp2 = cache_specs(cs2, cfg2, MESH, rules)["units"]["b0"]["k"]
    assert sp2[2] == "model"
    assert sp2[1] == ("pod", "data")  # batch 128 shardable
    # codeqwen as published: kv=4 NOT divisible by 16 -> sequence-sharded
    cfg3 = get_config("codeqwen1.5-7b")
    assert cfg3.n_kv_heads == 4
    cs3 = jax.eval_shape(lambda: {"units": {"b0": {
        "k": jnp.zeros((8, 128, cfg3.n_kv_heads, 32768, cfg3.head_dim), jnp.bfloat16)}}})
    sp3 = cache_specs(cs3, cfg3, MESH, rules)["units"]["b0"]["k"]
    assert sp3[2] is None and sp3[3] == "model"


def test_cache_specs_batch1_seq_over_dp():
    """long_500k: batch axis unshardable -> cache sequence takes dp axes."""
    cfg = get_config("zamba2-2.7b")
    cs = jax.eval_shape(lambda: {"units": {"b0": {
        "k": jnp.zeros((7, 1, 32, 524288, 80), jnp.bfloat16)}}})
    sp = cache_specs(cs, cfg, MESH, ShardingRules())["units"]["b0"]["k"]
    assert sp[1] is None
    assert sp[3] == ("pod", "data")


def test_input_specs_struct_only():
    """input_specs produces ShapeDtypeStructs (no allocation) for all kinds."""
    for arch, shape in [("qwen3-0.6b", "train_4k"), ("qwen3-0.6b", "prefill_32k"),
                        ("qwen3-0.6b", "decode_32k"), ("hubert-xlarge", "train_4k"),
                        ("llava-next-34b", "prefill_32k")]:
        spec = input_specs(arch, shape)
        for leaf in jax.tree.leaves(spec):
            assert isinstance(leaf, jax.ShapeDtypeStruct)


def test_shard_hint_noop_outside_context():
    clear_hints()
    x = jnp.zeros((4, 4))
    assert shard_hint(x, "dp", "tp") is x


def test_shard_hint_respects_divisibility():
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with hints(dp=("data",), tp="model", sizes={"data": 1, "model": 1}):
        with mesh:
            x = jnp.zeros((4, 6))
            y = shard_hint(x, "dp", "tp")
            assert y.shape == x.shape  # applies cleanly on a 1x1 mesh


def test_dp_axes_rules():
    r = ShardingRules()
    assert dp_axes(MESH, r) == ("pod", "data")
    r2 = ShardingRules(dp_over_pod=False)
    assert dp_axes(MESH, r2) == ("data",)
