"""Runtime: fault injection/retry/restore, straggler watchdog, gradient
compression (error feedback), elastic mesh planning."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import (
    FaultTolerantRunner,
    RunnerConfig,
    StepTimeoutError,
    compress_int8,
    decompress_int8,
    error_feedback_update,
    plan_mesh,
)
from repro.runtime.compression import compressed_wire_bytes, raw_wire_bytes


# ------------------------------------------------------------------ #
# fault tolerance
# ------------------------------------------------------------------ #
def ok_step(state, batch):
    return state + batch, {"loss": state}


def test_transient_failure_retried():
    fails = {"n": 0}

    def hook(step):
        if step == 2 and fails["n"] < 2:
            fails["n"] += 1
            raise RuntimeError("injected device error")

    r = FaultTolerantRunner(ok_step, RunnerConfig(max_retries_per_step=2),
                            fault_hook=hook)
    s = jnp.float32(0)
    for i in range(4):
        s, _ = r.run_step(s, jnp.float32(1), i)
    assert float(s) == 4.0
    assert fails["n"] == 2
    assert any(st.retried for st in r.stats)


def test_exhausted_retries_restores_from_checkpoint():
    calls = {"n": 0, "restores": 0}

    def hook(step):
        if step == 1 and calls["restores"] == 0:
            raise RuntimeError("persistent failure")

    def restore_fn():
        calls["restores"] += 1
        return jnp.float32(100), 0

    r = FaultTolerantRunner(ok_step, RunnerConfig(max_retries_per_step=1),
                            restore_fn=restore_fn, fault_hook=hook)
    s = jnp.float32(0)
    s, _ = r.run_step(s, jnp.float32(1), 0)
    s, _ = r.run_step(s, jnp.float32(1), 1)  # fails twice -> restore -> ok
    assert calls["restores"] == 1
    assert float(s) == 101.0


def test_gives_up_after_restores_exhausted():
    def hook(step):
        raise RuntimeError("unrecoverable")

    r = FaultTolerantRunner(
        ok_step, RunnerConfig(max_retries_per_step=0, max_restores=1),
        restore_fn=lambda: (jnp.float32(0), 0), fault_hook=hook,
    )
    with pytest.raises(RuntimeError):
        r.run_step(jnp.float32(0), jnp.float32(1), 0)


def test_straggler_watchdog_timeout():
    def slow_step(state, batch):
        time.sleep(1.0)
        return state, {}

    r = FaultTolerantRunner(slow_step, RunnerConfig(
        max_retries_per_step=0, max_restores=0, step_timeout_s=0.1))
    with pytest.raises(StepTimeoutError):
        r.run_step(jnp.float32(0), jnp.float32(1), 0)


def test_straggler_detection_flags_slow_step():
    delays = [0.01] * 10 + [0.2]

    def step(state, batch):
        time.sleep(delays.pop(0))
        return state, {}

    r = FaultTolerantRunner(step, RunnerConfig(straggler_slack=3.0))
    for i in range(11):
        r.run_step(jnp.float32(0), jnp.float32(1), i)
    assert r.stats[-1].straggler
    assert not any(st.straggler for st in r.stats[:-1])


# ------------------------------------------------------------------ #
# gradient compression
# ------------------------------------------------------------------ #
def test_int8_roundtrip_bounded_error():
    x = jax.random.normal(jax.random.PRNGKey(0), (1024,)) * 3.0
    q, s = compress_int8(x)
    err = np.abs(np.asarray(decompress_int8(q, s) - x))
    assert q.dtype == jnp.int8
    assert err.max() <= float(s) / 2 + 1e-6  # half-ulp rounding


def test_error_feedback_contracts():
    """With EF, the cumulative applied update converges to the cumulative
    true gradient (residual stays bounded, does not accumulate)."""
    g = jax.random.normal(jax.random.PRNGKey(1), (256,))
    res = jnp.zeros_like(g)
    applied = jnp.zeros_like(g)
    for i in range(50):
        q, s, res, deq = error_feedback_update(g, res)
        applied += deq
    # average applied per step ~ g
    np.testing.assert_allclose(np.asarray(applied / 50), np.asarray(g),
                               rtol=0, atol=float(jnp.abs(g).max()) / 100)
    assert float(jnp.abs(res).max()) <= float(jnp.abs(g).max()) / 50


def test_wire_bytes_4x():
    tree = {"a": jnp.zeros((1024,), jnp.float32), "b": jnp.zeros((512,), jnp.float32)}
    assert raw_wire_bytes(tree) == 4 * 1536
    assert compressed_wire_bytes(tree) == 1536 + 8  # int8 + 2 scales
    assert compressed_wire_bytes(tree) * 3.5 < raw_wire_bytes(tree)


def test_compressed_allreduce_in_shard_map():
    """End-to-end: compressed psum over a 1-device axis equals plain mean."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.runtime.compression import make_compressed_allreduce

    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    ar = make_compressed_allreduce("pod")
    g = jax.random.normal(jax.random.PRNGKey(0), (64,))
    res = jnp.zeros_like(g)
    fn = shard_map(ar, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
    avg, new_res = fn(g, res)
    np.testing.assert_allclose(np.asarray(avg), np.asarray(g), atol=0.05)


# ------------------------------------------------------------------ #
# elastic mesh planning
# ------------------------------------------------------------------ #
def test_plan_mesh_shrinks_dp_keeps_tp():
    class D:  # fake device
        def __init__(self, i):
            self.id = i

        def __repr__(self):
            return f"D{self.id}"

    devs = [D(i) for i in range(512)]
    m = plan_mesh(512, model=16, prefer_pods=2, devices=devs)
    assert m.devices.shape == (2, 16, 16)
    # lose 100 chips -> DP shrinks, TP intact
    m2 = plan_mesh(412, model=16, prefer_pods=2, devices=devs[:412])
    assert m2.devices.shape[-1] == 16
    assert m2.devices.size <= 412
    # catastrophic loss (< 2 pods' worth) -> collapse to a single pod
    m3 = plan_mesh(17, model=16, prefer_pods=2, devices=devs[:17])
    assert m3.devices.shape[0] == 1
    assert m3.devices.shape[-1] == 16


# ------------------------------------------------------------------ #
# persistent compilation cache placement
# ------------------------------------------------------------------ #
@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache

    prior = (
        jax.config.jax_enable_compilation_cache,
        jax.config.jax_compilation_cache_dir,
    )
    yield
    jax.config.update("jax_enable_compilation_cache", prior[0])
    jax.config.update("jax_compilation_cache_dir", prior[1])
    compilation_cache.reset_cache()


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_config):
    from repro.runtime.compile_cache import use_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert jax.config.jax_enable_compilation_cache


def test_compile_cache_fixed_in_checkout(monkeypatch, restore_cache_config):
    from pathlib import Path

    from repro.runtime.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = use_compile_cache(), use_compile_cache()
    assert first == second == str(CHECKOUT_CACHE_DIR)
    checkout = Path(__file__).resolve().parent.parent
    assert Path(first).parent == checkout
    assert jax.config.jax_compilation_cache_dir == first
