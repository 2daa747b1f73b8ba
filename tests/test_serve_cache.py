"""The serving step's KV cache is donated and updated in place.

``WaveServer`` jits ``make_serve_step`` with the cache donated; the decode
step carries the stacked cache through its layer scan and writes each
layer's new token into it, so the compiled step's output aliases the whole
cache (``cache_alias_share`` 1.0). Donation must not change what is served:
a wave gives the tokens of the same wave served by the plain, undonated step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.launch import steps as steps_mod
from repro.launch.serve import Request, WaveServer
from repro.models import init_cache, init_params

# one architecture per kind of decode state: GQA k/v; MLA latents with a
# dense prefix layer outside the scan; mamba2 states beside attention;
# mLSTM and sLSTM states
ARCHS = ["qwen3-0.6b", "deepseek-v2-lite-16b", "zamba2-2.7b", "xlstm-1.3b"]
SLOTS, MAX_LEN, MAX_NEW = 2, 32, 5
PROMPTS = ([5, 7, 11, 13, 17], [19, 23, 29])


def _server(arch):
    cfg = get_config(arch + "_smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, WaveServer(cfg, params, batch_slots=SLOTS, max_len=MAX_LEN)


def _undonated_wave(cfg, params):
    """The wave ``WaveServer`` serves, by a plain loop over the undonated
    step: left-padded prefill one token a step, then greedy decode."""
    step = jax.jit(steps_mod.make_serve_step(cfg))
    L = max(len(p) for p in PROMPTS)
    toks = np.zeros((SLOTS, L), np.int32)
    for i, p in enumerate(PROMPTS):
        toks[i, L - len(p):] = p
    cache = init_cache(cfg, SLOTS, MAX_LEN)
    for t in range(L):
        nxt, cache = step(params, cache, jnp.asarray(toks[:, t : t + 1]), jnp.int32(t))
    out = [np.asarray(nxt)[:, 0]]
    for pos in range(L, L + MAX_NEW - 1):
        nxt, cache = step(params, cache, nxt, jnp.int32(pos))
        out.append(np.asarray(nxt)[:, 0])
    return np.stack(out, axis=1)


@pytest.mark.parametrize("arch", ARCHS)
def test_the_donated_step_updates_the_cache_in_place(arch):
    cfg, _, server = _server(arch)
    assert server.cache_alias_share is None  # recorded at the first compile
    server.run_wave([Request(i, list(p), 2) for i, p in enumerate(PROMPTS)])
    assert server.cache_alias_share == 1.0


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "zamba2-2.7b"])
def test_a_donated_wave_serves_the_undonated_steps_tokens(arch):
    cfg, params, server = _server(arch)
    wave = [Request(i, list(p), MAX_NEW) for i, p in enumerate(PROMPTS)]
    server.run_wave(wave)
    got = np.array([r.out for r in wave])
    np.testing.assert_array_equal(got, _undonated_wave(cfg, params))


def test_the_cache_is_stored_in_the_flash_kernels_layout():
    cfg = get_config("qwen3-0.6b_smoke")
    cache = jax.eval_shape(lambda: init_cache(cfg, SLOTS, MAX_LEN))
    n_units = cfg.n_layers // len(cfg.block_pattern)
    want = (n_units, SLOTS, cfg.n_kv_heads, MAX_LEN, cfg.head_dim)
    for leaf in ("k", "v"):
        assert cache["units"]["b0"][leaf].shape == want


def test_the_kernel_reads_the_stacked_cache_as_the_sliced_layer(monkeypatch):
    """With Pallas on (interpret mode), the donated step hands the flash
    kernel the whole stacked cache and the layer; its logits over a run of
    steps equal, bit for bit, those of the per-layer path, in which each
    layer is sliced out of the stack and the kernel handed that one layer."""
    import dataclasses
    from functools import partial

    from repro import kernels
    from repro.kernels.flash_attention import ops
    from repro.models import decode_step, layers

    cfg = dataclasses.replace(get_config("qwen3-0.6b_smoke"), n_layers=3)  # GQA group 2
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (12, SLOTS, 1), 0, cfg.vocab)
    n_units = cfg.n_layers // len(cfg.block_pattern)

    def run(donate):
        step = jax.jit(partial(decode_step, cfg), donate_argnums=(1,) if donate else ())
        cache = init_cache(cfg, SLOTS, MAX_LEN)
        out = []
        for pos, tok in enumerate(toks):
            logits, cache = step(params, cache, tok, jnp.int32(pos))
            out.append(np.asarray(logits))
        return np.stack(out)

    prior = kernels.pallas_enabled(), kernels.interpret_default()
    kernels.enable_pallas(True, interpret=True)
    try:
        ops.reset_kernel_record()
        stacked = run(donate=True)
        calls = list(ops.kernel_record())
        mha = layers.mha

        def per_layer(q, k, v, *, layer=None, **kw):
            if layer is not None:
                k, v = k[layer], v[layer]
            return mha(q, k, v, **kw)

        monkeypatch.setattr(layers, "mha", per_layer)
        ops.reset_kernel_record()
        sliced = run(donate=False)
        sliced_calls = list(ops.kernel_record())
    finally:
        kernels.enable_pallas(prior[0], interpret=prior[1])
    np.testing.assert_array_equal(stacked, sliced)
    assert [c.kv_layers for c in calls] == [n_units]
    assert [c.kv_layers for c in sliced_calls] == [0]
