"""DeepSeek-V2's block on the serving path against its plain reference, and
the expert layer at one chip's share of an expert-parallel deployment.

- Prefill then decode through the latent cache, the step ``WaveServer``
  serves, gives the logits of the reference's full forward
  (``bench/reference/mla_moe.py``) at the ``reduced()`` widths with a
  share of the routed experts held.
- The shares of one expert layer, with the shared experts counted once,
  add up to the uncut layer.
- YaRN rope equals a line-by-line transcription of the published formula,
  and the softmax scale carries its mscale^2.
- ``norm_topk_prob=False`` leaves the top-k gates as the router gave them.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import YarnScaling, get_config
from repro.models import decode_step, init_cache, layers
from repro.models import moe as moe_mod

DS = get_config("deepseek-v2-lite-16b")


def published_keys(cfg):
    """The reference's configuration: the published ``config.json`` keys
    of ``cfg``, with its expert share."""
    ys = cfg.rope_scaling
    return {
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.nope_head_dim, "qk_rope_head_dim": cfg.rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": cfg.norm_topk_prob,
        "n_routed_experts": cfg.n_routed_experts, "experts_held": cfg.n_experts_held,
        "first_expert": cfg.first_expert, "routed_scaling_factor": 1,
        "tie_word_embeddings": cfg.tie_embeddings,
        "rope_scaling": {"type": "yarn", "factor": ys.factor,
                         "original_max_position_embeddings": ys.original_max_position,
                         "beta_fast": ys.beta_fast, "beta_slow": ys.beta_slow,
                         "mscale": ys.mscale, "mscale_all_dim": ys.mscale_all_dim},
    }


def test_prefill_then_decode_matches_the_reference_forward():
    """Token by token through the donated-cache step (the path of
    ``WaveServer``'s prefill and decode), every position's logits equal the
    reference's full causal forward over the same sequence. The weights
    are float32 and products run at HIGHEST on both sides, so what is left
    is the latent cache's bfloat16 rounding: the widest logit difference
    reads 0.018 here (logits up to 4); 0.05 leaves nearly three times that,
    and the softmax scale without its mscale^2 reads 1.9."""
    from bench.reference import mla_moe
    from bench.weights import make_params

    cfg = dataclasses.replace(DS.reduced(), experts_held=4, first_expert=2)
    assert cfg.n_routed_experts == 8 and cfg.first_k_dense == 1
    params = jax.tree.map(lambda a: a.astype(jnp.float32), make_params(cfg, 11))
    assert params["units"]["b0"]["moe"]["w_gate"].shape[1] == 4
    S = 24
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, S), 1, cfg.vocab)
    step = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i), donate_argnums=(1,))
    cache = init_cache(cfg, 1, 32)
    got = []
    with jax.default_matmul_precision("highest"):
        for t in range(S):
            logits, cache = step(params, cache, toks[:, t:t + 1], jnp.int32(t))
            got.append(np.asarray(logits[0]))
        c = published_keys(cfg)
        h = mla_moe.hidden(c, params, toks[0])
        want = np.asarray(h @ params["lm_head"]["w"])
    diff = np.abs(np.stack(got) - want).max()
    assert diff < 0.05, diff


def test_the_expert_shares_add_up_to_the_whole_layer():
    """Eight chips' shares of one 64-expert top-6 layer: the sum of their
    outputs, with the shared experts (computed on every chip) counted
    once, is the uncut layer's output."""
    cfg = dataclasses.replace(DS.reduced(), n_routed_experts=64, top_k=6, d_model=64,
                              d_expert=32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 3, cfg.d_model), jnp.float32)
    whole = jax.tree.map(lambda a: a.astype(jnp.float32),
                         moe_mod.init_moe(jax.random.PRNGKey(0), cfg))
    sh = whole["shared"]
    shared = layers.dense(sh["down"], jax.nn.silu(layers.dense(sh["gate"], x))
                          * layers.dense(sh["up"], x))
    with jax.default_matmul_precision("highest"):
        want, _ = moe_mod.moe_apply(whole, cfg, x, dropless=True)
        total = -7 * shared
        for j in range(8):
            part = dataclasses.replace(cfg, experts_held=8, first_expert=8 * j)
            p = dict(whole, **{w: whole[w][8 * j:8 * (j + 1)]
                               for w in ("w_gate", "w_up", "w_down")})
            y, _ = moe_mod.moe_apply(p, part, x, dropless=True)
            total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=1e-5, atol=1e-5)


def _published_yarn_cos_sin(positions, dim, base, factor, original_max_position_embeddings,
                            beta_fast, beta_slow, mscale, mscale_all_dim):
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache``,
    line by line in numpy (the first half of its cat((freqs, freqs)))."""

    def yarn_find_correction_dim(num_rotations, dim, base=10000, max_position_embeddings=2048):
        return (dim * math.log(max_position_embeddings / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    def yarn_find_correction_range(low_rot, high_rot, dim, base=10000,
                                   max_position_embeddings=2048):
        low = math.floor(yarn_find_correction_dim(low_rot, dim, base, max_position_embeddings))
        high = math.ceil(yarn_find_correction_dim(high_rot, dim, base, max_position_embeddings))
        return max(low, 0), min(high, dim - 1)

    def yarn_get_mscale(scale=1, mscale=1):
        if scale <= 1:
            return 1.0
        return 0.1 * mscale * math.log(scale) + 1.0

    def yarn_linear_ramp_mask(min, max, dim):  # noqa: A002 -- the published names
        if min == max:
            max += 0.001
        linear_func = (np.arange(dim, dtype=np.float32) - min) / (max - min)
        return np.clip(linear_func, 0, 1)

    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    low, high = yarn_find_correction_range(beta_fast, beta_slow, dim, base,
                                           original_max_position_embeddings)
    inv_freq_mask = 1.0 - yarn_linear_ramp_mask(low, high, dim // 2)
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    freqs = np.outer(np.asarray(positions, np.float32), inv_freq)
    _mscale = float(yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor, mscale_all_dim))
    return inv_freq, np.cos(freqs) * _mscale, np.sin(freqs) * _mscale


@pytest.mark.parametrize("mscale_all_dim", [0.707, 0.0], ids=["published", "cos-sin-scaled"])
def test_yarn_rope_is_the_published_formula(mscale_all_dim):
    """Frequencies to float32 rounding; cos and sin within the float32
    rounding of an angle of up to 4096 radians (5e-4)."""
    ys = dataclasses.replace(DS.rope_scaling, mscale_all_dim=mscale_all_dim)
    pos = np.array([0, 1, 7, 100, 1000, 4095])
    inv, want_cos, want_sin = _published_yarn_cos_sin(
        pos, 64, 1e4, 40, 4096, 32, 1, 0.707, mscale_all_dim)
    np.testing.assert_allclose(np.asarray(layers.yarn_inv_freq(64, 1e4, ys)), inv, rtol=1e-6)
    cos, sin = layers.rope_cos_sin(jnp.asarray(pos), 64, 1e4, ys)
    np.testing.assert_allclose(np.asarray(cos), want_cos, atol=1e-3)
    np.testing.assert_allclose(np.asarray(sin), want_sin, atol=1e-3)
    plain, _ = layers.rope_cos_sin(jnp.asarray(pos), 64, 1e4)
    assert not np.allclose(np.asarray(plain), want_cos, atol=1e-2)  # YaRN differs


def test_the_softmax_scale_carries_mscale_squared():
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.26080, abs=1e-5)
    assert layers.mla_softmax_scale(DS) == pytest.approx(192 ** -0.5 * mscale ** 2)
    plain = dataclasses.replace(DS, rope_scaling=None)
    assert layers.mla_softmax_scale(plain) == pytest.approx(192 ** -0.5)
    assert DS.rope_scaling == YarnScaling(40.0, 4096, 32.0, 1.0, 0.707, 0.707)


def test_norm_topk_prob_false_leaves_the_gates_unnormalized():
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0), (5, 64)), axis=-1)
    raw, idx = moe_mod.top_k_gates(DS, probs)
    np.testing.assert_array_equal(np.asarray(raw),
                                  np.take_along_axis(np.asarray(probs), np.asarray(idx), 1))
    assert float(raw.sum(-1).max()) < 0.5  # six of 64 probabilities
    normed, idx2 = moe_mod.top_k_gates(dataclasses.replace(DS, norm_topk_prob=True), probs)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx2))
    np.testing.assert_allclose(np.asarray(normed.sum(-1)), 1.0, rtol=1e-6)
    assert get_config("qwen2-moe-a2.7b").norm_topk_prob  # the default keeps renormalizing
