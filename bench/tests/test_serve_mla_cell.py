"""The ``deepseek-v2-lite.serve_mla_chat`` cell's shape on the CPU: latent
attention with YaRN rope, a dense layer 0, then routed experts of which
this chip holds a share, through the serving driver with the Pallas kernel
in interpret mode. A sound run is correct; each fault the cell can have
turns ``correct`` false; the float8 control at the cell's widths fails the
cell's own limit. And the readers of the cell's three new metrics: the
program's records of a decode call at the cell's shape, and the FLOP
count, against hand counts."""

import dataclasses
import json
import math
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops_mla_moe, harness
from bench.tests import cells
from bench.tests.test_serve_cell import _broken_step

SEED = 2**31 + 1919
CELL = "deepseek-v2-lite.serve_mla_chat"
CONFIG = json.loads((cells.BENCH / "configs/deepseek-v2-lite.json").read_text())
# widest served-logit gap the tiny cell allows: on seeds SEED to SEED + 5
# sound runs read 0.0 to 0.030 and with the gates renormalized 0.164 to
# 0.380; on seeds SEED to SEED + 3, with the softmax scale left without
# YaRN's mscale^2 0.36 and more, with the shared experts dropped 1.94 and
# more, with a served token moved off the argmax 3.9 and more
TINY_MLA_GAP = 0.08
TINY = dict(CONFIG, num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=128, moe_intermediate_size=32, experts_held=16, first_expert=8,
            vocab_size=512)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``cells.make_root`` plus a tiny cell of the new shape (layer 0 and two
    expert layers, 16 of 64 experts held from expert 8, top-6; eight
    requests checked) and a wide one at the cell's
    widths (layer 0 and one expert layer, a 4,096-token vocabulary) held
    to the cell's own limit."""
    root = cells.make_root(tmp_path_factory.mktemp("serve_mla"))
    bench = root / "bench"
    (bench / "configs/tinymla.json").write_text(json.dumps(TINY))
    wide = dict(CONFIG, num_hidden_layers=2, vocab_size=4096)
    (bench / "configs/widemla.json").write_text(json.dumps(wide))
    mix = json.loads((cells.BENCH / "traffic/serve_mla_chat.json").read_text())
    mix.update(clients=4, slots=4, max_len=64,
               prompt=dict(mix["prompt"], median=8, min=2, max=20),
               answer=dict(mix["answer"], median=6, min=2, max=12),
               check=dict(mix["check"], max_requests=8))
    (bench / "traffic/mla_chat.json").write_text(json.dumps(mix))
    (bench / "traffic/wide_mla_chat.json").write_text(json.dumps(dict(mix, max_len=32)))
    (bench / "limits/tinymla.mla_chat.json").write_text(
        json.dumps({"served_logit_gap": TINY_MLA_GAP}))
    (bench / "limits/widemla.mla_chat.json").write_text(
        (cells.BENCH / f"limits/{CELL}.json").read_text())

    spec = json.loads((root / "BENCHMARK.json").read_text())
    for name in ("tinymla", "widemla"):
        spec["configs"].append(dict(spec["configs"][0], name=name,
                                    file=f"bench/configs/{name}.json", reduced=["experts_held"]))
    spec["workloads"] += [
        {"name": "tinymla.mla_chat", "config": "tinymla", "traffic": "mla_chat", "chips": 1,
         "why": "test"},
        {"name": "widemla.mla_chat", "config": "widemla", "traffic": "wide_mla_chat",
         "chips": 1, "why": "test"},
    ]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.chat" in m.get("workloads", ()):
            m["workloads"].append("tinymla.mla_chat")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_sound_run_is_correct(root):
    res = cells.run(root, "tinymla.mla_chat", SEED, 2.0)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"serve_tokens_per_s", "ttft_p90_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["served_logit_gap"]["limit"] == TINY_MLA_GAP


def _shared_dropped_step():
    """The step as built, served with the shared experts' weights zeroed:
    the reference keeps them."""
    from repro.launch import steps

    sound = steps.make_serve_step

    def make(cfg, **kw):
        step = sound(cfg, **kw)

        def no_shared(path, x):
            names = [getattr(k, "key", None) for k in path]
            return jnp.zeros_like(x) if "shared" in names else x

        def broken(params, cache, tokens, pos):
            return step(jax.tree_util.tree_map_with_path(no_shared, params), cache, tokens, pos)

        return broken

    return make


@pytest.mark.parametrize("kind", ["token", "scale", "gates", "shared"])
def test_each_fault_turns_correct_false(root, monkeypatch, kind):
    from repro.launch import steps
    from repro.models import layers, moe

    if kind == "token":
        monkeypatch.setattr(steps, "make_serve_step", _broken_step("token"))
    elif kind == "shared":
        monkeypatch.setattr(steps, "make_serve_step", _shared_dropped_step())
    elif kind == "scale":  # 1/sqrt(q/k head dim), without YaRN's mscale^2
        monkeypatch.setattr(layers, "mla_softmax_scale",
                            lambda cfg: 1 / math.sqrt(cfg.nope_head_dim + cfg.rope_head_dim))
    else:  # the top-k gates renormalized to sum to 1
        sound = moe.top_k_gates
        monkeypatch.setattr(moe, "top_k_gates", lambda cfg, probs: sound(
            dataclasses.replace(cfg, norm_topk_prob=True), probs))
    res = cells.run(root, "tinymla.mla_chat", SEED, 1.0)
    assert not res["correct"], res["checks"]
    assert res["checks"]["served_logit_gap"]["value"] > TINY_MLA_GAP


@partial(jax.jit, static_argnames="c_items")
def _control_logits(params, tokens, i, c_items):
    from bench.reference import dense, mla_moe

    c = dict(c_items)
    c["rope_scaling"] = dict(c["rope_scaling"])
    head = params["lm_head"]["w"].astype(jnp.float32)
    return dense._mm("fp8")("d,dv->v", mla_moe.hidden(c, params, tokens, quant="fp8")[i], head)


def _control_decode(c, params, prompt, n, max_len):
    """Greedy tokens of the float8 control: the reference with every
    matrix product's operands rounded to float8, decoding ``n`` tokens."""
    from bench.reference import mla_moe

    items = mla_moe.config_items(c)
    seq = list(prompt)
    for _ in range(n):
        toks = np.zeros(max_len, np.int32)
        toks[: len(seq)] = seq
        seq.append(int(np.argmax(_control_logits(params, toks, len(seq) - 1, items))))
    return seq[len(prompt):]


def test_the_float8_control_fails_the_limit(root):
    """The float8 control put in the program's place: its greedy tokens,
    served at the cell's widths (MLA, 8 of 64 experts top-6 and 2 shared;
    layer 0 and one expert layer, a 4,096-token vocabulary), go through
    the driver's own comparison against ``served_logit_gap`` of the cell's
    limit file and make ``correct`` false on every seed."""
    from bench.drivers import serve as sd
    from bench.drivers import serve_mla_moe as smd
    from bench.weights import make_params

    cell = harness.load_cell("widemla.mla_chat", root, root / "bench")
    limit = json.loads((cells.BENCH / f"limits/{CELL}.json").read_text())
    mix = cell.traffic
    cfg = smd.model_config(cell.config)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, cfg.n_experts_held, cfg.top_k) == (
        2048, 16, 512, 8, 6)
    for seed in (SEED, SEED + 1, SEED + 2):
        params = make_params(cfg, seed)
        rng = np.random.default_rng(seed)
        loop = sd.Loop(None, None, None, int(mix["slots"]))
        for client in range(2):
            prompt = rng.integers(1, cfg.vocab, size=12).tolist()
            out = sd.Stamped()
            for t in _control_decode(cell.config, params, prompt, 16, int(mix["max_len"])):
                out.append(t)
            loop.recs.append(sd.Rec(client, 0.0, prompt, 16, len(prompt), out))
        ctx = harness.RunContext(cell=cell, seed=seed, seconds=0.0, trace=False,
                                 t_start=0.0, devices=jax.devices())
        smd._check(ctx, params, loop)
        (check,) = ctx.checks
        assert check.limit == limit["served_logit_gap"]
        assert not check.ok, (seed, check)
    jax.clear_caches()


def test_the_parent_program_fails_cleanly(monkeypatch):
    """A program without the expert share or YaRN rope cannot build the
    cell's model: the driver says so with the benchmark's own error."""
    import repro.configs.base as base
    from bench.drivers import serve_mla_moe as smd

    monkeypatch.delattr(base, "YarnScaling")
    with pytest.raises(harness.BenchError):
        smd.model_config(CONFIG)
    with pytest.raises(harness.BenchError):
        smd.model_config(dict(CONFIG, scoring_func="sigmoid"))


# ------------------------------------------------------------------ #
# the new metrics' readers and the FLOP count
# ------------------------------------------------------------------ #
UPPROJ = harness.load_module(cells.BENCH / "metrics/mla.upproj_rows_per_cached_row.py",
                             "metrics.mla.upproj_rows_per_cached_row")
ROWS = harness.load_module(cells.BENCH / "metrics/moe.expert_row_ratio.py",
                           "metrics.moe.expert_row_ratio")


class FakeReduction:
    def __init__(self, steps):
        self.steps = steps

    def modules_matching(self, pattern):
        return (self.steps, 1.0) if pattern == UPPROJ.STEP else (0, 0.0)


def _ctx(slots, steps):
    return SimpleNamespace(counters={"slots": slots}, trace_reduction=FakeReduction(steps))


def _trace_the_cells_decode_layers(slots, max_len=1024):
    """Trace (shapes only) one MLA decode call and one expert-layer call
    of the cell's decode step: ``slots`` single-token queries against a
    ``max_len`` latent cache."""
    from bench.drivers import serve_mla_moe as smd
    from repro.models import layers, moe, model

    cfg = smd.model_config(CONFIG)
    layers.reset_mla_record()
    moe.reset_moe_record()
    blk = jax.eval_shape(lambda: model._init_block(jax.random.PRNGKey(0), cfg, "attn", True))
    cache = jax.eval_shape(lambda: model._init_block_cache(cfg, "attn", slots, max_len))
    x = jax.ShapeDtypeStruct((slots, 1, cfg.d_model), jnp.bfloat16)
    jax.eval_shape(lambda p, c, x: model.apply_block(cfg, "attn", p, x, jnp.arange(1), c,
                                                     jnp.int32(0)), blk, cache, x)


@pytest.mark.parametrize("slots,steps,upproj", [
    # serve_chat's wave shape at 32 slots, 895 steps against a 1024-row
    # latent cache: 1024 x 895 / (895 x 896 / 2) = 2.29 rows up-projected
    # per row attended; 32 tokens x 6 experts over 64 send 24 rows to the 8
    # held, whose dropless buffers hold 8 x 32 x 6 = 1536: 64.0
    (32, 895, 2.29),
    # the cell's wave at 16 slots, 872 steps: 1024 x 2 / 873 = 2.35; 12
    # rows sent, 8 x 16 x 6 = 768 held: 64.0
    (16, 872, 2.35),
], ids=["32-slots", "16-slots"])
def test_the_readers_on_a_wave_of_the_cells_shape(slots, steps, upproj):
    _trace_the_cells_decode_layers(slots)
    assert UPPROJ.read(_ctx(slots, steps)) == pytest.approx(2 * 1024 / (steps + 1))
    assert round(UPPROJ.read(_ctx(slots, steps)), 2) == upproj
    assert ROWS.read(_ctx(slots, steps)) == 64.0


def test_the_readers_read_none_without_a_record(monkeypatch):
    from repro.models import layers, moe

    _trace_the_cells_decode_layers(slots=8)
    assert UPPROJ.read(_ctx(16, 872)) is None  # another slot count
    assert ROWS.read(_ctx(16, 872)) is None
    assert UPPROJ.read(SimpleNamespace(counters={}, trace_reduction=None)) is None
    monkeypatch.delattr(layers, "mla_record")  # a program that keeps none
    monkeypatch.delattr(moe, "moe_record")
    assert UPPROJ.read(_ctx(8, 872)) is None
    assert ROWS.read(_ctx(8, 872)) is None


ONE = dict(CONFIG, num_hidden_layers=1, first_k_dense_replace=0, hidden_size=8,
           num_attention_heads=2, kv_lora_rank=4, qk_nope_head_dim=3, qk_rope_head_dim=2,
           v_head_dim=5, intermediate_size=7, moe_intermediate_size=6, n_routed_experts=8,
           experts_held=4, num_experts_per_tok=2, n_shared_experts=1, vocab_size=10)


def test_flops_by_hand_on_one_expert_layer():
    # W_q 8*2*(3+2)=80, W_dkv 8*(4+2)=48, W_o 2*5*8=80; absorption
    # 2*(3*4+4*5)=64; router 8*8=64, shared 3*8*6=144, held share of the
    # routed experts 2 x 4/8 = 1 expert, 144; head 8*10=80
    assert flops_mla_moe.token_matmul_params(ONE) == 80 + 48 + 80 + 64 + 64 + 144 + 144 + 80
    # scores 2*2*(4+2) and weighted latents 2*2*4 per position
    assert flops_mla_moe.attention_flops(ONE, 5) == (24 + 16) * 5
    assert flops_mla_moe.token_flops(ONE, 5) == 2 * 704 + 200
    want = sum(flops_mla_moe.token_flops(ONE, k) for k in (1, 2, 3))
    assert flops_mla_moe.request_flops(ONE, 3) == want
    # layer 0 dense: its MLP in place of the expert layer's parts
    dense0 = dict(ONE, num_hidden_layers=2, first_k_dense_replace=1)
    assert flops_mla_moe.token_matmul_params(dense0) == 2 * 272 + 3 * 8 * 7 + 352 + 80
