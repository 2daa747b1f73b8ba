"""The ``codeqwen1.5-7b.serve_code`` cell's shape on the CPU: GQA group 8,
QKV bias and an untied head through the serving driver with the Pallas
kernel in interpret mode. A sound run is correct, the reference is the
program's forward, each fault the cell can have (a bias the program drops
among them) turns ``correct`` false, and the float8 control at codeqwen's
attention widths fails the cell's own limit. And the reader of
``union_flash_attention_fetch_ratio``: the bytes the program records for
a decode call, over what the wave's calls need; silent where the program
keeps no record."""

import json
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.tests import cells
from bench.tests.test_serve_cell import _broken_step

SEED = 2**31 + 1515
CELL = "codeqwen1.5-7b.serve_code"
# widest served-logit gap the tiny cell allows: on seeds SEED to SEED + 3
# sound runs read 0.0 to 0.0141 (bf16 near ties at group 8), served with
# the QKV biases dropped 0.146, 0.074, 0.0, 0.052, with a served token
# moved off the argmax or the cache left unchanged 3.7 or more
TINY_CODE_GAP = 0.03
# the float8 control rounds every weight to float8 at each decoded token,
# which the CPU emulates: at d_ff 13440 its test took 127 s on a CPU, so the
# wide cell cuts d_ff to a tenth; every attention width stays codeqwen's
WIDE_D_FF = 1344


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``cells.make_root`` plus a codeqwen-shaped tiny cell (8 query heads
    on 1 KV head, bias, untied head, 2 layers, a short ``serve_code``-
    shaped mix) and a wide one at codeqwen's attention widths (1 layer, a
    4,096-token vocabulary, d_ff cut) held to the cell's own limit."""
    root = cells.make_root(tmp_path_factory.mktemp("serve_code"))
    bench = root / "bench"
    code = json.loads((cells.BENCH / "configs/codeqwen1.5-7b.json").read_text())
    tiny = dict(code, model="codeqwen1.5-7b_smoke", n_layers=2, d_model=64, n_heads=8,
                n_kv_heads=1, d_head=16, d_ff=128, vocab=512)
    (bench / "configs/tinycode.json").write_text(json.dumps(tiny))
    wide = dict(code, n_layers=1, d_ff=WIDE_D_FF, vocab=4096)
    (bench / "configs/widecode.json").write_text(json.dumps(wide))
    mix = json.loads((cells.BENCH / "traffic/serve_code.json").read_text())
    mix.update(clients=4, slots=4, max_len=64,
               prompt=dict(mix["prompt"], median=16, min=4, max=40),
               answer=dict(mix["answer"], median=4, min=2, max=8),
               check=dict(mix["check"], max_requests=4))
    (bench / "traffic/code.json").write_text(json.dumps(mix))
    (bench / "traffic/wide_code.json").write_text(json.dumps(dict(mix, max_len=32)))
    (bench / "limits/tinycode.code.json").write_text(
        json.dumps({"served_logit_gap": TINY_CODE_GAP}))
    (bench / "limits/widecode.code.json").write_text(
        (cells.BENCH / f"limits/{CELL}.json").read_text())

    spec = json.loads((root / "BENCHMARK.json").read_text())
    for name in ("tinycode", "widecode"):
        spec["configs"].append(dict(spec["configs"][0], name=name,
                                    file=f"bench/configs/{name}.json", reduced=["n_layers"]))
    spec["workloads"] += [
        {"name": "tinycode.code", "config": "tinycode", "traffic": "code", "chips": 1, "why": "test"},
        {"name": "widecode.code", "config": "widecode", "traffic": "wide_code", "chips": 1,
         "why": "test"},
    ]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.chat" in m.get("workloads", ()):
            m["workloads"].append("tinycode.code")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_sound_run_is_correct(root):
    res = cells.run(root, "tinycode.code", SEED, 2.0)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"serve_tokens_per_s", "ttft_p90_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["served_logit_gap"]["limit"] == TINY_CODE_GAP


def _bias_dropped_step():
    """The step as built, served with every QKV bias zeroed: the
    reference keeps the drawn ones."""
    from repro.launch import steps

    sound = steps.make_serve_step

    def make(cfg, **kw):
        step = sound(cfg, **kw)

        def no_bias(path, x):
            names = [getattr(k, "key", None) for k in path]
            return jnp.zeros_like(x) if names[-1] == "b" and names[-2] in ("wq", "wk", "wv") else x

        def broken(params, cache, tokens, pos):
            return step(jax.tree_util.tree_map_with_path(no_bias, params), cache, tokens, pos)

        return broken

    return make


@pytest.mark.parametrize("kind", ["token", "state", "bias"])
def test_each_fault_turns_correct_false(root, monkeypatch, kind):
    from repro.launch import steps

    make = _bias_dropped_step() if kind == "bias" else _broken_step(kind)
    monkeypatch.setattr(steps, "make_serve_step", make)
    res = cells.run(root, "tinycode.code", SEED, 1.0)
    assert not res["correct"], res["checks"]
    assert res["checks"]["served_logit_gap"]["value"] > TINY_CODE_GAP


def test_reference_matches_the_program_forward(root):
    """The plain reference and the program's own forward agree on one
    sequence at group 8 with bias and an untied head (float32 program
    weights, no kernel)."""
    from bench import harness
    from bench.drivers import serve as sd
    from bench.reference import dense
    from bench.weights import make_params
    from repro.models import forward

    cell = harness.load_cell("tinycode.code", root, root / "bench")
    cfg = sd.model_config(cell.config)
    assert (cfg.n_heads // cfg.n_kv_heads, cfg.qkv_bias, cfg.tie_embeddings) == (8, True, False)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), make_params(cfg, 7))
    assert "lm_head" in params and "b" in params["units"]["b0"]["attn"]["wq"]
    toks = np.arange(1, 33, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(cfg, params, {"tokens": jnp.asarray(toks)[None]}, remat=False)
    logits = np.asarray(logits[0], np.float32)
    served = logits.argmax(-1).astype(np.int32)
    gap, _ = dense.served_gaps(cell.config, params, toks, served)
    assert gap.max() < 1e-4


@partial(jax.jit, static_argnames="c_items")
def _control_logits(params, tokens, i, c_items):
    from bench.reference import dense

    c = dict(c_items)
    head = dense._head(c, params)
    return dense._mm("fp8")("d,dv->v", dense.hidden(c, params, tokens, quant="fp8")[i], head)


def _control_decode(c, params, prompt, n, max_len):
    """Greedy tokens of the float8 control: the reference with every
    matrix product's operands rounded to float8, decoding ``n`` tokens
    (one compile for every seed and prompt)."""
    from bench.reference import dense

    items = tuple((k, c[k]) for k in dense.REF_KEYS)
    seq = list(prompt)
    for _ in range(n):
        toks = np.zeros(max_len, np.int32)
        toks[: len(seq)] = seq
        seq.append(int(np.argmax(_control_logits(params, toks, len(seq) - 1, items))))
    return seq[len(prompt):]


def test_the_float8_control_fails_the_limit(root):
    """The float8 control put in the program's place: its greedy tokens,
    served at codeqwen's attention widths (d_model 4096, 32/4 heads x 128,
    bias, untied head; one layer, a 4,096-token vocabulary, d_ff cut to
    ``WIDE_D_FF``),
    go through the driver's own comparison against ``served_logit_gap`` of
    the cell's limit file and make ``correct`` false on every seed."""
    from bench import harness
    from bench.drivers import serve as sd
    from bench.weights import make_params

    cell = harness.load_cell("widecode.code", root, root / "bench")
    limit = json.loads((cells.BENCH / f"limits/{CELL}.json").read_text())
    mix = cell.traffic
    cfg = sd.model_config(cell.config)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff) == (
        4096, 32, 4, 128, WIDE_D_FF)
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
        sd._check(ctx, params, loop)
        (check,) = ctx.checks
        assert check.limit == limit["served_logit_gap"]
        assert not check.ok, (seed, check)
    jax.clear_caches()


# ------------------------------------------------------------------ #
# union_flash_attention_fetch_ratio
# ------------------------------------------------------------------ #
READER = harness.load_module(cells.BENCH / "metrics/union_flash_attention_fetch_ratio.py",
                             "metrics.union_flash_attention_fetch_ratio")
CHAT = json.loads((cells.BENCH / "configs/qwen3-0.6b.json").read_text())


class FakeReduction:
    def __init__(self, steps):
        self.steps = steps

    def modules_matching(self, pattern):
        return (self.steps, 1.0) if pattern == READER.STEP else (0, 0.0)


def _ctx(cfg, slots, steps):
    return SimpleNamespace(cell=SimpleNamespace(config=cfg), counters={"slots": slots},
                           trace_reduction=FakeReduction(steps))


def _build_decode_call(cfg, slots, max_len, blocks):
    from repro.kernels.flash_attention import ops

    ops.reset_kernel_record()
    q = jax.ShapeDtypeStruct((slots, 1, cfg["n_heads"], cfg["d_head"]), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((slots, cfg["n_kv_heads"], max_len, cfg["d_head"]), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=False, q_offset=jnp.int32(0), kv_len=jnp.int32(1), blocks=blocks,
        interpret=True, kv_heads_major=True), q, kv, kv)


CODE = json.loads((cells.BENCH / "configs/codeqwen1.5-7b.json").read_text())


@pytest.mark.parametrize("cfg,slots,steps,blocks,expect", [
    # serve_chat's wave: 895 steps of 32 slots, group 2 (qwen3-0.6b). With
    # two KV blocks a head every query head fetches the whole cache:
    # 2 x 1024 x 895 / (895 x 896 / 2) = 4.57, Q and output beside
    (CHAT, 32, 895, (128, 512), 4.57),
    # at the planned blocks one KV block spans the cache and is fetched
    # once for the group: 1024 x 895 / (895 x 896 / 2) = 2.29, and Q
    (CHAT, 32, 895, None, 2.31),
    # serve_code's wave: 1007 steps of 64 slots, group 8 (codeqwen)
    (CODE, 64, 1007, (128, 512), 16.13),
    (CODE, 64, 1007, None, 2.13),
], ids=["chat-two-blocks", "chat-planned", "code-two-blocks", "code-planned"])
def test_a_wave_of_the_cells_shape(cfg, slots, steps, blocks, expect):
    _build_decode_call(cfg, slots, 1024, blocks)
    assert READER.read(_ctx(cfg, slots, steps)) == pytest.approx(expect, abs=0.05)


def test_no_record_reads_none(monkeypatch):
    from repro.kernels.flash_attention import ops

    monkeypatch.setattr(ops, "kernel_record", lambda: {})
    assert READER.read(_ctx(CHAT, 32, 895)) is None
    monkeypatch.delattr(ops, "kernel_record")  # a program that keeps none
    assert READER.read(_ctx(CHAT, 32, 895)) is None
    assert READER.read(SimpleNamespace(trace_reduction=None)) is None


def test_a_record_of_another_shape_reads_none():
    _build_decode_call(CHAT, 16, 1024, None)
    assert READER.read(_ctx(CHAT, 32, 895)) is None
