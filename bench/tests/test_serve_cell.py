"""The serving driver end to end on the CPU at a smoke size: a sound run
is correct, and each fault the cell can have turns ``correct`` false."""

import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests import cells

SEED = 2**31 + 4242


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cells.make_root(tmp_path_factory.mktemp("serve"))


def test_sound_run_is_correct_and_reports_its_metrics(root):
    res = cells.run(root, "tiny.chat", SEED, 3.0)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"serve_tokens_per_s", "ttft_p90_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["served_logit_gap"]["limit"] == cells.TINY_SERVE_GAP


def _broken_step(kind):
    from repro.launch import steps

    sound = steps.make_serve_step

    def make(cfg, **kw):
        step = sound(cfg, **kw)

        def broken(params, cache, tokens, pos):
            nxt, new_cache = step(params, cache, tokens, pos)
            if kind == "token":  # one slot's token per step, a different slot each step
                slot = pos % nxt.shape[0]
                nxt = nxt.at[slot].set((nxt[slot] + 1) % cfg.vocab)
            else:  # the state comes back unchanged
                new_cache = cache
            return nxt, new_cache

        return broken

    return make


@pytest.mark.parametrize("kind", ["token", "state"])
def test_each_fault_turns_correct_false(root, monkeypatch, kind):
    from repro.launch import steps

    monkeypatch.setattr(steps, "make_serve_step", _broken_step(kind))
    res = cells.run(root, "tiny.chat", SEED, 2.0)
    assert not res["correct"], res["checks"]
    assert res["checks"]["served_logit_gap"]["value"] > cells.TINY_SERVE_GAP


def _control_decode(c, params, prompt, n, max_len):
    """Greedy tokens of the float8 control: the reference with every
    matrix product's operands rounded to float8, decoding ``n`` tokens."""
    import jax

    from bench.reference import dense

    head = dense._head(c, params)
    logits = jax.jit(lambda p, t, i: dense._mm("fp8")(
        "d,dv->v", dense.hidden(c, p, t, quant="fp8")[i], head))
    seq = list(prompt)
    for _ in range(n):
        toks = np.zeros(max_len, np.int32)
        toks[: len(seq)] = seq
        seq.append(int(np.argmax(logits(params, toks, len(seq) - 1))))
    return seq[len(prompt):]


def test_the_float8_control_fails_the_limit(root):
    """The float8 control put in the program's place: its greedy tokens,
    served at the serving cell's widths (depth and vocabulary cut), go
    through the driver's own comparison against ``served_logit_gap`` of
    ``bench/limits/qwen3-0.6b.serve_chat.json`` and make ``correct``
    false on every seed."""
    import json

    import jax

    from bench import harness
    from bench.drivers import serve as sd
    from bench.weights import make_params

    cell = harness.load_cell("wide.chat", root, root / "bench")
    limit = json.loads((cells.BENCH / "limits/qwen3-0.6b.serve_chat.json").read_text())
    mix = cell.traffic
    cfg = sd.model_config(cell.config)
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


def test_reference_matches_the_program_forward(root):
    """The plain reference and the program's own forward agree on one
    sequence (float32 program weights, no kernel)."""
    import jax

    from bench import harness
    from bench.drivers import serve as sd
    from bench.reference import dense
    from bench.weights import make_params
    from repro.models import forward

    cell = harness.load_cell("tiny.chat", root, root / "bench")
    cfg = sd.model_config(cell.config)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), make_params(cfg, 7))
    toks = np.arange(1, 33, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(cfg, params, {"tokens": jnp.asarray(toks)[None]}, remat=False)
    logits = np.asarray(logits[0], np.float32)
    served = logits.argmax(-1).astype(np.int32)
    gap, _ = dense.served_gaps(cell.config, params, toks, served)
    assert gap.max() < 1e-4
