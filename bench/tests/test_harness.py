"""The harness: a new cell, mix and metric are found from new files
alone; the result line keeps the contract's shape; no chip, no result."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.tests import cells

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cells.make_root(tmp_path_factory.mktemp("harness"))


def test_a_new_config_mix_and_metric_are_found_by_name(root, monkeypatch):
    """Only new files and entries: a config, a mix, a limit, a reader."""
    bench = root / "bench"
    cfg = json.loads((bench / "configs/tiny.json").read_text())
    cfg["n_layers"] = 1
    (bench / "configs/tiny1.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic/chat.json").read_text())
    mix.update(clients=2, slots=2)
    (bench / "traffic/pair.json").write_text(json.dumps(mix))
    (bench / "limits/tiny1.pair.json").write_text(json.dumps({"served_logit_gap": 0.01}))
    (bench / "metrics/pair.tokens.py").write_text(
        "def read(ctx):\n    return float(sum(ctx.counters['served']))\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="tiny1", file="bench/configs/tiny1.json"))
    spec["workloads"].append({"name": "tiny1.pair", "config": "tiny1", "traffic": "pair",
                              "chips": 1, "why": "test"})
    spec["per_layer"] = [{"name": "pair.tokens", "unit": "tokens", "better": "higher",
                          "source": "program_counter", "layer": "server",
                          "moves": "serve_tokens_per_s", "workloads": ["tiny1.pair"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("tiny1.pair", root, bench)
    assert cell.config["n_layers"] == 1 and cell.traffic["slots"] == 2
    assert [m["name"] for m in cell.per_layer] == ["pair.tokens"]

    from bench import trace

    def no_device_trace(self):  # the CPU has no device plane to reduce
        return trace.Reduction(1.0, 0.5, {}, {}, {}, 0.0, 0.0, [], 1)

    monkeypatch.setattr(trace.Tracer, "reduce", no_device_trace)
    res = cells.run(root, "tiny1.pair", 5, 1.0, trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["pair.tokens"]["value"] > 0
    assert res["device"]["busy_s"] == 0.5 and res["device"]["window_s"] == 1.0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_unknown_workload_or_device_kind_is_an_error(root):
    with pytest.raises(harness.BenchError):
        harness.load_cell("no.such", root, root / "bench")
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v99")


def _run_py(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


def test_run_exits_nonzero_without_a_tpu():
    p = _run_py(REPO, "--workload", "qwen3-0.6b.serve_chat", "--seed", str(2**31 + 5),
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    import shutil

    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    p = _run_py(tmp_path, "--workload", "qwen3-0.6b.serve_chat", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
