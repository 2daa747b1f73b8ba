"""Small copies of the benchmark's cells for CPU tests: the same drivers
and metric readers, a smoke-size model and short traffic, in a root of
their own."""

from __future__ import annotations

import json
import os
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

# widest served-logit gap the tiny serving cell allows: sound runs read 0
# at this size, the float8 control 0.065 to 0.072 on three seeds, a
# served token moved off the argmax or a cache left unchanged 0.7 or more
TINY_SERVE_GAP = 0.01


def make_root(root: Path) -> Path:
    bench = root / "bench"
    for d in ("configs", "traffic", "limits", "metrics"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    os.symlink(BENCH / "drivers", bench / "drivers")
    for f in (BENCH / "metrics").glob("*.py"):
        os.symlink(f, bench / "metrics" / f.name)
    (bench / "peaks.json").write_text((BENCH / "peaks.json").read_text())

    cfg = json.loads((BENCH / "configs/qwen3-0.6b.json").read_text())
    cfg.update(model="qwen3-0.6b_smoke", n_layers=2, d_model=64, n_heads=4,
               n_kv_heads=2, d_head=16, d_ff=128, vocab=512)
    (bench / "configs/tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic/serve_chat.json").read_text())
    mix.update(clients=4, slots=4, max_len=64,
               prompt=dict(median=8, sigma=0.5, min=2, max=20),
               answer=dict(median=6, sigma=0.5, min=2, max=12),
               check=dict(served_tokens=24, max_requests=4))
    (bench / "traffic/chat.json").write_text(json.dumps(mix))
    (bench / "limits/tiny.chat.json").write_text(json.dumps({"served_logit_gap": TINY_SERVE_GAP}))

    # the serving cell's widths, two layers and a small vocabulary: the
    # float8 control is held to the serving cell's own limit here
    wide = dict(json.loads((BENCH / "configs/qwen3-0.6b.json").read_text()),
                n_layers=2, d_ff=512, vocab=4096)
    (bench / "configs/wide.json").write_text(json.dumps(wide))
    (bench / "traffic/wide_chat.json").write_text(json.dumps(dict(mix, max_len=32)))
    (bench / "limits/wide.chat.json").write_text(
        (BENCH / "limits/qwen3-0.6b.serve_chat.json").read_text())

    mm = json.loads((BENCH / "traffic/map_gemm.json").read_text())
    mm["gemms"] = [g for g in mm["gemms"] if g["problem"] in ("attn.wk", "head")]
    mm["mapper_kw"] = {"samples": 256}
    mm["check"] = {"queries": 3}
    (bench / "traffic/gemm.json").write_text(json.dumps(mm))
    (bench / "limits/qwen3-0.6b.gemm.json").write_text(
        (BENCH / "limits/qwen3-0.6b.map_gemm.json").read_text())
    (bench / "configs/qwen3-0.6b.json").write_text((BENCH / "configs/qwen3-0.6b.json").read_text())

    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [
        dict(spec["configs"][0], name="tiny", file="bench/configs/tiny.json"),
        dict(spec["configs"][0], file="bench/configs/qwen3-0.6b.json"),
        dict(spec["configs"][0], name="wide", file="bench/configs/wide.json"),
    ]
    spec["workloads"] = [
        {"name": "tiny.chat", "config": "tiny", "traffic": "chat", "chips": 1, "why": "test"},
        {"name": "qwen3-0.6b.gemm", "config": "qwen3-0.6b", "traffic": "gemm", "chips": 1, "why": "test"},
        {"name": "wide.chat", "config": "wide", "traffic": "wide_chat", "chips": 1, "why": "test"},
    ]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            serve = any("serve" in w for w in m["workloads"])
            m["workloads"] = ["tiny.chat"] if serve else ["qwen3-0.6b.gemm"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool = False, **kw):
    import jax

    from bench import harness

    def peaks(ctx):
        ctx.peaks = harness.load_json(BENCH / "peaks.json")["TPU v5 lite"]

    return harness.run_cell(
        workload, seed, seconds, trace, root=root, bench_dir=root / "bench",
        devices=jax.devices(), interpret=True, before_run=peaks, **kw,
    )
