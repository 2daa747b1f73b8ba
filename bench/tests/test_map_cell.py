"""The mapping driver on the CPU, and the plain cost reference against
the program's cost model."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench.reference import timeloop
from bench.tests import cells

SEED = 2**31 + 99
MIX = json.loads((Path(__file__).resolve().parents[1] / "traffic/map_gemm.json").read_text())
LIMITS = json.loads(
    (Path(__file__).resolve().parents[1] / "limits/qwen3-0.6b.map_gemm.json").read_text())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cells.make_root(tmp_path_factory.mktemp("map"))


def test_sound_run_is_correct(root):
    res = cells.run(root, "qwen3-0.6b.gemm", SEED, 2.0)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"map_queries_per_s", "setup_s"}
    assert res["metrics"]["map_queries_per_s"]["value"] > 0


def test_an_altered_answer_turns_correct_false(root, monkeypatch):
    from bench.drivers import map as md

    sound = md.query

    def altered(q, arch, mix, seed, backend):
        sol = sound(q, arch, mix, seed, backend)
        if backend == "jax":  # the answer is altered where it is produced
            sol.cost.energy_pj *= 1.0 + 1e-9
        return sol

    monkeypatch.setattr(md, "query", altered)
    import bench.harness as h

    real = h.load_module

    def load(path, name):  # the harness loads drivers by path: hand it this one
        return md if name == "drivers.map" else real(path, name)

    monkeypatch.setattr(h, "load_module", load)
    res = cells.run(root, "qwen3-0.6b.gemm", SEED, 2.0)
    assert not res["correct"], res["checks"]
    assert res["checks"]["edp_gap_vs_reference"]["value"] > LIMITS["edp_gap_vs_reference"]


def _problem(b, i, o):
    from repro.core.opstream import build_einsum

    return build_einsum("gemm", "bi,io->bo", {"b": b, "i": i, "o": o}, "GEMM", MIX["word_bytes"])


@pytest.mark.parametrize("dims", [(128, 1024, 2048), (1048576, 3072, 1024), (128, 1024, 151936)])
def test_reference_agrees_with_the_program_on_random_mappings(dims):
    """Legality and cost of 64 sampled mappings per GEMM, reference against
    the program's scalar model; the float32 reference (the control) misses."""
    from bench.drivers.map import architecture
    from repro.core.cost import TimeloopLikeModel
    from repro.core.mapspace import MapSpace

    b, i, o = dims
    prob = _problem(b, i, o)
    arch = architecture(MIX["arch"])
    space = MapSpace(prob, arch)
    rng = np.random.default_rng(5)
    model = TimeloopLikeModel()
    worst32, seen = 0.0, 0
    for _ in range(64):
        m = space.random_mapping(rng)
        d = m.to_dict()
        dd = {"b": b, "i": i, "o": o}
        assert (timeloop.violations(dd, MIX["arch"], d, MIX["word_bytes"]) == []) == m.is_legal(prob, arch)
        if not m.is_legal(prob, arch):
            continue
        seen += 1
        want = model.evaluate(prob, m, arch)
        got = timeloop.cost(dd, MIX["arch"], d, MIX["word_bytes"])
        assert got["latency_cycles"] == pytest.approx(want.latency_cycles, rel=1e-13)
        assert got["energy_pj"] == pytest.approx(want.energy_pj, rel=1e-13)
        assert abs(got["edp"] - want.edp) / want.edp <= LIMITS["edp_gap_vs_reference"]
        g32 = timeloop.cost(dd, MIX["arch"], d, MIX["word_bytes"], dtype=np.float32)["edp"]
        worst32 = max(worst32, abs(g32 - want.edp) / want.edp)
    assert seen > 10
    assert worst32 > LIMITS["edp_gap_vs_reference"]


def test_reference_finds_illegal_tiles():
    from bench.drivers.map import architecture
    from repro.core.mapspace import MapSpace

    prob = _problem(128, 1024, 2048)
    m = MapSpace(prob, architecture(MIX["arch"])).random_mapping(np.random.default_rng(1)).to_dict()
    m["levels"][1]["temporal_tile_sizes"]["b"] = 3
    assert timeloop.violations({"b": 128, "i": 1024, "o": 2048}, MIX["arch"], m, 2)
