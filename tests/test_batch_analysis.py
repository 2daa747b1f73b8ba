"""Vectorized batch analysis: the array-program backend must be
bit-identical to the scalar evaluation path for every cost model, the
engine's batch counters must match the dedup semantics exactly, and the
fig8 TTGT comparison must include the transpose DRAM traffic."""

import math
import random

import pytest

from repro.core.architecture import (
    cloud_accelerator,
    edge_accelerator,
    tpu_v5e_pod,
)
from repro.core.cost import (
    EvaluationEngine,
    MaestroLikeModel,
    TimeloopLikeModel,
    TPURooflineModel,
)
from repro.core.cost.analysis import get_context
from repro.core.ir.ttgt import best_ttgt_plan, enumerate_ttgt_plans, transpose_cost
from repro.core.mapping import Mapping
from repro.core.mapspace import MapSpace
from repro.core.optimizer import union_opt
from repro.core.problem import Problem

GEMM = Problem.gemm(64, 32, 16, word_bytes=1)
CONV = Problem.conv2d(2, 8, 8, 7, 7, 3, 3, stride=2, name="conv_t", word_bytes=1)
MODELS = [TimeloopLikeModel, MaestroLikeModel, TPURooflineModel]


def _costs_equal(a, b):
    return (
        a.latency_cycles == b.latency_cycles
        and a.energy_pj == b.energy_pj
        and a.utilization == b.utilization
        and a.macs == b.macs
        and a.frequency_hz == b.frequency_hz
        and a.breakdown == b.breakdown
    )


def _scalar_cost(cm, problem, arch, genome, sig):
    """The engine's per-candidate path: fused signature evaluation when the
    model provides it, full evaluate otherwise."""
    c = cm.evaluate_signature(problem, arch, sig)
    if c is None:
        c = cm.evaluate(problem, genome.to_mapping(), arch)
    return c


@pytest.mark.parametrize("problem", [GEMM, CONV], ids=["gemm", "conv"])
@pytest.mark.parametrize("model_cls", MODELS)
@pytest.mark.parametrize(
    "mk_arch",
    [edge_accelerator, cloud_accelerator, lambda: tpu_v5e_pod(1, 2, 2)],
    ids=["edge", "cloud", "tpu_pod"],
)
def test_batch_bit_identical_to_scalar(problem, model_cls, mk_arch):
    """evaluate_signature_batch == the scalar path, bit for bit, for all
    three cost models (incl. the roofline's collective terms on a mesh
    architecture)."""
    arch = mk_arch()
    cm = model_cls()
    ctx = get_context(problem, arch)
    space = MapSpace(problem, arch)
    rng = random.Random(0)
    genomes = [space.random_genome(rng) for _ in range(40)]
    sigs = [g.signature(ctx.dims) for g in genomes]
    batch = cm.evaluate_signature_batch(problem, arch, sigs)
    assert batch is not None and len(batch) == len(sigs)
    for g, sig, c in zip(genomes, sigs, batch):
        assert _costs_equal(c, _scalar_cost(cm, problem, arch, g, sig))
        # and therefore identical to the full evaluate as well
        assert c.latency_cycles == cm.evaluate(problem, g.to_mapping(), arch).latency_cycles


@pytest.mark.parametrize("model_cls", MODELS)
def test_batch_fixed_cases(model_cls):
    """Deterministic corner candidates: the trivial all-serial mapping and
    a heavily-spatial one must also round-trip bit-identically."""
    arch = cloud_accelerator()
    cm = model_cls()
    ctx = get_context(GEMM, arch)
    space = MapSpace(GEMM, arch)
    trivial = Mapping.trivial(GEMM, arch)
    others = [space.random_genome(random.Random(s)) for s in range(5)]
    cands = [trivial] + [g.to_mapping() for g in others]
    from repro.core.mapping import mapping_signature

    sigs = [mapping_signature(m, ctx.dims) for m in cands]
    batch = cm.evaluate_signature_batch(GEMM, arch, sigs)
    assert batch is not None
    for m, c in zip(cands, batch):
        assert _costs_equal(c, cm.evaluate(GEMM, m, arch))


def test_hypothesis_batch_equivalence():
    """Randomized GEMM shapes x seeds: batch == scalar, bit for bit."""
    pytest.importorskip(
        "hypothesis", reason="hypothesis not installed (see requirements-dev.txt)"
    )
    from hypothesis import given, settings, strategies as st

    sizes = st.sampled_from([1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64])

    @given(sizes, sizes, sizes, st.integers(0, 2**20))
    @settings(max_examples=25, deadline=None)
    def check(M, N, K, seed):
        problem = Problem.gemm(M, N, K, word_bytes=1)
        arch = edge_accelerator()
        ctx = get_context(problem, arch)
        space = MapSpace(problem, arch)
        rng = random.Random(seed)
        genomes = [space.random_genome(rng) for _ in range(6)]
        sigs = [g.signature(ctx.dims) for g in genomes]
        for cm in (TimeloopLikeModel(), MaestroLikeModel()):
            batch = cm.evaluate_signature_batch(problem, arch, sigs)
            assert batch is not None
            for g, sig, c in zip(genomes, sigs, batch):
                assert _costs_equal(c, _scalar_cost(cm, problem, arch, g, sig))

    check()


def test_jax_backend_matches_numpy():
    """The jitted JAX backend (x64 forced inside the core) produces the
    same stacked traffic as numpy, and engine results stay bit-identical."""
    pytest.importorskip("jax")
    import numpy as np

    arch = cloud_accelerator()
    ctx = get_context(GEMM, arch)
    space = MapSpace(GEMM, arch)
    rng = random.Random(11)
    sigs = [space.random_genome(rng).signature(ctx.dims) for _ in range(13)]
    bt_np = ctx.signature_traffic_batch(sigs, backend="numpy")
    bt_jax = ctx.signature_traffic_batch(sigs, backend="jax")
    assert not ctx._jax_failed
    assert np.array_equal(bt_np.compute_cycles, bt_jax.compute_cycles)
    assert np.array_equal(bt_np.inst_at, bt_jax.inst_at)
    for rn, rj in zip(bt_np.rows, bt_jax.rows):
        for a, b in zip(rn, rj):
            assert np.array_equal(a, b)
    cm = TimeloopLikeModel()
    costs_np = cm.evaluate_signature_batch(GEMM, arch, sigs, backend="numpy")
    costs_jax = cm.evaluate_signature_batch(GEMM, arch, sigs, backend="jax")
    for a, b in zip(costs_np, costs_jax):
        assert _costs_equal(a, b)


def test_engine_backend_search_identical():
    """A full search through the vectorized engine == the scalar engine:
    same best mapping, same cost, same counters."""
    arch = cloud_accelerator()
    sols = {
        be: union_opt(
            GEMM, arch, mapper="random", cost_model="timeloop",
            samples=400, engine_backend=be,
        )
        for be in ("numpy", "none")
    }
    a, b = sols["numpy"], sols["none"]
    assert a.cost.edp == b.cost.edp
    assert a.mapping.to_dict() == b.mapping.to_dict()
    for attr in ("evaluated", "analyzed", "cache_hits", "pruned"):
        assert getattr(a.search, attr) == getattr(b.search, attr), attr


def test_duplicate_pruned_batch_counters():
    """In-batch duplicates of a pruned candidate: the bound runs ONCE and
    ``stats.pruned`` counts the candidate once per batch (matching the
    dedup semantics of ``evaluated``)."""
    arch = cloud_accelerator()
    cm = TimeloopLikeModel()
    space = MapSpace(GEMM, arch)
    eng = EvaluationEngine(cm, GEMM, arch, metric="edp")
    # a strong incumbent plus the worst legal mapping => certain prune
    incumbent = union_opt(GEMM, arch, mapper="heuristic", cost_model=cm).cost.edp
    bad = Mapping.trivial(GEMM, arch)
    assert eng._should_prune(bad, incumbent)

    calls = []
    orig = eng._should_prune
    eng._should_prune = lambda cand, inc: calls.append(1) or orig(cand, inc)
    res = eng.evaluate_batch([bad, bad, bad], incumbent=incumbent)
    assert res == [None, None, None]
    assert eng.stats.pruned == 1  # counted once per batch, not per duplicate
    assert len(calls) == 1  # bound work matches the dedup semantics
    # pruned keys are tracked PER BATCH: a later batch re-admits the key
    eng.evaluate_batch([bad], incumbent=incumbent)
    assert eng.stats.pruned == 2


def test_probe_chunk_identical_results():
    """Incumbent-aware first-chunk sizing changes counters, never results."""
    arch = cloud_accelerator()
    base = union_opt(GEMM, arch, mapper="random", cost_model="timeloop",
                     samples=500, probe=0)
    probed = union_opt(GEMM, arch, mapper="random", cost_model="timeloop",
                       samples=500, probe=8)
    assert probed.cost.edp == base.cost.edp
    assert probed.mapping.to_dict() == base.mapping.to_dict()
    # the warm start admits the bound filter earlier => at least as many prunes
    assert probed.search.pruned >= base.search.pruned
    ex_base = union_opt(GEMM, arch, mapper="exhaustive", cost_model="timeloop",
                        max_mappings=600, probe=0)
    ex_probe = union_opt(GEMM, arch, mapper="exhaustive", cost_model="timeloop",
                         max_mappings=600, probe=8)
    assert ex_probe.cost.edp == ex_base.cost.edp
    assert ex_probe.mapping.to_dict() == ex_base.mapping.to_dict()
    dc_base = union_opt(GEMM, arch, mapper="decoupled", cost_model="timeloop",
                        offchip_samples=100, onchip_samples=100, probe=0)
    dc_probe = union_opt(GEMM, arch, mapper="decoupled", cost_model="timeloop",
                         offchip_samples=100, onchip_samples=100, probe=8)
    assert dc_probe.cost.edp == dc_base.cost.edp
    assert dc_probe.mapping.to_dict() == dc_base.mapping.to_dict()
    assert dc_probe.search.pruned >= dc_base.search.pruned


def test_fig8_includes_transpose_traffic():
    """The TTGT side of the fig8 comparison pays for its transposes."""
    from benchmarks.fig8_ttgt import ttgt_total_edp

    problem = Problem.tc_intensli2(16, word_bytes=1)
    arch = cloud_accelerator()
    plans = [p for p in enumerate_ttgt_plans(problem) if p.transpose_elems > 0]
    assert plans, "expected at least one plan with explicit transposes"
    plan = plans[0]
    cyc, pj = transpose_cost(plan, arch, word_bytes=1)
    assert pj > 0  # outermost-level read+write energy is charged
    assert cyc > 0  # and the bytes take time through the fill boundary
    gemm = plan.gemm_problem(word_bytes=1)
    sol = union_opt(gemm, arch, mapper="heuristic", cost_model="timeloop")
    with_t = ttgt_total_edp(sol.cost, plan, arch, include_transpose=True)
    without = ttgt_total_edp(sol.cost, plan, arch, include_transpose=False)
    assert without == sol.cost.edp  # --no-transpose-cost reproduces old numbers
    assert with_t > without  # transposes are no longer free
    expected = ((sol.cost.energy_pj + pj) * 1e-12) * (
        (sol.cost.latency_cycles + cyc) / sol.cost.frequency_hz
    )
    assert with_t == expected
    # a transpose-free plan costs nothing extra
    free = [p for p in enumerate_ttgt_plans(problem) if p.transpose_elems == 0]
    for p in free:
        assert transpose_cost(p, arch) == (0.0, 0.0)


def test_best_plan_minimizes_transpose_volume():
    for tds in (4, 16):
        problem = Problem.tc_ccsd7(tds, word_bytes=1)
        plans = enumerate_ttgt_plans(problem)
        assert best_ttgt_plan(problem).transpose_elems == min(
            p.transpose_elems for p in plans
        )
