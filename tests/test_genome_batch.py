"""Array-native candidate generation: GenomeBatch + vectorized samplers.

Contracts under test:

  * genome <-> batch ROUND-TRIP: ``GenomeBatch.from_genomes`` /
    ``genome(i)`` / ``signature(i)`` are exact inverses of each other and
    of ``Genome.signature`` (hypothesis-driven over random spaces);
  * DEDUP PARITY: the engine's array-native GenomeBatch path serves the
    exact costs AND counters of the per-candidate list path, across
    scalar/numpy/jax backends, and the canonical key rows collapse ONLY
    rows with bit-identical costs;
  * BATCH LEGALITY == SCALAR LEGALITY: ``chains_legal_batch`` and
    ``constraints_ok_batch`` reproduce ``_chains_legal`` + the
    ``Constraints.check`` verdicts on generated candidates;
  * PER-MAPPER EQUIVALENCE: the exhaustive vectorized enumerator is
    bit-identical (stream, results, counters) to the scalar generator; the
    seed-versioned v2 samplers are deterministic per seed and produce
    bit-identical searches across engine backends; ``seed_version=1``
    reproduces the historical scalar stream.
"""

import random

import numpy as np
import pytest

from repro.core.architecture import cloud_accelerator, edge_accelerator
from repro.core.constraints import (
    Constraints,
    mxu_aligned,
    nvdla_style,
    weight_stationary,
)
from repro.core.cost import MaestroLikeModel, TimeloopLikeModel
from repro.core.cost.engine import EvaluationEngine
from repro.core.mappers import get_mapper
from repro.core.mapspace import MapSpace
from repro.core.optimizer import SweepTask, union_opt, union_opt_sweep
from repro.core.problem import Problem
from repro.core import genome_batch as gbm

GEMM = Problem.gemm(64, 32, 16, word_bytes=1)
CONV = Problem.conv2d(2, 8, 8, 7, 7, 3, 3, stride=2, name="conv_t", word_bytes=1)


def _costs_equal(a, b):
    if a is None or b is None:
        return a is b
    return (
        a.latency_cycles == b.latency_cycles
        and a.energy_pj == b.energy_pj
        and a.utilization == b.utilization
        and a.breakdown == b.breakdown
    )


# --------------------------------------------------------------------- #
# Round-trip
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("problem", [GEMM, CONV], ids=["gemm", "conv"])
@pytest.mark.parametrize(
    "mk_arch", [edge_accelerator, cloud_accelerator], ids=["edge", "cloud"]
)
def test_genome_batch_round_trip(problem, mk_arch):
    space = MapSpace(problem, mk_arch())
    rng = random.Random(3)
    genomes = [space.random_genome(rng) for _ in range(40)]
    gb = gbm.GenomeBatch.from_genomes(space, genomes)
    assert len(gb) == len(genomes)
    for i, g in enumerate(genomes):
        g2 = gb.genome(i)
        assert g2.chains == g.chains
        assert g2.orders == g.orders
        assert gb.signature(i) == g.signature(space.dims)
        # key round-trip: from_genomes(genome(i)) is the same row
        again = gbm.GenomeBatch.from_genomes(space, [g2])
        assert again.row_key(0) == gb.row_key(i)


def test_genome_batch_round_trip_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    space = MapSpace(GEMM, cloud_accelerator())

    @settings(max_examples=30, deadline=None)
    @given(st.integers(min_value=0, max_value=2**31 - 1), st.integers(2, 12))
    def inner(seed, k):
        gb = space.random_genome_batch(gbm.philox_rng(seed), k)
        for i in range(k):
            g = gb.genome(i)
            assert space._chains_legal(g.chains)
            back = gbm.GenomeBatch.from_genomes(space, [g])
            assert back.signature(0) == gb.signature(i)
            assert (back.tt[0] == gb.tt[i]).all()
            assert (back.st[0] == gb.st[i]).all()
            assert (back.perm[0] == gb.perm[i]).all()

    inner()


# --------------------------------------------------------------------- #
# Dedup parity + canonical keys
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", [None, "numpy", "jax"])
@pytest.mark.parametrize(
    "model_cls", [TimeloopLikeModel, MaestroLikeModel], ids=["timeloop", "maestro"]
)
def test_engine_genome_batch_matches_list_path(model_cls, backend):
    if backend == "jax":
        pytest.importorskip("jax")
    arch = cloud_accelerator()
    space = MapSpace(GEMM, arch)
    gb0 = space.random_genome_batch(gbm.philox_rng(1), 120)
    idx = np.concatenate([np.arange(120), np.arange(0, 120, 9)])  # dups
    gb = gb0.select(idx)
    genomes = [gb.genome(i) for i in range(len(gb))]
    cm = model_cls()
    inc = cm.evaluate(GEMM, genomes[0].to_mapping(), arch).metric("edp")
    e_list = EvaluationEngine(model_cls(), GEMM, arch, metric="edp", backend=backend)
    e_gb = EvaluationEngine(model_cls(), GEMM, arch, metric="edp", backend=backend)
    c1 = e_list.evaluate_batch(genomes, incumbent=inc, probe=8)
    c2 = e_gb.evaluate_batch(gb, incumbent=inc, probe=8)
    assert all(_costs_equal(a, b) for a, b in zip(c1, c2))
    for attr in ("evaluated", "cache_hits", "pruned", "considered", "store_hits"):
        assert getattr(e_list.stats, attr) == getattr(e_gb.stats, attr), attr


def test_dedup_array_program_matches_dict_dedup():
    space = MapSpace(GEMM, cloud_accelerator())
    gb0 = space.random_genome_batch(gbm.philox_rng(5), 60)
    idx = np.concatenate([np.arange(60), np.arange(0, 60, 7), np.arange(0, 60, 13)])
    gb = gb0.select(idx)
    rep, inv = gb.dedup()
    # reference: first-occurrence dict over the canonical key bytes
    seen = {}
    ref_rep, ref_inv = [], []
    for b in range(len(gb)):
        k = gb.row_key(b)
        if k not in seen:
            seen[k] = len(ref_rep)
            ref_rep.append(b)
        ref_inv.append(seen[k])
    assert rep.tolist() == ref_rep
    assert inv.tolist() == ref_inv


def test_canonical_keys_collapse_only_cost_identical_rows():
    """Rows sharing a key row MUST have bit-identical costs (the memo
    soundness contract); rows that differ only in inactive-dim order
    placement DO collapse."""
    arch = cloud_accelerator()
    space = MapSpace(GEMM, arch)
    gb = space.random_genome_batch(gbm.philox_rng(11), 200)
    cm = TimeloopLikeModel()
    seen = {}
    for b in range(len(gb)):
        k = gb.key_rows()[b].tobytes()
        c = cm.evaluate(GEMM, gb.genome(b).to_mapping(), arch)
        rec = (c.latency_cycles, c.energy_pj, c.utilization,
               tuple(sorted(c.breakdown.items())))
        if k in seen:
            assert seen[k] == rec
        else:
            seen[k] = rec
    # a synthetic twin pair: all-serial rows where EVERY dim is inactive
    # at inner levels -- permuting inner orders must not change the key
    n, D = space.n_levels, len(space.dims)
    tt = np.ones((2, n, D), dtype=np.int64)
    st = np.ones((2, n, D), dtype=np.int64)
    perm = np.tile(np.arange(D, dtype=np.int64), (2, n, 1))
    perm[1, -1] = perm[1, -1][::-1]  # inner level: all dims inactive
    twins = gbm.GenomeBatch(space, tt, st, perm)
    assert twins.row_key(0) == twins.row_key(1)
    assert (
        cm.evaluate(GEMM, twins.genome(0).to_mapping(), arch).edp
        == cm.evaluate(GEMM, twins.genome(1).to_mapping(), arch).edp
    )


# --------------------------------------------------------------------- #
# Batch legality == scalar legality
# --------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "cons",
    [
        None,
        nvdla_style(("m", "n")),
        Constraints(name="cap1", max_concurrent_spatial=1),
        mxu_aligned(["m"], 8),
        weight_stationary(["k"], cloud_accelerator().clusters[1].name),
        Constraints(name="util", min_utilization=0.01, max_utilization=0.9),
    ],
    ids=["none", "nvdla", "cap1", "mxu", "ws", "util"],
)
def test_batch_legality_matches_scalar(cons):
    arch = cloud_accelerator()
    space = MapSpace(GEMM, arch, cons)
    rng = gbm.philox_rng(3)
    tt, st = gbm.sample_chains_batch(space, rng, 200)
    gbm.repair_fanout_batch(space, rng, tt, st)
    perm, ok = gbm.sample_orders_batch(space, rng, 200)
    assert ok
    gb = gbm.GenomeBatch(space, tt, st, perm)
    legal = gbm.chains_legal_batch(space, tt, st)
    cok = gbm.constraints_ok_batch(space, tt, st, perm)
    for b in range(200):
        g = gb.genome(b)
        assert bool(legal[b]) == space._chains_legal(g.chains), b
        if legal[b] and cons is not None:
            assert bool(cok[b]) == cons.ok(g.to_mapping(), GEMM, arch), b
    # the end-to-end sampler emits only legal rows (or the documented
    # trivial fallback, which the scalar sampler shares)
    ones = (1,) * (2 * arch.n_levels)
    gb2 = space.random_genome_batch(gbm.philox_rng(5), 80)
    for b in range(80):
        g = gb2.genome(b)
        if all(g.chains[d] == ones for d in space.dims):
            continue
        m = g.to_mapping()
        assert m.is_legal(GEMM, arch)
        assert cons is None or cons.ok(m, GEMM, arch)


# --------------------------------------------------------------------- #
# Per-mapper equivalence
# --------------------------------------------------------------------- #
def test_exhaustive_vectorized_bit_identical_to_generator():
    """The mixed-radix decoded stream reproduces the recursive DFS stream
    exactly: same best mapping, same costs, same engine counters."""
    arch = cloud_accelerator()
    for max_mappings in (400, 1100):
        a = union_opt(GEMM, arch, mapper="exhaustive", cost_model="timeloop",
                      max_mappings=max_mappings)
        b = union_opt(GEMM, arch, mapper="exhaustive", cost_model="timeloop",
                      max_mappings=max_mappings, vectorized=False)
        assert a.cost.edp == b.cost.edp
        assert a.mapping.to_dict() == b.mapping.to_dict()
        for attr in ("evaluated", "analyzed", "cache_hits", "pruned", "considered"):
            assert getattr(a.search, attr) == getattr(b.search, attr), attr


@pytest.mark.parametrize("mapper,kw", [
    ("random", {"samples": 300}),
    ("genetic", {"generations": 5}),
    ("decoupled", {"offchip_samples": 80, "onchip_samples": 120}),
])
def test_v2_mappers_deterministic_and_backend_invariant(mapper, kw):
    """seed_version=2 searches: (a) bit-identical across engine backends
    (generation never touches the engine), (b) reproducible per seed,
    (c) seed-sensitive."""
    arch = cloud_accelerator()
    base = union_opt(GEMM, arch, mapper=mapper, cost_model="timeloop", **kw)
    again = union_opt(GEMM, arch, mapper=mapper, cost_model="timeloop", **kw)
    assert base.cost.edp == again.cost.edp
    assert base.mapping.to_dict() == again.mapping.to_dict()
    assert base.search.considered == again.search.considered
    for backend in ("none", "jax"):
        if backend == "jax":
            pytest.importorskip("jax")
        other = union_opt(GEMM, arch, mapper=mapper, cost_model="timeloop",
                          engine_backend=backend, **kw)
        assert base.cost.edp == other.cost.edp, backend
        assert base.mapping.to_dict() == other.mapping.to_dict(), backend
        for attr in ("evaluated", "analyzed", "cache_hits", "pruned",
                     "considered"):
            assert getattr(base.search, attr) == getattr(other.search, attr), (
                backend, attr)
    seeded = union_opt(GEMM, arch, mapper=mapper, cost_model="timeloop",
                       seed=99, **kw)
    assert seeded.search.considered > 0  # a different stream still works


def test_seed_version_1_reproduces_historical_stream():
    """The v1 random sampler must submit EXACTLY the candidates the
    historical per-candidate sampler draws (the explicit seed-version
    contract: v2 is a different, documented stream)."""
    arch = cloud_accelerator()
    space = MapSpace(GEMM, arch)
    rng = random.Random(7)
    expected = [space.random_genome(rng) for _ in range(50)]
    sol = union_opt(GEMM, arch, mapper="random", cost_model="timeloop",
                    samples=50, seed=7, seed_version=1)
    # replay: scoring the expected stream through a fresh engine gives the
    # same best cost/mapping
    eng = EvaluationEngine(TimeloopLikeModel(), GEMM, arch, metric="edp")
    best = min(
        (eng.evaluate(g).metric("edp") for g in expected),
    )
    assert sol.cost.edp == best
    # and v2 differs (seed-versioned stream)
    v2 = union_opt(GEMM, arch, mapper="random", cost_model="timeloop",
                   samples=50, seed=7)
    assert v2.search.considered == sol.search.considered == 50


# --------------------------------------------------------------------- #
# Sweep + warmup
# --------------------------------------------------------------------- #
def test_union_opt_sweep_shares_engines_and_keeps_per_task_stats():
    arch = cloud_accelerator()
    sw = union_opt_sweep([
        SweepTask(GEMM, arch, mapper="heuristic"),
        SweepTask(GEMM, arch, mapper="random", mapper_kw={"samples": 200}),
        SweepTask(CONV, arch, mapper="random", mapper_kw={"samples": 100}),
    ])
    assert sw.stats["engines"] == 2  # GEMM tasks share; CONV separate
    assert len(sw) == 3
    solo = union_opt(GEMM, arch, mapper="heuristic")
    assert sw[0].cost.edp == solo.cost.edp
    assert sw[0].mapping.to_dict() == solo.mapping.to_dict()
    # per-task counters are snapshot diffs, not engine lifetime totals
    assert sw[0].search.considered == solo.search.considered
    solo_r = union_opt(GEMM, arch, mapper="random", samples=200)
    assert sw[1].cost.edp == solo_r.cost.edp
    assert sw[1].search.considered == solo_r.search.considered
    # the shared engine's memo warms the second search: it analyzes no
    # more than a cold engine would
    assert sw[1].search.analyzed <= solo_r.search.analyzed
    assert sw[1].search.cache_hits >= solo_r.search.cache_hits


def test_sweep_content_equal_instances_share_context():
    from repro.core.cost.analysis import get_context

    a1, a2 = cloud_accelerator(), cloud_accelerator()
    p1 = Problem.gemm(48, 24, 12, word_bytes=1)
    p2 = Problem.gemm(48, 24, 12, word_bytes=1)
    assert get_context(p1, a1) is get_context(p2, a2)
    assert get_context(p1, a1) is not get_context(CONV, a1)


def test_bucketed_warmup_pretraces_and_preserves_results():
    pytest.importorskip("jax")
    from repro.core.cost.analysis import get_context, reset_trace_registry

    arch = cloud_accelerator()
    cm = TimeloopLikeModel()
    eng = EvaluationEngine(cm, GEMM, arch, metric="edp", backend="jax")
    ctx = get_context(GEMM, arch)
    # warmup skips buckets the SHAPE CLASS has already traced (any prior
    # engine/test in this process counts), so reset for determinism
    reset_trace_registry()
    before = ctx.jax_dispatches
    n = eng.warmup([6, 100, 3])  # pow2 buckets: 8, 128 (3 < _BATCH_MIN)
    assert not ctx._jax_failed
    assert n == 2
    assert ctx.jax_dispatches - before == 2
    # warmup touches no engine counters and no memo state
    assert eng.stats.considered == 0 and eng.stats.evaluated == 0
    assert len(eng._cache) == 0
    # warmed search == unwarmed search, bit for bit
    cold = union_opt(GEMM, arch, mapper="random", cost_model="timeloop",
                     samples=200, engine_backend="jax")
    space = MapSpace(GEMM, arch)
    res = get_mapper("random", samples=200).search(space, cm, "edp", engine=eng)
    assert res.best_cost.edp == cold.cost.edp
    assert res.best_mapping.to_dict() == cold.mapping.to_dict()
    # numpy engines: warmup is a no-op
    eng_np = EvaluationEngine(cm, GEMM, arch, metric="edp", backend="numpy")
    assert eng_np.warmup([64]) == 0
