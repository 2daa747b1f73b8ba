"""EvaluationEngine correctness: cache/bound/batch paths must be exactly
the direct cost-model evaluation, and pruning must never discard a
candidate better than the incumbent."""

import math
import random

import pytest

from repro.core.architecture import cloud_accelerator, edge_accelerator
from repro.core.cost import (
    EvaluationEngine,
    MaestroLikeModel,
    TimeloopLikeModel,
    TPURooflineModel,
    mapping_signature,
)
from repro.core.cost.analysis import get_context
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


@pytest.mark.parametrize("problem", [GEMM, CONV], ids=["gemm", "conv"])
@pytest.mark.parametrize("model_cls", MODELS)
def test_engine_bitwise_identical_to_direct_evaluate(problem, model_cls):
    """Engine-cached results == direct cost_model.evaluate, bit for bit,
    for all three cost models on GEMM and CONV."""
    arch = edge_accelerator()
    cm = model_cls()
    space = MapSpace(problem, arch)
    rng = random.Random(0)
    eng = EvaluationEngine(cm, problem, arch, metric="edp")
    mappings = [space.random_mapping(rng) for _ in range(30)]
    genomes = [space.random_genome(rng) for _ in range(30)]
    for m in mappings:
        assert _costs_equal(eng.evaluate(m), cm.evaluate(problem, m, arch))
    # second pass: served from cache, still identical
    hits_before = eng.stats.cache_hits
    for m in mappings:
        assert _costs_equal(eng.evaluate(m), cm.evaluate(problem, m, arch))
    assert eng.stats.cache_hits >= hits_before + len(mappings)
    # genome candidates and the batch path agree too
    costs = eng.evaluate_batch(genomes)
    for g, c in zip(genomes, costs):
        assert _costs_equal(c, cm.evaluate(problem, g.to_mapping(), arch))


@pytest.mark.parametrize("problem", [GEMM, CONV], ids=["gemm", "conv"])
@pytest.mark.parametrize("model_cls", MODELS)
def test_lower_bound_never_exceeds_true_cost(problem, model_cls):
    """Seeded property test: the admission bound is a true lower bound, so
    pruning can never discard a candidate better than the incumbent."""
    arch = cloud_accelerator()
    cm = model_cls()
    space = MapSpace(problem, arch)
    ctx = get_context(problem, arch)
    rng = random.Random(1234)
    for metric in ("edp", "latency", "energy"):
        eng = EvaluationEngine(cm, problem, arch, metric=metric)
        for _ in range(120):
            g = space.random_genome(rng)
            m = g.to_mapping()
            true = cm.evaluate(problem, m, arch).metric(metric)
            lb = eng.lower_bound(m)
            assert lb <= true + 1e-12 * max(1.0, abs(true)), (
                model_cls.__name__,
                metric,
            )
            # chain-level bound (genome fast path) matches the sig bound
            fn = cm.lower_bound_chains_fn(problem, arch)
            if fn is not None:
                assert fn(g.chain_list, g.orders) == cm.lower_bound_fn(
                    problem, arch
                )(g.signature(ctx.dims))


@pytest.mark.parametrize("problem", [GEMM, CONV], ids=["gemm", "conv"])
@pytest.mark.parametrize("model_cls", [TimeloopLikeModel, MaestroLikeModel])
def test_pruned_candidates_cannot_beat_incumbent(problem, model_cls):
    arch = cloud_accelerator()
    cm = model_cls()
    space = MapSpace(problem, arch)
    rng = random.Random(7)
    eng = EvaluationEngine(cm, problem, arch, metric="edp")
    incumbent = cm.evaluate(problem, space.random_mapping(rng), arch).metric("edp")
    pruned_seen = 0
    for _ in range(200):
        g = space.random_genome(rng)
        c = eng.evaluate_admit(g, incumbent)
        true = cm.evaluate(problem, g.to_mapping(), arch).metric("edp")
        if c is None:
            pruned_seen += 1
            assert true >= incumbent  # never prunes an improver
        else:
            assert c.metric("edp") == true
    assert pruned_seen > 0  # the filter actually engages on this workload
    assert eng.stats.pruned == pruned_seen


def test_bound_pruned_search_identical_to_unpruned():
    """Search with cache+bound on == search with both off: same best cost."""
    arch = cloud_accelerator()
    for mapper in ("random", "genetic", "heuristic", "exhaustive"):
        kw = {"max_mappings": 400} if mapper == "exhaustive" else {}
        on = union_opt(GEMM, arch, mapper=mapper, cost_model="timeloop", **kw)
        off = union_opt(
            GEMM, arch, mapper=mapper, cost_model="timeloop",
            engine_prune=False, engine_cache=1, **kw,
        )
        assert on.cost.edp == off.cost.edp, mapper
        assert on.mapping.to_dict() == off.mapping.to_dict(), mapper


def test_signature_canonicalizes_equivalent_orders():
    arch = edge_accelerator()
    space = MapSpace(GEMM, arch)
    m = space.random_mapping(random.Random(5))
    dims = tuple(GEMM.dims)
    for lm in m.levels:  # declared order = problem order at every level
        lm.temporal_order = dims
    sig1 = mapping_signature(m, dims)
    m2 = m.clone()
    # an empty declared order normalizes to problem order: same signature
    m2.levels[0].temporal_order = ()
    assert mapping_signature(m2, dims) == sig1


def test_search_counters_reported():
    arch = cloud_accelerator()
    sol = union_opt(
        dnn := Problem.gemm(128, 64, 64, word_bytes=1), arch,
        mapper="random", cost_model="timeloop", samples=600,
    )
    res = sol.search
    assert res.pruned > 0
    assert res.analyzed > 0
    assert res.candidates == res.evaluated + res.pruned
    assert res.evals_per_s > 0
    gen = union_opt(dnn, arch, mapper="genetic", cost_model="timeloop")
    assert gen.search.cache_hits > 0


def test_engine_batch_dedups_within_batch():
    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    space = MapSpace(GEMM, arch)
    g = space.random_genome(random.Random(3))
    eng = EvaluationEngine(cm, GEMM, arch)
    costs = eng.evaluate_batch([g, g, g])
    assert eng.stats.evaluated == 1
    assert all(c is costs[0] for c in costs)


def test_engine_worker_pool_matches_serial():
    """Optional process-pool fan-out returns the same costs (skipped
    gracefully if the sandbox forbids subprocesses)."""
    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    space = MapSpace(GEMM, arch)
    rng = random.Random(9)
    ms = [space.random_mapping(rng) for _ in range(16)]
    serial = EvaluationEngine(cm, GEMM, arch)
    pooled = EvaluationEngine(cm, GEMM, arch, workers=2)
    try:
        got = pooled.evaluate_batch(ms)
        want = serial.evaluate_batch(ms)
        for a, b in zip(got, want):
            assert _costs_equal(a, b)
    finally:
        pooled.close()


def test_engine_worker_pool_children_never_import_jax():
    """Pool children score on the scalar path and never import jax, so
    a jax engine's pool cannot reach for the parent's chip."""
    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    space = MapSpace(GEMM, arch)
    ms = [space.random_mapping(random.Random(9)) for _ in range(4)]
    pooled = EvaluationEngine(cm, GEMM, arch, backend="jax", workers=2)
    try:
        pool = pooled._get_pool()
        pool.submit(_pool_eval_probe, [m.to_dict() for m in ms]).result()
        probe = "'jax' in __import__('sys').modules"
        assert pool.submit(eval, probe).result() is False
    finally:
        pooled.close()


def _pool_eval_probe(dicts):
    from repro.core.cost.engine import _pool_eval

    return _pool_eval(dicts)


# --------------------------------------------------------------------- #
# Nearest-neighbor incumbent seeding (seed_incumbent)
# --------------------------------------------------------------------- #
def test_seed_incumbent_prunes_early_but_never_changes_results():
    """A valid (upper-bound) seed warm-starts admission pruning from
    candidate #1 yet the search converges to the identical best."""
    from repro.core.mappers import RandomMapper

    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    space = MapSpace(GEMM, arch)

    plain = EvaluationEngine(cm, GEMM, arch, metric="edp")
    ref = RandomMapper(samples=200, seed=3).search(
        space, cm, "edp", engine=plain
    )
    assert ref.best_mapping is not None

    seeded = EvaluationEngine(cm, GEMM, arch, metric="edp")
    seeded.seed_incumbent = ref.best_metric * 2.0  # a sound upper bound
    res = RandomMapper(samples=200, seed=3).search(
        space, cm, "edp", engine=seeded
    )
    assert res.best_metric == ref.best_metric
    assert res.best_mapping.to_dict() == ref.best_mapping.to_dict()
    assert seeded.stats.seeded_batches > 0
    assert seeded.stats.pruned >= plain.stats.pruned  # never prunes less


def test_seed_incumbent_too_optimistic_prunes_everything():
    """An absurdly low seed bounds out every candidate: the search comes
    back empty (the CALLER's cue to retry unseeded) rather than silently
    returning a worse-than-seed mapping."""
    from repro.core.mappers import RandomMapper

    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    space = MapSpace(GEMM, arch)
    eng = EvaluationEngine(cm, GEMM, arch, metric="edp")
    eng.seed_incumbent = 1e-300
    res = RandomMapper(samples=100, seed=5).search(
        space, cm, "edp", engine=eng
    )
    assert res.best_mapping is None
    assert eng.stats.pruned > 0


def test_seed_incumbent_ignored_by_population_fitness_calls():
    """Genetic full-fitness batches (incumbent=inf, no probe) must never
    consume the seed -- every individual needs a true score."""
    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    ref = union_opt(GEMM, arch, mapper="genetic", cost_model="timeloop")

    from repro.core.mappers import GeneticMapper

    space = MapSpace(GEMM, arch)
    eng = EvaluationEngine(cm, GEMM, arch, metric="edp")
    eng.seed_incumbent = 1e-300  # would prune EVERYTHING if consumed
    res = GeneticMapper().search(space, cm, "edp", engine=eng)
    assert res.best_mapping is not None
    assert res.best_metric == ref.search.best_metric
    assert eng.stats.seeded_batches == 0


def test_seed_incumbent_ignored_with_finite_incumbent_or_no_prune():
    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    eng = EvaluationEngine(cm, GEMM, arch, metric="edp")
    eng.seed_incumbent = 123.0
    assert eng._seed_for(math.inf, 8) == 123.0
    assert eng._seed_for(50.0, 8) is None  # a real incumbent exists
    assert eng._seed_for(math.inf, 0) is None  # not a probe batch
    eng2 = EvaluationEngine(cm, GEMM, arch, metric="edp", prune=False)
    eng2.seed_incumbent = 123.0
    assert eng2._seed_for(math.inf, 8) is None  # nothing to prune with
    eng.seed_incumbent = math.inf
    assert eng._seed_for(math.inf, 8) is None  # non-finite seed dropped


# --------------------------------------------------------------------- #
# Circuit-breaker hook: degrade -> open, restore -> probe -> closed
# --------------------------------------------------------------------- #
def test_engine_breaker_degrade_open_then_probe_recovers():
    pytest.importorskip("jax")
    from repro.core.cost.analysis import get_context as _ctx_of
    from repro.core.mappers import RandomMapper
    from repro.runtime.fault_tolerance import CircuitBreaker

    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    space = MapSpace(GEMM, arch)
    br = CircuitBreaker(failure_threshold=1, probe_interval=1)
    eng = EvaluationEngine(cm, GEMM, arch, metric="edp", backend="jax",
                           breaker=br)
    ctx = _ctx_of(GEMM, arch)
    prior = ctx._jax_failed
    try:
        ctx._jax_failed = True  # poison: next batch degrades
        res = RandomMapper(samples=64, seed=2).search(
            space, cm, "edp", engine=eng
        )
        assert res.best_mapping is not None  # numpy path kept answering
        assert eng.backend == "numpy"
        assert eng.stats.backend_fallbacks == 1
        assert br.state == CircuitBreaker.OPEN

        # fault cleared + breaker admits the probe: jax path re-armed
        ctx._jax_failed = False
        assert eng.maybe_restore_backend() is True
        assert eng.backend == "jax" and br.state == CircuitBreaker.HALF_OPEN
        before = eng.stats.fused_dispatches
        res2 = RandomMapper(samples=64, seed=4).search(
            space, cm, "edp", engine=eng
        )
        assert res2.best_mapping is not None
        assert eng.stats.fused_dispatches > before  # real jax evidence
        assert br.state == CircuitBreaker.CLOSED
        assert br.recovered == 1
        assert br.transitions == [
            "closed->open", "open->half_open", "half_open->closed"
        ]
    finally:
        ctx._jax_failed = prior


def _enable_x64_missing(monkeypatch):
    import jax

    monkeypatch.delattr(jax, "enable_x64")


def _jax_import_broken(monkeypatch):
    from repro.core.cost.analysis import AnalysisContext

    def broken(self):
        raise ImportError("jax cannot be imported")

    monkeypatch.setattr(AnalysisContext, "_ensure_jax", broken)


def _x64_off(monkeypatch):
    import contextlib

    from repro.core.cost import analysis

    monkeypatch.setattr(analysis, "x64", contextlib.nullcontext)


@pytest.mark.parametrize(
    "breakage, error",
    [
        (_jax_import_broken, ImportError),
        (_enable_x64_missing, AttributeError),
        (_x64_off, RuntimeError),
    ],
    ids=["import-error", "attribute-error", "float32-output"],
)
@pytest.mark.parametrize("mapper", ["random", "genetic"])
@pytest.mark.filterwarnings("ignore:Explicitly requested dtype float64")
def test_jax_defects_raise_out_of_union_opt(monkeypatch, breakage, error, mapper):
    """A defect in the jax path -- an import or attribute error, or
    float32 output where float64 is required -- fails the search loudly
    instead of degrading it to numpy."""
    pytest.importorskip("jax")
    arch = edge_accelerator()
    breakage(monkeypatch)
    kw = {"samples": 96} if mapper == "random" else {"generations": 2}
    with pytest.raises(error):
        union_opt(GEMM, arch, mapper=mapper, cost_model="timeloop",
                  engine_backend="jax", **kw)
    assert not get_context(GEMM, arch)._jax_failed


@pytest.mark.parametrize("mapper", ["random", "genetic"])
def test_core_compile_error_raises_out_of_union_opt(monkeypatch, mapper):
    """XLA reports a refused compile as a JaxRuntimeError, the device
    fault type; it must still fail the search instead of degrading it."""
    jax = pytest.importorskip("jax")
    from repro.core.cost.analysis import CoreCompileError, reset_trace_registry

    def refuse(self, *a, **kw):
        raise jax.errors.JaxRuntimeError("planted compile failure")

    # a problem no other test maps, so no compiled core is cached for it
    problem = Problem.gemm(48, 40, 24, word_bytes=1, name="compile_fail")
    arch = edge_accelerator()
    reset_trace_registry()
    monkeypatch.setattr(jax.stages.Lowered, "compile", refuse)
    kw = {"samples": 96} if mapper == "random" else {"generations": 2}
    with pytest.raises(CoreCompileError):
        union_opt(problem, arch, mapper=mapper, cost_model="timeloop",
                  engine_backend="jax", **kw)
    assert not get_context(problem, arch)._jax_failed
    monkeypatch.undo()
    reset_trace_registry()


def test_accelerator_costs_keyed_apart_and_best_rescored(monkeypatch, tmp_path):
    """An engine on an accelerator stores its costs under their own key,
    and a search reports the best mapping's cost as the host computes it,
    whatever the device returned."""
    from dataclasses import replace

    from repro.core.cost import engine as engine_mod
    from repro.core.cost.store import ResultStore
    from repro.core.mappers.base import _Tracker

    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    store = ResultStore(str(tmp_path))
    host = EvaluationEngine(cm, GEMM, arch, backend="numpy", store=store)
    assert host.device is None
    monkeypatch.setattr(engine_mod, "device_platform", lambda b: "tpu")
    dev = EvaluationEngine(cm, GEMM, arch, backend="jax", store=store)
    assert dev.device == "tpu" and dev._store_skey != host._store_skey
    m = MapSpace(GEMM, arch).random_mapping(random.Random(3))
    exact = cm.evaluate(GEMM, m, arch)
    drifted = replace(exact, energy_pj=exact.energy_pj * (1 + 2**-50))
    tracker = _Tracker("edp", dev)
    tracker.offer(m, drifted)
    assert _costs_equal(tracker.result().best_cost, exact)
    host_tracker = _Tracker("edp", host)
    host_tracker.offer(m, drifted)
    assert host_tracker.result().best_cost is drifted


def test_injected_jax_fault_degrades_then_breaker_recovers(monkeypatch):
    """``UNION_FAULT_JAX`` is a device fault: the engine degrades to
    numpy through its breaker, and recovers once the fault is gone."""
    pytest.importorskip("jax")
    from repro.core.mappers import RandomMapper
    from repro.runtime.fault_tolerance import CircuitBreaker

    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    space = MapSpace(GEMM, arch)
    br = CircuitBreaker(failure_threshold=1, probe_interval=1)
    eng = EvaluationEngine(cm, GEMM, arch, metric="edp", backend="jax",
                           breaker=br)
    ctx = get_context(GEMM, arch)
    want = union_opt(GEMM, arch, mapper="random", cost_model="timeloop",
                     engine_backend="numpy", samples=64, seed=2)
    monkeypatch.setenv("UNION_FAULT_JAX", "1")
    try:
        res = RandomMapper(samples=64, seed=2).search(space, cm, "edp", engine=eng)
        assert eng.backend == "numpy" and eng.stats.backend_fallbacks == 1
        assert br.state == CircuitBreaker.OPEN
        assert res.best_mapping.to_dict() == want.mapping.to_dict()
        monkeypatch.delenv("UNION_FAULT_JAX")
        assert eng.maybe_restore_backend() is True
        before = eng.stats.fused_dispatches
        RandomMapper(samples=64, seed=4).search(space, cm, "edp", engine=eng)
        assert eng.stats.fused_dispatches > before
        assert br.state == CircuitBreaker.CLOSED
    finally:
        ctx._jax_failed = False


def test_maybe_restore_backend_noop_paths():
    from repro.runtime.fault_tolerance import CircuitBreaker

    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    # no breaker: PR 6's one-way degradation is preserved
    plain = EvaluationEngine(cm, GEMM, arch, backend="numpy")
    assert plain.maybe_restore_backend() is False
    # breaker attached but the backend never degraded: nothing to do
    br = CircuitBreaker(failure_threshold=1, probe_interval=1)
    jax_eng = EvaluationEngine(cm, GEMM, arch, backend="jax", breaker=br)
    if jax_eng.backend == "jax":  # may auto-degrade where jax is absent
        assert jax_eng.maybe_restore_backend() is False
    # degraded with the circuit still open and no probe due: denied
    br2 = CircuitBreaker(failure_threshold=1, probe_interval=3)
    eng = EvaluationEngine(cm, GEMM, arch, backend="jax", breaker=br2)
    eng.backend = "numpy"
    br2.record_failure()
    assert br2.state == CircuitBreaker.OPEN
    assert eng.maybe_restore_backend() is False  # denied call 1 of 3
    assert eng.backend == "numpy"
