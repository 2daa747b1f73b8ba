"""Device-resident search loops: the mega-batch random/exhaustive
precompute and the generation-resident GA scorer must reproduce the host
loop EXACTLY -- best mapping, best cost, trajectory, engine counters and
memo contents -- while syncing the host at most once per K units."""

import math

import numpy as np
import pytest

from repro.core.architecture import cloud_accelerator, edge_accelerator
from repro.core.cost import EvaluationEngine, TimeloopLikeModel
from repro.core.device_loop import (
    DeviceGAScorer,
    device_loop_enabled,
    device_precompute,
    sync_cadence,
)
from repro.core.genome_batch import random_genome_batch
from repro.core.mappers.exhaustive import ExhaustiveMapper
from repro.core.mappers.genetic import GeneticMapper
from repro.core.mappers.random_search import RandomMapper
from repro.core.mapspace import MapSpace
from repro.core.problem import Problem

GEMM = Problem.gemm(64, 32, 16, word_bytes=1)


# ------------------------------------------------------------------ #
# knobs + gating (no jax required)
# ------------------------------------------------------------------ #


def test_sync_cadence_env(monkeypatch):
    monkeypatch.delenv("UNION_DEVICE_K", raising=False)
    assert sync_cadence() == 8
    monkeypatch.setenv("UNION_DEVICE_K", "3")
    assert sync_cadence() == 3
    monkeypatch.setenv("UNION_DEVICE_K", "0")
    assert sync_cadence() == 1  # clamped, never a zero cadence
    monkeypatch.setenv("UNION_DEVICE_K", "garbage")
    assert sync_cadence() == 8  # malformed -> default, never a crash


def test_device_loop_gating(monkeypatch):
    arch = edge_accelerator()
    eng_np = EvaluationEngine(TimeloopLikeModel(), GEMM, arch, backend="numpy")
    eng_jx = EvaluationEngine(TimeloopLikeModel(), GEMM, arch, backend="jax")
    monkeypatch.delenv("UNION_DEVICE_LOOP", raising=False)
    assert not device_loop_enabled(eng_np)
    assert device_loop_enabled(eng_jx)
    monkeypatch.setenv("UNION_DEVICE_LOOP", "0")
    assert not device_loop_enabled(eng_jx)


def test_device_primitives_degrade_to_none_on_numpy(monkeypatch):
    """Numpy engines get None/inactive primitives -- callers keep the
    host loop with zero device state touched."""
    monkeypatch.delenv("UNION_DEVICE_LOOP", raising=False)
    arch = edge_accelerator()
    eng = EvaluationEngine(TimeloopLikeModel(), GEMM, arch, backend="numpy")
    gb = random_genome_batch(MapSpace(GEMM, arch), np.random.default_rng(0), 8)
    assert device_precompute(eng, [gb]) is None
    scorer = DeviceGAScorer(eng, lambda g, cs: None)
    assert not scorer.active
    assert scorer.score(gb) is None
    scorer.flush()  # empty flush is a no-op
    assert eng.stats.device_syncs == 0 and eng.stats.n_traces == 0


# ------------------------------------------------------------------ #
# host-loop equivalence (jax)
# ------------------------------------------------------------------ #


def _run(mapper, backend):
    arch = cloud_accelerator()
    space = MapSpace(GEMM, arch)
    cm = TimeloopLikeModel()
    engine = EvaluationEngine(cm, GEMM, arch, metric="edp", backend=backend)
    res = mapper.search(space, cm, metric="edp", engine=engine)
    return res, engine


def _assert_results_equal(a, b, same_backend=True):
    assert a.best_cost.latency_cycles == b.best_cost.latency_cycles
    assert a.best_cost.energy_pj == b.best_cost.energy_pj
    assert a.best_cost.utilization == b.best_cost.utilization
    assert a.best_cost.breakdown == b.best_cost.breakdown
    assert a.best_mapping.to_dict() == b.best_mapping.to_dict()
    assert a.trajectory == b.trajectory
    assert a.evaluated == b.evaluated
    assert a.considered == b.considered
    assert a.pruned == b.pruned
    assert a.analyzed == b.analyzed
    assert a.cache_hits == b.cache_hits
    if same_backend:
        # miss-batches served by the fused program: the device loop's
        # replay counts each batch exactly like a fresh host dispatch
        # (numpy runs legitimately report 0, so jax-vs-jax only)
        assert a.fused_dispatches == b.fused_dispatches


def _assert_memos_equal(ea, eb):
    """The engines' memo caches -- same keys, same Cost values bit for
    bit (the device loop replays every commit through the same path)."""
    ka, kb = list(ea._cache.keys()), list(eb._cache.keys())
    assert ka == kb
    for k in ka:
        ca, cb = ea._cache[k], eb._cache[k]
        assert ca.latency_cycles == cb.latency_cycles
        assert ca.energy_pj == cb.energy_pj
        assert ca.utilization == cb.utilization
        assert ca.breakdown == cb.breakdown


@pytest.mark.parametrize("patience", [0, 60], ids=["no-patience", "patience"])
def test_random_device_loop_matches_host(monkeypatch, patience):
    """Device-resident random search (one mega dispatch per K chunks) ==
    host-loop jax run == numpy run, down to the memo contents."""
    pytest.importorskip("jax")
    mk = lambda: RandomMapper(
        samples=192, seed=3, batch_size=32, probe=8, patience=patience
    )
    monkeypatch.setenv("UNION_DEVICE_LOOP", "0")
    res_host, eng_host = _run(mk(), "jax")
    assert res_host.device_syncs == 0
    monkeypatch.setenv("UNION_DEVICE_LOOP", "1")
    res_dev, eng_dev = _run(mk(), "jax")
    assert not eng_dev._ctx._jax_failed
    assert res_dev.device_syncs >= 1
    _assert_results_equal(res_dev, res_host)
    _assert_memos_equal(eng_dev, eng_host)
    res_np, eng_np = _run(mk(), "numpy")
    _assert_results_equal(res_dev, res_np, same_backend=False)
    _assert_memos_equal(eng_dev, eng_np)


def test_roofline_takes_the_device_loop(monkeypatch):
    """The roofline model has the same fused runner as the hierarchical
    models, so its jax random search runs the device loop too -- and
    matches the numpy search down to the memo contents."""
    pytest.importorskip("jax")
    from repro.core.architecture import tpu_v5e_pod
    from repro.core.cost import TPURooflineModel

    arch = tpu_v5e_pod(1, 2, 2)
    space = MapSpace(GEMM, arch)

    def run(backend):
        engine = EvaluationEngine(
            TPURooflineModel(), GEMM, arch, metric="edp", backend=backend
        )
        mapper = RandomMapper(samples=192, seed=3, batch_size=32, probe=8)
        res = mapper.search(space, engine.cost_model, metric="edp", engine=engine)
        return res, engine

    monkeypatch.setenv("UNION_DEVICE_LOOP", "1")
    res_dev, eng_dev = run("jax")
    assert not eng_dev._ctx._jax_failed
    assert res_dev.device_syncs >= 1
    res_np, eng_np = run("numpy")
    _assert_results_equal(res_dev, res_np, same_backend=False)
    _assert_memos_equal(eng_dev, eng_np)


def test_random_device_sync_cadence(monkeypatch):
    """Chunks per host sync == UNION_DEVICE_K: 10 chunks at K=3 is
    exactly ceil(10/3) = 4 mega dispatches."""
    pytest.importorskip("jax")
    monkeypatch.setenv("UNION_DEVICE_LOOP", "1")
    monkeypatch.setenv("UNION_DEVICE_K", "3")
    mapper = RandomMapper(samples=320, seed=7, batch_size=32, patience=0)
    res, eng = _run(mapper, "jax")
    assert not eng._ctx._jax_failed
    assert res.device_syncs == math.ceil(10 / 3)
    monkeypatch.setenv("UNION_DEVICE_LOOP", "0")
    res_host, eng_host = _run(
        RandomMapper(samples=320, seed=7, batch_size=32, patience=0), "jax"
    )
    _assert_results_equal(res, res_host)
    _assert_memos_equal(eng, eng_host)


def test_exhaustive_device_loop_matches_host(monkeypatch):
    """The exhaustive mapper's windowed stream through device_precompute
    == its host loop, including the early-stop budget accounting."""
    pytest.importorskip("jax")
    mk = lambda: ExhaustiveMapper(max_mappings=200, batch_size=32)
    monkeypatch.setenv("UNION_DEVICE_LOOP", "0")
    res_host, eng_host = _run(mk(), "jax")
    monkeypatch.setenv("UNION_DEVICE_LOOP", "1")
    res_dev, eng_dev = _run(mk(), "jax")
    assert not eng_dev._ctx._jax_failed
    _assert_results_equal(res_dev, res_host)
    _assert_memos_equal(eng_dev, eng_host)
    res_np, _ = _run(mk(), "numpy")
    _assert_results_equal(res_dev, res_np, same_backend=False)


def test_genetic_device_loop_matches_host(monkeypatch):
    """Generation-resident GA: device-scalarized fitness drives the SAME
    population dynamics, and the K-deferred replay reproduces the host
    loop's incumbent/trajectory/memo exactly."""
    pytest.importorskip("jax")
    mk = lambda: GeneticMapper(population=16, generations=8, seed=5)
    monkeypatch.setenv("UNION_DEVICE_LOOP", "0")
    res_host, eng_host = _run(mk(), "jax")
    assert res_host.device_syncs == 0
    monkeypatch.setenv("UNION_DEVICE_LOOP", "1")
    res_dev, eng_dev = _run(mk(), "jax")
    assert not eng_dev._ctx._jax_failed
    # initial pop + 8 generations = 9 scored batches, K=8 -> <= 2 syncs
    assert 1 <= res_dev.device_syncs <= math.ceil(9 / sync_cadence()) + 1
    _assert_results_equal(res_dev, res_host)
    _assert_memos_equal(eng_dev, eng_host)
    res_np, eng_np = _run(mk(), "numpy")
    _assert_results_equal(res_dev, res_np, same_backend=False)
    _assert_memos_equal(eng_dev, eng_np)


def test_genetic_device_fitness_is_engine_metric(monkeypatch):
    """The fitness vector fetched per generation is the engine metric of
    the replayed costs, bit for bit (the GA's selection sees EXACTLY the
    values the host loop would compute)."""
    pytest.importorskip("jax")
    monkeypatch.setenv("UNION_DEVICE_LOOP", "1")
    arch = cloud_accelerator()
    eng = EvaluationEngine(TimeloopLikeModel(), GEMM, arch, metric="edp", backend="jax")
    gb = random_genome_batch(MapSpace(GEMM, arch), np.random.default_rng(1), 16)
    got = {}
    scorer = DeviceGAScorer(eng, lambda g, cs: got.__setitem__("costs", cs))
    assert scorer.active
    fitness = scorer.score(gb)
    assert fitness is not None and fitness.dtype == np.float64
    scorer.flush()
    costs = got["costs"]
    assert len(costs) == len(gb) and all(c is not None for c in costs)
    host = np.asarray([c.metric("edp") for c in costs], dtype=np.float64)
    assert np.array_equal(fitness, host)
    assert eng.stats.device_syncs == 1


# ------------------------------------------------------------------ #
# one host pull per read point (jax)
# ------------------------------------------------------------------ #


def _count_device_gets(monkeypatch, fail_from=None):
    """Spy on ``jax.device_get``, the one transfer every read point makes:
    returns the call counter; from call ``fail_from`` on, every pull
    raises the device's runtime error (the device is gone)."""
    import jax

    real = jax.device_get
    calls = [0]

    def spy(tree):
        calls[0] += 1
        if fail_from is not None and calls[0] >= fail_from:
            raise jax.errors.JaxRuntimeError("planted fault in the host pull")
        return real(tree)

    monkeypatch.setattr(jax, "device_get", spy)
    return calls


@pytest.mark.parametrize(
    "mk, device_loop",
    [
        (lambda: RandomMapper(samples=320, seed=7, batch_size=32, patience=0), "1"),
        (lambda: ExhaustiveMapper(max_mappings=200, batch_size=32), "1"),
        (lambda: RandomMapper(samples=320, seed=7, batch_size=32, patience=0), "0"),
    ],
    ids=["random-device-loop", "exhaustive-device-loop", "random-host-loop"],
)
def test_one_pull_per_read_point(monkeypatch, mk, device_loop):
    """Every read of fused-dispatch results is one ``jax.device_get``:
    a device loop pulls once per sync, the host loop once per fused
    batch, and ``host_pulls`` counts exactly those transfers."""
    pytest.importorskip("jax")
    monkeypatch.setenv("UNION_DEVICE_LOOP", device_loop)
    monkeypatch.setenv("UNION_DEVICE_K", "3")
    calls = _count_device_gets(monkeypatch)
    res, eng = _run(mk(), "jax")
    assert not eng._ctx._jax_failed
    assert res.host_pulls == calls[0] > 0
    assert res.stats_dict()["host_pulls"] == res.host_pulls
    if device_loop == "1":
        assert res.host_pulls == res.device_syncs
    else:
        assert res.device_syncs == 0
        assert res.host_pulls == res.fused_dispatches


@pytest.mark.parametrize("generations, k", [(1, 8), (7, 3), (8, 8)])
def test_genetic_pulls_once_per_generation_and_flush(monkeypatch, generations, k):
    """The GA scorer pulls guards and fitness together once a generation,
    and every buffered generation's outputs together once a flush."""
    pytest.importorskip("jax")
    monkeypatch.setenv("UNION_DEVICE_LOOP", "1")
    monkeypatch.setenv("UNION_DEVICE_K", str(k))
    arch = cloud_accelerator()
    eng = EvaluationEngine(TimeloopLikeModel(), GEMM, arch, metric="edp", backend="jax")
    space = MapSpace(GEMM, arch)
    rng = np.random.default_rng(11)
    replayed = []
    scorer = DeviceGAScorer(eng, lambda g, cs: replayed.append(len(cs)))
    calls = _count_device_gets(monkeypatch)
    sizes = [16 + 3 * g for g in range(generations)]
    for n in sizes:
        assert scorer.score(random_genome_batch(space, rng, n)) is not None
    scorer.flush()
    assert replayed == sizes
    assert eng.stats.device_syncs == math.ceil(generations / k)
    assert eng.stats.host_pulls == generations + eng.stats.device_syncs
    assert calls[0] == eng.stats.host_pulls


@pytest.mark.parametrize("read_point", ["device-precompute", "ga-score"])
@pytest.mark.filterwarnings("ignore:Explicitly requested dtype float64")
def test_float32_pull_raises(monkeypatch, read_point):
    """float32 results still fail loudly through ``require_float64`` on
    the pulled arrays; they are a defect, not a device fault."""
    pytest.importorskip("jax")
    import contextlib

    from repro.core.cost import analysis

    monkeypatch.setenv("UNION_DEVICE_LOOP", "1")
    monkeypatch.setattr(analysis, "x64", contextlib.nullcontext)
    # a problem no other test maps: the context's runner keeps the
    # parameters it uploads, here in float32
    problem = Problem.gemm(40, 24, 16, word_bytes=1, name="float32_pull")
    arch = edge_accelerator()
    eng = EvaluationEngine(
        TimeloopLikeModel(), problem, arch, metric="edp", backend="jax"
    )
    gb = random_genome_batch(MapSpace(problem, arch), np.random.default_rng(2), 16)
    with pytest.raises(RuntimeError, match="not float64"):
        if read_point == "device-precompute":
            device_precompute(eng, [gb, gb])
        else:
            DeviceGAScorer(eng, lambda g, cs: None).score(gb)
    assert not eng._ctx._jax_failed


@pytest.mark.parametrize("fail_from", [1, 2], ids=["first-pull", "second-pull"])
@pytest.mark.parametrize(
    "mk",
    [
        lambda: RandomMapper(samples=320, seed=7, batch_size=32, patience=0),
        lambda: GeneticMapper(population=16, generations=8, seed=5),
    ],
    ids=["random", "genetic"],
)
def test_fault_in_the_pull_degrades_to_host(monkeypatch, mk, fail_from):
    """A device fault raised by the single pull degrades the search to the
    host path, which finds the same answer as a numpy run, bit for bit."""
    pytest.importorskip("jax")
    from repro.core.cost.analysis import get_context

    monkeypatch.setenv("UNION_DEVICE_LOOP", "1")
    monkeypatch.setenv("UNION_DEVICE_K", "3")
    res_np, _ = _run(mk(), "numpy")
    calls = _count_device_gets(monkeypatch, fail_from=fail_from)
    try:
        res, eng = _run(mk(), "jax")
        assert calls[0] >= fail_from
        assert eng.backend == "numpy" and res.backend_fallbacks == 1
        _assert_results_equal(res, res_np, same_backend=False)
    finally:
        get_context(GEMM, cloud_accelerator())._jax_failed = False
