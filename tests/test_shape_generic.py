"""Fused cores: the ONE-program-per-shape-class core must reproduce the
scalar path bit for bit (costs, bounds and admission decisions), and the
process-wide program cache must actually be shared -- a second problem in
the same shape class adds zero traces and zero warmup buckets."""

import math

import numpy as np
import pytest

from repro.codesign import CalibrationScale
from repro.core.architecture import cloud_accelerator, edge_accelerator
from repro.core.cost import (
    EvaluationEngine,
    MaestroLikeModel,
    TimeloopLikeModel,
    TPURooflineModel,
)
from repro.core.cost.analysis import (
    _make_generic_fused_core,
    get_context,
    global_trace_count,
    reset_trace_registry,
)
from repro.core.genome_batch import random_genome_batch
from repro.core.mapspace import MapSpace
from repro.core.problem import Problem

GEMM = Problem.gemm(64, 32, 16, word_bytes=1)
# same shape class as GEMM (ranks/levels/data-space structure), different
# content (dim sizes, word widths) -- the sharing tests hinge on this pair
GEMM_B = Problem.gemm(128, 64, 48, word_bytes=2)
CONV = Problem.conv2d(2, 8, 8, 7, 7, 3, 3, stride=2, name="conv_t", word_bytes=1)
MODELS = [TimeloopLikeModel, MaestroLikeModel, TPURooflineModel]


def _batch(problem, arch, seed, B=24):
    space = MapSpace(problem, arch)
    return random_genome_batch(space, np.random.default_rng(seed), B)


def _fused_out(cm, problem, arch, gb, metric, incumbent=math.inf, core=None):
    """Run the fused core with xp=numpy (no jax involved: this isolates
    the core's ALGEBRA from the jit machinery). ``core`` overrides the
    program built from this problem's own entry."""
    ctx = get_context(problem, arch)
    model = cm.batch_cost_terms(problem, arch)
    assert model is not None, f"{cm.name} lost its batch_cost_terms hook"
    _key, model_params, terms, lb = model
    if core is None:
        core = _make_generic_fused_core(
            ctx.shape_class_key(), terms, lb, metric, np, None
        )
    sb = gb.stacked()
    return core(sb.tt, sb.st, sb.perm, incumbent, ctx.model_pack(model_params))


def _score(metric, cyc, en, freq):
    """The engine's host scalarization of a (cycles, energy) pair."""
    if metric == "latency":
        return cyc
    if metric == "energy":
        return en
    return (en * 1e-12) * (cyc / freq)


def _assert_matches_scalar(out, cm, problem, arch, gb, metric, incumbent=math.inf):
    """Every row of a fused output == the scalar reference: the Cost of
    ``evaluate_signature`` (``evaluate`` where the model has no signature
    path), the bound of ``lower_bound_fn`` and the admit bit the host
    filter derives from that bound."""
    admit, _lbmx, lat, en, ut, _smx, extras = out
    lb_fn = cm.lower_bound_fn(problem, arch)
    freq = arch.frequency_hz
    for b in range(len(gb)):
        sig = gb.signature(b)
        cost = cm.evaluate_signature(problem, arch, sig)
        if cost is None:
            cost = cm.evaluate(problem, gb.genome(b).to_mapping(), arch)
        assert lat[b] == cost.latency_cycles, b
        assert en[b] == cost.energy_pj, b
        assert ut[b] == cost.utilization, b
        assert extras["metric_score"][b] == cost.metric(metric), b
        lb_cyc, lb_en = lb_fn(sig)
        assert extras["lb_cycles"][b] == lb_cyc, b
        assert extras["lb_energy"][b] == lb_en, b
        assert bool(admit[b]) == (_score(metric, lb_cyc, lb_en, freq) < incumbent), b


@pytest.mark.parametrize("problem", [GEMM, CONV], ids=["gemm", "conv"])
@pytest.mark.parametrize("model_cls", MODELS)
@pytest.mark.parametrize(
    "mk_arch", [edge_accelerator, cloud_accelerator], ids=["edge", "cloud"]
)
@pytest.mark.parametrize("metric", ["edp", "latency", "energy"])
def test_generic_core_bit_identical_to_per_context(problem, model_cls, mk_arch, metric):
    """Fused core (values as a parameter pack) == the scalar path, bit for
    bit, on numpy -- across randomized candidate batches and a real
    finite incumbent."""
    arch = mk_arch()
    cm = model_cls()
    for seed in (0, 7, 23):
        gb = _batch(problem, arch, seed)
        g = _fused_out(cm, problem, arch, gb, metric)
        _assert_matches_scalar(g, cm, problem, arch, gb, metric)
        # admission compares the LOWER-BOUND scores against the incumbent,
        # so a median lb score makes the admit bits non-trivial
        lb_scores = _score(
            metric,
            np.asarray(g[6]["lb_cycles"]),
            np.asarray(g[6]["lb_energy"]),
            arch.frequency_hz,
        )
        inc = float(np.median(lb_scores))
        g2 = _fused_out(cm, problem, arch, gb, metric, incumbent=inc)
        _assert_matches_scalar(g2, cm, problem, arch, gb, metric, incumbent=inc)
        admit = np.asarray(g2[0])
        assert not admit.all(), "median lb incumbent should reject some rows"
        if np.unique(lb_scores).size > 1:
            assert admit.any(), "median lb incumbent should admit some rows"


@pytest.mark.parametrize("model_cls", MODELS)
def test_generic_core_calibrated_scale_bit_identical(model_cls):
    """With a (non-power-of-two) calibration attached, the traced
    ``calib_scale`` parameter reproduces the calibrated scalar path bit
    for bit -- the same program serves every calibration value."""
    arch = cloud_accelerator()
    cm = model_cls().set_calibration(CalibrationScale(1.7, 1, "test"))
    gb = _batch(GEMM, arch, 3)
    g = _fused_out(cm, GEMM, arch, gb, "edp")
    _assert_matches_scalar(g, cm, GEMM, arch, gb, "edp")
    # and the scale really is in effect: raw model differs
    raw = _fused_out(model_cls(), GEMM, arch, gb, "edp")
    assert not np.array_equal(np.asarray(g[2]), np.asarray(raw[2]))


@pytest.mark.parametrize("model_cls", MODELS)
def test_one_generic_program_serves_the_shape_class(model_cls):
    """GEMM and GEMM_B share a shape class; ONE fused core object fed
    each problem's parameter pack must reproduce each problem's scalar
    path bit for bit."""
    arch = cloud_accelerator()
    cm = model_cls()
    ctx_a = get_context(GEMM, arch)
    skey = ctx_a.shape_class_key()
    assert skey == get_context(GEMM_B, arch).shape_class_key()
    model_a = cm.batch_cost_terms(GEMM, arch)
    assert model_a[0] == cm.batch_cost_terms(GEMM_B, arch)[0]
    core = _make_generic_fused_core(skey, model_a[2], model_a[3], "edp", np, None)
    for problem in (GEMM, GEMM_B):
        gb = _batch(problem, arch, 11)
        g = _fused_out(cm, problem, arch, gb, "edp", core=core)
        _assert_matches_scalar(g, cm, problem, arch, gb, "edp")


# ------------------------------------------------------------------ #
# jitted path (jax required from here on)
# ------------------------------------------------------------------ #


def _costs_equal(a, b):
    return (
        a.latency_cycles == b.latency_cycles
        and a.energy_pj == b.energy_pj
        and a.utilization == b.utilization
        and a.macs == b.macs
        and a.frequency_hz == b.frequency_hz
        and a.breakdown == b.breakdown
    )


def _engine_costs(cm, problem, arch, backend, seed=5, B=32):
    eng = EvaluationEngine(cm, problem, arch, metric="edp", backend=backend)
    gb = random_genome_batch(
        MapSpace(problem, arch), np.random.default_rng(seed), B
    )
    costs = eng.evaluate_batch(gb)
    assert all(c is not None for c in costs)
    return eng, costs


@pytest.mark.parametrize("problem", [GEMM, CONV], ids=["gemm", "conv"])
@pytest.mark.parametrize("model_cls", MODELS)
def test_jax_generic_engine_matches_numpy(problem, model_cls):
    """Engine results through the jitted shape-generic runner ==
    numpy-backend engine results, bit for bit (incl. breakdowns)."""
    pytest.importorskip("jax")
    arch = cloud_accelerator()
    eng_np, costs_np = _engine_costs(model_cls(), problem, arch, "numpy")
    eng_jx, costs_jx = _engine_costs(model_cls(), problem, arch, "jax")
    assert eng_jx.backend == "jax" and not eng_jx._ctx._jax_failed
    for a, b in zip(costs_np, costs_jx):
        assert _costs_equal(a, b)


@pytest.mark.parametrize("model_cls", MODELS)
def test_calibrated_jax_engine_matches_numpy(model_cls):
    """Calibrated models keep the fused jax path and stay bit-identical
    to the numpy engine (the scale is a final multiply on both)."""
    pytest.importorskip("jax")
    arch = cloud_accelerator()
    mk = lambda: model_cls().set_calibration(CalibrationScale(1.7, 1, "test"))
    eng_np, costs_np = _engine_costs(mk(), GEMM, arch, "numpy")
    eng_jx, costs_jx = _engine_costs(mk(), GEMM, arch, "jax")
    assert not eng_jx._ctx._jax_failed
    for a, b in zip(costs_np, costs_jx):
        assert _costs_equal(a, b)
    assert all("calibration_scale" in c.breakdown for c in costs_jx)


def _two_stage_jax(problem, arch):
    """The two-stage jax programs (admission bound, then traffic) over one
    batch, each checked bit for bit against its numpy program."""
    ctx = get_context(problem, arch)
    sb = _batch(problem, arch, 5, B=32).stacked()
    lb_np = ctx.lower_bound_batch(stacked=sb, backend="numpy")
    bt_np = ctx.signature_traffic_batch(stacked=sb, backend="numpy")
    lb_jx = ctx.lower_bound_batch(stacked=sb, backend="jax")
    bt_jx = ctx.signature_traffic_batch(stacked=sb, backend="jax")
    assert not ctx._jax_failed
    for a, b in zip(lb_np, lb_jx):
        assert np.array_equal(a, b)
    for a, b in zip(bt_np[:7], bt_jx[:7]):
        assert np.array_equal(a, b)
    for rn, rj in zip(bt_np.rows, bt_jx.rows):
        for a, b in zip(rn, rj):
            assert np.array_equal(a, b)


@pytest.mark.parametrize("path", ["fused", "two_stage"])
def test_second_problem_in_class_adds_zero_traces(path):
    """After GEMM traces the class's programs, a content-different problem
    in the SAME shape class (GEMM_B) dispatches with ZERO new traces --
    one compiled program per (shape class, model, metric) on the fused
    path, and one per shape class for the two-stage bound and traffic
    programs."""
    pytest.importorskip("jax")
    reset_trace_registry()
    arch = cloud_accelerator()
    if path == "two_stage":
        _two_stage_jax(GEMM, arch)
        assert global_trace_count() == 2  # the bound and the traffic program
        _two_stage_jax(GEMM_B, arch)
        assert global_trace_count() == 2
        return
    eng_a, _ = _engine_costs(TimeloopLikeModel(), GEMM, arch, "jax", B=32)
    assert not eng_a._ctx._jax_failed
    assert eng_a.stats.n_traces >= 1
    before = global_trace_count()
    eng_b, costs_b = _engine_costs(TimeloopLikeModel(), GEMM_B, arch, "jax", B=32)
    assert not eng_b._ctx._jax_failed
    assert global_trace_count() == before
    assert eng_b.stats.n_traces == 0
    # and the shared program still produces exact results for problem B
    _, costs_np = _engine_costs(TimeloopLikeModel(), GEMM_B, arch, "numpy", B=32)
    for a, b in zip(costs_np, costs_b):
        assert _costs_equal(a, b)


def test_warmup_covers_the_whole_shape_class():
    """One engine's warmup pre-traces the class-wide program buckets; a
    second engine on a same-class problem has nothing left to trace."""
    pytest.importorskip("jax")
    reset_trace_registry()
    arch = cloud_accelerator()
    eng_a = EvaluationEngine(
        TimeloopLikeModel(), GEMM, arch, metric="edp", backend="jax"
    )
    n_a = eng_a.warmup([16, 64])
    assert n_a == 2
    assert eng_a.stats.n_traces == 2
    # repeat warmup on the SAME engine: all buckets already traced
    assert eng_a.warmup([16, 64]) == 0
    eng_b = EvaluationEngine(
        TimeloopLikeModel(), GEMM_B, arch, metric="edp", backend="jax"
    )
    assert eng_b.warmup([16, 64]) == 0
    assert eng_b.stats.n_traces == 0


def test_trace_counter_attributes_per_engine():
    """``EngineStats.n_traces`` is the engine-local delta of the global
    registry: distinct metrics are distinct programs, repeats are free."""
    pytest.importorskip("jax")
    reset_trace_registry()
    arch = edge_accelerator()
    eng, _ = _engine_costs(MaestroLikeModel(), GEMM, arch, "jax", B=16)
    first = eng.stats.n_traces
    assert first >= 1
    # same bucket again: no retrace
    _ = eng.evaluate_batch(
        random_genome_batch(MapSpace(GEMM, arch), np.random.default_rng(9), 16)
    )
    assert eng.stats.n_traces == first
