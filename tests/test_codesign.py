"""Unified co-design layer (src/repro/codesign): planner edge cases,
A/B equivalence with the pre-refactor per-kernel planners, plan-cache
behavior, fallback ledger, and the calibration subsystem.

The A/B tests pin BOTH sides to the unified 8 MiB VMEM budget
(``DEFAULT_VMEM_BUDGET``): flash_attention and ssd_scan always planned at
8 MiB, while matmul historically planned at the 16 MiB ``tpu_chip()``
default -- unifying that convention is an intended behavior change of the
refactor (PR 7), so the legacy replicas here are the old ALGORITHMS run
at the new budget.
"""

import json
import math
import random

import pytest

from repro import codesign
from repro.codesign import (
    DEFAULT_VMEM_BUDGET,
    CalibrationScale,
    CalibrationTable,
    KernelSpace,
    plan,
    planner_stats,
    repair_tile,
    reset_planner_stats,
    round_up,
)
from repro.core.architecture import tpu_chip
from repro.core.constraints import mxu_aligned
from repro.core.cost.store import ResultStore
from repro.core.cost.timeloop_like import TimeloopLikeModel
from repro.core.cost.maestro_like import MaestroLikeModel
from repro.core.cost.roofline import TPURooflineModel
from repro.core.optimizer import union_opt
from repro.core.problem import Problem

jax = pytest.importorskip("jax")

from repro.kernels.flash_attention.ops import FLASH_ATTENTION_SPACE, plan_blocks
from repro.kernels.matmul.ops import MATMUL_SPACE, matmul_vmem_bytes, plan_tiles
from repro.kernels.ssd_scan.ops import SSD_SCAN_SPACE, plan_chunk

V8 = 8 * (1 << 20)


# ------------------------------------------------------------------ #
# legacy replicas: the pre-refactor planner algorithms, budget-pinned
# ------------------------------------------------------------------ #
def _legacy_fix(b, dim, default, cap=None):
    if b >= 128 and dim % b == 0 and (cap is None or b <= cap):
        return b
    d = min(default, dim)
    while dim % d != 0:
        d //= 2
    return max(d, 1)


def _legacy_plan_tiles(M, N, K, mapper="heuristic", budget=400):
    problem = Problem.gemm(M, N, K)
    arch = tpu_chip(vmem_tile_budget=V8)  # unified budget (see module doc)
    cons = mxu_aligned(["m", "n", "k"], 128)
    try:
        sol = union_opt(
            problem, arch, mapper=mapper, cost_model="timeloop",
            metric="latency", constraints=cons, climb_steps=budget,
        )
        leaf = sol.mapping.levels[-1]
        bm, bn, bk = leaf.tt("m"), leaf.tt("n"), leaf.tt("k")
    except Exception:
        bm = bn = bk = 0
    return _legacy_fix(bm, M, 256), _legacy_fix(bn, N, 256), _legacy_fix(bk, K, 512)


def _legacy_plan_blocks(Sq, Skv, D):
    problem = Problem.from_einsum(
        "attn_scores", "qd,kd->qk", {"q": Sq, "k": Skv, "d": D}, "GEMM"
    )
    cons = mxu_aligned(["q", "k"], 128)
    try:
        sol = union_opt(
            problem, tpu_chip(vmem_tile_budget=V8),
            mapper="heuristic", cost_model="timeloop",
            metric="latency", constraints=cons, climb_steps=200,
        )
        leaf = sol.mapping.levels[-1]
        bq, bk = leaf.tt("q"), leaf.tt("k")
    except Exception:
        bq = bk = 0
    return _legacy_fix(bq, Sq, 512, cap=1024), _legacy_fix(bk, Skv, 512, cap=1024)


def _legacy_plan_chunk(hp, n, vmem_budget=V8):
    cl = 1024
    while cl > 64:
        ws = 4 * (2 * cl * cl + cl * (hp + 2 * n + 2) + n * hp)
        if ws <= vmem_budget:
            return cl
        cl //= 2
    return 64


# the shapes test_kernels.py drives through each planner (matmul shapes
# are what matmul() actually plans: dims rounded up to 128)
MATMUL_AB = [
    (128, 128, 128), (256, 128, 384), (384, 256, 128), (128, 512, 256),
    (128, 384, 128), (4096, 4096, 4096), (8192, 1024, 512),
]
FLASH_AB = [(4096, 4096, 128), (128, 128, 64), (256, 128, 128)]
SSD_AB = [(64, 128), (64, 64), (256, 64)]


@pytest.mark.parametrize("mnk", MATMUL_AB)
def test_ab_matmul_tiles_match_legacy(mnk):
    # the legacy tiles stand wherever the VMEM the compiler allocates for
    # them fits the budget; elsewhere legalize shrinks them into it
    legacy = _legacy_plan_tiles(*mnk)
    got = plan_tiles(*mnk)
    if matmul_vmem_bytes(legacy) <= V8:
        assert got == legacy
    else:
        assert matmul_vmem_bytes(got) <= V8
        assert all(g <= l for g, l in zip(got, legacy))
        assert all(d % g == 0 and g % 128 == 0 for g, d in zip(got, mnk))


@pytest.mark.parametrize("sqd", FLASH_AB)
def test_ab_flash_blocks_match_legacy(sqd):
    assert plan_blocks(*sqd) == _legacy_plan_blocks(*sqd)


@pytest.mark.parametrize("hpn", SSD_AB)
def test_ab_ssd_chunk_matches_legacy(hpn):
    assert plan_chunk(*hpn) == _legacy_plan_chunk(*hpn)


# ------------------------------------------------------------------ #
# repair_tile / legalize edge cases: odd, non-pow2, < 128 dims
# ------------------------------------------------------------------ #
def _assert_legal(space, shape, config):
    dims = space.decode_dims
    tiles = space.block_tiles(shape, config)
    problem = space.problem(shape)
    for d, t in tiles.items():
        full = problem.dims[d]
        assert t >= 1, f"{space.name}{shape}: tile {d}={t} < 1"
        assert full % t == 0, f"{space.name}{shape}: {d}={t} !| {full}"
    assert len(config) == len(dims)


def test_repair_tile_seeded_random_edges():
    rng = random.Random(0)
    for _ in range(500):
        dim = rng.randint(1, 9000)  # odd, prime, < 128 all included
        b = rng.choice([0, 1, 7, 127, 128, 333, dim, dim * 2, 4096])
        default = rng.choice([64, 128, 256, 512])
        cap = rng.choice([None, 1024])
        t = repair_tile(b, dim, default, cap=cap)
        assert 1 <= t <= dim and dim % t == 0
        if cap is not None and b >= 128 and dim % b == 0 and b <= cap:
            assert t == b  # good candidates pass through untouched


def test_repair_tile_hypothesis_edges():
    pytest.importorskip(
        "hypothesis", reason="hypothesis not installed (see requirements-dev.txt)"
    )
    from hypothesis import given, settings, strategies as st

    @given(
        st.integers(0, 10_000), st.integers(1, 10_000),
        st.sampled_from([64, 128, 256, 512]), st.sampled_from([None, 1024]),
    )
    @settings(max_examples=200, deadline=None)
    def prop(b, dim, default, cap):
        t = repair_tile(b, dim, default, cap=cap)
        assert 1 <= t <= dim and dim % t == 0

    prop()


@pytest.mark.parametrize(
    "shape", [(300, 200, 100), (1, 257, 33), (127, 127, 127), (64, 96, 80)]
)
def test_matmul_legalize_odd_shapes(shape):
    # legalize must repair ANY candidate into legal divisor tiles
    for cand in [(0, 0, 0), (128, 128, 128), (999, 7, 1)]:
        cfg = MATMUL_SPACE.legalize(cand, shape)
        _assert_legal(MATMUL_SPACE, shape, cfg)


@pytest.mark.parametrize("shape", [(136, 72, 64), (8, 8, 8), (1024, 333, 128)])
def test_flash_legalize_odd_shapes(shape):
    for cand in [(0, 0), (2048, 2048), (512, 512)]:
        cfg = FLASH_ATTENTION_SPACE.legalize(cand, shape)
        _assert_legal(FLASH_ATTENTION_SPACE, shape, cfg)
        assert cfg[0] <= 1024 and cfg[1] <= 1024  # R3 cap


@pytest.mark.parametrize(
    "space, cand, shape",
    [
        (MATMUL_SPACE, (1024, 1536, 1024), (4096, 3072, 1024)),
        (MATMUL_SPACE, (1024, 1024, 1536), (4096, 1024, 3072)),
        (MATMUL_SPACE, (2048, 2048, 2048), (4096, 4096, 4096)),
        (FLASH_ATTENTION_SPACE, (1024, 1024), (4096, 4096, 256)),
    ],
    ids=["mm-4096x3072x1024", "mm-4096x1024x3072", "mm-4096-cubed", "flash-d256"],
)
def test_legalize_holds_compiler_vmem_within_budget(space, cand, shape):
    # these candidates need more VMEM than the budget once double
    # buffering and the f32 scratch are counted (the matmul ones more than
    # the v5e compiler's scoped limit, which refused them)
    from repro.codesign import SCOPED_VMEM_LIMIT
    from repro.kernels.flash_attention.ops import flash_vmem_bytes

    def need(cfg):
        if space is MATMUL_SPACE:
            return matmul_vmem_bytes(cfg)
        return flash_vmem_bytes(cfg, shape[2])

    assert need(cand) > (
        SCOPED_VMEM_LIMIT if space is MATMUL_SPACE else DEFAULT_VMEM_BUDGET
    )
    cfg = space.legalize(cand, shape)
    assert need(cfg) <= DEFAULT_VMEM_BUDGET <= SCOPED_VMEM_LIMIT
    _assert_legal(space, shape, cfg)
    assert all(t % 128 == 0 for t in cfg)


def test_fit_tiles_refuses_what_cannot_shrink():
    from repro.codesign import fit_tiles

    # the largest tile shrinks first, the later one on ties
    area = lambda t: t[0] * t[1]  # noqa: E731
    assert fit_tiles((512, 256), (512, 256), area, 256 * 128) == (256, 128)
    with pytest.raises(ValueError, match="cannot shrink"):
        fit_tiles((128, 128), (128, 128), area, 1)


def test_ssd_legalize_is_binding():
    # the mapper hint is intentionally ignored: policy = largest pow2
    # chunk under R3 (exactly the pre-refactor plan_chunk rule)
    for hint in [(0,), (64,), (1024,)]:
        assert SSD_SCAN_SPACE.legalize(hint, (64, 128)) == (512,)
    # tiny budget degenerates to the 64 floor
    assert SSD_SCAN_SPACE.legalize((0,), (64, 128), vmem_budget=1024) == (64,)


@pytest.mark.parametrize("shape", [(64, 128), (64, 64), (128, 256)])
def test_ssd_chunk_is_the_largest_within_the_compiler_budget(shape):
    """The planned chunk's compiler VMEM (padded, double-buffered blocks
    plus the (cl, cl) values) fits the one budget; twice the chunk does
    not."""
    from repro.kernels.ssd_scan.ops import ssd_vmem_bytes

    hp, n = shape
    (cl,) = SSD_SCAN_SPACE.legalize((0,), shape)
    assert ssd_vmem_bytes(cl, hp, n) <= DEFAULT_VMEM_BUDGET
    assert ssd_vmem_bytes(2 * cl, hp, n) > DEFAULT_VMEM_BUDGET
    # six (512, <=128)-lane blocks -- the (cl, 1) decay columns take whole
    # 128-lane tiles too -- the (1, cl) row padded to 8 sublanes, the
    # (n, hp) state, all double-buffered; plus two (cl, cl) f32 values
    assert ssd_vmem_bytes(512, 64, 64) == (
        2 * 4 * (512 * 128 * 6 + 8 * 512 + 64 * 128) + 2 * 4 * 512 * 512
    )


def test_plan_search_on_odd_shapes_yields_legal_tiles():
    # full plan() path (search included) on shapes the MXU constraints
    # can only satisfy via the full-dim escape hatch
    for shape in [(300, 200, 100), (1, 257, 33)]:
        p = plan(MATMUL_SPACE, shape, store=ResultStore())
        _assert_legal(MATMUL_SPACE, shape, p.config)


# ------------------------------------------------------------------ #
# unified VMEM budget convention
# ------------------------------------------------------------------ #
def test_vmem_budget_unified():
    assert DEFAULT_VMEM_BUDGET == V8
    for space in (MATMUL_SPACE, FLASH_ATTENTION_SPACE, SSD_SCAN_SPACE):
        assert space.vmem_budget == DEFAULT_VMEM_BUDGET
        assert space.arch().clusters[-1].memory_bytes == DEFAULT_VMEM_BUDGET
    # the ssd wrapper's kwarg default follows the constant too
    import inspect

    sig = inspect.signature(plan_chunk.__wrapped__)
    assert sig.parameters["vmem_budget"].default == DEFAULT_VMEM_BUDGET


def test_vmem_budget_parameter_reaches_legality():
    # a smaller budget must shrink the planned ssd chunk
    assert plan_chunk(64, 128, vmem_budget=1 << 20) < plan_chunk(64, 128)


# ------------------------------------------------------------------ #
# plan cache: warm queries answer from the store without a search
# ------------------------------------------------------------------ #
def test_warm_plan_query_skips_search():
    store = ResultStore()
    reset_planner_stats()
    p1 = plan(MATMUL_SPACE, (128, 128, 128), store=store)
    s = planner_stats()
    assert (s["plan_searches"], s["plan_store_hits"]) == (1, 0)
    p2 = plan(MATMUL_SPACE, (128, 128, 128), store=store)
    s = planner_stats()
    assert (s["plan_searches"], s["plan_store_hits"]) == (1, 1)
    assert p2.source == "store" and p2.config == p1.config
    assert p2.cost is not None and p2.cost.latency_cycles == p1.cost.latency_cycles


def test_plan_cache_round_trips_disk(tmp_path):
    store = ResultStore(tmp_path)
    p1 = plan(MATMUL_SPACE, (256, 128, 384), store=store)
    store.flush()
    reset_planner_stats()
    p2 = plan(MATMUL_SPACE, (256, 128, 384), store=ResultStore(tmp_path))
    s = planner_stats()
    assert s["plan_searches"] == 0 and s["plan_store_hits"] == 1
    assert p2.source == "store" and p2.config == p1.config


def test_plan_key_is_constraints_and_model_inclusive():
    cons = MATMUL_SPACE.constraints((128, 128, 128))
    m = TimeloopLikeModel()
    k1 = codesign.plan_space_key(MATMUL_SPACE, cons, "heuristic", 400, "latency", m)
    k2 = codesign.plan_space_key(MATMUL_SPACE, cons, "heuristic", 100, "latency", m)
    k3 = codesign.plan_space_key(
        MATMUL_SPACE, mxu_aligned(["m", "n", "k"], 256), "heuristic", 400,
        "latency", m,
    )
    mc = TimeloopLikeModel().set_calibration(CalibrationScale(2.0, 1, "t"))
    k4 = codesign.plan_space_key(MATMUL_SPACE, cons, "heuristic", 400, "latency", mc)
    assert len({k1, k2, k3, k4}) == 4


# ------------------------------------------------------------------ #
# fallback ledger + narrow exception discipline
# ------------------------------------------------------------------ #
class _Mac3Space(KernelSpace):
    """Non-conformable with the timeloop model (unit op mac3): every
    search raises ValueError -- the EXPECTED failure class."""

    name = "_test_mac3"
    decode_dims = ("i", "j")

    def problem(self, shape):
        return Problem.mttkrp(*shape)

    def legalize(self, config, shape, vmem_budget=None):
        I, J, _K, _L = shape
        return (repair_tile(config[0], I, 64), repair_tile(config[1], J, 64))


class _BrokenSpace(_Mac3Space):
    name = "_test_broken"

    def problem(self, shape):
        raise KeyError("not a search failure")


def test_expected_search_failure_counts_fallback():
    reset_planner_stats()
    p = plan(_Mac3Space(), (64, 64, 64, 64), store=ResultStore(), predict=False)
    s = planner_stats()
    assert s["plan_fallbacks"] == 1
    assert p.source == "fallback" and p.fallback
    _assert_legal(_Mac3Space(), (64, 64, 64, 64), p.config)


def test_unexpected_errors_propagate():
    # the historical bare `except Exception` would have swallowed this
    with pytest.raises(KeyError):
        plan(_BrokenSpace(), (64, 64, 64, 64), store=ResultStore(), predict=False)


def test_fallback_plan_is_cached_with_flag():
    store = ResultStore()
    plan(_Mac3Space(), (64, 64, 64, 64), store=store, predict=False)
    reset_planner_stats()
    p = plan(_Mac3Space(), (64, 64, 64, 64), store=store, predict=False)
    assert planner_stats()["plan_searches"] == 0
    assert p.source == "store" and p.fallback


# ------------------------------------------------------------------ #
# calibration table
# ------------------------------------------------------------------ #
def test_calibration_table_round_trip(tmp_path):
    path = tmp_path / "cal.json"
    t = CalibrationTable(path)
    t.record("matmul", (128, 128, 128), (128, 128, 128), ("timeloop_like", "mac2"),
             predicted_cycles=1e6, frequency_hz=1e9, measured_s=2e-3)
    t.record("matmul", (256, 256, 256), (128, 128, 128), ("timeloop_like", "mac2"),
             predicted_cycles=8e6, frequency_hz=1e9, measured_s=1.6e-2)
    assert t.flush() == 2
    t2 = CalibrationTable(path)
    assert len(t2.rows) == 2 and t2.corrupt_payloads == 0
    sc = t2.scale_for("matmul")
    # both rows have measured/predicted = 2.0 exactly -> geomean 2.0
    assert sc.n_records == 2 and sc.scale == pytest.approx(2.0)
    rep = t2.model_error_report("matmul")
    assert len(rep) == 2
    assert all(r["abs_error_pct"] == pytest.approx(0.0, abs=1e-9) for r in rep)


def test_calibration_table_rerecord_replaces():
    t = CalibrationTable()
    for ms in (1e-3, 4e-3):
        t.record("k", (8,), (8,), ("m",), 1e6, 1e9, ms)
    assert len(t.rows) == 1 and t.rows[0]["measured_s"] == 4e-3


def test_calibration_table_tolerates_corruption(tmp_path):
    path = tmp_path / "cal.json"
    path.write_text("{ not json !!")
    t = CalibrationTable(path)
    assert t.rows == [] and t.corrupt_payloads == 1
    path.write_text(json.dumps({"version": 999, "rows": []}))
    t = CalibrationTable(path)
    assert t.rows == [] and t.version_mismatches == 1
    # bad rows inside a good payload are dropped, good ones kept
    good = {"kernel": "k", "shape": [8], "config": [8], "model": ["m"],
            "predicted_cycles": 1e6, "frequency_hz": 1e9,
            "predicted_s": 1e-3, "measured_s": 2e-3, "interpret": True,
            "repeats": 1, "ts": 0.0}
    path.write_text(json.dumps(
        {"version": 1, "rows": [good, {"kernel": 5}, "junk"]}
    ))
    t = CalibrationTable(path)
    assert len(t.rows) == 1 and t.corrupt_payloads == 2


def test_calibration_scale_validates():
    with pytest.raises(ValueError):
        CalibrationScale(0.0)
    with pytest.raises(ValueError):
        CalibrationScale(float("nan"))
    with pytest.raises(ValueError):
        CalibrationScale(float("inf"))


def test_scale_never_mixes_interpret_and_device():
    t = CalibrationTable()
    t.record("k", (8,), (8,), ("m",), 1e6, 1e9, 2e-3, interpret=True)
    t.record("k", (8,), (8,), ("m",), 1e6, 1e9, 5e-3, interpret=False)
    assert t.scale_for("k", interpret=True).scale == pytest.approx(2.0)
    assert t.scale_for("k", interpret=False).scale == pytest.approx(5.0)
    assert t.scale_for("other") is None


# ------------------------------------------------------------------ #
# calibrated cost models
# ------------------------------------------------------------------ #
@pytest.mark.parametrize(
    "model_cls", [TimeloopLikeModel, MaestroLikeModel, TPURooflineModel]
)
def test_calibrated_store_key_differs_and_rescales(model_cls):
    problem, mapping, arch = MATMUL_SPACE.canonical_mapping(
        (256, 256, 256), (128, 128, 128)
    )
    raw = model_cls()
    cal = model_cls().set_calibration(CalibrationScale(2.5, 1, "interpret:t"))
    assert raw.store_key_parts() != cal.store_key_parts()
    c_raw = raw.evaluate(problem, mapping, arch)
    c_cal = cal.evaluate(problem, mapping, arch)
    assert c_cal.latency_cycles == pytest.approx(2.5 * c_raw.latency_cycles)
    assert c_cal.energy_pj == c_raw.energy_pj
    assert c_cal.breakdown["calibration_scale"] == 2.5
    # admission invariant survives: bound scales by the same factor
    lb_raw = raw.lower_bound(problem, mapping, arch)
    lb_cal = cal.lower_bound(problem, mapping, arch)
    assert lb_cal[0] == pytest.approx(2.5 * lb_raw[0])
    assert lb_cal[0] <= c_cal.latency_cycles * (1 + 1e-12)
    # vectorized fast paths STAY available while calibrated (the scale is
    # a final multiply inside the batch programs) and match the calibrated
    # scalar path bit for bit
    assert cal.lower_bound_batch_fn(problem, arch) is not None
    assert cal.batch_cost_terms(problem, arch) is not None
    from repro.core.cost.analysis import get_context
    from repro.core.mapping import mapping_signature

    sig = mapping_signature(mapping, get_context(problem, arch).dims)
    (c_batch,) = cal.evaluate_signature_batch(problem, arch, [sig])
    assert c_batch.latency_cycles == c_cal.latency_cycles
    assert c_batch.energy_pj == c_cal.energy_pj
    assert c_batch.breakdown == c_cal.breakdown
    # uncalibrating restores the raw behavior exactly
    cal.set_calibration(None)
    assert cal.store_key_parts() == raw.store_key_parts()
    assert cal.evaluate(problem, mapping, arch).latency_cycles == c_raw.latency_cycles


def test_set_calibration_rejects_bad_scales():
    class _Bad:
        scale = -1.0

        def key_parts(self):
            return ()

    with pytest.raises(ValueError):
        TimeloopLikeModel().set_calibration(_Bad())


def test_calibrated_plan_keys_apart_in_store():
    store = ResultStore()
    raw = TimeloopLikeModel()
    cal = TimeloopLikeModel().set_calibration(CalibrationScale(3.0, 1, "t"))
    p_raw = plan(MATMUL_SPACE, (128, 128, 128), store=store, model=raw)
    reset_planner_stats()
    p_cal = plan(MATMUL_SPACE, (128, 128, 128), store=store, model=cal)
    # different model key parts -> different plan space key -> fresh search
    assert planner_stats()["plan_searches"] == 1
    assert p_cal.cost.latency_cycles == pytest.approx(3.0 * p_raw.cost.latency_cycles)


# ------------------------------------------------------------------ #
# measurement loop (interpret mode, CPU -- the CI configuration)
# ------------------------------------------------------------------ #
def test_calibrate_kernel_end_to_end():
    table = codesign.calibrate_kernel(
        MATMUL_SPACE, [(128, 128, 128)], store=ResultStore(), repeats=1,
    )
    assert len(table.rows) == 1
    row = table.rows[0]
    assert row["kernel"] == "matmul" and row["measured_s"] > 0
    sc = table.scale_for("matmul")
    assert sc is not None and sc.scale > 0
    rep = table.model_error_report()
    assert len(rep) == 1 and rep[0]["abs_error_pct"] == pytest.approx(0.0, abs=1e-6)
    # closing the loop: the distilled scale calibrates a model
    m = TimeloopLikeModel().set_calibration(sc)
    assert "calibrated" in m.store_key_parts()


# ------------------------------------------------------------------ #
# canonical mapping sanity
# ------------------------------------------------------------------ #
def test_canonical_mapping_rejects_non_divisor():
    with pytest.raises(ValueError):
        MATMUL_SPACE.canonical_mapping((256, 256, 256), (100, 128, 128))


def test_round_up():
    assert round_up(1, 128) == 128
    assert round_up(128, 128) == 128
    assert round_up(129, 128) == 256


def test_registry_resolves_all_kernel_spaces():
    spaces = codesign.all_spaces()
    for name in ("matmul", "flash_attention", "ssd_scan"):
        assert name in spaces
        assert codesign.get_space(name) is spaces[name]
