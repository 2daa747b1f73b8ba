"""Fully-batched admission pipeline + persistent result store.

The vectorized lower bound must be BIT-identical to the scalar bound for
every cost model (values, admit/reject decisions, and engine counters),
the single-dispatch fused jax admit+score program must be bit-identical
to the numpy and scalar paths (costs, decisions, counters) while issuing
exactly ONE jitted dispatch per miss-batch, the engine-level probe warm
start must never change results, and the cross-search ResultStore must
round-trip Costs exactly, survive corrupt or version-mismatched disk
files, evict LRU entries at its per-space cap, keep concurrent flushes
lossless up to that cap, and leave search outputs unchanged on warm runs.
"""

import dataclasses
import json
import math
import random

import numpy as np
import pytest

from repro.core.architecture import (
    cloud_accelerator,
    edge_accelerator,
    tpu_v5e_pod,
)
from repro.core.cost import (
    EvaluationEngine,
    MaestroLikeModel,
    ResultStore,
    TimeloopLikeModel,
    TPURooflineModel,
)
from repro.core.cost.analysis import get_context
from repro.core.cost.store import STORE_VERSION, space_key
from repro.core.optimizer import union_opt
from repro.core.mapspace import MapSpace
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


# --------------------------------------------------------------------- #
# Batched lower bound == scalar lower bound
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("problem", [GEMM, CONV], ids=["gemm", "conv"])
@pytest.mark.parametrize("model_cls", MODELS)
@pytest.mark.parametrize(
    "mk_arch",
    [edge_accelerator, cloud_accelerator, lambda: tpu_v5e_pod(1, 2, 2)],
    ids=["edge", "cloud", "tpu_pod"],
)
def test_lower_bound_batch_bit_identical(problem, model_cls, mk_arch):
    """lower_bound_batch_fn == lower_bound_fn per signature, bit for bit,
    for all three cost models on every architecture family."""
    arch = mk_arch()
    cm = model_cls()
    ctx = get_context(problem, arch)
    space = MapSpace(problem, arch)
    rng = random.Random(3)
    sigs = [space.random_genome(rng).signature(ctx.dims) for _ in range(60)]
    batch_fn = cm.lower_bound_batch_fn(problem, arch)
    assert batch_fn is not None
    lb = batch_fn(sigs)
    assert lb is not None
    cyc, en = lb
    assert cyc.dtype == np.float64 and en.dtype == np.float64
    scalar_fn = cm.lower_bound_fn(problem, arch)
    for i, sig in enumerate(sigs):
        sc, se = scalar_fn(sig)
        assert float(sc) == cyc[i]
        assert float(se) == en[i]


def test_lower_bound_batch_hypothesis_equivalence():
    """Randomized GEMM shapes x seeds: batched bound == scalar bound."""
    pytest.importorskip(
        "hypothesis", reason="hypothesis not installed (see requirements-dev.txt)"
    )
    from hypothesis import given, settings, strategies as st

    sizes = st.sampled_from([1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64])

    @given(sizes, sizes, sizes, st.integers(0, 2**20))
    @settings(max_examples=25, deadline=None)
    def check(M, N, K, seed):
        problem = Problem.gemm(M, N, K, word_bytes=1)
        arch = cloud_accelerator()
        ctx = get_context(problem, arch)
        space = MapSpace(problem, arch)
        rng = random.Random(seed)
        sigs = [space.random_genome(rng).signature(ctx.dims) for _ in range(6)]
        for cm in (TimeloopLikeModel(), MaestroLikeModel(), TPURooflineModel()):
            lb = cm.lower_bound_batch_fn(problem, arch)(sigs)
            assert lb is not None
            scalar_fn = cm.lower_bound_fn(problem, arch)
            for i, sig in enumerate(sigs):
                sc, se = scalar_fn(sig)
                assert float(sc) == lb[0][i]
                assert float(se) == lb[1][i]

    check()


def test_lower_bound_batch_jax_matches_numpy():
    """The jitted JAX lower-bound core produces the same arrays as numpy
    (device-resident StackedBatch shared with the traffic program)."""
    pytest.importorskip("jax")
    arch = cloud_accelerator()
    ctx = get_context(GEMM, arch)
    space = MapSpace(GEMM, arch)
    rng = random.Random(7)
    sigs = [space.random_genome(rng).signature(ctx.dims) for _ in range(13)]
    lb_np = ctx.lower_bound_batch(sigs, backend="numpy")
    sb = ctx.stacked_batch(sigs)
    lb_jax = ctx.lower_bound_batch(backend="jax", stacked=sb)
    assert not ctx._jax_failed
    assert np.array_equal(lb_np[0], lb_jax[0])
    assert np.array_equal(lb_np[1], lb_jax[1])
    # the uploaded matrices stay on the handle for the scoring pass
    assert sb.dev is not None
    bt_dev = ctx.signature_traffic_batch(backend="jax", stacked=sb, select=[0, 2, 5])
    bt_np = ctx.signature_traffic_batch([sigs[i] for i in (0, 2, 5)], backend="numpy")
    assert np.array_equal(bt_dev.compute_cycles, bt_np.compute_cycles)
    for rd, rn in zip(bt_dev.rows, bt_np.rows):
        for a, b in zip(rd, rn):
            assert np.array_equal(a, b)


def test_admit_decisions_and_counters_match_scalar_path():
    """Full searches through the batched admission filter == the scalar
    per-candidate filter: same best mapping/cost AND same counters, across
    the mapper x cost-model matrix."""
    arch = cloud_accelerator()
    matrix = [
        ("random", "timeloop", {"samples": 400}),
        ("random", "maestro", {"samples": 400}),
        ("exhaustive", "timeloop", {"max_mappings": 600}),
        ("exhaustive", "maestro", {"max_mappings": 600}),
        ("decoupled", "timeloop", {"offchip_samples": 80, "onchip_samples": 120}),
        ("heuristic", "timeloop", {}),
    ]
    for mapper, cm, kw in matrix:
        a = union_opt(GEMM, arch, mapper=mapper, cost_model=cm,
                      engine_backend="numpy", **kw)
        b = union_opt(GEMM, arch, mapper=mapper, cost_model=cm,
                      engine_backend="none", **kw)
        assert a.cost.edp == b.cost.edp, (mapper, cm)
        assert a.mapping.to_dict() == b.mapping.to_dict(), (mapper, cm)
        for attr in ("evaluated", "analyzed", "cache_hits", "pruned", "store_hits"):
            assert getattr(a.search, attr) == getattr(b.search, attr), (mapper, cm, attr)


def test_engine_probe_param_identical_results():
    """The engine-level probe warm start changes counters, never results."""
    arch = cloud_accelerator()
    cm = TimeloopLikeModel()
    space = MapSpace(GEMM, arch)
    rng = random.Random(5)
    batch = [space.random_genome(rng) for _ in range(64)]
    plain = EvaluationEngine(cm, GEMM, arch, metric="edp")
    probed = EvaluationEngine(cm, GEMM, arch, metric="edp")
    want = plain.evaluate_batch(batch, incumbent=math.inf)
    got = probed.evaluate_batch(batch, incumbent=math.inf, probe=8)
    # no incumbent given: plain evaluates everything; probed may prune
    # candidates that provably cannot beat the head's best -- every
    # non-None cost must agree, and the head must be fully scored
    assert all(c is not None for c in got[:8])
    for a, b in zip(got, want):
        if a is not None:
            assert _costs_equal(a, b)
    assert probed.stats.pruned > 0  # the warm start engaged the filter


# --------------------------------------------------------------------- #
# Single-dispatch fused admit+score (jax backend)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("model_cls", MODELS)
def test_fused_single_dispatch_per_miss_batch(model_cls):
    """Under engine_backend='jax', ONE jitted dispatch covers admit+score
    for a whole miss-batch (dispatch-count probe on the context), and the
    resulting Costs/decisions are served without further array programs."""
    pytest.importorskip("jax")
    arch = cloud_accelerator()
    cm = model_cls()
    ctx = get_context(GEMM, arch)
    space = MapSpace(GEMM, arch)
    rng = random.Random(9)
    batch = [space.random_genome(rng) for _ in range(64)]
    eng = EvaluationEngine(cm, GEMM, arch, metric="edp", backend="jax")
    inc = eng.evaluate(batch[0]).metric("edp")
    before = ctx.jax_dispatches
    costs = eng.evaluate_batch(batch, incumbent=inc)
    assert not ctx._jax_failed
    assert ctx.jax_dispatches - before == 1
    assert eng.stats.fused_dispatches == 1
    assert any(c is not None for c in costs)
    # a second batch reuses the jitted program: still one dispatch each
    batch2 = [space.random_genome(rng) for _ in range(32)]
    eng.evaluate_batch(batch2, incumbent=inc)
    assert ctx.jax_dispatches - before == 2
    assert eng.stats.fused_dispatches == 2


def test_fused_jax_matches_numpy_and_scalar_searches():
    """Full searches under the fused jax single-dispatch pipeline produce
    bit-identical best costs, mappings, AND counters vs the numpy and
    scalar engines, across the mapper x cost-model matrix."""
    pytest.importorskip("jax")
    arch = cloud_accelerator()
    matrix = [
        ("random", "timeloop", {"samples": 400}),
        ("random", "maestro", {"samples": 400}),
        ("exhaustive", "timeloop", {"max_mappings": 600}),
        ("genetic", "maestro", {"generations": 6}),
        ("heuristic", "timeloop", {}),
        ("decoupled", "timeloop", {"offchip_samples": 80, "onchip_samples": 120}),
    ]
    for mapper, cm, kw in matrix:
        a = union_opt(GEMM, arch, mapper=mapper, cost_model=cm,
                      engine_backend="jax", **kw)
        b = union_opt(GEMM, arch, mapper=mapper, cost_model=cm,
                      engine_backend="numpy", **kw)
        c = union_opt(GEMM, arch, mapper=mapper, cost_model=cm,
                      engine_backend="none", **kw)
        assert not get_context(GEMM, arch)._jax_failed
        assert a.cost.edp == b.cost.edp == c.cost.edp, (mapper, cm)
        assert _costs_equal(a.cost, b.cost) and _costs_equal(b.cost, c.cost)
        assert (
            a.mapping.to_dict() == b.mapping.to_dict() == c.mapping.to_dict()
        ), (mapper, cm)
        for attr in (
            "evaluated", "analyzed", "cache_hits", "pruned", "store_hits",
            "considered",
        ):
            assert (
                getattr(a.search, attr)
                == getattr(b.search, attr)
                == getattr(c.search, attr)
            ), (mapper, cm, attr)
        assert a.search.fused_dispatches > 0, (mapper, cm)


def test_fused_tpu_roofline_on_pod():
    """The roofline model's own admission bound drives the fused program
    on a TPU-pod architecture, bit-identically to the numpy flow."""
    pytest.importorskip("jax")
    arch = tpu_v5e_pod(1, 2, 2)
    a = union_opt(GEMM, arch, mapper="random", cost_model="tpu_roofline",
                  engine_backend="jax", samples=300)
    b = union_opt(GEMM, arch, mapper="random", cost_model="tpu_roofline",
                  engine_backend="numpy", samples=300)
    assert not get_context(GEMM, arch)._jax_failed
    assert _costs_equal(a.cost, b.cost)
    assert a.mapping.to_dict() == b.mapping.to_dict()
    assert a.search.pruned == b.search.pruned
    assert a.search.analyzed == b.search.analyzed


# --------------------------------------------------------------------- #
# ResultStore
# --------------------------------------------------------------------- #
def test_store_roundtrip_and_flush(tmp_path):
    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    ctx = get_context(GEMM, arch)
    space = MapSpace(GEMM, arch)
    rng = random.Random(0)
    sigs = [space.random_genome(rng).signature(ctx.dims) for _ in range(5)]
    skey = space_key(cm, GEMM, arch)
    store = ResultStore(tmp_path / "store")
    costs = {sig: cm.evaluate_signature(GEMM, arch, sig) for sig in sigs}
    for sig, c in costs.items():
        store.put(skey, sig, c)
    assert store.puts == len(costs)
    assert store.flush() == len(costs)
    # a fresh instance reads the disk tier lazily and returns EXACT Costs
    fresh = ResultStore(tmp_path / "store")
    for sig, c in costs.items():
        got = fresh.get(skey, sig)
        assert got is not None and _costs_equal(got, c)
    assert fresh.hits == len(costs) and fresh.disk_loaded == len(costs)
    assert fresh.get(skey, ("missing",)) is None
    assert fresh.misses == 1


def test_store_version_mismatch_and_corruption(tmp_path):
    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    ctx = get_context(GEMM, arch)
    g = MapSpace(GEMM, arch).random_genome(random.Random(1))
    sig = g.signature(ctx.dims)
    skey = space_key(cm, GEMM, arch)
    cost = cm.evaluate_signature(GEMM, arch, sig)

    store = ResultStore(tmp_path)
    store.put(skey, sig, cost)
    store.flush()
    f = tmp_path / f"{skey}.json"
    assert f.exists()

    # version mismatch: entries are discarded (counted), not raised
    payload = json.loads(f.read_text())
    payload["version"] = STORE_VERSION + 1
    f.write_text(json.dumps(payload))
    stale = ResultStore(tmp_path)
    assert stale.get(skey, sig) is None
    assert stale.corrupt == 1
    # and the space is rewritten at the current version on the next flush
    stale.put(skey, sig, cost)
    stale.flush()
    assert json.loads(f.read_text())["version"] == STORE_VERSION

    # truncated/garbled file: ignored, store starts fresh
    f.write_text("{\"version\": this is not json")
    broken = ResultStore(tmp_path)
    assert broken.get(skey, sig) is None
    assert broken.corrupt == 1
    broken.put(skey, sig, cost)
    broken.flush()
    again = ResultStore(tmp_path)
    assert _costs_equal(again.get(skey, sig), cost)


def _sig_pool(problem, arch, n, seed=0):
    ctx = get_context(problem, arch)
    space = MapSpace(problem, arch)
    rng = random.Random(seed)
    sigs, seen = [], set()
    while len(sigs) < n:
        s = space.random_genome(rng).signature(ctx.dims)
        if s not in seen:
            seen.add(s)
            sigs.append(s)
    return sigs


def test_store_eviction_cap_and_lru_order(tmp_path):
    """The per-space cap is respected in both tiers, eviction is LRU
    (a ``get`` refreshes recency), and flush compacts the disk tier."""
    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    skey = space_key(cm, GEMM, arch)
    sigs = _sig_pool(GEMM, arch, 8)
    costs = {s: cm.evaluate_signature(GEMM, arch, s) for s in sigs}

    store = ResultStore(tmp_path / "s", max_entries_per_space=4)
    for s in sigs[:4]:
        store.put(skey, s, costs[s])
    # touch the OLDEST entry so it becomes most recent
    assert store.get(skey, sigs[0]) is not None
    # two more puts evict the two least-recently-used (sigs[1], sigs[2])
    store.put(skey, sigs[4], costs[sigs[4]])
    store.put(skey, sigs[5], costs[sigs[5]])
    assert store.evicted == 2
    assert store.get(skey, sigs[1]) is None
    assert store.get(skey, sigs[2]) is None
    assert store.get(skey, sigs[0]) is not None  # survived: recently used
    assert store.flush() == 4  # disk tier holds exactly the cap

    fresh = ResultStore(tmp_path / "s", max_entries_per_space=4)
    kept = [s for s in sigs if fresh.get(skey, s) is not None]
    assert len(kept) == 4
    assert sigs[0] in kept and sigs[4] in kept and sigs[5] in kept

    # an uncapped reader sees the same 4 surviving entries
    uncapped = ResultStore(tmp_path / "s")
    assert sum(uncapped.get(skey, s) is not None for s in sigs) == 4


def test_store_concurrent_flush_union_of_survivors(tmp_path):
    """Two writers sharing a directory: flush unions the disk tier with
    the in-memory view before compacting, so below the cap NOTHING from
    either writer is lost, and above it exactly ``cap`` entries survive
    with the other writer's prior entries ranked least recent."""
    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    skey = space_key(cm, GEMM, arch)
    sigs = _sig_pool(GEMM, arch, 10)
    costs = {s: cm.evaluate_signature(GEMM, arch, s) for s in sigs}

    # both writers opened before either flushes (lazy loads see no file)
    a = ResultStore(tmp_path / "s", max_entries_per_space=8)
    b = ResultStore(tmp_path / "s", max_entries_per_space=8)
    a.get(skey, sigs[0])  # force lazy load of the (absent) disk tier
    b.get(skey, sigs[0])
    for s in sigs[:4]:
        a.put(skey, s, costs[s])
    for s in sigs[4:8]:
        b.put(skey, s, costs[s])
    a.flush()
    b.flush()  # must union a's flushed entries, not clobber them
    merged = ResultStore(tmp_path / "s")
    assert sum(merged.get(skey, s) is not None for s in sigs[:8]) == 8

    # a third writer pushes the union past the cap: the oldest (on-disk,
    # i.e. other writers') entries are compacted away, newest survive
    c = ResultStore(tmp_path / "s", max_entries_per_space=8)
    for s in sigs[8:]:
        c.put(skey, s, costs[s])
    c.flush()
    final = ResultStore(tmp_path / "s")
    survivors = [s for s in sigs if final.get(skey, s) is not None]
    assert len(survivors) == 8
    assert sigs[8] in survivors and sigs[9] in survivors


def test_store_multi_space_flush_concurrent_writers_union(tmp_path):
    """Multi-space flush batching: one flush call writes ALL dirty spaces
    in a single atomic pass (one lock acquisition), and concurrent
    writers whose dirty sets cover DIFFERENT spaces -- plus one shared
    space -- still union losslessly on disk."""
    import threading

    arch_e, arch_c = edge_accelerator(), cloud_accelerator()
    cm = TimeloopLikeModel()
    key_e = space_key(cm, GEMM, arch_e)
    key_c = space_key(cm, GEMM, arch_c)
    key_conv = space_key(cm, CONV, arch_e)
    sigs_e = _sig_pool(GEMM, arch_e, 6)
    sigs_c = _sig_pool(GEMM, arch_c, 6)
    sigs_v = _sig_pool(CONV, arch_e, 6)
    ce = {s: cm.evaluate_signature(GEMM, arch_e, s) for s in sigs_e}
    cc = {s: cm.evaluate_signature(GEMM, arch_c, s) for s in sigs_c}
    cv = {s: cm.evaluate_signature(CONV, arch_e, s) for s in sigs_v}

    a = ResultStore(tmp_path / "s")
    b = ResultStore(tmp_path / "s")
    # writer a: edge space + half the shared conv space
    for s in sigs_e:
        a.put(key_e, s, ce[s])
    for s in sigs_v[:3]:
        a.put(key_conv, s, cv[s])
    # writer b: cloud space + the other half of the shared conv space
    for s in sigs_c:
        b.put(key_c, s, cc[s])
    for s in sigs_v[3:]:
        b.put(key_conv, s, cv[s])
    assert len(a._dirty) == 2 and len(b._dirty) == 2

    errs = []

    def flush(st):
        try:
            st.flush()
        except Exception as e:  # pragma: no cover - diagnostic
            errs.append(e)

    ta, tb = threading.Thread(target=flush, args=(a,)), threading.Thread(
        target=flush, args=(b,)
    )
    ta.start(), tb.start()
    ta.join(), tb.join()
    assert not errs
    assert not a._dirty and not b._dirty

    merged = ResultStore(tmp_path / "s")
    assert all(merged.get(key_e, s) is not None for s in sigs_e)
    assert all(merged.get(key_c, s) is not None for s in sigs_c)
    assert all(merged.get(key_conv, s) is not None for s in sigs_v)
    # flush with no dirty spaces is a cheap no-op
    assert a.flush() == 0


def test_store_space_key_canonicalizes_numpy_scalars():
    """numpy scalar arch attrs must not fork the space key: repr() of
    np.float64(x) differs from repr(x) on numpy>=2, which silently
    orphaned disk entries across writers."""
    base = edge_accelerator()
    k_base = space_key(TimeloopLikeModel(), GEMM, base)

    npy = edge_accelerator()
    npy.attrs["word_bytes"] = np.int64(npy.attrs["word_bytes"])
    npy.attrs["extra_bw"] = np.float64(2.0)
    plain = edge_accelerator()
    plain.attrs["extra_bw"] = 2.0
    assert space_key(TimeloopLikeModel(), GEMM, npy) == space_key(
        TimeloopLikeModel(), GEMM, plain
    )

    # numpy scalar fill_bandwidth (incl. the inf encoding) is canonical too
    npy_bw = edge_accelerator()
    npy_bw.clusters = [
        dataclasses.replace(c, fill_bandwidth=np.float64(c.fill_bandwidth))
        for c in npy_bw.clusters
    ]
    assert space_key(TimeloopLikeModel(), GEMM, npy_bw) == k_base

    # different VALUES still separate
    other = edge_accelerator()
    other.attrs["extra_bw"] = 3.0
    assert space_key(TimeloopLikeModel(), GEMM, other) != space_key(
        TimeloopLikeModel(), GEMM, plain
    )


def test_store_space_key_separates_configurations():
    arch = edge_accelerator()
    k1 = space_key(TimeloopLikeModel(), GEMM, arch)
    assert k1 == space_key(TimeloopLikeModel(), GEMM, arch)  # deterministic
    assert k1 != space_key(MaestroLikeModel(), GEMM, arch)
    assert k1 != space_key(TimeloopLikeModel(), CONV, arch)
    assert k1 != space_key(TimeloopLikeModel(), GEMM, cloud_accelerator())
    assert k1 != space_key(TimeloopLikeModel("mac3"), GEMM, arch)  # model config
    # problem NAME is excluded: identical shapes share the space
    renamed = Problem.gemm(64, 32, 16, name="other_layer", word_bytes=1)
    assert k1 == space_key(TimeloopLikeModel(), renamed, arch)


def test_store_warm_search_identical_outputs(tmp_path):
    """A second (warm) run with the on-disk store reports nonzero store
    hits and byte-identical outputs, across mappers and models."""
    arch = cloud_accelerator()
    for mapper, cm, kw in (
        ("random", "timeloop", {"samples": 300}),
        ("heuristic", "maestro", {}),
    ):
        base = union_opt(GEMM, arch, mapper=mapper, cost_model=cm, **kw)
        cold_store = ResultStore(tmp_path / "s")
        cold = union_opt(GEMM, arch, mapper=mapper, cost_model=cm,
                         result_store=cold_store, **kw)
        cold_store.flush()
        warm_store = ResultStore(tmp_path / "s")
        warm = union_opt(GEMM, arch, mapper=mapper, cost_model=cm,
                         result_store=warm_store, **kw)
        assert warm.search.store_hits > 0, (mapper, cm)
        assert warm.search.analyzed == 0, (mapper, cm)  # nothing re-scored
        for sol in (cold, warm):
            assert sol.cost.edp == base.cost.edp, (mapper, cm)
            assert sol.mapping.to_dict() == base.mapping.to_dict(), (mapper, cm)
        # the submitted-candidate total is warm/cold INVARIANT even though
        # the evaluated/pruned split shifts (store hits bypass admission)
        assert (
            base.search.considered
            == cold.search.considered
            == warm.search.considered
        ), (mapper, cm)
        assert warm.search.considered > 0


def test_search_counters_include_phases_and_store():
    sol = union_opt(GEMM, cloud_accelerator(), mapper="random",
                    cost_model="timeloop", samples=400)
    d = sol.search.stats_dict()
    for key in ("store_hits", "admit_s", "score_s", "considered",
                "fused_dispatches"):
        assert key in d
    assert d["store_hits"] == 0  # no store attached
    assert d["considered"] >= d["candidates"] > 0
    assert d["admit_s"] >= 0.0 and d["score_s"] > 0.0


# --------------------------------------------------------------------- #
# Read-refresh mode + space metadata (nearest-neighbor warm start)
# --------------------------------------------------------------------- #
def test_store_refresh_reloads_foreign_flush(tmp_path):
    """A refresh-mode store sees another process's flush on a get-miss
    (mtime probe + reload + ``reloads`` counter); a plain store does
    not; a store's OWN flush never triggers a self-reload."""
    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    ctx = get_context(GEMM, arch)
    space = MapSpace(GEMM, arch)
    rng = random.Random(0)
    sigs = [space.random_genome(rng).signature(ctx.dims) for _ in range(3)]
    skey = space_key(cm, GEMM, arch)
    costs = {s: cm.evaluate_signature(GEMM, arch, s) for s in sigs}

    reader = ResultStore(tmp_path / "s", refresh=True)
    plain = ResultStore(tmp_path / "s")
    assert reader.get(skey, sigs[0]) is None  # both load the empty tier
    assert plain.get(skey, sigs[0]) is None

    writer = ResultStore(tmp_path / "s")
    writer.put(skey, sigs[0], costs[sigs[0]])
    writer.flush()

    got = reader.get(skey, sigs[0])
    assert got is not None and _costs_equal(got, costs[sigs[0]])
    assert reader.reloads == 1
    assert plain.get(skey, sigs[0]) is None  # no refresh, no reload
    assert plain.reloads == 0

    # a self-flush records its own mtime: no spurious self-reload
    reader.put(skey, sigs[1], costs[sigs[1]])
    reader.flush()
    assert reader.get(skey, sigs[1]) is not None
    assert reader.reloads == 1
    assert reader.stats_dict()["reloads"] == 1


def test_store_space_meta_roundtrip_and_nearest(tmp_path):
    """register_space_meta persists through flush; nearest_space picks
    the content-closest space under the SAME model + arch only, honors
    ``exclude``, and best_in_space returns the space's stored minimum."""
    arch = edge_accelerator()
    cm = TimeloopLikeModel()
    probs = {
        "close": Problem.gemm(64, 64, 48, name="near-a"),
        "far": Problem.gemm(1024, 1024, 1024, name="near-b"),
    }
    query = Problem.gemm(64, 64, 64, name="near-q")
    store = ResultStore(tmp_path / "s")
    keys = {}
    for tag, p in probs.items():
        sp = MapSpace(p, arch)
        ctx = get_context(p, arch)
        skey = space_key(cm, p, arch)
        keys[tag] = skey
        store.register_space_meta(skey, cm, p, arch)
        rng = random.Random(1)
        for _ in range(4):
            sig = sp.random_genome(rng).signature(ctx.dims)
            store.put(skey, sig, cm.evaluate_signature(p, arch, sig))
    store.flush()
    assert (tmp_path / "s" / "_meta.json").exists()

    # a FRESH store (new process) reads the persisted meta registry
    fresh = ResultStore(tmp_path / "s")
    got = fresh.nearest_space(cm, query, arch)
    assert got is not None
    skey, dist = got
    assert skey == keys["close"]
    assert dist >= 0.0
    # exclude the winner: the far space is next
    skey2, dist2 = fresh.nearest_space(cm, query, arch, exclude=keys["close"])
    assert skey2 == keys["far"] and dist2 > dist
    # registration is idempotent
    fresh.register_space_meta(keys["close"], cm, probs["close"], arch)
    assert fresh.space_meta(keys["close"])["macs"] == 64 * 64 * 48

    best = fresh.best_in_space(keys["close"], "edp")
    d = fresh._space(keys["close"])
    assert best == min(c.metric("edp") for c in d.values())
    assert fresh.best_in_space("no-such-space", "edp") is None


def test_store_nearest_space_filters_model_and_arch(tmp_path):
    """Costs from a different cost model or machine are not comparable:
    they must never be offered as a neighbor."""
    store = ResultStore(tmp_path / "s")
    tl, ms = TimeloopLikeModel(), MaestroLikeModel()
    edge, cloud = edge_accelerator(), cloud_accelerator()
    p = Problem.gemm(128, 128, 64, name="nn-f")
    store.register_space_meta(space_key(ms, p, edge), ms, p, edge)
    store.register_space_meta(space_key(tl, p, cloud), tl, p, cloud)
    assert store.nearest_space(tl, p, edge) is None
    store.register_space_meta(space_key(tl, p, edge), tl, p, edge)
    got = store.nearest_space(tl, Problem.gemm(128, 128, 96), edge)
    assert got is not None and got[0] == space_key(tl, p, edge)


def test_store_meta_corruption_tolerated(tmp_path):
    sdir = tmp_path / "s"
    sdir.mkdir()
    (sdir / "_meta.json").write_text("{definitely not json")
    store = ResultStore(sdir)
    p = Problem.gemm(32, 32, 32, name="nn-c")
    cm = TimeloopLikeModel()
    arch = edge_accelerator()
    assert store.nearest_space(cm, p, arch) is None
    assert store.corrupt == 1
    # registration + flush rewrites a clean registry
    store.register_space_meta(space_key(cm, p, arch), cm, p, arch)
    store.flush()
    fresh = ResultStore(sdir)
    assert fresh.nearest_space(cm, Problem.gemm(48, 32, 32), arch) is not None
