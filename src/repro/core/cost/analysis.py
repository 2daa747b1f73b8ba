"""Shared reuse/traffic analysis over an expanded mapping loop nest.

This module turns (Problem, Mapping, Architecture) into per-buffer-level
access counts per data space, using the classic analytical-cost-model
reuse rules (Timeloop/Interstellar style):

  * A buffer at cluster level i holds one temporal tile TT^i per data space.
  * The tile held changes whenever a RELEVANT temporal loop above the
    residency advances (relevant = the loop's dim projects into the data
    space), or when an IRRELEVANT temporal loop that encloses a deeper
    relevant temporal loop advances (re-walk => refetch).
  * Relevant spatial distribution partitions data across instances;
    irrelevant spatial distribution multicasts the same tile (distinct
    parent reads are counted once under ideal multicast; per-instance
    fills are always counted).
  * Output data spaces additionally pay read-modify-write traffic when
    reduction loops enclose their residency.

The analysis is the hot path of every mapper search, so it is organised
around :class:`AnalysisContext`: all (problem, arch)-dependent metadata is
computed once and reused across the thousands of mappings a search
evaluates, and the per-mapping pass runs on the canonical signature (flat
int tuples in problem-dim order) with prefix products -- all-integer, so
results are exactly the ones the naive nested-loop formulation produces.
"""

from __future__ import annotations

import math
import os
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from repro.core.architecture import Architecture
from repro.core.mapping import Mapping, mapping_signature
from repro.core.problem import DataSpace, Problem

# Exactness headroom for the vectorized (float64) batch path: every
# integer-valued product the scalar analysis computes exactly (arbitrary-
# precision Python ints) must stay below 2**53 for the float pipeline to be
# bit-identical. Models reject the batch result (falling back to the scalar
# path) when any guarded quantity reaches this threshold; the extra factor
# of 2 absorbs rounding drift in the guard computation itself.
BATCH_EXACT_LIMIT = float(1 << 52)

# ---------------------------------------------------------------------- #
# Process-global trace registry. Every jitted dispatch registers its
# (program identity, padded batch size) combination here; the set's size
# is therefore the number of DISTINCT XLA traces the process has paid
# for. Shape-generic programs register under their structural
# ShapeClassKey -- content-different contexts in one shape class share a
# single entry per bucket -- while the legacy per-context programs
# register under the context's identity (each context is its own trace).
# Engines sample ``global_trace_count()`` deltas around their dispatches
# to attribute traces to a search (``EngineStats.n_traces``).
# ---------------------------------------------------------------------- #
_GENERIC_PROGRAMS: Dict[tuple, object] = {}
_TRACE_COMBOS: set = set()


def global_trace_count() -> int:
    """Number of distinct (program, padded batch size) jit traces this
    process has dispatched (shape-generic programs count once per shape
    class, not once per context)."""
    return len(_TRACE_COMBOS)


def _record_trace(program_key, padded_batch: int) -> None:
    _TRACE_COMBOS.add((program_key, int(padded_batch)))


class InjectedJaxFault(RuntimeError):
    """The device failure ``UNION_FAULT_JAX`` injects (see
    :meth:`AnalysisContext._ensure_jax`)."""


class CoreCompileError(RuntimeError):
    """XLA refused to compile a fused core (see :class:`CompiledCore`)."""


def device_faults() -> Tuple[type, ...]:
    """The failures a jax engine survives by degrading to numpy through
    its circuit breaker: an injected fault, or a runtime error raised by
    the device while a compiled core runs. Anything else -- an import,
    attribute, type, trace or compile error (:class:`CoreCompileError`),
    or float32 output where float64 is required -- is a defect and
    propagates out of the search."""
    import jax

    return (InjectedJaxFault, jax.errors.JaxRuntimeError)


class CompiledCore:
    """``jax.jit(fn)`` compiled ahead of time once per argument shape.

    XLA reports a compile failure as the same ``JaxRuntimeError`` a
    failing device raises; compiling explicitly lets this wrapper re-raise
    it as :class:`CoreCompileError`, which no degradation handler catches,
    so only running a compiled core can degrade the engine."""

    def __init__(self, fn, donate_argnums=()) -> None:
        import jax

        self._jax = jax
        self._jit = jax.jit(fn, donate_argnums=donate_argnums)
        self._compiled: Dict[tuple, object] = {}

    def lower(self, *args):
        return self._jit.lower(*args)

    def __call__(self, *args):
        leaves, tree = self._jax.tree_util.tree_flatten(args)
        key = (tree, tuple((np.shape(a), str(a.dtype)) for a in leaves))
        exe = self._compiled.get(key)
        if exe is None:
            try:
                exe = self._jit.lower(*args).compile()
            except self._jax.errors.JaxRuntimeError as e:
                raise CoreCompileError(f"XLA cannot compile the core: {e}") from e
            self._compiled[key] = exe
        return exe(*args)


def x64():
    """Context manager that runs the fused cores in float64 (bit-identity
    with the scalar path needs it)."""
    import jax

    return jax.enable_x64(True)


def host_pull(tree):
    """Host copy of a dispatch's device results in ONE blocking transfer.

    ``jax.device_get`` starts the copy of every leaf before it waits, so
    the outputs of one dispatch come back in one round trip, not one per
    array; each point that reads device results pulls through here once."""
    import jax

    return jax.device_get(tree)


def require_float64(arr: np.ndarray) -> np.ndarray:
    """Host copy of a fused core's output, checked to be float64: float32
    output cannot honour the bit-identity contract, so it raises."""
    arr = np.asarray(arr)
    if arr.dtype != np.float64:
        raise RuntimeError(
            f"fused core returned {arr.dtype}, not float64: x64 is off, so "
            "the jax engine cannot match the scalar path bit for bit"
        )
    return arr


def reset_trace_registry() -> None:
    """Drop trace accounting AND the shared generic-program cache (test
    isolation helper; compiled programs are rebuilt on demand)."""
    _TRACE_COMBOS.clear()
    _GENERIC_PROGRAMS.clear()


def exact_divisor(xp, v):
    """A host constant to DIVIDE by inside a traced array program.

    numpy returns the plain value. Under a jax trace the constant is
    wrapped in an optimization barrier so XLA's simplifier cannot fold
    ``x / c`` into ``x * (1/c)`` -- that rewrite is exact only for powers
    of two and would break bit-identity with the host numpy division for
    every other bandwidth/frequency/PE-count constant.
    """
    if xp is np:
        return v
    from jax import lax

    # asarray (not the float64 constructor) so TRACED scalars -- the
    # shape-generic cores divide by parameter values -- pass through the
    # barrier unchanged; host constants take the same asarray path.
    return lax.optimization_barrier(xp.asarray(v, dtype=xp.float64))


def ordered_sum(xp, init, addends):
    """Left-associated ``((init + a0) + a1) + ...`` with numpy semantics.

    On numpy this is the plain accumulation loop. Under a jax trace the
    addends are stacked and summed by ``lax.scan``: the while-loop
    boundary forces every addend (typically an ``int_counts * energy``
    product) to be materialized -- i.e. ROUNDED -- before the sequential
    adds, and XLA cannot fuse producer multiplies into the loop body, so
    the LLVM backend can never contract ``acc + a*b`` into an FMA. This
    is what keeps fractional (energy) accumulations bit-identical between
    the host numpy program and the fused jitted core; integer-valued
    accumulations don't need it (exact under FMA or not).
    """
    if xp is np:
        acc = init
        for a in addends:
            acc = acc + a
        return acc
    if not addends:
        return init
    from jax import lax

    stacked = xp.stack([xp.broadcast_to(a, init.shape) for a in addends])
    out, _ = lax.scan(lambda acc, a: (acc + a, None), init, stacked)
    return out


def ordered_pair_sum(xp, init, pairs):
    """Left-associated ``acc + (x + y)`` accumulation over ``pairs``, with
    the same contraction-proof scan structure as :func:`ordered_sum` (the
    inner ``x + y`` rounds first, exactly as the scalar/numpy programs
    associate their two-term energy addends). Pass ``y = 0.0`` for single
    addends: ``x + 0.0`` is exact for the non-negative energy terms."""
    if xp is np:
        acc = init
        for x, y in pairs:
            acc = acc + (x + y)
        return acc
    if not pairs:
        return init
    from jax import lax

    stacked = xp.stack(
        [
            xp.stack(
                [xp.broadcast_to(x, init.shape), xp.broadcast_to(y, init.shape)]
            )
            for x, y in pairs
        ]
    )
    out, _ = lax.scan(
        lambda acc, p: (acc + (p[0] + p[1]), None), init, stacked
    )
    return out


def batch_projection_footprint(axes, ttf_lvl, xp=np):
    """Batched data-space footprint over one level's tile rows.

    ``axes`` is one entry of :attr:`AnalysisContext.ds_projection_axes`
    (lists of ``(|coeff|, dim_index)`` terms per projection axis);
    ``ttf_lvl`` is the clamped float64 tile matrix ``[B, D]`` of one
    level. Replays the scalar span math (``span = 1 + sum(coeff *
    (tt[j] - 1))``, footprint = product of spans) in the same float-op
    order, so results are exact below :data:`BATCH_EXACT_LIMIT`. The one
    batched form of the projection-span product -- the lower-bound cores
    and the roofline bound all consume it.
    """
    B = ttf_lvl.shape[0]
    foot = xp.ones(B, dtype=xp.float64)
    for ax in axes:
        span = xp.ones(B, dtype=xp.float64)
        for coeff, j in ax:
            span = span + coeff * (ttf_lvl[:, j] - 1.0)
        foot = foot * span
    return foot


class StackedBatch:
    """Stacked (tt, st, perm) matrices for one batch of signatures.

    One StackedBatch is built per engine miss-batch and SHARED between the
    admission stage (:meth:`AnalysisContext.lower_bound_batch`) and the
    scoring stage (:meth:`AnalysisContext.signature_traffic_batch`), so the
    batch is stacked exactly once. On the JAX backend the matrices are
    additionally uploaded to the device once (``dev``) and reused by both
    jitted programs; the scoring stage gathers the admitted subset directly
    on device (``select``), so only the admitted candidates' traffic ever
    returns to host.
    """

    __slots__ = ("tt", "st", "perm", "dev", "devp")

    def __init__(self, tt: np.ndarray, st: np.ndarray, perm: np.ndarray) -> None:
        self.tt = tt
        self.st = st
        self.perm = perm
        self.dev = None  # (tt, st, perm) device arrays, uploaded lazily
        # pow2-PADDED device arrays for the fused full-batch programs
        # (padding runs host-side in numpy before ONE upload: traced
        # pad ops cost ~1ms/dispatch on CPU jax, numpy pads in ~2us)
        self.devp = None

    @property
    def size(self) -> int:
        return int(self.tt.shape[0])


class DsTrafficBatch(NamedTuple):
    """Per-data-space traffic arrays over a signature batch.

    Every array is float64 of shape ``[B, L]`` where ``L`` indexes
    ``AnalysisContext.real_levels``. Values are exact integers as long as
    they stay below :data:`BATCH_EXACT_LIMIT` (the models enforce this).
    """

    fills: np.ndarray
    drains: np.ndarray
    parent_reads: np.ndarray
    parent_writes: np.ndarray
    foot: np.ndarray


class BatchTraffic(NamedTuple):
    """Stacked result of :meth:`AnalysisContext.signature_traffic_batch`.

    The float arrays mirror the tuples :meth:`signature_traffic` returns
    per candidate; ``tt``/``st``/``fans`` are the clamped int64 tile
    matrices (``[B, n_levels, D]``) so model-specific terms (e.g. the
    roofline collective model) can derive further quantities without
    re-stacking the signatures.
    """

    compute_cycles: np.ndarray  # [B] float64
    total_trips: np.ndarray  # [B] float64
    par: np.ndarray  # [B] float64
    inst_at: np.ndarray  # [B, n_levels] float64 (instances above each level)
    tt: np.ndarray  # [B, n_levels, D] int64
    st: np.ndarray  # [B, n_levels, D] int64
    fans: np.ndarray  # [B, n_levels, D] int64
    rows: Tuple[DsTrafficBatch, ...]  # one entry per data space


class Loop(NamedTuple):
    level: int  # mapping/cluster level index (0 = outermost)
    kind: str  # "temporal" | "spatial"
    dim: str
    trips: int


class LevelTraffic(NamedTuple):
    """Per-buffer-level traffic for ONE data space (elements, not bytes)."""

    fills_per_instance: int = 0  # elements read into one instance from parent
    drains_per_instance: int = 0  # output elements written back to parent
    parent_reads: int = 0  # distinct element-reads served by ONE parent instance
    parent_writes: int = 0  # distinct element-writes absorbed by ONE parent instance
    instances: int = 1  # number of instances of this level in the machine
    tile_elems: int = 0  # resident tile footprint (elements)


@dataclass
class AccessProfile:
    """Full result of the analysis."""

    loops: List[Loop]
    # traffic[(ds_name, level_idx)] -> LevelTraffic; only non-virtual levels
    traffic: Dict[Tuple[str, int], LevelTraffic] = field(default_factory=dict)
    compute_cycles: float = 0.0
    leaf_tile_macs: int = 0
    total_temporal_trips: int = 1
    parallelism: int = 1
    utilization: float = 0.0
    l1_reads: Dict[str, int] = field(default_factory=dict)  # innermost accesses per ds
    # convenience lookups the cost models would otherwise re-derive per level:
    instances_at: List[int] = field(default_factory=list)  # spatial instances above each level
    real_parent: List[Optional[int]] = field(default_factory=list)  # nearest non-virtual level above


def expand_loops(problem: Problem, mapping: Mapping) -> List[Loop]:
    loops: List[Loop] = []
    for i, lm in enumerate(mapping.levels):
        trips = mapping.temporal_trips(i, problem)
        order = list(lm.temporal_order) + [d for d in problem.dims if d not in lm.temporal_order]
        for d in order:
            if trips[d] > 1:
                loops.append(Loop(i, "temporal", d, trips[d]))
        fan = mapping.spatial_fanout(i, problem)
        for d in problem.dims:
            if fan[d] > 1:
                loops.append(Loop(i, "spatial", d, fan[d]))
    return loops


def _real_parent(arch: Architecture, i: int) -> Optional[int]:
    """Nearest non-virtual cluster level above i (list index)."""
    for j in range(i - 1, -1, -1):
        if not arch.clusters[j].virtual:
            return j
    return None


class AnalysisContext:
    """Precomputed (Problem, Architecture) metadata for fast repeated analysis.

    One context is built per (problem, arch) pair and amortised over every
    mapping a search evaluates. ``analyze`` on a context produces results
    identical to evaluating the classic formulation loop by loop (the
    module-level :func:`analyze` delegates here).
    """

    def __init__(self, problem: Problem, arch: Architecture) -> None:
        self.problem = problem
        self.arch = arch
        self.dims: List[str] = list(problem.dims.keys())
        self.dim_sizes: Dict[str, int] = dict(problem.dims)
        self.n_levels = arch.n_levels
        self.virtual: List[bool] = [cl.virtual for cl in arch.clusters]
        self.real_levels: List[int] = [
            i for i in range(self.n_levels) if not self.virtual[i]
        ]
        self.real_parent: List[Optional[int]] = [
            _real_parent(arch, i) for i in range(self.n_levels)
        ]
        self.macs_per_cycle = max(1, arch.clusters[-1].macs_per_cycle)
        self.num_pes = max(1, arch.num_pes)
        self.total_macs = problem.macs
        self._dims_t: Tuple[str, ...] = tuple(self.dims)
        self._dim_index = {d: j for j, d in enumerate(self.dims)}
        # order tuple -> dim-index tuple memo (orders repeat heavily)
        self._order_idx: Dict[Tuple[str, ...], Tuple[int, ...]] = {}
        self._size_tuple: Tuple[int, ...] = tuple(problem.dims[d] for d in self.dims)
        # per data space: relevance (names + dim indices) + innermost accesses
        self.ds_rel: List[Tuple[DataSpace, frozenset]] = [
            (ds, frozenset(ds.dims)) for ds in problem.data_spaces
        ]
        self._ds_rel_idx: List[Tuple[int, ...]] = [
            tuple(sorted(self._dim_index[d] for d in ds.dims))
            for ds in problem.data_spaces
        ]
        self._ds_rel_sets: List[set] = [set(t) for t in self._ds_rel_idx]
        self.l1_reads: Dict[str, int] = {
            ds.name: (2 * self.total_macs if ds.is_output else self.total_macs)
            for ds in problem.data_spaces
        }
        # footprint memo: (ds index, level tile tuple) -> elements. Level
        # tiles recur heavily across candidates (elites, crossover reuse
        # whole per-dim chains), so this short-circuits most extent math.
        self._foot_cache: Dict[Tuple[int, Tuple[int, ...]], int] = {}
        # --- signature-based lower-bound machinery (engine hot path) ---- #
        freq = arch.frequency_hz
        self._lb_bw_levels: List[Tuple[int, float]] = [
            (i, freq / arch.clusters[i].fill_bandwidth)
            for i in self.real_levels
            if i > 0 and not math.isinf(arch.clusters[i].fill_bandwidth)
        ]
        self._ds_axes_idx: List[Tuple[int, List[List[Tuple[int, int]]], Tuple[int, ...]]] = [
            (
                ds.word_bytes,
                [
                    [(abs(t.coeff), self._dim_index[t.dim]) for t in expr.terms]
                    for expr in ds.projection
                ],
                self._ds_rel_idx[k],
            )
            for k, ds in enumerate(problem.data_spaces)
        ]
        leaf = arch.clusters[-1]
        self._lb_energy_base: float = problem.macs * leaf.mac_energy + sum(
            self.l1_reads[ds.name] * ds.word_bytes * leaf.read_energy
            for ds in problem.data_spaces
        )
        # The first real level whose parent is the (real) outermost level:
        # its parent_reads/parent_writes energy terms can be reproduced
        # exactly in the lower bound (n_parent == 1 there). When the
        # architecture has no such level the energy floor degrades to the
        # base (MAC + innermost) term.
        self._lb_dram_child: Optional[int] = None
        self._top_read_e = 0.0
        self._top_write_e = 0.0
        if len(self.real_levels) >= 2 and self.real_levels[0] == 0:
            self._lb_dram_child = self.real_levels[1]
            self._top_read_e = arch.clusters[0].read_energy
            self._top_write_e = arch.clusters[0].write_energy
        # --- vectorized batch-analysis state (built lazily) ------------- #
        self._np_batch_core = None
        self._jax_batch_core = None
        self._np_lb_core = None
        self._jax_lb_core = None
        self._jax = None
        self._jax_failed = False
        self._jax_core_donates = False
        # jitted-program invocations (lb, traffic, or fused admit+score):
        # the observable "dispatches per batch" count tests probe.
        self.jax_dispatches = 0
        # fused admit+score runners, keyed by (model store-key parts,
        # metric): engines come and go per search, the compiled program
        # is reused (equal store_key_parts => bit-identical costs, so
        # sharing is sound by the same contract the ResultStore relies on)
        self._fused_runners: Dict[Tuple, object] = {}
        # shape-generic machinery (lazy): the structural key + traced
        # parameter pack that let ONE process-global compiled program
        # serve every context in this shape class
        self._shape_class_key: Optional[tuple] = None
        self._shape_params: Optional[Dict[str, np.ndarray]] = None

    @property
    def ds_projection_axes(self) -> List[Tuple[int, List[List[Tuple[int, int]]], Tuple[int, ...]]]:
        """Per data space (problem order): ``(word_bytes, axes, rel_idx)``.

        ``axes`` holds one list of ``(|coeff|, dim_index)`` terms per
        projection axis (the span of axis ``a`` over a tile ``tt`` is
        ``1 + sum(coeff * (tt[j] - 1))``); ``rel_idx`` is the sorted tuple
        of dim indices that project into the data space. This is the public
        form of the projection metadata the footprint/bound math consumes
        -- model-specific terms (e.g. the roofline collective sharding
        spans) should use it instead of the private ``_ds_axes_idx``.
        """
        return self._ds_axes_idx

    # ------------------------------------------------------------------ #
    # Shape-generic program support. ``shape_class_key`` captures every
    # STRUCTURAL property the batch/lower-bound cores branch or reshape
    # on (ranks, level topology, projection term layout, which levels
    # carry bandwidth terms); ``shape_params`` packs every VALUE those
    # cores consume (dim sizes, projection coefficients, energies,
    # bandwidth reciprocals) as arrays whose shapes are fully determined
    # by the key. Two contexts with equal keys therefore run the SAME
    # compiled program -- only the parameter pack differs -- and because
    # the generic cores replay the per-context closures' float operations
    # in the identical order, results stay bit-identical per row.
    # ------------------------------------------------------------------ #
    def shape_class_key(self) -> tuple:
        """Structural identity of this context's array programs (hashable;
        equal keys <=> one compiled shape-generic program serves both
        contexts)."""
        if self._shape_class_key is None:
            axes_struct = tuple(
                tuple(tuple(j for _c, j in ax) for ax in axes)
                for _wb, axes, _rel in self._ds_axes_idx
            )
            self._shape_class_key = (
                self.n_levels,
                len(self.dims),
                len(self._ds_rel_sets),
                tuple(self.real_levels),
                tuple(-1 if p is None else p for p in self.real_parent),
                tuple(bool(ds.is_output) for ds, _rel in self.ds_rel),
                axes_struct,
                -1 if self._lb_dram_child is None else self._lb_dram_child,
                tuple(lv for lv, _c in self._lb_bw_levels),
            )
        return self._shape_class_key

    def shape_params(self) -> Dict[str, np.ndarray]:
        """Traced parameter pack for the shape-generic cores: every value
        the per-context closures bake in as Python constants, as arrays
        keyed/shaped by :meth:`shape_class_key` (content may differ across
        contexts of one class; shapes never do)."""
        if self._shape_params is None:
            D = len(self.dims)
            coeffs = [
                float(c)
                for _wb, axes, _rel in self._ds_axes_idx
                for ax in axes
                for c, _j in ax
            ]
            self._shape_params = {
                "sizes": np.asarray(self._size_tuple, dtype=np.int64),
                "mpc": np.float64(self.macs_per_cycle),
                "rel": np.array(
                    [[j in rset for j in range(D)] for rset in self._ds_rel_sets],
                    dtype=bool,
                ),
                "coeffs": np.asarray(coeffs, dtype=np.float64),
                "wb": np.asarray(
                    [wb for wb, _a, _r in self._ds_axes_idx], dtype=np.float64
                ),
                "e_base": np.float64(self._lb_energy_base),
                "tre": np.float64(self._top_read_e),
                "twe": np.float64(self._top_write_e),
                "bw_cpb": np.asarray(
                    [c for _lv, c in self._lb_bw_levels], dtype=np.float64
                ),
                "freq": np.float64(self.arch.frequency_hz),
            }
        return self._shape_params

    # ------------------------------------------------------------------ #
    def analyze(self, mapping: Mapping) -> AccessProfile:
        # the engine / Genome stash the already-computed signature on the
        # mapping object; mappings are treated as immutable once evaluated
        cached = mapping.__dict__.get("_sig_cache")
        if cached is not None and cached[0] == self._dims_t:
            return self.analyze_signature(cached[1])
        return self.analyze_signature(mapping_signature(mapping, self.dims))

    def signature_traffic(self, sig):
        """The reuse core, off the canonical signature, as plain arrays.

        ``sig`` is ``mapping_signature(mapping, self.dims)``: per level the
        (effective order, TT tuple, ST tuple) in problem-dim order.

        Returns ``(compute_cycles, par, inst_at, tloops, sloops, rows)``:
        ``rows[ds_idx]`` lists, per entry of ``self.real_levels``, the tuple
        ``(fills, drains, parent_reads, parent_writes, instances, foot)``.
        Both :meth:`analyze_signature` (object form) and the cost models'
        fused ``evaluate_signature`` paths consume THIS single core, so the
        reuse rules live in exactly one place.
        """
        dims = self.dims
        dim_index = self._dim_index
        D = len(dims)
        n = self.n_levels

        # ---- loop nest expansion (identical to expand_loops) ----------- #
        order_idx = self._order_idx
        tloops: List[Tuple[int, int, int]] = []  # (level, dim_idx, trips)
        sloops: List[Tuple[int, int, int]] = []
        outer = self._size_tuple
        for i in range(n):
            order, tt, st = sig[i]
            trips = [0] * D
            for j in range(D):
                trips[j] = max(1, outer[j] // max(1, tt[j]))
            oidx = order_idx.get(order)
            if oidx is None:
                oidx = tuple(dim_index[d] for d in order)
                order_idx[order] = oidx
            for j in oidx:
                q = trips[j]
                if q > 1:
                    tloops.append((i, j, q))
            for j in range(D):
                f = max(1, tt[j]) // max(1, st[j])
                if f > 1:
                    sloops.append((i, j, f))
            outer = st

        # ---- totals ---------------------------------------------------- #
        total_trips = 1
        for _lv, _j, q in tloops:
            total_trips *= q
        par = 1
        for _lv, _j, f in sloops:
            par *= f
        leaf_macs = 1
        for t in sig[-1][1]:
            leaf_macs *= t
        compute_cycles = total_trips * math.ceil(leaf_macs / self.macs_per_cycle)

        # ---- per-level shared precomputation --------------------------- #
        # tloops/sloops are ordered by level, so the loops "above" a level's
        # residency are a PREFIX of each list:
        #   temporal prefix at level i = tloops with level <= i
        #   spatial  prefix at level i = sloops with level <  i
        t_prefix = [0] * n
        s_prefix = [0] * n
        k = 0
        for i in range(n):
            while k < len(tloops) and tloops[k][0] <= i:
                k += 1
            t_prefix[i] = k
        c = 0
        for i in range(n):
            while c < len(sloops) and sloops[c][0] < i:
                c += 1
            s_prefix[i] = c
        # product of ALL spatial trips in each prefix (= instances)
        sall = [1] * (len(sloops) + 1)
        for j, (_lv, _dj, f) in enumerate(sloops):
            sall[j + 1] = sall[j] * f
        inst_at = [sall[s_prefix[i]] for i in range(n)]

        foot_cache = self._foot_cache
        if len(foot_cache) > (1 << 17):
            foot_cache.clear()
        tiles_dicts: List[Optional[Dict[str, int]]] = [None] * n
        real_levels = self.real_levels
        real_parent = self.real_parent

        # ---- per data space -------------------------------------------- #
        rows: List[List[Tuple[int, int, int, int, int, int]]] = []
        for ds_idx, (ds, _rel) in enumerate(self.ds_rel):
            rel_set = self._ds_rel_sets[ds_idx]
            # temporal prefix products:
            #   relprod[j] = prod of RELEVANT trips among first j temporal loops
            #   chgprod[j] = relprod[j] * (irrelevant trips enclosing a deeper
            #                relevant loop) -- i.e. irrelevant loops positioned
            #                before the LAST relevant loop in the prefix.
            T = len(tloops)
            relprod = [1] * (T + 1)
            chgprod = [1] * (T + 1)
            rp = 1
            ip = 1  # running product of irrelevant trips seen so far
            lastrel_ip = 1  # irrelevant product at the most recent relevant loop
            for j, (_lv, dj, q) in enumerate(tloops):
                if dj in rel_set:
                    rp *= q
                    lastrel_ip = ip
                else:
                    ip *= q
                relprod[j + 1] = rp
                chgprod[j + 1] = rp * lastrel_ip
            # spatial prefix products restricted to relevant dims
            srel = [1] * (len(sloops) + 1)
            for j, (_lv, dj, f) in enumerate(sloops):
                srel[j + 1] = srel[j] * (f if dj in rel_set else 1)

            is_out = ds.is_output
            ds_rows: List[Tuple[int, int, int, int, int, int]] = []
            for i in real_levels:
                kT = t_prefix[i]
                changes = chgprod[kT]
                unique = relprod[kT]
                tt = sig[i][1]
                fkey = (ds_idx, tt)
                foot = foot_cache.get(fkey)
                if foot is None:
                    tile = tiles_dicts[i]
                    if tile is None:
                        tile = {dims[j]: tt[j] for j in range(D)}
                        tiles_dicts[i] = tile
                    foot = ds.footprint(tile)
                    foot_cache[fkey] = foot
                cS = s_prefix[i]
                inst = sall[cS]
                pr = real_parent[i]
                if pr is None:
                    rel_spatial = 1
                else:
                    rel_spatial = srel[cS] // srel[s_prefix[pr]]

                cf = changes * foot
                if not is_out:
                    # one parent instance serves the instances between parent
                    # and i; ideal multicast: only RELEVANT spatial splits are
                    # distinct.
                    ds_rows.append((cf, 0, cf * rel_spatial, 0, inst, foot))
                else:
                    rmw = max(0, changes - unique) * foot  # RMW refills
                    ds_rows.append(
                        (rmw, cf, rmw * rel_spatial, cf * rel_spatial, inst, foot)
                    )
            rows.append(ds_rows)
        return compute_cycles, par, inst_at, tloops, sloops, rows

    def analyze_signature(self, sig) -> AccessProfile:
        """Object form of :meth:`signature_traffic` (AccessProfile API)."""
        dims = self.dims
        compute_cycles, par, inst_at, tloops, sloops, rows = self.signature_traffic(sig)
        # rebuild the interleaved loop list (temporal then spatial per level)
        loops: List[Loop] = []
        ti = si = 0
        for i in range(self.n_levels):
            while ti < len(tloops) and tloops[ti][0] == i:
                _lv, j, q = tloops[ti]
                loops.append(Loop(i, "temporal", dims[j], q))
                ti += 1
            while si < len(sloops) and sloops[si][0] == i:
                _lv, j, f = sloops[si]
                loops.append(Loop(i, "spatial", dims[j], f))
                si += 1
        prof = AccessProfile(loops=loops)
        total_trips = 1
        for _lv, _j, q in tloops:
            total_trips *= q
        leaf_macs = 1
        for t in sig[-1][1]:
            leaf_macs *= t
        prof.leaf_tile_macs = leaf_macs
        prof.total_temporal_trips = total_trips
        prof.parallelism = par
        prof.utilization = par / self.num_pes
        prof.compute_cycles = compute_cycles
        prof.l1_reads = dict(self.l1_reads)
        prof.instances_at = inst_at
        prof.real_parent = self.real_parent
        for ds_idx, (ds, _rel) in enumerate(self.ds_rel):
            ds_rows = rows[ds_idx]
            for pos, i in enumerate(self.real_levels):
                prof.traffic[(ds.name, i)] = LevelTraffic(*ds_rows[pos])
        return prof

    # ------------------------------------------------------------------ #
    # Vectorized batch analysis: a whole miss-batch of signatures scored
    # as one array program. ``signature_traffic_batch`` stacks the batch
    # into dense [B, n_levels, D] tile/order matrices and runs the same
    # reuse rules as ``signature_traffic`` over all candidates at once --
    # numpy by default, optionally a jitted JAX program for device
    # sweeps. All quantities are integer-valued and computed in float64;
    # they are exact (bit-identical to the scalar path) as long as they
    # stay below BATCH_EXACT_LIMIT, which the cost models enforce before
    # trusting a batch result.
    # ------------------------------------------------------------------ #
    def stack_signatures(self, sigs):
        """Dense (tt, st, perm) int64 matrices ``[B, n_levels, D]`` for a
        batch of canonical signatures. ``perm[b, i, p]`` is the dim index
        at position ``p`` of level ``i``'s effective temporal order."""
        n = self.n_levels
        order_idx = self._order_idx
        dim_index = self._dim_index
        B = len(sigs)
        D = len(self.dims)
        count = B * n * D
        tt = np.fromiter(
            (v for sig in sigs for lvl in sig for v in lvl[1]),
            dtype=np.int64,
            count=count,
        ).reshape(B, n, D)
        st = np.fromiter(
            (v for sig in sigs for lvl in sig for v in lvl[2]),
            dtype=np.int64,
            count=count,
        ).reshape(B, n, D)

        def idx_of(order):
            oidx = order_idx.get(order)
            if oidx is None:
                oidx = tuple(dim_index[d] for d in order)
                order_idx[order] = oidx
            return oidx

        perm = np.fromiter(
            (j for sig in sigs for lvl in sig for j in idx_of(lvl[0])),
            dtype=np.int64,
            count=count,
        ).reshape(B, n, D)
        return tt, st, perm

    def stacked_batch(self, sigs) -> StackedBatch:
        """One :class:`StackedBatch` handle over ``stack_signatures(sigs)``,
        shareable between the admission and scoring array programs."""
        return StackedBatch(*self.stack_signatures(sigs))

    def _make_batch_core(self, xp, lax=None):
        """Build the (tt, st, perm) -> stacked-traffic array program.

        ``xp`` is numpy or jax.numpy; ``lax`` supplies ``cummax`` on the
        JAX path. The program is the exact vectorization of
        :meth:`signature_traffic`: same trip/fan derivation, same
        relevant/irrelevant prefix products (the order-dependent
        ``changes`` term uses a cummax over the last relevant loop
        position), same footprint spans.
        """
        sizes_row = np.asarray(self._size_tuple, dtype=np.int64)[None, None, :]
        n = self.n_levels
        D = len(self.dims)
        real_levels = list(self.real_levels)
        L = len(real_levels)
        real_parent = self.real_parent
        mpc = self.macs_per_cycle
        K = len(self._ds_rel_sets)
        # [K, D] relevance mask, stacked over data spaces: the reuse
        # cumprods below run for ALL data spaces in one array op.
        rel_stack = np.array(
            [[j in rset for j in range(D)] for rset in self._ds_rel_sets], dtype=bool
        )
        ds_axes = [axes for _wb, axes, _rel in self._ds_axes_idx]
        ds_out = [ds.is_output for ds, _rel in self.ds_rel]
        ends = np.asarray([(i + 1) * D - 1 for i in real_levels])
        real_arr = np.asarray(real_levels)
        # parent gather indices for rel_spatial (parentless levels divide by
        # themselves -> ratio 1.0 exactly)
        parent_arr = np.asarray(
            [real_parent[i] if real_parent[i] is not None else i for i in real_levels]
        )
        pos_seq = np.arange(n * D)

        def core(tt, st, perm):
            B = tt.shape[0]
            tt = xp.maximum(tt, 1)
            st = xp.maximum(st, 1)
            outer = xp.concatenate(
                [xp.broadcast_to(xp.asarray(sizes_row), (B, 1, D)), st[:, :-1, :]],
                axis=1,
            )
            trips = xp.maximum(outer // tt, 1)
            fans = xp.maximum(tt // st, 1)
            tripsf = trips.astype(xp.float64)
            fansf = fans.astype(xp.float64)
            total_trips = xp.prod(tripsf.reshape(B, n * D), axis=1)
            leaf_macs = xp.prod(tt[:, -1, :].astype(xp.float64), axis=1)
            compute_cycles = total_trips * xp.ceil(leaf_macs / exact_divisor(xp, mpc))
            par = xp.prod(fansf.reshape(B, n * D), axis=1)
            lvl_all = xp.prod(fansf, axis=2)  # [B, n]
            cp_all = xp.cumprod(lvl_all, axis=1)
            inst_at = xp.concatenate(
                [xp.ones((B, 1), dtype=xp.float64), cp_all[:, :-1]], axis=1
            )
            # temporal loop sequence in emission order (order-major per level)
            perm_flat = perm.reshape(B, n * D)
            tseqf = xp.take_along_axis(trips, perm, axis=2).reshape(B, n * D).astype(
                xp.float64
            )
            # ---- all data spaces at once: [K, B, S] ---------------------- #
            rel_seq = xp.asarray(rel_stack)[:, perm_flat]  # [K, B, S]
            present = (tseqf > 1.0)[None, :, :]
            relm = rel_seq & present
            irrm = (~rel_seq) & present
            tseq_b = xp.broadcast_to(tseqf[None, :, :], (K, B, n * D))
            relprod = xp.cumprod(xp.where(relm, tseq_b, 1.0), axis=2)
            irrprod = xp.cumprod(xp.where(irrm, tseq_b, 1.0), axis=2)
            # irrelevant-trip product at the LAST relevant loop <= s: gather
            # the (exclusive == inclusive, s is relevant) irrprod at that
            # position, 1.0 when no relevant loop yet.
            idx = xp.where(relm, pos_seq[None, None, :], -1)
            if lax is None:
                lastrel = np.maximum.accumulate(idx, axis=2)
            else:
                lastrel = lax.cummax(idx, axis=2)
            gathered = xp.take_along_axis(irrprod, xp.maximum(lastrel, 0), axis=2)
            ip = xp.where(lastrel >= 0, gathered, 1.0)
            unique = relprod[:, :, ends]  # [K, B, L]
            changes = unique * ip[:, :, ends]
            # spatial: relevant-fan products per level, exclusive cumprod
            lvl_rel = xp.prod(
                xp.where(xp.asarray(rel_stack)[:, None, None, :], fansf[None], 1.0),
                axis=3,
            )  # [K, B, n]
            cp_rel = xp.cumprod(lvl_rel, axis=2)
            srel_excl = xp.concatenate(
                [xp.ones((K, B, 1), dtype=xp.float64), cp_rel[:, :, :-1]], axis=2
            )
            # exact: srel_excl at the parent divides srel_excl at the level
            rel_sp = srel_excl[:, :, real_arr] / srel_excl[:, :, parent_arr]
            # footprints per data space (projections differ per ds)
            ttf_real = tt[:, real_arr, :].astype(xp.float64)  # [B, L, D]
            rows = []
            for k in range(K):
                foot = xp.ones((B, L), dtype=xp.float64)
                for ax in ds_axes[k]:
                    span = xp.ones((B, L), dtype=xp.float64)
                    for coeff, j in ax:
                        span = span + coeff * (ttf_real[:, :, j] - 1.0)
                    foot = foot * span
                cf = changes[k] * foot
                if ds_out[k]:
                    rmw = xp.maximum(changes[k] - unique[k], 0.0) * foot
                    rows.append((rmw, cf, rmw * rel_sp[k], cf * rel_sp[k], foot))
                else:
                    z = xp.zeros_like(cf)
                    rows.append((cf, z, cf * rel_sp[k], z, foot))
            return compute_cycles, total_trips, par, inst_at, tt, st, fans, tuple(rows)

        return core

    def _ensure_jax(self):
        """Import JAX lazily; memoized on the context.

        ``UNION_FAULT_JAX=1`` simulates a device failure at the exact point
        every jax path funnels through: the raise is caught by the
        callers' degradation handling (:func:`device_faults`), which sets
        ``_jax_failed`` and falls back to numpy -- the path the sweep
        executor's ``jaxfail`` fault spec and the CI fault-injection tests
        exercise without needing a failing device.
        """
        if os.environ.get("UNION_FAULT_JAX"):
            raise InjectedJaxFault("injected jax backend failure (UNION_FAULT_JAX)")
        if self._jax is None:
            import jax

            self._jax = jax
        return self._jax

    def _jax_device_arrays(self, sb: StackedBatch):
        """Upload a StackedBatch's matrices to the device once (int64; the
        caller holds :func:`x64`) and memoize them on the handle, so the
        admission and scoring programs share one transfer."""
        if sb.dev is None:
            jax = self._ensure_jax()
            sb.dev = tuple(jax.device_put(a) for a in (sb.tt, sb.st, sb.perm))
        return sb.dev

    def _jax_device_padded(self, sb: StackedBatch):
        """Pow2-padded device matrices for the fused full-batch programs:
        ``(tt, st, perm, B)`` with the batch axis padded to the next power
        of two by repeating row 0 (a real candidate -- identical to
        :meth:`_pad_pow2`, so guards and results are bit-identical).
        Padding runs HOST-SIDE in numpy and the three matrices ship as a
        single transfer, memoized on the handle: device-side pad ops cost
        ~1ms of dispatch overhead per call on CPU jax, which dominated the
        per-generation cost of the device-resident search loops."""
        if sb.devp is None:
            jax = self._ensure_jax()
            B = sb.size
            B2 = 1 << max(0, (B - 1).bit_length())
            if B2 == B:
                mats = (sb.tt, sb.st, sb.perm)
            else:
                padn = B2 - B
                mats = tuple(
                    np.ascontiguousarray(
                        np.concatenate(
                            [a, np.broadcast_to(a[:1], (padn,) + a.shape[1:])]
                        )
                    )
                    for a in (sb.tt, sb.st, sb.perm)
                )
            sb.devp = jax.device_put(mats) + (B,)
        return sb.devp

    @staticmethod
    def _pad_pow2(tt, st, perm, xp):
        """Pad the batch axis to the next power of two (bounding jit
        retraces) by repeating row 0 -- a real candidate, so padding can
        never trip the exactness guard (the lb core's guard reduces over
        the padded batch) -- and return the original size too."""
        B = int(tt.shape[0])
        B2 = 1 << max(0, (B - 1).bit_length())
        if B2 == B:
            return tt, st, perm, B
        padn = B2 - B

        def pad(a):
            return xp.concatenate(
                [a, xp.broadcast_to(a[:1], (padn,) + tuple(a.shape[1:]))]
            )

        return pad(tt), pad(st), pad(perm), B

    def _run_jax_core(self, sb: StackedBatch, select=None):
        """JAX-jitted batch core over a (device-resident) StackedBatch:
        optionally gathers the ``select`` row subset ON DEVICE, pads the
        batch to a power of two (bounding retraces), runs in float64 under
        :func:`x64`, returns numpy arrays of the unpadded (selected)
        batch -- or None after a device fault (:func:`device_faults`), so
        the caller falls back to numpy."""
        if self._jax_failed:
            return None
        try:
            jax = self._ensure_jax()
            from jax import lax
            import jax.numpy as jnp

            if self._jax_batch_core is None:
                # Buffer donation lets XLA reuse the input matrices' device
                # memory for the program's temporaries; it is unsupported
                # (and warns) on CPU, so only accelerator backends request
                # it. The donated buffers are the batch matrices, which are
                # re-uploaded from the host copy if the handle is reused.
                donate = (0, 1, 2) if jax.default_backend() != "cpu" else ()
                self._jax_core_donates = bool(donate)
                self._jax_batch_core = CompiledCore(
                    self._make_batch_core(jnp, lax), donate_argnums=donate
                )
            with x64():
                tt, st, perm = self._jax_device_arrays(sb)
                if select is not None:
                    sel = jnp.asarray(np.asarray(select, dtype=np.int64))
                    tt, st, perm = tt[sel], st[sel], perm[sel]
                tt, st, perm, B = self._pad_pow2(tt, st, perm, jnp)
                self.jax_dispatches += 1
                _record_trace(("ctx-core", id(self)), int(tt.shape[0]))
                out = self._jax_batch_core(tt, st, perm)
            if self._jax_core_donates and select is None:
                sb.dev = None  # donated away; re-upload on next use
            out = host_pull(out)
        except device_faults():
            self._jax_failed = True
            return None
        require_float64(out[0])
        if out[0].shape[0] != B:
            out = _tree_slice(out, B)
        return out

    def signature_traffic_batch(
        self,
        sigs=None,
        backend: str = "numpy",
        stacked: Optional[StackedBatch] = None,
        select=None,
    ) -> Optional[BatchTraffic]:
        """Stacked :meth:`signature_traffic` over a batch of signatures.

        ``backend`` selects the array program: ``"numpy"`` (default) or
        ``"jax"`` (jitted, falls back to numpy when JAX cannot deliver
        float64). ``stacked`` reuses an already-stacked batch -- the
        evaluation engine stacks each miss-batch ONCE and shares the handle
        between the admission filter and this scoring pass. ``select``
        restricts the program to the given row indices of the stacked
        batch (on the jax backend the gather runs on device, so pruned
        candidates' traffic never returns to host). Returns None for an
        empty batch/selection.
        """
        sb = stacked
        if sb is None:
            if not sigs:
                return None
            sb = self.stacked_batch(sigs)
        if sb.size == 0 or (select is not None and len(select) == 0):
            return None
        out = None
        if backend == "jax":
            out = self._run_jax_core(sb, select=select)
        if out is None:
            if self._np_batch_core is None:
                self._np_batch_core = self._make_batch_core(np)
            tt, st, perm = sb.tt, sb.st, sb.perm
            if select is not None:
                idx = np.asarray(select, dtype=np.int64)
                tt, st, perm = tt[idx], st[idx], perm[idx]
            out = self._np_batch_core(tt, st, perm)
        compute_cycles, total_trips, par, inst_at, tt_c, st_c, fans, rows = out
        return BatchTraffic(
            compute_cycles=np.asarray(compute_cycles),
            total_trips=np.asarray(total_trips),
            par=np.asarray(par),
            inst_at=np.asarray(inst_at),
            tt=np.asarray(tt_c),
            st=np.asarray(st_c),
            fans=np.asarray(fans),
            rows=tuple(DsTrafficBatch(*(np.asarray(a) for a in r)) for r in rows),
        )

    # ------------------------------------------------------------------ #
    # Cheap chain-only bounds (no reuse analysis). Used by the evaluation
    # engine's admission filter: every quantity here is a LOWER bound on
    # the corresponding quantity of the full analysis. All operate on the
    # canonical signature, so the engine reuses the tuple it already
    # computed for the cache probe.
    # ------------------------------------------------------------------ #
    def signature_compute_cycles(self, sig) -> float:
        """Exactly ``AccessProfile.compute_cycles``, without the analysis."""
        outer = self._size_tuple
        D = len(outer)
        total_trips = 1
        for _order, tt, st in sig:
            for j in range(D):
                q = outer[j] // (tt[j] or 1)
                if q > 1:
                    total_trips *= q
            outer = st
        leaf_macs = 1
        for t in sig[-1][1]:
            leaf_macs *= max(1, t)
        return total_trips * math.ceil(leaf_macs / self.macs_per_cycle)

    def signature_min_boundary_bytes(self, sig, level: int) -> float:
        """Lower bound on fill+drain bytes into one instance of ``level``
        from compulsory traffic alone (one tile footprint per data space)."""
        tt = sig[level][1]
        total = 0.0
        for wb, axes, _rel in self._ds_axes_idx:
            foot = 1
            for ax in axes:
                span = 1
                for coeff, j in ax:
                    span += coeff * (max(1, tt[j]) - 1)
                foot *= span
            total += foot * wb
        return total

    def signature_lower_bound(self, sig) -> Tuple[float, float]:
        """(cycles, energy_pj) lower bounds for the hierarchical models.

        cycles: max of the exact compute cycles and, per bandwidth-limited
        level, a fill-time floor of ``unique x footprint`` bytes per data
        space -- ``unique`` (the product of relevant temporal trips above
        the residency) never exceeds ``changes``, and both fills (inputs)
        and drains (outputs) scale with ``changes``, so this stays a true
        lower bound while discriminating much harder against reuse-poor
        tilings than compulsory traffic alone.

        energy: MAC + innermost-operand terms plus the EXACT outermost-
        memory access term (parent reads/writes of the level right below
        the top real memory, where ``n_parent == 1``); remaining buffer and
        NoC terms are non-negative, so the sum stays a true lower bound.
        At that same level the fill-cycle floor uses the exact ``changes``
        too.
        """
        outer = self._size_tuple
        D = len(outer)
        total_trips = 1
        trips_rows: List[List[int]] = []
        for _order, tt, st in sig:
            row = [1] * D
            for j in range(D):
                q = outer[j] // (tt[j] or 1)
                if q > 1:
                    row[j] = q
                    total_trips *= q
            trips_rows.append(row)
            outer = st
        leaf_macs = 1
        for t in sig[-1][1]:
            leaf_macs *= max(1, t)
        cycles = total_trips * math.ceil(leaf_macs / self.macs_per_cycle)

        energy = self._lb_energy_base
        dc = self._lb_dram_child
        dc_boundary = 0.0
        if dc is not None:
            # temporal loops of levels <= dc in effective emission order and
            # spatial fans of levels < dc: enough to reproduce the model's
            # changes/unique/rel_spatial at the dram-child level exactly.
            order_idx = self._order_idx
            dim_index = self._dim_index
            tl: List[Tuple[int, int]] = []
            for i in range(dc + 1):
                row = trips_rows[i]
                order = sig[i][0]
                oidx = order_idx.get(order)
                if oidx is None:
                    oidx = tuple(dim_index[d] for d in order)
                    order_idx[order] = oidx
                for j in oidx:
                    q = row[j]
                    if q > 1:
                        tl.append((j, q))
            fans: List[Tuple[int, int]] = []
            for i in range(dc):
                _o, tt_i, st_i = sig[i]
                for j in range(D):
                    f = max(1, tt_i[j]) // max(1, st_i[j])
                    if f > 1:
                        fans.append((j, f))
            tt_dc = sig[dc][1]
            tre = self._top_read_e
            twe = self._top_write_e
            for ds_idx, (ds, _r) in enumerate(self.ds_rel):
                rel_set = self._ds_rel_sets[ds_idx]
                rp = 1
                ip = 1
                lastrel = 1
                for j, q in tl:
                    if j in rel_set:
                        rp *= q
                        lastrel = ip
                    else:
                        ip *= q
                changes = rp * lastrel
                unique = rp
                wb, axes, _rel = self._ds_axes_idx[ds_idx]
                foot = 1
                for ax in axes:
                    span = 1
                    for coeff, j in ax:
                        span += coeff * (max(1, tt_dc[j]) - 1)
                    foot *= span
                rel_sp = 1
                for j, f in fans:
                    if j in rel_set:
                        rel_sp *= f
                cf = changes * foot
                if ds.is_output:
                    rmw = max(0, changes - unique) * foot
                    energy += cf * rel_sp * wb * twe + rmw * rel_sp * wb * tre
                    dc_boundary += (cf + rmw) * wb
                else:
                    energy += cf * rel_sp * wb * tre
                    dc_boundary += cf * wb

        for level, cyc_per_byte in self._lb_bw_levels:
            if level == dc:
                cyc = dc_boundary * cyc_per_byte  # exact fill bytes there
                if cyc > cycles:
                    cycles = cyc
                continue
            b = 0
            tt = sig[level][1]
            for wb, axes, rel in self._ds_axes_idx:
                unique = 1
                for r in range(level + 1):
                    row = trips_rows[r]
                    for j in rel:
                        unique *= row[j]
                foot = 1
                for ax in axes:
                    span = 1
                    for coeff, j in ax:
                        span += coeff * (max(1, tt[j]) - 1)
                    foot *= span
                b += unique * foot * wb
            cyc = b * cyc_per_byte
            if cyc > cycles:
                cycles = cyc
        return cycles, energy

    # ------------------------------------------------------------------ #
    # Batched lower bounds: the admission filter's counterpart of
    # ``signature_traffic_batch``. One array program reproduces
    # ``signature_lower_bound`` for a whole stacked batch -- same integer
    # quantities, same float-operation order -- so the engine admits or
    # rejects an entire miss-batch with one masked program instead of a
    # per-candidate Python walk. All guarded quantities are integer-valued;
    # the program tracks their max and the wrapper rejects the batch
    # (caller falls back to the scalar bound) beyond BATCH_EXACT_LIMIT.
    # ------------------------------------------------------------------ #
    def _make_lb_core(self, xp, lax=None):
        """Build the (tt, st, perm) -> (cycles[B], energy_pj[B], guard_max)
        program: the exact vectorization of :meth:`signature_lower_bound`."""
        sizes_row = np.asarray(self._size_tuple, dtype=np.int64)[None, None, :]
        n = self.n_levels
        D = len(self.dims)
        mpc = self.macs_per_cycle
        K = len(self._ds_rel_sets)
        rel_stack = np.array(
            [[j in rset for j in range(D)] for rset in self._ds_rel_sets], dtype=bool
        )
        wb_list = [wb for wb, _axes, _rel in self._ds_axes_idx]
        ds_axes = [axes for _wb, axes, _rel in self._ds_axes_idx]
        ds_out = [ds.is_output for ds, _rel in self.ds_rel]
        e_base = self._lb_energy_base
        dc = self._lb_dram_child
        tre = self._top_read_e
        twe = self._top_write_e
        bw_levels = list(self._lb_bw_levels)
        pos_seq = np.arange(n * D)

        def ds_foot(ttf_lvl, k):
            return batch_projection_footprint(ds_axes[k], ttf_lvl, xp)

        def core(tt, st, perm):
            B = tt.shape[0]
            tt = xp.maximum(tt, 1)
            st = xp.maximum(st, 1)
            outer = xp.concatenate(
                [xp.broadcast_to(xp.asarray(sizes_row), (B, 1, D)), st[:, :-1, :]],
                axis=1,
            )
            trips = xp.maximum(outer // tt, 1)
            tripsf = trips.astype(xp.float64)
            total_trips = xp.prod(tripsf.reshape(B, n * D), axis=1)
            leaf_macs = xp.prod(tt[:, -1, :].astype(xp.float64), axis=1)
            cycles = total_trips * xp.ceil(leaf_macs / exact_divisor(xp, mpc))
            # fractional energy addends are collected as (x, y) pairs and
            # summed through ordered_pair_sum -- contraction-proof on the
            # jitted path, plain left-associated adds on numpy
            e_pairs = []
            mx = xp.maximum(xp.maximum(total_trips, leaf_macs), cycles)

            dc_boundary = None
            if dc is not None:
                # temporal loops of levels <= dc in effective emission order
                # (order-major): enough to reproduce changes/unique exactly.
                S = (dc + 1) * D
                perm_pref = perm[:, : dc + 1, :]
                tseqf = (
                    xp.take_along_axis(trips[:, : dc + 1, :], perm_pref, axis=2)
                    .reshape(B, S)
                    .astype(xp.float64)
                )
                rel_seq = xp.asarray(rel_stack)[:, perm_pref.reshape(B, S)]  # [K,B,S]
                present = (tseqf > 1.0)[None, :, :]
                relm = rel_seq & present
                irrm = (~rel_seq) & present
                tseq_b = xp.broadcast_to(tseqf[None, :, :], (K, B, S))
                unique = xp.prod(xp.where(relm, tseq_b, 1.0), axis=2)  # [K, B]
                irrprod = xp.cumprod(xp.where(irrm, tseq_b, 1.0), axis=2)
                # irrelevant-trip product at the LAST relevant loop: position
                # itself is relevant, so the inclusive irrprod there equals
                # the scalar path's exclusive ``lastrel_ip``; 1.0 when no
                # relevant loop exists.
                idx = xp.where(relm, pos_seq[None, None, :S], -1)
                lastrel = xp.max(idx, axis=2)
                gathered = xp.take_along_axis(
                    irrprod, xp.maximum(lastrel, 0)[:, :, None], axis=2
                )[:, :, 0]
                changes = unique * xp.where(lastrel >= 0, gathered, 1.0)
                ttf_dc = tt[:, dc, :].astype(xp.float64)
                if dc > 0:
                    fans_pref = xp.maximum(tt[:, :dc, :] // st[:, :dc, :], 1).astype(
                        xp.float64
                    )
                dc_boundary = xp.zeros(B, dtype=xp.float64)
                for k in range(K):
                    foot = ds_foot(ttf_dc, k)
                    if dc > 0:
                        rel_sp = xp.prod(
                            xp.where(
                                xp.asarray(rel_stack[k])[None, None, :], fans_pref, 1.0
                            ).reshape(B, dc * D),
                            axis=1,
                        )
                    else:
                        rel_sp = xp.ones(B, dtype=xp.float64)
                    cf = changes[k] * foot
                    mx = xp.maximum(mx, changes[k])
                    t1 = cf * rel_sp * wb_list[k]
                    mx = xp.maximum(mx, t1)
                    if ds_out[k]:
                        rmw = xp.maximum(changes[k] - unique[k], 0.0) * foot
                        t2 = rmw * rel_sp * wb_list[k]
                        mx = xp.maximum(mx, t2)
                        e_pairs.append((t1 * twe, t2 * tre))
                        dc_boundary = dc_boundary + (cf + rmw) * wb_list[k]
                    else:
                        # x + 0.0 is exact for the non-negative term, so the
                        # pair form reproduces ``energy + t1 * tre``
                        e_pairs.append((t1 * tre, 0.0))
                        dc_boundary = dc_boundary + cf * wb_list[k]
                mx = xp.maximum(mx, dc_boundary)
            energy = ordered_pair_sum(
                xp, xp.full((B,), e_base, dtype=xp.float64), e_pairs
            )

            for level, cyc_per_byte in bw_levels:
                if level == dc:
                    cycles = xp.maximum(cycles, dc_boundary * cyc_per_byte)
                    continue
                ttf_lvl = tt[:, level, :].astype(xp.float64)
                # unique per ds: product of relevant trips of levels <= level
                relprod_lvl = xp.prod(
                    xp.where(
                        xp.asarray(rel_stack)[:, None, None, :],
                        tripsf[None, :, : level + 1, :],
                        1.0,
                    ).reshape(K, B, (level + 1) * D),
                    axis=2,
                )
                b = xp.zeros(B, dtype=xp.float64)
                for k in range(K):
                    term = relprod_lvl[k] * ds_foot(ttf_lvl, k) * wb_list[k]
                    mx = xp.maximum(mx, term)
                    b = b + term
                mx = xp.maximum(mx, b)
                cycles = xp.maximum(cycles, b * cyc_per_byte)
            return cycles, energy, xp.max(mx)

        return core

    def _run_jax_lb(self, sb: StackedBatch):
        """Jitted lower-bound core over a device-resident StackedBatch; the
        uploaded matrices stay on ``sb.dev`` for the scoring pass. Returns
        numpy (cycles, energy, guard) or None after a device fault
        (fallback to numpy)."""
        if self._jax_failed:
            return None
        try:
            jax = self._ensure_jax()
            from jax import lax
            import jax.numpy as jnp

            if self._jax_lb_core is None:
                # never donate here: the scoring pass reuses sb.dev
                self._jax_lb_core = CompiledCore(self._make_lb_core(jnp, lax))
            with x64():
                tt, st, perm = self._jax_device_arrays(sb)
                tt, st, perm, B = self._pad_pow2(tt, st, perm, jnp)
                self.jax_dispatches += 1
                _record_trace(("ctx-lb", id(self)), int(tt.shape[0]))
                cyc, en, mx = self._jax_lb_core(tt, st, perm)
            cyc, en, mx = host_pull((cyc, en, mx))
        except device_faults():
            self._jax_failed = True
            return None
        return require_float64(cyc)[:B], en[:B], mx

    def lower_bound_batch(
        self,
        sigs=None,
        backend: str = "numpy",
        stacked: Optional[StackedBatch] = None,
    ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Stacked :meth:`signature_lower_bound`: float64 ``(cycles[B],
        energy_pj[B])`` arrays, bit-identical per candidate to the scalar
        bound, or None when the batch is empty or exactness cannot be
        guaranteed (any guarded integer quantity at/above
        :data:`BATCH_EXACT_LIMIT` -- the caller then falls back to the
        per-candidate bound). ``stacked`` shares an already-stacked batch
        with the scoring pass (see :meth:`signature_traffic_batch`)."""
        sb = stacked
        if sb is None:
            if not sigs:
                return None
            sb = self.stacked_batch(sigs)
        if sb.size == 0:
            return None
        out = None
        if backend == "jax":
            out = self._run_jax_lb(sb)
        if out is None:
            if self._np_lb_core is None:
                self._np_lb_core = self._make_lb_core(np)
            out = self._np_lb_core(sb.tt, sb.st, sb.perm)
        cycles, energy, mx = out
        if not (float(mx) < BATCH_EXACT_LIMIT):
            return None
        return np.asarray(cycles), np.asarray(energy)

    # ------------------------------------------------------------------ #
    # Single-dispatch fused admit+score. One jitted program runs the
    # model's lower-bound core, derives the admit mask, runs the traffic
    # core, and accumulates the model's latency/energy/utilization terms
    # -- so one dispatch per miss-batch covers the whole pipeline and only
    # per-candidate scalars (plus small [B] breakdown arrays) ever return
    # to host. The numpy backend keeps the two-stage flow but runs the
    # SAME terms array program per row, so values are bit-identical.
    # ------------------------------------------------------------------ #
    def _metric_scalarize(self, metric: str, xp):
        """Device-traceable twin of ``EvaluationEngine._scalarize_batch``:
        identical float operations per element (the frequency divisor goes
        through :func:`exact_divisor`), so on-device admit/reject decisions
        are bit-identical to the host filter."""
        freq = self.arch.frequency_hz
        if metric == "latency":
            return lambda cyc, en: cyc
        if metric == "energy":
            return lambda cyc, en: en
        if metric == "edp":
            return lambda cyc, en: (en * 1e-12) * (cyc / exact_divisor(xp, freq))
        return lambda cyc, en: cyc * 0.0

    def _make_fused_core(self, xp, lax, lb_builder, terms, metric: str):
        """Build the (tt, st, perm, incumbent) -> (admit[B], lb_guard,
        latency[B], energy[B], util[B], score_guard, extras) program.

        ``lb_builder(xp, lax)`` yields the model's admission-bound core
        (``CostModel.batch_admit_core_builder``); ``terms`` is the model's
        cost-terms program (``CostModel.batch_cost_terms_fn``). Both guard
        maxes come back so the host can fall back exactly where the
        two-stage path would (lb guard -> scalar bound; score guard ->
        scalar/numpy scoring of the admitted subset).
        """
        lb_core = lb_builder(xp, lax)
        traffic_core = self._make_batch_core(xp, lax)
        scalarize = self._metric_scalarize(metric, xp)

        def core(tt, st, perm, incumbent):
            lb_cyc, lb_en, lb_mx = lb_core(tt, st, perm)
            admit = scalarize(lb_cyc, lb_en) < incumbent
            out = traffic_core(tt, st, perm)
            bt = BatchTraffic(
                compute_cycles=out[0],
                total_trips=out[1],
                par=out[2],
                inst_at=out[3],
                tt=out[4],
                st=out[5],
                fans=out[6],
                rows=tuple(DsTrafficBatch(*r) for r in out[7]),
            )
            latency, energy, util, score_mx, extras = terms(bt, xp)
            return admit, lb_mx, latency, energy, util, score_mx, extras

        return core

    def build_fused_runner(self, lb_builder, terms, metric: str, cache_key=None):
        """Jitted single-dispatch admit+score runner for one (model,
        metric): ``run(sb, incumbent) -> (admit[B] bool, lb_guard float,
        latency[B], energy[B], util[B], score_guard float, extras)`` as
        host numpy, or None after a device fault (:func:`device_faults`;
        the engine then keeps the two-stage flow). The stacked
        batch is uploaded once and padded to a power of two (padding
        repeats row 0, a real candidate, so neither guard can trip on
        padding); only [B]-sized result arrays cross back to host.

        ``cache_key`` (model store-key parts + metric, from the engine)
        memoizes the runner on the context so repeated searches over the
        same (problem, arch, model, metric) reuse the compiled program
        instead of re-tracing per engine.
        """
        if self._jax_failed:
            return None
        if cache_key is not None:
            cached = self._fused_runners.get(cache_key)
            if cached is not None:
                return cached
        try:
            jax = self._ensure_jax()
        except device_faults():
            self._jax_failed = True
            return None
        from jax import lax
        import jax.numpy as jnp

        # Donation mirrors the traffic core: XLA may reuse the batch
        # matrices' device memory on accelerator backends (unsupported
        # on CPU); the incumbent scalar (arg 3) is never donated.
        donate = (0, 1, 2) if jax.default_backend() != "cpu" else ()
        core = CompiledCore(
            self._make_fused_core(jnp, lax, lb_builder, terms, metric),
            donate_argnums=donate,
        )

        def run(sb: StackedBatch, incumbent: float):
            if self._jax_failed:
                return None
            try:
                with x64():
                    tt, st, perm, B = self._jax_device_padded(sb)
                    inc = jnp.asarray(float(incumbent), dtype=jnp.float64)
                    self.jax_dispatches += 1
                    _record_trace(
                        ("ctx-fused", id(self), cache_key), int(tt.shape[0])
                    )
                    out = core(tt, st, perm, inc)
                if donate:
                    sb.devp = None  # donated away; fallbacks re-upload
                return _fused_to_host(out, B)
            except device_faults():
                self._jax_failed = True
                return None

        if cache_key is not None:
            self._fused_runners[cache_key] = run
        return run

    def build_generic_fused_runner(self, generic, metric: str, cache_key=None):
        """Shape-generic twin of :meth:`build_fused_runner`: the jitted
        program is compiled ONCE per (shape class, model structure,
        metric) process-wide (``_GENERIC_PROGRAMS``) and this context's
        values enter as a traced parameter pack, so content-different
        sweep points in one shape class share a single trace.

        ``generic`` is ``CostModel.batch_cost_terms_generic`` output:
        ``(model_struct_key, model_params, terms)`` with
        ``terms(bt, xp, p)``. Returns a :class:`GenericFusedRunner`
        (same call protocol as the per-context runner) or None after a
        device fault (callers fall back exactly as for the per-context
        builder). ``cache_key`` memoizes the runner on
        the context as the lookup tier ABOVE the global program cache.
        """
        if self._jax_failed:
            return None
        if cache_key is not None:
            cached = self._fused_runners.get(cache_key)
            if cached is not None:
                return cached
        model_key, model_params, terms = generic
        try:
            jax = self._ensure_jax()
        except device_faults():
            self._jax_failed = True
            return None
        from jax import lax
        import jax.numpy as jnp

        skey = self.shape_class_key()
        pkey = ("generic-fused", skey, model_key, metric)
        entry = _GENERIC_PROGRAMS.get(pkey)
        if entry is None:
            donate = (0, 1, 2) if jax.default_backend() != "cpu" else ()
            core = CompiledCore(
                _make_generic_fused_core(skey, terms, metric, jnp, lax),
                donate_argnums=donate,
            )
            entry = (core, bool(donate))
            _GENERIC_PROGRAMS[pkey] = entry
        params = dict(self.shape_params())
        params.update(model_params)
        runner = GenericFusedRunner(self, entry[0], params, pkey, entry[1])
        if cache_key is not None:
            self._fused_runners[cache_key] = runner
        return runner

    def chains_lower_bound(
        self, chain_list, orders, incumbent: float = math.inf, scalarize=None
    ) -> Tuple[float, float]:
        """``signature_lower_bound`` computed directly off per-dim divisor
        chains (in problem-dim order) + per-level orders -- the genome fast
        path, skipping signature construction for candidates that will be
        pruned. Returns exactly what ``signature_lower_bound`` returns for
        the equivalent signature, EXCEPT when the caller provides
        ``(incumbent, scalarize)`` and the compute-cycles term alone already
        proves domination: then the boundary/energy refinements are skipped
        and a smaller (still valid) energy floor is returned.
        """
        sizes = self._size_tuple
        D = len(sizes)
        n = self.n_levels
        trips_rows: List[List[int]] = [[1] * D for _ in range(n)]
        total_trips = 1
        leaf_macs = 1
        last = 2 * n - 2
        for j in range(D):
            ch = chain_list[j]
            prev = sizes[j]
            for i in range(n):
                q = prev // (ch[2 * i] or 1)
                if q > 1:
                    trips_rows[i][j] = q
                    total_trips *= q
                prev = ch[2 * i + 1]
            leaf_macs *= max(1, ch[last])
        cycles = total_trips * math.ceil(leaf_macs / self.macs_per_cycle)

        energy = self._lb_energy_base
        if scalarize is not None and scalarize(cycles, energy) >= incumbent:
            # already dominated by the cheap floor -- skip the refinements
            return cycles, energy
        dc = self._lb_dram_child
        dc_boundary = 0.0
        if dc is not None:
            order_idx = self._order_idx
            dim_index = self._dim_index
            tl: List[Tuple[int, int]] = []
            for i in range(dc + 1):
                row = trips_rows[i]
                order = orders[i]
                oidx = order_idx.get(order)
                if oidx is None:
                    oidx = tuple(dim_index[d] for d in order)
                    order_idx[order] = oidx
                for j in oidx:
                    q = row[j]
                    if q > 1:
                        tl.append((j, q))
            fans: List[Tuple[int, int]] = []
            for i in range(dc):
                k = 2 * i
                for j in range(D):
                    ch = chain_list[j]
                    f = max(1, ch[k]) // max(1, ch[k + 1])
                    if f > 1:
                        fans.append((j, f))
            kdc = 2 * dc
            tre = self._top_read_e
            twe = self._top_write_e
            for ds_idx, (ds, _r) in enumerate(self.ds_rel):
                rel_set = self._ds_rel_sets[ds_idx]
                rp = 1
                ip = 1
                lastrel = 1
                for j, q in tl:
                    if j in rel_set:
                        rp *= q
                        lastrel = ip
                    else:
                        ip *= q
                changes = rp * lastrel
                unique = rp
                wb, axes, _rel = self._ds_axes_idx[ds_idx]
                foot = 1
                for ax in axes:
                    span = 1
                    for coeff, j in ax:
                        span += coeff * (max(1, chain_list[j][kdc]) - 1)
                    foot *= span
                rel_sp = 1
                for j, f in fans:
                    if j in rel_set:
                        rel_sp *= f
                cf = changes * foot
                if ds.is_output:
                    rmw = max(0, changes - unique) * foot
                    energy += cf * rel_sp * wb * twe + rmw * rel_sp * wb * tre
                    dc_boundary += (cf + rmw) * wb
                else:
                    energy += cf * rel_sp * wb * tre
                    dc_boundary += cf * wb

        for level, cyc_per_byte in self._lb_bw_levels:
            if level == dc:
                cyc = dc_boundary * cyc_per_byte  # exact fill bytes there
                if cyc > cycles:
                    cycles = cyc
                continue
            kl = 2 * level
            b = 0
            for wb, axes, rel in self._ds_axes_idx:
                unique = 1
                for r in range(level + 1):
                    row = trips_rows[r]
                    for j in rel:
                        unique *= row[j]
                foot = 1
                for ax in axes:
                    span = 1
                    for coeff, j in ax:
                        span += coeff * (max(1, chain_list[j][kl]) - 1)
                    foot *= span
                b += unique * foot * wb
            cyc = b * cyc_per_byte
            if cyc > cycles:
                cycles = cyc
        return cycles, energy

    # Mapping-object conveniences (tests / non-engine callers)
    def cheap_compute_cycles(self, mapping: Mapping) -> float:
        return self.signature_compute_cycles(mapping_signature(mapping, self.dims))

    def min_boundary_bytes(self, mapping: Mapping, level: int) -> float:
        return self.signature_min_boundary_bytes(
            mapping_signature(mapping, self.dims), level
        )


def _tree_slice(out, B: int):
    """Slice the leading (batch) axis of every array in the core's output
    tuple to ``B`` entries (drops JAX padding)."""
    compute_cycles, total_trips, par, inst_at, tt, st, fans, rows = out
    return (
        compute_cycles[:B],
        total_trips[:B],
        par[:B],
        inst_at[:B],
        tt[:B],
        st[:B],
        fans[:B],
        tuple(tuple(a[:B] for a in r) for r in rows),
    )


# ---------------------------------------------------------------------- #
# Shape-generic array programs. These are the per-context closures
# (``_make_lb_core`` / ``_make_batch_core`` / the fused admit+score core)
# re-derived from a structural ShapeClassKey plus a traced parameter pack
# ``p`` (see ``AnalysisContext.shape_class_key`` / ``shape_params``): the
# loop/branch/reshape STRUCTURE comes from the key, every VALUE from
# ``p``. Because the float operations run in the identical order with
# identical values, the per-row results are bit-identical to the
# per-context closures -- but one compiled program now serves every
# context in the shape class.
# ---------------------------------------------------------------------- #
def _axes_coeff_layout(axes_struct):
    """Per ds/axis/term: ``(flat coeff index, dim index)`` -- the build
    order of ``shape_params()['coeffs']``, so generic span math consumes
    coefficients exactly where the closures baked them in."""
    layout = []
    fi = 0
    for axes in axes_struct:
        ds_list = []
        for ax in axes:
            ax_list = []
            for j in ax:
                ax_list.append((fi, j))
                fi += 1
            ds_list.append(ax_list)
        layout.append(ds_list)
    return layout


def _generic_ds_foot(coeff_layout, k, ttf_lvl, xp, p):
    """Generic :func:`batch_projection_footprint`: identical span math
    over ``[..., D]`` tiles with traced coefficients."""
    shape = ttf_lvl.shape[:-1]
    foot = xp.ones(shape, dtype=xp.float64)
    for ax in coeff_layout[k]:
        span = xp.ones(shape, dtype=xp.float64)
        for ci, j in ax:
            span = span + p["coeffs"][ci] * (ttf_lvl[..., j] - 1.0)
        foot = foot * span
    return foot


def _make_generic_lb_core(skey, xp, lax=None):
    """Shape-generic ``_make_lb_core``: ``core(tt, st, perm, p) ->
    (cycles[B], energy_pj[B], guard_max)``."""
    n, D, K, _real_levels, _real_parent, ds_out, axes_struct, dc, bw_lvls = skey
    if dc < 0:
        dc = None
    coeff_layout = _axes_coeff_layout(axes_struct)
    pos_seq = np.arange(n * D)

    def core(tt, st, perm, p):
        B = tt.shape[0]
        rel_stack = p["rel"]
        wb = p["wb"]
        tt = xp.maximum(tt, 1)
        st = xp.maximum(st, 1)
        sizes_row = xp.reshape(p["sizes"], (1, 1, D))
        outer = xp.concatenate(
            [xp.broadcast_to(sizes_row, (B, 1, D)), st[:, :-1, :]], axis=1
        )
        trips = xp.maximum(outer // tt, 1)
        tripsf = trips.astype(xp.float64)
        total_trips = xp.prod(tripsf.reshape(B, n * D), axis=1)
        leaf_macs = xp.prod(tt[:, -1, :].astype(xp.float64), axis=1)
        cycles = total_trips * xp.ceil(leaf_macs / exact_divisor(xp, p["mpc"]))
        e_pairs = []
        mx = xp.maximum(xp.maximum(total_trips, leaf_macs), cycles)

        dc_boundary = None
        if dc is not None:
            S = (dc + 1) * D
            perm_pref = perm[:, : dc + 1, :]
            tseqf = (
                xp.take_along_axis(trips[:, : dc + 1, :], perm_pref, axis=2)
                .reshape(B, S)
                .astype(xp.float64)
            )
            rel_seq = rel_stack[:, perm_pref.reshape(B, S)]  # [K, B, S]
            present = (tseqf > 1.0)[None, :, :]
            relm = rel_seq & present
            irrm = (~rel_seq) & present
            tseq_b = xp.broadcast_to(tseqf[None, :, :], (K, B, S))
            unique = xp.prod(xp.where(relm, tseq_b, 1.0), axis=2)  # [K, B]
            irrprod = xp.cumprod(xp.where(irrm, tseq_b, 1.0), axis=2)
            idx = xp.where(relm, pos_seq[None, None, :S], -1)
            lastrel = xp.max(idx, axis=2)
            gathered = xp.take_along_axis(
                irrprod, xp.maximum(lastrel, 0)[:, :, None], axis=2
            )[:, :, 0]
            changes = unique * xp.where(lastrel >= 0, gathered, 1.0)
            ttf_dc = tt[:, dc, :].astype(xp.float64)
            if dc > 0:
                fans_pref = xp.maximum(tt[:, :dc, :] // st[:, :dc, :], 1).astype(
                    xp.float64
                )
            dc_boundary = xp.zeros(B, dtype=xp.float64)
            for k in range(K):
                foot = _generic_ds_foot(coeff_layout, k, ttf_dc, xp, p)
                if dc > 0:
                    rel_sp = xp.prod(
                        xp.where(
                            rel_stack[k][None, None, :], fans_pref, 1.0
                        ).reshape(B, dc * D),
                        axis=1,
                    )
                else:
                    rel_sp = xp.ones(B, dtype=xp.float64)
                cf = changes[k] * foot
                mx = xp.maximum(mx, changes[k])
                t1 = cf * rel_sp * wb[k]
                mx = xp.maximum(mx, t1)
                if ds_out[k]:
                    rmw = xp.maximum(changes[k] - unique[k], 0.0) * foot
                    t2 = rmw * rel_sp * wb[k]
                    mx = xp.maximum(mx, t2)
                    e_pairs.append((t1 * p["twe"], t2 * p["tre"]))
                    dc_boundary = dc_boundary + (cf + rmw) * wb[k]
                else:
                    e_pairs.append((t1 * p["tre"], 0.0))
                    dc_boundary = dc_boundary + cf * wb[k]
            mx = xp.maximum(mx, dc_boundary)
        energy = ordered_pair_sum(
            xp, xp.full((B,), p["e_base"], dtype=xp.float64), e_pairs
        )

        for bw_pos, level in enumerate(bw_lvls):
            cyc_per_byte = p["bw_cpb"][bw_pos]
            if level == dc:
                cycles = xp.maximum(cycles, dc_boundary * cyc_per_byte)
                continue
            ttf_lvl = tt[:, level, :].astype(xp.float64)
            relprod_lvl = xp.prod(
                xp.where(
                    rel_stack[:, None, None, :],
                    tripsf[None, :, : level + 1, :],
                    1.0,
                ).reshape(K, B, (level + 1) * D),
                axis=2,
            )
            b = xp.zeros(B, dtype=xp.float64)
            for k in range(K):
                term = (
                    relprod_lvl[k]
                    * _generic_ds_foot(coeff_layout, k, ttf_lvl, xp, p)
                    * wb[k]
                )
                mx = xp.maximum(mx, term)
                b = b + term
            mx = xp.maximum(mx, b)
            cycles = xp.maximum(cycles, b * cyc_per_byte)
        return cycles, energy, xp.max(mx)

    return core


def _make_generic_batch_core(skey, xp, lax=None):
    """Shape-generic ``_make_batch_core``: ``core(tt, st, perm, p) ->``
    the stacked-traffic 8-tuple."""
    n, D, K, real_levels, real_parent, ds_out, axes_struct, _dc, _bw = skey
    real_levels = list(real_levels)
    L = len(real_levels)
    coeff_layout = _axes_coeff_layout(axes_struct)
    ends = np.asarray([(i + 1) * D - 1 for i in real_levels])
    real_arr = np.asarray(real_levels)
    parent_arr = np.asarray(
        [real_parent[i] if real_parent[i] >= 0 else i for i in real_levels]
    )
    pos_seq = np.arange(n * D)

    def core(tt, st, perm, p):
        B = tt.shape[0]
        rel_stack = p["rel"]
        tt = xp.maximum(tt, 1)
        st = xp.maximum(st, 1)
        sizes_row = xp.reshape(p["sizes"], (1, 1, D))
        outer = xp.concatenate(
            [xp.broadcast_to(sizes_row, (B, 1, D)), st[:, :-1, :]], axis=1
        )
        trips = xp.maximum(outer // tt, 1)
        fans = xp.maximum(tt // st, 1)
        tripsf = trips.astype(xp.float64)
        fansf = fans.astype(xp.float64)
        total_trips = xp.prod(tripsf.reshape(B, n * D), axis=1)
        leaf_macs = xp.prod(tt[:, -1, :].astype(xp.float64), axis=1)
        compute_cycles = total_trips * xp.ceil(
            leaf_macs / exact_divisor(xp, p["mpc"])
        )
        par = xp.prod(fansf.reshape(B, n * D), axis=1)
        lvl_all = xp.prod(fansf, axis=2)  # [B, n]
        cp_all = xp.cumprod(lvl_all, axis=1)
        inst_at = xp.concatenate(
            [xp.ones((B, 1), dtype=xp.float64), cp_all[:, :-1]], axis=1
        )
        perm_flat = perm.reshape(B, n * D)
        tseqf = xp.take_along_axis(trips, perm, axis=2).reshape(B, n * D).astype(
            xp.float64
        )
        rel_seq = rel_stack[:, perm_flat]  # [K, B, S]
        present = (tseqf > 1.0)[None, :, :]
        relm = rel_seq & present
        irrm = (~rel_seq) & present
        tseq_b = xp.broadcast_to(tseqf[None, :, :], (K, B, n * D))
        relprod = xp.cumprod(xp.where(relm, tseq_b, 1.0), axis=2)
        irrprod = xp.cumprod(xp.where(irrm, tseq_b, 1.0), axis=2)
        idx = xp.where(relm, pos_seq[None, None, :], -1)
        if lax is None:
            lastrel = np.maximum.accumulate(idx, axis=2)
        else:
            lastrel = lax.cummax(idx, axis=2)
        gathered = xp.take_along_axis(irrprod, xp.maximum(lastrel, 0), axis=2)
        ip = xp.where(lastrel >= 0, gathered, 1.0)
        unique = relprod[:, :, ends]  # [K, B, L]
        changes = unique * ip[:, :, ends]
        lvl_rel = xp.prod(
            xp.where(rel_stack[:, None, None, :], fansf[None], 1.0),
            axis=3,
        )  # [K, B, n]
        cp_rel = xp.cumprod(lvl_rel, axis=2)
        srel_excl = xp.concatenate(
            [xp.ones((K, B, 1), dtype=xp.float64), cp_rel[:, :, :-1]], axis=2
        )
        rel_sp = srel_excl[:, :, real_arr] / srel_excl[:, :, parent_arr]
        ttf_real = tt[:, real_arr, :].astype(xp.float64)  # [B, L, D]
        rows = []
        for k in range(K):
            foot = _generic_ds_foot(coeff_layout, k, ttf_real, xp, p)
            cf = changes[k] * foot
            if ds_out[k]:
                rmw = xp.maximum(changes[k] - unique[k], 0.0) * foot
                rows.append((rmw, cf, rmw * rel_sp[k], cf * rel_sp[k], foot))
            else:
                z = xp.zeros_like(cf)
                rows.append((cf, z, cf * rel_sp[k], z, foot))
        return compute_cycles, total_trips, par, inst_at, tt, st, fans, tuple(rows)

    return core


def generic_hierarchical_energy(real_levels, real_parent, K, bt, xp, p, hop=False):
    """Shape-generic :func:`batch_hierarchical_energy`: the identical
    level-walk float-operation sequence with energies / word widths /
    precomputed innermost+MAC terms read from the parameter pack
    (``lvl_read_e`` / ``lvl_write_e`` / ``wb`` / ``l1_terms`` /
    ``mac_term`` / ``hop``). ``real_parent`` uses -1 for parentless.
    Returns ``(energy[B], noc_energy[B] or None, mx)``."""
    inst_at = bt.inst_at
    mx = xp.zeros(())
    e_terms = []
    noc_terms = [] if hop else None
    for k in range(K):
        wbk = p["wb"][k]
        r = bt.rows[k]
        for pos, i in enumerate(real_levels):
            t = r.fills[:, pos] * inst_at[:, i] * wbk
            mx = xp.maximum(mx, xp.max(t))
            e_terms.append(t * p["lvl_write_e"][i])
            t = r.drains[:, pos] * inst_at[:, i] * wbk
            mx = xp.maximum(mx, xp.max(t))
            e_terms.append(t * p["lvl_read_e"][i])
            parent_idx = real_parent[i]
            if parent_idx >= 0:
                n_parent = inst_at[:, parent_idx]
                t = r.parent_reads[:, pos] * n_parent * wbk
                mx = xp.maximum(mx, xp.max(t))
                e_terms.append(t * p["lvl_read_e"][parent_idx])
                t = r.parent_writes[:, pos] * n_parent * wbk
                mx = xp.maximum(mx, xp.max(t))
                e_terms.append(t * p["lvl_write_e"][parent_idx])
                if noc_terms is not None:
                    t = (r.fills[:, pos] + r.drains[:, pos]) * inst_at[:, i] * wbk
                    mx = xp.maximum(mx, xp.max(t))
                    noc_terms.append(t * p["hop"])
        e_terms.append(p["l1_terms"][k])
    e_terms.append(p["mac_term"])
    energy = ordered_sum(xp, xp.zeros_like(bt.compute_cycles), e_terms)
    noc_energy = (
        ordered_sum(xp, xp.zeros_like(energy), noc_terms)
        if noc_terms is not None
        else None
    )
    return energy, noc_energy, mx


def _generic_scalarize(metric: str, xp):
    """Shape-generic ``_metric_scalarize``: frequency comes from the
    parameter pack (same exact-divisor barrier, so decisions stay
    bit-identical to the host filter)."""
    if metric == "latency":
        return lambda cyc, en, p: cyc
    if metric == "energy":
        return lambda cyc, en, p: en
    if metric == "edp":
        return lambda cyc, en, p: (en * 1e-12) * (
            cyc / exact_divisor(xp, p["freq"])
        )
    return lambda cyc, en, p: cyc * 0.0


def _make_generic_fused_core(skey, terms, metric: str, xp, lax):
    """Shape-generic fused admit+score core: ``core(tt, st, perm,
    incumbent, p) -> (admit, lb_guard, latency, energy, util,
    score_guard, extras)``.

    The calibration scale enters as the traced ``p['calib_scale']``
    parameter (1.0 when uncalibrated -- ``x * 1.0`` is bit-exact, so the
    uncalibrated program matches the unscaled per-context path and ONE
    compiled program serves every calibration value). Extras additionally
    carry the raw admission-bound arrays (``lb_cycles`` / ``lb_energy``,
    already calibrated) and the scalarized ``metric_score`` so
    device-resident loops can replay admission and selection host-side
    without a second dispatch.
    """
    lb_core = _make_generic_lb_core(skey, xp, lax)
    traffic_core = _make_generic_batch_core(skey, xp, lax)
    scalarize = _generic_scalarize(metric, xp)

    def core(tt, st, perm, incumbent, p):
        lb_cyc, lb_en, lb_mx = lb_core(tt, st, perm, p)
        lb_cyc = lb_cyc * p["calib_scale"]
        admit = scalarize(lb_cyc, lb_en, p) < incumbent
        out = traffic_core(tt, st, perm, p)
        bt = BatchTraffic(
            compute_cycles=out[0],
            total_trips=out[1],
            par=out[2],
            inst_at=out[3],
            tt=out[4],
            st=out[5],
            fans=out[6],
            rows=tuple(DsTrafficBatch(*r) for r in out[7]),
        )
        latency, energy, util, score_mx, extras = terms(bt, xp, p)
        latency = latency * p["calib_scale"]
        extras = dict(extras)
        extras["lb_cycles"] = lb_cyc
        extras["lb_energy"] = lb_en
        extras["metric_score"] = scalarize(latency, energy, p)
        return admit, lb_mx, latency, energy, util, score_mx, extras

    return core


class GenericFusedRunner:
    """Dispatch handle for one (context, model, metric) over a SHARED
    shape-generic compiled program: the program lives in the process-wide
    ``_GENERIC_PROGRAMS`` cache keyed by (shape class, model structure,
    metric); this object carries the context's parameter pack (uploaded
    to device once, lazily) and implements the same ``(sb, incumbent) ->
    7-tuple or None`` protocol as ``build_fused_runner``'s closures, plus
    the device-resident extensions the search loops use
    (:meth:`dispatch_device`, :meth:`is_traced`)."""

    supports_precompute = True

    def __init__(self, ctx, core, params, pkey, donates: bool) -> None:
        self._ctx = ctx
        self._core = core
        self._params = params
        self._pkey = pkey
        self._donates = donates
        self._dev_params = None
        self._dev_inf = None  # cached device scalar for incumbent=inf

    @property
    def program_key(self):
        return self._pkey

    def is_traced(self, padded_batch: int) -> bool:
        """Whether the shared program has already been traced at this
        pow2 bucket (by ANY context in the shape class) -- lets warmup
        skip re-dispatching buckets the class already covers."""
        return (self._pkey, int(padded_batch)) in _TRACE_COMBOS

    def dispatch_device(self, sb: StackedBatch):
        """One fused dispatch, results left ON DEVICE: returns the raw
        (possibly padded -- callers slice to the batch size) output
        tuple, or None on failure. Device-resident loops use this to
        fetch only small scalars per generation and defer full
        materialization to the K-generation sync."""
        ctx = self._ctx
        if ctx._jax_failed:
            return None
        try:
            return self._dispatch(sb, math.inf)[0]
        except device_faults():
            ctx._jax_failed = True
            return None

    def _dispatch(self, sb: StackedBatch, incumbent: float):
        """One fused dispatch under x64: (raw device outputs, batch size)."""
        ctx = self._ctx
        jax = ctx._ensure_jax()
        import jax.numpy as jnp

        with x64():
            tt, st, perm, B = ctx._jax_device_padded(sb)
            if self._dev_params is None:
                self._dev_params = jax.device_put(self._params)
            if incumbent == math.inf:
                if self._dev_inf is None:
                    self._dev_inf = jnp.asarray(math.inf, dtype=jnp.float64)
                inc = self._dev_inf
            else:
                inc = jnp.asarray(float(incumbent), dtype=jnp.float64)
            ctx.jax_dispatches += 1
            _record_trace(self._pkey, int(tt.shape[0]))
            out = self._core(tt, st, perm, inc, self._dev_params)
        if self._donates:
            sb.devp = None  # donated away; fallbacks re-upload
        return out, B

    def __call__(self, sb: StackedBatch, incumbent: float):
        ctx = self._ctx
        if ctx._jax_failed:
            return None
        try:
            return _fused_to_host(*self._dispatch(sb, incumbent))
        except device_faults():
            ctx._jax_failed = True
            return None


def _fused_to_host(out, B: int):
    """Host copy of one fused dispatch's outputs in one pull, cut to the
    ``B`` real rows: ``(admit, lb_guard, latency, energy, util,
    score_guard, extras)``."""
    admit, lb_mx, latency, energy, util, score_mx, extras = host_pull(out)
    return (
        admit[:B],
        float(lb_mx),
        require_float64(latency)[:B],
        energy[:B],
        util[:B],
        float(score_mx),
        {k: v[:B] for k, v in extras.items()},
    )


# ---------------------------------------------------------------------- #
# Two-tier context cache. The fast tier is identity-keyed: entries pin
# strong references to the exact (problem, arch) objects they were looked
# up with, so an id() key can never alias a dead object while resident.
# Identity misses fall back to a CONTENT digest (problems and archs with
# equal cost-relevant content produce bit-identical analyses), so the many
# content-equal instances a figure sweep builds -- dnn_layers() re-invoked
# per benchmark, repeated accelerator constructors -- all alias ONE
# context, sharing its numpy cores, jitted programs, fused runners and
# footprint memos instead of re-tracing per instance. Digests are memoized
# on the objects themselves (falling back to recomputation for immutable
# types).
# ---------------------------------------------------------------------- #
_CTX_CACHE: "OrderedDict[Tuple[int, int], Tuple[Problem, Architecture, AnalysisContext]]" = (
    OrderedDict()
)
_CTX_BY_CONTENT: "OrderedDict[Tuple[str, str], AnalysisContext]" = OrderedDict()
_CTX_CACHE_SIZE = 64


def _content_digest(obj, canon) -> str:
    d = getattr(obj, "_ctx_digest", None)
    if d is None:
        import hashlib
        import json

        d = hashlib.sha256(
            json.dumps(canon(obj), sort_keys=True, default=repr).encode()
        ).hexdigest()
        try:
            obj._ctx_digest = d
        except AttributeError:
            pass  # frozen/slots type: recompute next time
    return d


def get_context(problem: Problem, arch: Architecture) -> AnalysisContext:
    key = (id(problem), id(arch))
    entry = _CTX_CACHE.get(key)
    if entry is not None and entry[0] is problem and entry[1] is arch:
        _CTX_CACHE.move_to_end(key)
        return entry[2]
    from repro.core.cost.store import _canon_arch, _canon_problem

    ckey = (
        _content_digest(problem, _canon_problem),
        _content_digest(arch, _canon_arch),
    )
    ctx = _CTX_BY_CONTENT.get(ckey)
    if ctx is None:
        ctx = AnalysisContext(problem, arch)
        _CTX_BY_CONTENT[ckey] = ctx
        while len(_CTX_BY_CONTENT) > _CTX_CACHE_SIZE:
            _CTX_BY_CONTENT.popitem(last=False)
    else:
        _CTX_BY_CONTENT.move_to_end(ckey)
    _CTX_CACHE[key] = (problem, arch, ctx)
    while len(_CTX_CACHE) > _CTX_CACHE_SIZE:
        _CTX_CACHE.popitem(last=False)
    return ctx


def analyze(problem: Problem, mapping: Mapping, arch: Architecture) -> AccessProfile:
    return get_context(problem, arch).analyze(mapping)


def hierarchical_lower_bound(
    problem: Problem, mapping: Optional[Mapping], arch: Architecture, sig=None
) -> Tuple[float, float]:
    """(cycles, energy_pj) lower bounds for the hierarchical models.

    Valid for both the Timeloop-like and MAESTRO-like models:

      * cycles: both take max(compute, per-level fill time) or add
        non-negative terms on top, and per-level fill bytes are bounded
        below by ``unique x tile footprint`` per data space;
      * energy: both include the innermost operand movement and MAC energy
        exactly, plus non-negative buffer/NoC terms.

    ``sig`` short-circuits signature extraction when the caller (the
    evaluation engine) already computed it for the cache probe.
    """
    ctx = get_context(problem, arch)
    if sig is None:
        sig = mapping_signature(mapping, ctx.dims)
    return ctx.signature_lower_bound(sig)


def batch_hierarchical_energy(
    ctx: AnalysisContext,
    arch: Architecture,
    problem: Problem,
    bt: BatchTraffic,
    hop_pj_byte: Optional[float] = None,
    xp=np,
):
    """Shared level-walk energy accumulation for the hierarchical models'
    ``evaluate_signature_batch`` (timeloop_like and maestro_like run the
    identical sequence of float operations here; maestro additionally
    accumulates the NoC delivery term, enabled via ``hop_pj_byte``).

    ``xp`` selects the array stack: numpy for host-side scoring, jax.numpy
    when the walk runs inside the fused single-dispatch jitted core (the
    per-element float-operation order is identical either way).

    Returns ``(energy[B], noc_energy[B] or None, mac_term, mx)`` where
    ``energy`` already includes the innermost-operand and MAC terms (the
    scalar paths add them in exactly this order) and ``mx`` is an xp
    scalar holding the max of every guarded integer-valued product (the
    caller folds it into its BATCH_EXACT_LIMIT check host-side). NoC
    energy is NOT folded into ``energy`` -- maestro adds it after the MAC
    term, as its scalar path does.
    """
    clusters = arch.clusters
    real_levels = ctx.real_levels
    real_parent = ctx.real_parent
    leaf = clusters[-1]
    inst_at = bt.inst_at
    mx = xp.zeros(())
    # The access-count products (t) are integer-valued and exact, but the
    # per-byte energies are fractional: each ``t * energy`` product must be
    # ROUNDED before it joins the accumulator, exactly as numpy does.
    # Addends are collected and summed through :func:`ordered_sum`, whose
    # scan structure stops XLA's LLVM backend from contracting
    # ``acc + t * e`` into an FMA (one rounding instead of two) on the
    # fused jitted path.
    e_terms = []
    noc_terms = [] if hop_pj_byte is not None else None
    for k, ds in enumerate(problem.data_spaces):
        wb = ds.word_bytes
        r = bt.rows[k]
        for pos, i in enumerate(real_levels):
            cl = clusters[i]
            t = r.fills[:, pos] * inst_at[:, i] * wb
            mx = xp.maximum(mx, xp.max(t))
            e_terms.append(t * cl.write_energy)
            t = r.drains[:, pos] * inst_at[:, i] * wb
            mx = xp.maximum(mx, xp.max(t))
            e_terms.append(t * cl.read_energy)
            parent_idx = real_parent[i]
            if parent_idx is not None:
                parent = clusters[parent_idx]
                n_parent = inst_at[:, parent_idx]
                t = r.parent_reads[:, pos] * n_parent * wb
                mx = xp.maximum(mx, xp.max(t))
                e_terms.append(t * parent.read_energy)
                t = r.parent_writes[:, pos] * n_parent * wb
                mx = xp.maximum(mx, xp.max(t))
                e_terms.append(t * parent.write_energy)
                if noc_terms is not None:
                    # every DELIVERED copy pays a NoC hop (multicast reads
                    # the parent once; see maestro_like)
                    t = (r.fills[:, pos] + r.drains[:, pos]) * inst_at[:, i] * wb
                    mx = xp.maximum(mx, xp.max(t))
                    noc_terms.append(t * hop_pj_byte)
        e_terms.append(ctx.l1_reads[ds.name] * wb * leaf.read_energy)
    mac_term = problem.macs * leaf.mac_energy
    e_terms.append(mac_term)
    energy = ordered_sum(xp, xp.zeros_like(bt.compute_cycles), e_terms)
    noc_energy = (
        ordered_sum(xp, xp.zeros_like(energy), noc_terms)
        if noc_terms is not None
        else None
    )
    return energy, noc_energy, mac_term, mx


def boundary_bytes_per_instance(
    prof: AccessProfile, problem: Problem, level: int
) -> float:
    """Total fill+drain bytes crossing INTO one instance of `level`."""
    total = 0.0
    for ds in problem.data_spaces:
        lt = prof.traffic.get((ds.name, level))
        if lt is None:
            continue
        total += (lt.fills_per_instance + lt.drains_per_instance) * ds.word_bytes
    return total
