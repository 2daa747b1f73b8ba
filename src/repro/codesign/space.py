"""KernelSpace: the per-kernel half of the co-design contract.

A kernel package defines ONE :class:`KernelSpace` subclass and registers a
singleton instance. The space owns everything the unified planner
(:func:`repro.codesign.planner.plan`) needs to turn a shape into a legal
BlockConfig:

  * ``problem(shape)``      -- the Union :class:`Problem` whose C1 temporal
                               tile IS the kernel's BlockSpec,
  * ``constraints(shape)``  -- mapper constraints (MXU alignment, ...),
  * ``arch(vmem_budget)``   -- the cluster hierarchy mapped onto
                               (``tpu_chip`` by default); legality rule R3
                               at C1 makes every legal mapping a valid
                               BlockSpec within the VMEM budget,
  * ``decode(mapping, shape)``   -- read the BlockConfig out of the C1
                               (innermost-level) temporal tile,
  * ``legalize(config, shape, vmem_budget)`` -- repair ANY candidate into
                               a launchable config (divisor tiles, MXU
                               floors, working-set rules) -- this subsumes
                               the three historical per-kernel ``_fix``
                               copies,
  * ``default_config(shape)``    -- the no-search fallback seed (always
                               run through ``legalize``),
  * ``example_inputs``/``run``   -- the calibration hooks ``calibrate.py``
                               uses to benchmark the emitted kernel.

The **VMEM budget convention** is unified here: every space defaults to
:data:`DEFAULT_VMEM_BUDGET`, and every ``legalize`` holds the VMEM the
TPU compiler actually allocates for the kernel
(:func:`pipeline_vmem_bytes`) within it.

``BlockConfig`` is a plain ``Tuple[int, ...]`` in ``decode_dims`` order --
it is stored in plan caches and calibration tables, so it stays a
JSON-friendly value type.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

from repro.core.architecture import Architecture, tpu_chip
from repro.core.constraints import Constraints
from repro.core.mapping import Mapping
from repro.core.mapspace import MapSpace
from repro.core.problem import Problem

BlockConfig = Tuple[int, ...]

#: The TPU v5e compiler's default scoped VMEM limit for one kernel: a
#: pallas_call whose pipeline allocates more is refused at compile time.
SCOPED_VMEM_LIMIT = 16 * (1 << 20)

#: The one VMEM budget every kernel space plans under by default, counted
#: the way the compiler allocates (:func:`pipeline_vmem_bytes`; matmul and
#: flash attention count bf16 operands, the dtype the models run in). It
#: is half of :data:`SCOPED_VMEM_LIMIT`: the same tiles with float32
#: operands at most double the footprint and still compile, and the
#: compiler's own internal scratch (score blocks, temporaries) has room.
#: Kernel-specific overrides go through ``KernelSpace.vmem_budget`` or the
#: ``vmem_budget=`` parameter of :func:`repro.codesign.planner.plan` --
#: never through inline literals.
DEFAULT_VMEM_BUDGET = SCOPED_VMEM_LIMIT // 2


def pipeline_vmem_bytes(blocks: Sequence[int], scratch: Sequence[int] = ()) -> int:
    """VMEM the TPU compiler allocates for one pallas_call's pipeline:
    every in and out block twice (the grid double-buffers them) plus each
    scratch buffer once. Both arguments are byte counts."""
    return 2 * sum(int(b) for b in blocks) + sum(int(s) for s in scratch)


def round_up(x: int, m: int) -> int:
    """Round ``x`` up to a multiple of ``m`` (the single shared copy of
    the helper each kernel ``ops.py`` used to duplicate)."""
    return (x + m - 1) // m * m


def repair_tile(
    b: int,
    dim: int,
    default: int,
    *,
    min_tile: int = 128,
    cap: Optional[int] = None,
) -> int:
    """The shared tile-repair rule (historical ``_fix``): keep ``b`` when
    it is an MXU-worthy exact divisor (``b >= min_tile``, ``dim % b == 0``,
    optionally ``b <= cap``); otherwise fall back to the largest divisor of
    ``dim`` reachable from ``min(default, dim)`` by halving. Always returns
    a legal divisor tile >= 1, for any dim >= 1 (odd, non-pow2, < 128)."""
    if b >= min_tile and dim % b == 0 and (cap is None or b <= cap):
        return int(b)
    d = min(default, dim)
    while d > 1 and dim % d != 0:
        d //= 2
    return max(int(d), 1)


def fit_tiles(
    tiles: Sequence[int],
    dims: Sequence[int],
    footprint,
    budget: int,
    *,
    min_tile: int = 128,
) -> BlockConfig:
    """Shrink divisor ``tiles`` of ``dims`` until ``footprint(tiles)``
    (bytes) fits ``budget``. Each step moves the largest tile (the later
    one on ties) to the next smaller ``min_tile``-multiple divisor of its
    dim, so the result stays legal and MXU-aligned. Raises ValueError when
    no tile can shrink further and the footprint still does not fit."""
    tiles = [int(t) for t in tiles]
    while footprint(tuple(tiles)) > budget:
        pick = None
        for i, (t, d) in enumerate(zip(tiles, dims)):
            smaller = [c for c in range(min_tile, t, min_tile) if d % c == 0]
            if smaller and (pick is None or t >= tiles[pick[0]]):
                pick = (i, smaller[-1])
        if pick is None:
            raise ValueError(
                f"tiles {tuple(tiles)} of {tuple(dims)} need "
                f"{footprint(tuple(tiles))} bytes of VMEM, over the "
                f"{budget}-byte budget, and cannot shrink"
            )
        tiles[pick[0]] = pick[1]
    return tuple(tiles)


class KernelSpace:
    """Base class of the per-kernel co-design contract (see module doc).

    Subclasses set the class attributes and implement the abstract
    methods; instances are stateless singletons registered via
    :func:`register_space`."""

    #: registry key; also the kernel label in plan caches + calibration
    name: str = "kernel"
    #: problem dims whose C1 temporal tile forms the BlockConfig, in order
    decode_dims: Tuple[str, ...] = ()
    #: unified VMEM budget (see DEFAULT_VMEM_BUDGET)
    vmem_budget: int = DEFAULT_VMEM_BUDGET
    #: default planner knobs (overridable per plan() call)
    mapper: str = "heuristic"
    cost_model: str = "timeloop"
    metric: str = "latency"
    search_budget: int = 400  # heuristic climb steps

    # ------------------------------------------------------------------ #
    # mapping space
    # ------------------------------------------------------------------ #
    def problem(self, shape: Sequence[int]) -> Problem:
        raise NotImplementedError

    def constraints(self, shape: Sequence[int]) -> Constraints:
        return Constraints()

    def arch(self, vmem_budget: Optional[int] = None) -> Architecture:
        return tpu_chip(
            vmem_tile_budget=int(vmem_budget or self.vmem_budget)
        )

    # ------------------------------------------------------------------ #
    # mapping -> BlockConfig
    # ------------------------------------------------------------------ #
    def decode(self, mapping: Mapping, shape: Sequence[int]) -> BlockConfig:
        """Read the BlockConfig from the C1 (innermost) temporal tile."""
        leaf = mapping.levels[-1]
        return tuple(int(leaf.tt(d)) for d in self.decode_dims)

    def legalize(
        self,
        config: BlockConfig,
        shape: Sequence[int],
        vmem_budget: Optional[int] = None,
    ) -> BlockConfig:
        raise NotImplementedError

    def default_config(self, shape: Sequence[int]) -> BlockConfig:
        """No-search seed; the planner always legalizes it before use."""
        return tuple(0 for _ in self.decode_dims)

    # ------------------------------------------------------------------ #
    # BlockConfig -> canonical mapping (for cost prediction)
    # ------------------------------------------------------------------ #
    def block_tiles(
        self, shape: Sequence[int], config: BlockConfig
    ) -> Dict[str, int]:
        """Problem-dim -> C1 temporal tile for a given BlockConfig (dims
        omitted here stay fully resident, tile == full extent)."""
        return dict(zip(self.decode_dims, config))

    def canonical_mapping(
        self,
        shape: Sequence[int],
        config: BlockConfig,
        arch: Optional[Architecture] = None,
    ) -> Tuple[Problem, Mapping, Architecture]:
        """The mapping a BlockConfig denotes on this space's hierarchy:
        full problem at the outermost level, the block tile at every level
        below (the Pallas grid iterates full/block steps; the block is
        VMEM-resident). This is what the calibration layer evaluates to
        get the model's predicted cycles for the exact launched config."""
        problem = self.problem(shape)
        arch = arch or self.arch()
        tiles = self.block_tiles(shape, config)
        chains: Dict[str, Tuple[int, ...]] = {}
        for d, full in problem.dims.items():
            t = int(tiles.get(d, full))
            if t <= 0 or full % t != 0:
                raise ValueError(
                    f"{self.name}: block tile {t} does not divide dim "
                    f"{d}={full} (legalize first)"
                )
            chain = [int(full), int(full)]
            for _ in range(arch.n_levels - 1):
                chain += [t, t]
            chains[d] = tuple(chain)
        space = MapSpace(problem, arch, None)
        return problem, space._chain_to_mapping(chains), arch

    # ------------------------------------------------------------------ #
    # calibration hooks (optional; NotImplementedError disables
    # measurement for this space)
    # ------------------------------------------------------------------ #
    def example_inputs(self, shape: Sequence[int], seed: int = 0):
        """Representative inputs for benchmarking at ``shape``."""
        raise NotImplementedError

    def run(self, inputs, config: BlockConfig, interpret: bool = True):
        """Execute the kernel on ``inputs`` with the given BlockConfig;
        return the (unblocked) jax output(s) for ``block_until_ready``."""
        raise NotImplementedError


# ---------------------------------------------------------------------- #
# registry
# ---------------------------------------------------------------------- #
_REGISTRY: Dict[str, KernelSpace] = {}


def register_space(space: KernelSpace) -> KernelSpace:
    """Register a kernel's space singleton (idempotent by name)."""
    _REGISTRY[space.name] = space
    return space


def get_space(name: str) -> KernelSpace:
    if name not in _REGISTRY:
        all_spaces()  # trigger kernel-package registration
    return _REGISTRY[name]


def all_spaces() -> Dict[str, KernelSpace]:
    """All registered spaces, importing the in-repo kernel packages first
    (they register their spaces at import time). Lazy so that the
    codesign core stays importable without jax."""
    import importlib

    for mod in (
        "repro.kernels.matmul.ops",
        "repro.kernels.flash_attention.ops",
        "repro.kernels.ssd_scan.ops",
    ):
        try:
            importlib.import_module(mod)
        except ImportError:  # pragma: no cover - jax-free environment
            pass
    return dict(_REGISTRY)
