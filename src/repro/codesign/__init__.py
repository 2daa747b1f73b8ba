"""Unified HW-SW co-design layer: one planner + calibration loop for all
Pallas kernels (docs/codesign.md).

Before this package, each kernel package hand-rolled its own copy of the
kernel<->mapper bridge (``plan_tiles`` / ``plan_blocks`` / ``plan_chunk``
with duplicated ``_round_up``/``_fix`` repair and divergent VMEM-budget
conventions) and no measured kernel performance ever flowed back into the
cost models. Now:

  * :class:`KernelSpace` (``space.py``) is the one abstraction a kernel
    registers: its mapping ``Problem``, ``Constraints``, a ``decode`` that
    reads the C1 temporal tile out of a Union mapping, a ``legalize``
    repair that turns any candidate into a valid BlockSpec, safe defaults,
    and the shared :data:`DEFAULT_VMEM_BUDGET`, counted the way the TPU
    compiler allocates VMEM (:func:`pipeline_vmem_bytes`).
  * :func:`plan` (``planner.py``) is the single search path all kernels
    tile through: it drives the existing ``union_opt`` /
    ``EvaluationEngine`` machinery and caches finished plans in a
    :class:`~repro.core.cost.store.ResultStore` under a
    constraints-inclusive space key, so warm plan queries answer in O(ms)
    without invoking a mapper search.
  * ``calibrate.py`` closes the loop: it benchmarks the emitted kernel per
    (kernel, shape, BlockConfig), records measured time next to the
    model's predicted cycles in a versioned, corruption-tolerant
    :class:`CalibrationTable`, and produces the
    :class:`~repro.core.cost.base.CostModel` calibration hook
    (``set_calibration``) that rescales predictions and reports per-kernel
    x shape model error.
"""

from repro.codesign.space import (  # noqa: F401
    DEFAULT_VMEM_BUDGET,
    SCOPED_VMEM_LIMIT,
    KernelSpace,
    all_spaces,
    fit_tiles,
    get_space,
    pipeline_vmem_bytes,
    register_space,
    repair_tile,
    round_up,
)
from repro.codesign.planner import (  # noqa: F401
    PLAN_SEARCH_ERRORS,
    PLANNER_VERSION,
    Plan,
    get_plan_store,
    plan,
    plan_space_key,
    planner_stats,
    predict_cost,
    reset_planner_stats,
    set_plan_store,
)
from repro.codesign.calibrate import (  # noqa: F401
    CALIBRATION_VERSION,
    CalibrationScale,
    CalibrationTable,
    calibrate_kernel,
    measure_kernel,
)
