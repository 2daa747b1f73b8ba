"""Runtime: fault tolerance, gradient compression, elastic mesh planning,
and the persistent compilation cache.

Attribute access is lazy (PEP 562): ``repro.runtime.fault_tolerance``
holds the jax-free watchdog/retry core that sweep worker PROCESSES import
on the numpy path, and an eager ``from .compression import ...`` here
would drag the multi-second jax import into every spawned worker.
``from repro.runtime import FaultTolerantRunner`` etc. keep working
unchanged -- the submodule is imported on first attribute access.
"""

_EXPORTS = {
    "FaultTolerantRunner": "repro.runtime.fault_tolerance",
    "RunnerConfig": "repro.runtime.fault_tolerance",
    "StepTimeoutError": "repro.runtime.fault_tolerance",
    "CallTimeoutError": "repro.runtime.fault_tolerance",
    "RetryPolicy": "repro.runtime.fault_tolerance",
    "RetryStats": "repro.runtime.fault_tolerance",
    "retry_call": "repro.runtime.fault_tolerance",
    "call_with_deadline": "repro.runtime.fault_tolerance",
    "StragglerMeter": "repro.runtime.fault_tolerance",
    "compress_int8": "repro.runtime.compression",
    "decompress_int8": "repro.runtime.compression",
    "error_feedback_update": "repro.runtime.compression",
    "make_compressed_allreduce": "repro.runtime.compression",
    "plan_mesh": "repro.runtime.elastic",
    "use_compile_cache": "repro.runtime.compile_cache",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module 'repro.runtime' has no attribute {name!r}")
    import importlib

    value = getattr(importlib.import_module(mod), name)
    globals()[name] = value  # cache: subsequent access skips __getattr__
    return value


def __dir__():
    return __all__
