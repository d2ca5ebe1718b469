"""XLA cost analysis for the MetricsPlane (DESIGN.md §13).

``jax``'s AOT path exposes the XLA cost model on compiled executables:
``jit(f).lower(*args).compile().cost_analysis()`` yields estimated
FLOPs and bytes accessed for the whole computation.  This module
normalizes that across jax versions (dict vs list-of-dicts vs None) and
publishes it as the ``repro_plan_cost_*`` gauge families.

Cost analysis is only extracted on *compile* dispatches — the lowering
needed to reach the executable retraces the function, so doing it per
execute dispatch would be both slow and would perturb the repo's
trace-count accounting.  ``plan_cost_of`` saves/restores the process
trace counter around its own lowering for exactly that reason.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

# cost_analysis() keys we surface, normalized to metric-friendly names.
_COST_KEYS = (
    ("flops", "flops"),
    ("bytes accessed", "bytes_accessed"),
    ("transcendentals", "transcendentals"),
    ("optimal_seconds", "optimal_seconds"),
)


def normalize_cost(raw: Any) -> Dict[str, float]:
    """Flatten ``compiled.cost_analysis()`` output to ``{key: float}``.

    Handles the per-version shapes: a dict, a list of per-computation
    dicts (summed), or None/empty when the backend reports nothing.
    Only top-level scalar keys are kept (per-opcode breakdowns like
    ``flops{add}`` are dropped).
    """
    if raw is None:
        return {}
    dicts = raw if isinstance(raw, (list, tuple)) else [raw]
    out: Dict[str, float] = {}
    for d in dicts:
        if not isinstance(d, dict):
            continue
        for raw_key, key in _COST_KEYS:
            v = d.get(raw_key)
            if isinstance(v, (int, float)):
                out[key] = out.get(key, 0.0) + float(v)
    return out


def plan_cost_of(fn, *args, **kwargs) -> Optional[Dict[str, float]]:
    """Cost analysis for a jitted callable at the given arguments.

    Returns the normalized dict, or None when the function has no AOT
    path or the backend reports no cost model.  The lowering retraces
    ``fn`` even on compile-cache hits, so the repo-wide trace counter is
    saved and restored — engine trace accounting must not observe it.
    """
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    from ..core.enginebase import _TRACE_COUNT

    before = _TRACE_COUNT[0]
    try:
        compiled = lower(*args, **kwargs).compile()
        cost = normalize_cost(compiled.cost_analysis())
    except Exception:
        return None
    finally:
        _TRACE_COUNT[0] = before
    return cost or None


def record_plan_cost(plane, family: str, plan: str,
                     cost: Dict[str, float]) -> None:
    """Publish one plan's XLA cost model as labeled gauges."""
    flops = plane.gauge("repro_plan_cost_flops",
                        "XLA cost model: estimated FLOPs per dispatch of a "
                        "compiled plan")
    nbytes = plane.gauge("repro_plan_cost_bytes",
                         "XLA cost model: estimated bytes accessed per "
                         "dispatch of a compiled plan")
    if "flops" in cost:
        flops.set(cost["flops"], family=family, plan=plan)
    if "bytes_accessed" in cost:
        nbytes.set(cost["bytes_accessed"], family=family, plan=plan)


__all__ = ["normalize_cost", "plan_cost_of", "record_plan_cost"]
