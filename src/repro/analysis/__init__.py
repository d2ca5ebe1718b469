"""Static-analysis plane (DESIGN.md §15): race/purity/retrace checks over
the kernel and plan registries, without executing anything on real data.

The paper's algorithms are "designed to minimize synchronization overhead"
— in this reproduction that means two interface-level invariants must hold
for *every* registered Pallas kernel and *every* jitted fixpoint plan:

* **write-race freedom**: no two grid programs of a Pallas kernel write
  overlapping output blocks unless the distinguishing grid axis is a
  declared-sequential (accumulation/carry) axis (``analysis.races``);
* **device purity**: a fixpoint plan's closed jaxpr contains no host
  callbacks or transfers inside ``while`` bodies, no silent 64-bit
  dtypes, no non-static shapes — and its ``instrument=False`` variant is
  byte-identical regardless of the stat-buffer capacity
  (``analysis.purity``).

Both are checked statically: kernels are traced under ``jax.eval_shape``
with their ``pallas_call`` grid/BlockSpec configuration captured
(``analysis.capture``) and the index maps swept concretely over a pinned
shape lattice; plans are traced on abstract shapes through one
process-wide jaxpr cache (``launch.lowering``).

``python -m repro.analysis.check --strict`` gates the real registry in
CI; ``--mutants`` proves every checker fires on the deliberately broken
kernel/plan twins in ``analysis.mutants``.
"""
from .capture import PallasCapture, captured_calls
from .catalog import (KERNEL_CATALOG, KERNEL_DECLARATIONS, PLAN_CATALOG,
                      KernelDecl, KernelEntry, PlanEntry)
from .findings import Finding, Report
from .mutants import MUTANT_KERNELS, MUTANT_PLANS
from .purity import (check_host_dtypes, check_instrument_diff,
                     check_plan_purity)
from .races import check_races
from .retrace import check_generator_dtypes, check_retrace_risk

__all__ = [
    "PallasCapture", "captured_calls",
    "KERNEL_CATALOG", "KERNEL_DECLARATIONS", "PLAN_CATALOG",
    "KernelDecl", "KernelEntry", "PlanEntry",
    "Finding", "Report",
    "MUTANT_KERNELS", "MUTANT_PLANS",
    "check_host_dtypes", "check_instrument_diff", "check_plan_purity",
    "check_races",
    "check_generator_dtypes", "check_retrace_risk",
]
