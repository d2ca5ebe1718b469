"""Shared base for the compile-once engine families (DESIGN.md §1, §8).

The repo has two engine families over the same lifecycle:

* **trim**  (``core.engine.TrimEngine``)  — arc-consistency fixpoint
  trimming, the paper's contribution;
* **reach** (``core.reach.ReachEngine``)  — frontier-sweep reachability,
  the primitive the paper's flagship application (FW-BW SCC, §1.1) spends
  most of its time in.

Both amortize the same per-call costs: a transpose built at most once
(``CSRGraph.transpose``: a device sort where G lives on an accelerator,
else a host O(n+m) counting sort; pre-seedable so a FW/BW engine pair
shares one build), a jitted kernel traced once per static configuration, and
device-resident results.  This module holds the plumbing they share:

* ``_TRACE_COUNT`` — process-wide count of kernel traces, bumped from
  *inside* traced functions (i.e. exactly once per compilation).  Engines
  attribute deltas to themselves around each dispatch.
* ``EngineBase._dispatch`` — runs a jitted callable while attributing
  traces and counting dispatches.  ``engine.dispatches`` is the number of
  device dispatches the engine issued (degenerate host shortcuts do not
  count); the batched SCC driver's per-generation contract — one trim
  dispatch, two reach dispatches — is asserted against it (DESIGN.md §8).

Every dispatch is additionally wrapped in an ``obs`` span (DESIGN.md
§11): engine family, plan signature, wall time, and compile-vs-execute
attribution (``phase="compile+execute"`` when the dispatch caused one or
more kernel traces).  The span is a profiler annotation named
``engine.dispatch``; the global recorder is disabled by default, in
which case it records nothing — un-observed runs pay an inactive
``TraceMe`` per dispatch.

When the process-global MetricsPlane is enabled (DESIGN.md §13) each
dispatch additionally feeds the continuous layer: a per-family latency
histogram split compile-vs-execute, dispatch/trace counters,
retrace-storm detection, per-plan XLA cost analysis (on compile
dispatches only — the lowering it needs would otherwise perturb trace
accounting), and the engine's live-buffer byte gauges via the
``nbytes()`` protocol.  The plane is disabled by default and guarded by
one ``enabled`` attribute read, the same contract as the recorder.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from .. import obs
from ..fault.plane import get_fault_plane
from .graph import CSRGraph

# Process-wide count of kernel traces (bumped from inside traced functions,
# i.e. exactly once per compilation).  Engines attribute deltas to
# themselves around each dispatch; tests assert on it (DESIGN.md §7).
_TRACE_COUNT = [0]


def jit_named(fn, name: str, in_axes=None):
    """``jax.jit`` of ``fn`` compiled as the program ``jit_<name>``, or,
    vmapped over ``in_axes``, as ``jit_<name>_batch``: the module names a
    profiler trace's ``XLA Modules`` line shows (``jit_trim_ac6``,
    ``jit_reach_pull_batch``).  Only the name changes, not the program."""
    import jax
    if in_axes is not None:
        name += "_batch"
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn if in_axes is None else jax.vmap(fn, in_axes=in_axes))


class EngineBase:
    """Compile-once execution over one graph: transpose cache + accounting.

    Subclasses implement ``plan``-style construction and ``run``/
    ``run_batch`` execution; the base owns the resources every family
    needs.
    """

    #: engine family name for span attribution; subclasses override
    family = "engine"

    def __init__(self, graph: CSRGraph, *, transpose: CSRGraph | None = None):
        self.graph = graph
        self._transpose = transpose
        self._transpose_builds = 0
        self._traces = 0
        self._dispatches = 0

    def plan_signature(self) -> str:
        """Stable short description of the plan's static configuration,
        used to label spans.  Subclasses refine it."""
        return f"{self.family}(n={self.graph.n},m={self.graph.m})"

    # -- cached resources --------------------------------------------------
    @property
    def transpose(self) -> CSRGraph:
        """Gᵀ, built at most once and cached: on G's device when G lives
        on an accelerator, else by the host counting sort
        (:meth:`CSRGraph.transpose`)."""
        if self._transpose is None:
            self._transpose = self.graph.transpose()
            self._transpose_builds += 1
        return self._transpose

    @property
    def transpose_builds(self) -> int:
        """How many times this engine actually built Gᵀ (0 or 1)."""
        return self._transpose_builds

    # -- accounting --------------------------------------------------------
    @property
    def traces(self) -> int:
        """Kernel traces this engine's dispatches caused (compile count)."""
        return self._traces

    @property
    def dispatches(self) -> int:
        """Device dispatches issued (each ``run`` = 1, each ``run_batch`` =
        1 regardless of batch size; degenerate host shortcuts = 0)."""
        return self._dispatches

    # -- memory accounting (nbytes protocol, DESIGN.md §13) ----------------
    def nbytes_breakdown(self) -> Dict[str, int]:
        """Live-buffer bytes by component (static shape × dtype, no device
        sync).  Subclasses extend with their plan caches; the base accounts
        the graph itself and the cached transpose."""
        out = {"graph": obs.array_nbytes(self.graph)}
        if self._transpose is not None:
            out["transpose"] = obs.array_nbytes(self._transpose)
        return out

    def nbytes(self) -> int:
        """Total live-buffer bytes held by this engine."""
        return sum(self.nbytes_breakdown().values())

    def _dispatch(self, fn, *args):
        """Call a jitted runner, attributing trace deltas and counting the
        dispatch.  Each dispatch is one ``obs`` span (no record when
        the global recorder is disabled) and, when the MetricsPlane is
        enabled, one latency-histogram sample plus counter updates.

        The FaultPlane (DESIGN.md §14) arms two points here:
        ``"pre-dispatch"`` before the device call, ``"post-dispatch"``
        after the runner returned but before the engine's accounting
        commits — so a faulted dispatch, retried, leaves the dispatch/
        trace counters exactly where a fault-free run would.  The default
        disabled plane costs one attribute read."""
        fplane = get_fault_plane()
        if fplane.enabled:
            fplane.arm("pre-dispatch", family=self.family,
                       seq=self._dispatches)
        before = _TRACE_COUNT[0]
        plane = obs.get_plane()
        t0 = time.perf_counter() if plane.enabled else 0.0
        with obs.span("dispatch", cat="engine", family=self.family,
                      plan=self.plan_signature(),
                      seq=self._dispatches) as sp:
            out = fn(*args)
            delta = _TRACE_COUNT[0] - before
            if sp is not None:
                sp.attrs["traces"] = delta
                sp.attrs["phase"] = ("compile+execute" if delta
                                     else "execute")
            if plane.enabled:
                self._feed_plane(plane, fn, args, delta,
                                 time.perf_counter() - t0, sp)
            if fplane.enabled:
                fplane.arm("post-dispatch", family=self.family,
                           seq=self._dispatches)
        self._traces += delta
        self._dispatches += 1
        return out

    def _feed_plane(self, plane, fn, args, delta, elapsed, sp) -> None:
        """Publish one dispatch to the MetricsPlane (enabled plane only).

        Latency is host-side dispatch time — the same quantity the span
        measures (jax dispatch is async; compile dispatches block on the
        trace, execute dispatches on enqueue).
        """
        phase = "compile" if delta else "execute"
        plane.histogram(
            "repro_dispatch_latency_seconds",
            "host-side engine dispatch latency by family, split "
            "compile-vs-execute",
        ).observe(elapsed, family=self.family, phase=phase)
        plane.counter(
            "repro_dispatches",
            "device dispatches issued per engine family",
        ).inc(family=self.family)
        if delta:
            plan = self.plan_signature()
            plane.counter(
                "repro_traces",
                "kernel traces (compilations) caused per engine family",
            ).inc(delta, family=self.family)
            plane.note_compile(self.family, plan)
            cost = obs.plan_cost_of(fn, *args)
            if cost:
                obs.record_plan_cost(plane, self.family, plan, cost)
                if sp is not None:
                    sp.attrs["cost"] = cost
        obs.publish_engine_memory(plane, self)

    # -- checkpoint/resume protocol (DESIGN.md §14) ------------------------
    def state_dict(self) -> Dict[str, object]:
        """Checkpointable state as a flat ``{name: array}`` tree.  The
        base serializes the graph and the transpose cache (if built);
        subclasses extend with their persistent state.  Everything else
        an engine holds is a pure function of these arrays plus the plan
        kwargs in :meth:`state_meta`, so restore is bit-identical."""
        out = {"graph_indptr": self.graph.indptr,
               "graph_indices": self.graph.indices}
        if self._transpose is not None:
            out["transpose_indptr"] = self._transpose.indptr
            out["transpose_indices"] = self._transpose.indices
        return out

    def state_meta(self) -> Dict[str, object]:
        """JSON-able companion of :meth:`state_dict`: the engine family,
        the plan kwargs a fresh process needs to re-plan, and the
        accounting counters (restored so resumed accounting continues
        where the checkpoint left off)."""
        return {"family": self.family, "plan": self.plan_signature(),
                "dispatches": self._dispatches, "traces": self._traces,
                "transpose_builds": self._transpose_builds,
                "plan_kwargs": self._plan_kwargs()}

    def _plan_kwargs(self) -> Dict[str, object]:
        """The kwargs that rebuild this plan (subclasses override)."""
        return {}

    def load_state(self, tree, meta) -> None:
        """Overwrite this engine's state with a checkpoint's exact arrays
        (``tree`` from :meth:`state_dict`/``fault.checkpoint.load_flat``,
        ``meta`` from :meth:`state_meta`).  Derived caches are dropped
        and rebuilt deterministically from the restored arrays."""
        import jax.numpy as jnp
        if meta.get("family") != self.family:
            raise ValueError(f"checkpoint family {meta.get('family')!r} "
                             f"does not match engine family "
                             f"{self.family!r}")
        self.graph = CSRGraph(
            jnp.asarray(np.asarray(tree["graph_indptr"]), jnp.int32),
            jnp.asarray(np.asarray(tree["graph_indices"]), jnp.int32))
        if "transpose_indptr" in tree:
            self._transpose = CSRGraph(
                jnp.asarray(np.asarray(tree["transpose_indptr"]),
                            jnp.int32),
                jnp.asarray(np.asarray(tree["transpose_indices"]),
                            jnp.int32))
        else:
            self._transpose = None
        self._dispatches = int(meta.get("dispatches", 0))
        self._traces = int(meta.get("traces", 0))
        self._transpose_builds = int(meta.get("transpose_builds", 0))
        self._invalidate_caches()

    def _invalidate_caches(self) -> None:
        """Drop plan caches derived from the graph/transpose arrays so
        the next run rebuilds them from the restored state (subclasses
        override; rebuilds are deterministic, so results stay
        bit-identical)."""

    def _publish_round_stats(self, rs) -> None:
        """Fold one run's :class:`~repro.obs.stats.RoundStats` into the
        MetricsPlane (rounds, per-stat work totals, worker skew).  No-op
        when the plane is disabled or the plan was not instrumented; an
        enabled plane forces the stats buffers to host."""
        plane = obs.get_plane()
        if rs is None or not plane.enabled:
            return
        plane.counter(
            "repro_fixpoint_rounds",
            "fixpoint rounds executed per engine family (summed over "
            "batches)",
        ).inc(int(np.sum(rs.rounds)), family=self.family)
        work = plane.counter(
            "repro_fixpoint_work",
            "per-round instrumented work totals by stat (edges = edges "
            "traversed, frontier = frontier sizes, decrements = counter "
            "decrements, r_sparse = rounds on the sparse path)")
        for name in rs.names:
            work.inc(float(np.sum(rs.total(name))),
                     family=self.family, stat=name)
        mwe = rs.max_worker_edges()
        if mwe is not None:
            plane.gauge(
                "repro_busiest_worker_edges",
                "edges traversed by the busiest worker in the last "
                "instrumented run (paper's per-worker load metric)",
            ).set(float(np.max(mwe)), family=self.family)
            plane.gauge(
                "repro_worker_imbalance",
                "max/mean per-worker traversed edges in the last "
                "instrumented run (1.0 = perfectly balanced)",
            ).set(float(np.max(rs.imbalance())), family=self.family)


__all__ = ["EngineBase", "_TRACE_COUNT", "jit_named"]
