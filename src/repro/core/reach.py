"""Compile-once / run-many reachability engine (DESIGN.md §8).

The paper's flagship application (FW-BW SCC decomposition, §1.1) spends
its non-trim time in BFS reachability.  The seed implementation ran that
on the host — a Python loop over ``np.concatenate`` per frontier — so the
fast trim kernels sat idle between passes.  :class:`ReachEngine` moves the
sweep into the same compiled substrate as trimming: a jitted
``lax.while_loop`` over dense (n,) masks, one device dispatch per query,
``vmap``-batched so many pivots advance in one dispatch.

The engine mirrors the :mod:`~repro.core.engine` lifecycle::

    engine = plan_reach(graph, backend="dense")
    res    = engine.run(seeds=pivot, active=mask)       # ReachResult
    res    = engine.run_batch(seed_masks, active_masks) # one vmapped dispatch

Two frontier-expansion methods, registered in the kernel registry under
family ``"reach"``:

    "push" (backend="dense")    — per-edge scatter: an edge fires when its
        source is on the frontier; ``.at[indices].max`` folds hits into
        the next frontier.  O(m) dense work per BSP round, no transpose.
    "pull" (backend="windowed") — per-vertex gather over *in*-neighbors
        (Gᵀ, shared with the trim engine's transpose cache).  On the
        Pallas path: a windowed (n, W) frontier-membership tile reduced
        by the ``kernels.frontier_expand`` kernel (block-level skipping
        of fully-visited vertex blocks) with a cond-gated scatter-free
        cumsum row-OR continuation for in-degrees beyond the window.
        Whether any vertex overflows the window is a static per-graph
        fact the engine computes once: overflow-free graphs compile the
        fallback out entirely, and batched execution on an overflowing
        graph uses the row-OR directly (vmap turns the gating cond into
        a select, so the tile would only add work — see
        :func:`reach_pull_kernel`).  Gather-only either way — no XLA
        scatter.

Both reach the same fixpoint: vertices reachable from ``seeds`` inside the
``active``-induced subgraph.
"""
from __future__ import annotations

import functools

import numpy as np

from .. import obs
from .common import FrontierPlan, frontier_plan
from .enginebase import _TRACE_COUNT, EngineBase, jit_named
from .graph import CSRGraph, row_ids
from .registry import KernelSpec, get_kernel, register_kernel

REACH_BACKENDS = ("dense", "windowed")

_STAT_NAMES = ("r_frontier", "r_edges")


# -- kernels (family "reach") --------------------------------------------------

def reach_push_kernel(indptr, indices, edge_src, seeds, active, *,
                      frontier: FrontierPlan = FrontierPlan(),
                      instrument: bool = False, max_rounds: int = 0):
    """Forward reachability by per-edge scatter (one dense O(m) pass per
    BSP round).  ``rounds`` counts frontier expansions executed.

    ``frontier`` (DESIGN.md §12) selects the sparse-frontier substrate:
    rounds whose frontier fits ``cap`` members and ``ecap`` out-edges
    compact the frontier (``kernels.frontier_compact``), expand only its
    CSR rows (``kernels.sparse_expand``), and scatter the ``ecap``-bounded
    edge buffer instead of all m edges — the hit mask is identical, so
    the sweep is bit-identical to the dense path including the round
    stats (the edge charge is the frontier's out-degree sum either way).

    ``instrument`` (DESIGN.md §11) carries per-round ``(max_rounds,)``
    buffers — frontier size and out-edges of the frontier per expansion —
    returned as a third output (``None`` when off)."""
    import jax
    import jax.numpy as jnp

    from ..kernels import ops as kops

    n = indptr.shape[0] - 1
    deg = indptr[1:] - indptr[:-1]
    visited0 = seeds & active
    sparse = frontier.mode != "dense"

    def dense_hits(f):
        edge_hit = f[edge_src]                             # (m,) bool
        return jnp.zeros((n,), bool).at[indices].max(edge_hit)

    def sparse_hits(f):
        ids, _ = kops.frontier_compact(f, frontier.cap)
        _, tgt, _, valid = kops.sparse_expand(indptr, indices, ids,
                                              frontier.ecap)
        return jnp.zeros((n,), bool).at[
            jnp.where(valid, tgt, n)].max(valid, mode="drop")

    def cond(state):
        return jnp.any(state["frontier"])

    def body(state):
        visited, front = state["visited"], state["frontier"]
        if sparse:
            count = jnp.sum(front)
            edges = jnp.sum(jnp.where(front, deg, 0))
            sparse_ok = (count <= frontier.cap) & (edges <= frontier.ecap)
            hit = jax.lax.cond(sparse_ok, sparse_hits, dense_hits, front)
        else:
            hit = dense_hits(front)
        new = hit & active & ~visited
        out = dict(visited=visited | new, frontier=new,
                   rounds=state["rounds"] + 1)
        if instrument:
            vals = dict(r_frontier=jnp.sum(front),
                        r_edges=(edges if sparse else
                                 jnp.sum(jnp.where(front, deg, 0))))
            if sparse:
                vals["r_sparse"] = sparse_ok.astype(jnp.int32)
            out["stats"] = obs.stats_record(
                state["stats"], state["rounds"], **vals)
        return out

    init = dict(visited=visited0, frontier=visited0,
                rounds=jnp.array(0, jnp.int32))
    if instrument:
        names = _STAT_NAMES + (("r_sparse",) if sparse else ())
        init["stats"] = obs.stats_init(max_rounds, names)
    out = jax.lax.while_loop(cond, body, init)
    return (out["visited"], out["rounds"],
            out["stats"] if instrument else None)


def reach_pull_kernel(t_indptr, t_indices, seeds, active, *,
                      window: int, use_kernel, batched: bool = False,
                      overflow: bool = True, fwd=None,
                      frontier: FrontierPlan = FrontierPlan(),
                      instrument: bool = False, max_rounds: int = 0):
    """Forward reachability by pull over in-neighbors (Gᵀ).

    Two statically-chosen round bodies:

    * **windowed tile** — gather, for every *pending* vertex (active,
      unvisited), the frontier membership of its first ``window``
      in-neighbors into an (n, W) tile and OR-reduce it with the
      ``frontier_expand`` kernel (block-level skipping on TPU); vertices
      with in-degree > W that found nothing fall back to the whole-row OR
      below, gated behind a ``lax.cond``.
    * **whole-row OR** — scatter-free full expansion: gather frontier
      membership per transpose edge, exclusive-cumsum it, and difference
      at the CSR row boundaries.  O(m) of gathers and one prefix sum, no
      serial rescans of hub adjacency lists.

    ``overflow`` is a static fact the engine computes once per graph: does
    any in-degree exceed the window?  When it is False the fallback is
    compiled out entirely — the tile alone is exact.  When it is True the
    tile body pays only if its work-skipping levers engage: the Pallas
    block skip (TPU) and the ``lax.cond`` around the fallback — and
    ``vmap`` lowers ``cond`` to a select that executes both branches, so
    under batching the cond skips nothing and the whole-row OR would run
    every round *on top of* the tile.  Hence the static choice: batched
    execution on an overflowing graph uses the whole-row body directly;
    everything else uses the tile (+ gated fallback only where needed).

    ``frontier`` (DESIGN.md §12) adds a third, sparse round body gated by
    a per-round ``lax.cond``: when the frontier fits ``cap`` members and
    ``ecap`` *out*-edges, its forward CSR rows (``fwd`` = the G arrays;
    required for non-dense plans) are expanded and scattered — push-shaped
    work on a pull engine, sound because "v has an in-neighbor on the
    frontier" and "some frontier out-edge lands on v" are the same
    predicate, so the visited evolution is bit-identical.  The ``r_edges``
    charge of a sparse-taken round is the frontier's *forward* degree sum
    (the work actually done), not the pull-side tile charge — the one
    per-round stat that is path-dependent (``r_frontier`` stays exact).
    """
    import jax
    import jax.numpy as jnp

    from ..kernels import ops as kops

    m = t_indices.shape[0]
    t_deg = t_indptr[1:] - t_indptr[:-1]
    n = t_indptr.shape[0] - 1
    sparse = frontier.mode != "dense"
    if sparse and fwd is None:
        raise ValueError("sparse-frontier pull needs the forward CSR "
                         "arrays (fwd=(indptr, indices))")
    if sparse:
        f_indptr, f_indices = fwd
        f_deg = f_indptr[1:] - f_indptr[:-1]
    # overflow-free graphs have m <= n*W, so the tile is never worse than
    # the whole-row body; only batched+overflow must avoid it (see above)
    use_tile = not (batched and overflow)
    if use_tile:
        offs = jnp.arange(window, dtype=jnp.int32)
        valid = offs[None, :] < t_deg[:, None]             # (n, W)
        addr = jnp.clip(t_indptr[:-1, None] + offs[None, :],
                        0, max(m - 1, 0))
        win_sources = t_indices[addr]                      # (n, W), static
    visited0 = seeds & active

    def row_hits(frontier_):
        edge_hit = frontier_[t_indices].astype(jnp.int32)  # (m,)
        csum = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(edge_hit)])
        return (csum[t_indptr[1:]] - csum[t_indptr[:-1]]) > 0

    def cond(state):
        return jnp.any(state["frontier"])

    def body(state):
        visited, front = state["visited"], state["frontier"]
        pending = active & ~visited

        def dense_new(f):
            edges = jnp.int32(0)
            if use_tile:
                flags = f[win_sources]                     # (n, W) bool
                hit_w = kops.frontier_expand(flags, valid, pending,
                                             use_kernel=use_kernel)
                if overflow:
                    # continuation: in-degree beyond the window, nothing
                    # found yet
                    rest = pending & ~hit_w & (t_deg > window)
                    found_r = jax.lax.cond(
                        jnp.any(rest), lambda f_: rest & row_hits(f_),
                        lambda _: jnp.zeros_like(rest), f)
                    new = hit_w | found_r
                    if instrument:
                        # tile gathers min(deg, W) per pending vertex; the
                        # gated whole-row continuation is an O(m) pass
                        edges = (jnp.sum(jnp.where(
                            pending, jnp.minimum(t_deg, window), 0))
                            + jnp.where(jnp.any(rest), m, 0))
                else:
                    new = hit_w    # no vertex overflows the window: exact
                    if instrument:
                        edges = jnp.sum(jnp.where(pending, t_deg, 0))
            else:
                new = pending & row_hits(f)
                if instrument:
                    # whole-row OR: O(m) pass
                    edges = jnp.array(m, jnp.int32)
            return new, edges

        def sparse_new(f):
            ids, _ = kops.frontier_compact(f, frontier.cap)
            _, tgt, _, valid_e = kops.sparse_expand(
                f_indptr, f_indices, ids, frontier.ecap)
            hit = jnp.zeros((n,), bool).at[
                jnp.where(valid_e, tgt, n)].max(valid_e, mode="drop")
            return pending & hit, jnp.sum(jnp.where(f, f_deg, 0))

        if sparse:
            count = jnp.sum(front)
            fedges = jnp.sum(jnp.where(front, f_deg, 0))
            sparse_ok = (count <= frontier.cap) & (fedges <= frontier.ecap)
            new, edges = jax.lax.cond(sparse_ok, sparse_new, dense_new,
                                      front)
        else:
            new, edges = dense_new(front)
        out = dict(visited=visited | new, frontier=new,
                   rounds=state["rounds"] + 1)
        if instrument:
            vals = dict(r_frontier=jnp.sum(front), r_edges=edges)
            if sparse:
                vals["r_sparse"] = sparse_ok.astype(jnp.int32)
            out["stats"] = obs.stats_record(
                state["stats"], state["rounds"], **vals)
        return out

    init = dict(visited=visited0, frontier=visited0,
                rounds=jnp.array(0, jnp.int32))
    if instrument:
        names = _STAT_NAMES + (("r_sparse",) if sparse else ())
        init["stats"] = obs.stats_init(max_rounds, names)
    out = jax.lax.while_loop(cond, body, init)
    return (out["visited"], out["rounds"],
            out["stats"] if instrument else None)


def _run_push(graph_arrays, transpose_arrays, seeds, active, *,
              window, use_kernel, batched=False, overflow=False,
              frontier=FrontierPlan(), instrument=False, max_rounds=0):
    indptr, indices, edge_src = graph_arrays
    return reach_push_kernel(indptr, indices, edge_src, seeds, active,
                             frontier=frontier, instrument=instrument,
                             max_rounds=max_rounds)


def _run_pull(graph_arrays, transpose_arrays, seeds, active, *,
              window, use_kernel, batched=False, overflow=True,
              frontier=FrontierPlan(), instrument=False, max_rounds=0):
    indptr, indices, _ = graph_arrays
    t_indptr, t_indices = transpose_arrays
    return reach_pull_kernel(t_indptr, t_indices, seeds, active,
                             window=window, use_kernel=use_kernel,
                             batched=batched, overflow=overflow,
                             fwd=(indptr, indices), frontier=frontier,
                             instrument=instrument, max_rounds=max_rounds)


register_kernel(KernelSpec(name="push", run=_run_push,
                           needs_transpose=False), family="reach")
register_kernel(KernelSpec(name="pull", run=_run_pull,
                           needs_transpose=True, supports_windowed=True),
                family="reach")


# -- jitted adapters -----------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _reach_runner(method: str, window: int, use_kernel, batched: bool,
                  overflow: bool, fplan: FrontierPlan = FrontierPlan(),
                  instrument: bool = False, max_rounds: int = 0):
    """Shared jitted adapter, cached process-wide on the static
    configuration (DESIGN.md §1): the SCC driver's FW engine (over G) and
    BW engine (over Gᵀ, same array shapes) share one compiled executable.
    ``overflow`` (any in-degree > window, a per-graph static fact) picks
    the pull method's round body — see :func:`reach_pull_kernel`.
    ``fplan`` (hashable, DESIGN.md §12) bakes the sparse-frontier
    capacities into the compiled sweep; the engine always hands the dense
    plan in here when ``batched`` (vmap lowers the direction switch to a
    select that would run both bodies).
    ``instrument``/``max_rounds`` select the stats-carrying variant
    (DESIGN.md §11); un-instrumented plans keep their own cache entries.
    """
    spec = get_kernel(method, family="reach")

    def call(garrs, tarrs, seeds, active):
        _TRACE_COUNT[0] += 1  # runs at trace time only
        return spec.run(garrs, tarrs, seeds, active, window=window,
                        use_kernel=use_kernel, batched=batched,
                        overflow=overflow, frontier=fplan,
                        instrument=instrument, max_rounds=max_rounds)

    return jit_named(call, f"reach_{method}",
                     (None, None, 0, 0) if batched else None)


# -- results -------------------------------------------------------------------

class ReachResult:
    """Output of a reachability run — device-resident, lazily materialized.

    mask:   (n,) bool for ``run`` / (B, n) bool for ``run_batch`` —
            vertices reachable from the seeds inside the active subgraph
            (seeds included).  Stays wherever the producer left it.
    rounds: frontier expansions executed (scalar, or (B,) for a batch);
            transfers to the host on first access and is cached.
    round_stats: per-round :class:`repro.obs.RoundStats` (frontier size,
            edges examined); None unless the plan had ``instrument=True``.
    """

    __slots__ = ("_mask", "_rounds", "_n_reached", "_round_stats")

    def __init__(self, mask, rounds, round_stats=None):
        self._mask = mask
        self._rounds = rounds
        self._n_reached = None
        self._round_stats = round_stats

    @property
    def mask(self):
        return self._mask

    @property
    def round_stats(self):
        return self._round_stats

    @property
    def rounds(self):
        r = self._rounds
        if r is not None and not isinstance(r, (int, np.ndarray)):
            arr = np.asarray(r)
            self._rounds = int(arr) if arr.ndim == 0 else arr
        return self._rounds

    @property
    def n_reached(self):
        """Vertices reached: an int for a single query, a (B,) int64
        array (one count per query) for a batched result.  Transfers to
        the host on first access and is cached, like ``rounds``."""
        if self._n_reached is None:
            counts = np.asarray(self._mask).sum(axis=-1)
            self._n_reached = int(counts) if counts.ndim == 0 else counts
        return self._n_reached

    def materialize(self) -> "ReachResult":
        """Force every field to the host (numpy mask, python ints)."""
        self._mask = np.asarray(self._mask)
        _ = self.rounds
        return self

    def __repr__(self):  # no device sync: report only static facts
        kind = "numpy" if isinstance(self._mask, np.ndarray) else "device"
        return f"ReachResult(shape={tuple(self._mask.shape)}, {kind})"


# -- the engine ----------------------------------------------------------------

def plan_reach(graph: CSRGraph, backend: str = "dense", *,
               window: int = 16, use_kernel: bool | None = None,
               transpose: CSRGraph | None = None, frontier: str = "auto",
               instrument: bool = False,
               max_rounds: int | None = None) -> "ReachEngine":
    """Build a :class:`ReachEngine` for ``graph``.

    ``backend``: "dense" (push scatter) or "windowed" (pull through the
    ``frontier_expand`` Pallas kernel).  ``transpose`` pre-seeds the Gᵀ
    cache (the SCC driver hands the trim engine's transpose over, so one
    FW-BW worklist builds Gᵀ exactly once).  ``frontier`` (DESIGN.md §12)
    selects the sparse-frontier substrate — "auto" (default) switches
    per round on device, "dense"/"sparse" pin a path; ``run_batch``
    always executes dense (vmap lowers the switch to a select).
    ``instrument`` attaches per-round stats to every result (DESIGN.md
    §11; zero cost when off).
    """
    return ReachEngine(graph, backend=backend, window=window,
                       use_kernel=use_kernel, transpose=transpose,
                       frontier=frontier, instrument=instrument,
                       max_rounds=max_rounds)


class ReachEngine(EngineBase):
    """Compile-once reachability over one graph.  Build with
    :func:`plan_reach`."""

    family = "reach"

    def __init__(self, graph, *, backend, window, use_kernel, transpose,
                 frontier="auto", instrument=False, max_rounds=None):
        if backend not in REACH_BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; expected one of "
                             f"{REACH_BACKENDS}")
        super().__init__(graph, transpose=transpose)
        self.backend = backend
        self.method = "pull" if backend == "windowed" else "push"
        self.spec = get_kernel(self.method, family="reach")
        self.window = window
        self.use_kernel = use_kernel
        self.fplan = frontier_plan(frontier, graph.n, graph.m)
        self.instrument = instrument
        self.max_rounds = (obs.round_capacity(graph.n, max_rounds)
                           if instrument else 0)
        self._garrs = None
        self._tarrs = None
        self._overflow = None

    def plan_signature(self) -> str:
        sig = (f"reach[{self.method}/{self.backend}]"
               f"(n={self.graph.n},m={self.graph.m})"
               f"+frontier[{self.fplan.mode}]")
        return sig + "+stats" if self.instrument else sig

    # -- checkpoint/resume (DESIGN.md §14) ---------------------------------
    def _plan_kwargs(self):
        return {"backend": self.backend, "window": self.window,
                "use_kernel": self.use_kernel,
                "frontier": self.fplan.mode, "instrument": self.instrument,
                "max_rounds": (self.max_rounds if self.instrument
                               else None)}

    def _invalidate_caches(self):
        self._garrs = None
        self._tarrs = None
        self._overflow = None

    # -- cached arrays -----------------------------------------------------
    def _graph_arrays(self):
        if self._garrs is None:
            g = self.graph
            edge_src = (row_ids(g.indptr, g.m)
                        if self.method == "push" else None)
            self._garrs = (g.indptr, g.indices, edge_src)
        return self._garrs

    def _transpose_arrays(self):
        if not self.spec.needs_transpose:
            return None
        if self._tarrs is None:
            gt = self.transpose
            self._tarrs = (gt.indptr, gt.indices)
        return self._tarrs

    def _has_overflow(self) -> bool:
        """Static per-graph fact: does any in-degree exceed the window?
        Computed once on the host; compiled into the pull runner so
        overflow-free graphs never pay the whole-row fallback."""
        if self.method != "pull":
            return False
        if self._overflow is None:
            indptr = np.asarray(self.transpose.indptr)
            deg = indptr[1:] - indptr[:-1]
            self._overflow = bool(deg.size and int(deg.max()) > self.window)
        return self._overflow

    # -- mask plumbing -----------------------------------------------------
    def _seed_mask(self, seeds):
        import jax.numpy as jnp
        n = self.graph.n
        if isinstance(seeds, (bool, np.bool_)):
            # bool is an int subclass: a stray True would silently read
            # as vertex 1
            raise ValueError("seeds must be a vertex id or an (n,) bool "
                             "mask, got a scalar bool")
        if isinstance(seeds, (int, np.integer)):
            if not 0 <= seeds < n:
                raise ValueError(f"seed vertex {seeds} out of range [0, {n})")
            return jnp.zeros((n,), bool).at[seeds].set(True)
        if np.shape(seeds) != (n,):
            raise ValueError(f"seeds must be a vertex id or an ({n},) bool "
                             f"mask, got shape {np.shape(seeds)}")
        return jnp.asarray(seeds, bool)

    def _active_mask(self, active, shape):
        import jax.numpy as jnp
        if active is None:
            return jnp.ones(shape, bool)
        if np.shape(active) != shape:
            raise ValueError(f"active mask must have shape {shape}, got "
                             f"{np.shape(active)}")
        return jnp.asarray(active, bool)

    # -- execution ---------------------------------------------------------
    def run(self, seeds, active=None) -> ReachResult:
        """Vertices reachable from ``seeds`` within the ``active``-induced
        subgraph.  ``seeds``: a vertex id or an (n,) bool mask."""
        import jax.numpy as jnp
        n, m = self.graph.n, self.graph.m
        seed_mask = self._seed_mask(seeds)
        act = self._active_mask(active, (n,))
        if n == 0 or m == 0:
            # no edges: nothing propagates beyond the seeds themselves
            rounds = jnp.array(0, jnp.int32)
            return ReachResult(mask=seed_mask & act, rounds=rounds,
                               round_stats=self._empty_stats(rounds))
        fn = _reach_runner(self.method, self.window, self.use_kernel,
                           batched=False, overflow=self._has_overflow(),
                           fplan=self.fplan, instrument=self.instrument,
                           max_rounds=self.max_rounds)
        reached, rounds, stats = self._dispatch(
            fn, self._graph_arrays(), self._transpose_arrays(),
            seed_mask, act)
        return ReachResult(mask=reached, rounds=rounds,
                           round_stats=self._wrap_stats(rounds, stats))

    def run_batch(self, seed_masks, active_masks=None) -> ReachResult:
        """B reachability queries in one vmapped dispatch.

        ``seed_masks``: (B, n) bool; ``active_masks``: (B, n) bool or
        ``None`` (whole graph).  Returns one :class:`ReachResult` with a
        stacked (B, n) ``mask`` and (B,) ``rounds``, equal row-wise to
        sequential ``run()`` calls.
        """
        import jax.numpy as jnp
        n, m = self.graph.n, self.graph.m
        seeds = jnp.asarray(seed_masks, bool)
        if seeds.ndim != 2 or seeds.shape[1] != n:
            raise ValueError(f"seed_masks must be (B, {n}) bool, got "
                             f"{seeds.shape}")
        act = self._active_mask(active_masks, (seeds.shape[0], n))
        if n == 0 or m == 0:
            rounds = jnp.zeros((seeds.shape[0],), jnp.int32)
            return ReachResult(mask=seeds & act, rounds=rounds,
                               round_stats=self._empty_stats(
                                   rounds, lanes=seeds.shape[0]))
        # vmap lowers the per-round direction cond to a select that runs
        # BOTH bodies every round, so batched sweeps always execute dense
        fn = _reach_runner(self.method, self.window, self.use_kernel,
                           batched=True, overflow=self._has_overflow(),
                           fplan=FrontierPlan(), instrument=self.instrument,
                           max_rounds=self.max_rounds)
        reached, rounds, stats = self._dispatch(
            fn, self._graph_arrays(), self._transpose_arrays(), seeds, act)
        return ReachResult(mask=reached, rounds=rounds,
                           round_stats=self._wrap_stats(rounds, stats))

    def _wrap_stats(self, rounds, stats):
        if not self.instrument:
            return None
        rs = obs.RoundStats(rounds, stats, max_rounds=self.max_rounds)
        self._publish_round_stats(rs)
        return rs

    def nbytes_breakdown(self):
        # _garrs[0:2]/_tarrs alias graph/transpose arrays (accounted by
        # the base); the push backend's edge_src row ids are new bytes
        out = super().nbytes_breakdown()
        if self._garrs is not None and self._garrs[2] is not None:
            out["edge_src"] = obs.array_nbytes(self._garrs[2])
        return out

    def _empty_stats(self, rounds, lanes: int = 0):
        if not self.instrument:
            return None
        return obs.RoundStats(
            rounds, obs.stats_init(self.max_rounds, _STAT_NAMES,
                                   lanes=lanes),
            max_rounds=self.max_rounds)


__all__ = ["plan_reach", "ReachEngine", "ReachResult", "REACH_BACKENDS",
           "reach_push_kernel", "reach_pull_kernel"]
