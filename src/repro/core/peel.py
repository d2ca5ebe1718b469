"""Compile-once bucketed k-core peeling on the AC-4 counter substrate
(DESIGN.md §10).

The paper's AC-4 trimming maintains live-out-degree support counters and
removes vertices whose counter hits zero — exactly the ``k = 1`` instance
of out-degree k-core peeling, the canonical counter-peeling workload
(GBBS; Dhulipala et al.).  :class:`PeelEngine` generalizes the trimming
substrate into that workload: one jitted bucketed fixpoint computes the
full out-degree *coreness* (peel value) of every vertex, from which every
``k_core(k)`` mask is a single comparison — and whose ``k = 1`` live mask
is bit-identical to :class:`~repro.core.engine.TrimEngine` AC-4 (the
differential harness asserts it).

The fixpoint is the AC-4 loop with a moving threshold.  State carries the
same ``(alive, counters)`` pair; each round

1. jumps the bucket level to ``max(k, min counter among alive)`` (empty
   buckets cost nothing — the level moves to the next occupied bucket in
   one reduction, and never moves past a cascade),
2. extracts the bucket's frontier ``alive & (counters <= k)`` through the
   ``kernels.bucket_peel`` Pallas kernel (block-level skipping of fully
   peeled vertex blocks, like ``frontier_expand``),
3. assigns the frontier coreness ``k`` and its peel round, and bulk
   fetch-and-adds the counter decrements through Gᵀ — the identical
   masked segment-sum AC-4 uses (``core/ac4.py``).

At ``k = 0`` rounds this *is* AC-4: the initial frontier is the zero
bucket and the cascade is the trimming fixpoint, so coreness ``>= 1``
equals the trimmed live mask bit-for-bit.

The peel order is a *degeneracy order* byproduct of the same counters:
sorting vertices by peel round (stably) yields an order in which every
vertex has at most ``coreness(v)`` out-neighbors peeled in its own round
or later — the counters at peel time are exactly the certificate.

Lifecycle mirrors the other engine families (family ``"peel"`` in the
kernel registry)::

    engine = plan_peel(graph)
    res    = engine.run()              # full coreness, one dispatch
    res    = engine.run(k=1)           # early-exit: peel below the k-core
    res    = engine.run_batch(masks)   # B induced subgraphs, one dispatch
    res.coreness                       # (n,) int32 peel values (device)
    res.k_core(3)                      # (n,) bool mask, one comparison
    res.degeneracy_order()             # host peel-order permutation
"""
from __future__ import annotations

import functools

import numpy as np

from .. import obs
from .common import FrontierPlan, frontier_plan
from .enginebase import _TRACE_COUNT, EngineBase, jit_named
from .graph import CSRGraph, row_ids
from .registry import KernelSpec, get_kernel, register_kernel

_INT32_MAX = np.iinfo(np.int32).max

_STAT_NAMES = ("r_frontier", "r_edges", "r_k")


# -- the kernel (family "peel") ------------------------------------------------

def peel_bucket_kernel(indptr, indices, t_indptr, t_indices, t_rows,
                       active, *, k_stop, use_kernel,
                       frontier: FrontierPlan = FrontierPlan(),
                       instrument: bool = False, max_rounds: int = 0):
    """Bucketed out-degree peeling to the coreness fixpoint.

    ``active``: (n,) bool — peel the induced subgraph (inactive vertices
    get coreness -1 and contribute to no counter).
    ``k_stop``: static — ``None`` peels everything (full coreness);
    an int peels only buckets ``< k_stop``, so survivors are exactly the
    ``k_stop``-core (early exit; ``k_stop = 1`` is AC-4 trimming).

    Returns ``(coreness, peel_round, rounds)``: (n,) int32 peel value
    (survivors of a bounded run get ``k_stop``; inactive get -1),
    (n,) int32 round at which each vertex peeled (-1 for survivors and
    inactive), and the scalar round count.

    ``instrument`` (DESIGN.md §11) appends a fourth output: per-round
    ``(max_rounds,)`` buffers of frontier size, Gᵀ edges traversed by the
    bulk decrement, and the bucket level ``k`` peeled that round (``r_k``
    is a per-slot value, not an accumulation — meaningful only for runs
    within the round capacity).

    ``frontier`` (DESIGN.md §12) selects the sparse-frontier substrate:
    rounds whose bucket fits ``cap`` members and ``ecap`` Gᵀ edges
    compact the bucket, expand only its in-edge rows, and scatter-add the
    ``ecap``-bounded buffer instead of segment-summing all m transpose
    edges.  The decrement vector is identical, so coreness, peel order,
    and every stat stay bit-identical.
    """
    import jax
    import jax.numpy as jnp

    from ..kernels import ops as kops

    n = indptr.shape[0] - 1
    # induced live out-degree: the AC-4 counter initialization
    src = row_ids(indptr, indices.shape[0])
    live_edge = (active[src] & active[indices]).astype(jnp.int32)
    deg = jax.ops.segment_sum(live_edge, src, num_segments=n)
    sparse = frontier.mode != "dense"
    if sparse:
        t_deg = t_indptr[1:] - t_indptr[:-1]

    def dense_dec(f):
        return jax.ops.segment_sum(f[t_rows].astype(jnp.int32),
                                   t_indices, num_segments=n)

    def sparse_dec(f):
        ids, _ = kops.frontier_compact(f, frontier.cap)
        _, tgt, _, valid = kops.sparse_expand(t_indptr, t_indices, ids,
                                              frontier.ecap)
        return jnp.zeros((n,), jnp.int32).at[
            jnp.where(valid, tgt, n)].add(1, mode="drop")

    def cond(s):
        if k_stop is None:
            return jnp.any(s["alive"])
        return jnp.any(s["alive"] & (s["counters"] < k_stop))

    def body(s):
        alive, counters = s["alive"], s["counters"]
        # jump to the next occupied bucket; never retreats below a cascade
        minc = jnp.min(jnp.where(alive, counters, _INT32_MAX))
        k = jnp.maximum(s["k"], minc)
        front = kops.bucket_peel(counters, alive, k,
                                 use_kernel=use_kernel)
        if sparse:
            count = jnp.sum(front)
            tedges = jnp.sum(jnp.where(front, t_deg, 0))
            sparse_ok = (count <= frontier.cap) & (tedges <= frontier.ecap)
            dec = jax.lax.cond(sparse_ok, sparse_dec, dense_dec, front)
        else:
            dec = dense_dec(front)
        new = dict(
            alive=alive & ~front,
            counters=counters - dec,
            coreness=jnp.where(front, k, s["coreness"]),
            peel_round=jnp.where(front, s["rounds"], s["peel_round"]),
            k=k,
            rounds=s["rounds"] + 1,
        )
        if instrument:
            vals = dict(r_frontier=jnp.sum(front),
                        r_edges=jnp.sum(dec),
                        r_k=k)
            if sparse:
                vals["r_sparse"] = sparse_ok.astype(jnp.int32)
            new["stats"] = obs.stats_record(s["stats"], s["rounds"], **vals)
        return new

    init = dict(
        alive=active,
        counters=deg.astype(jnp.int32),
        coreness=jnp.full((n,), -1, jnp.int32),
        peel_round=jnp.full((n,), -1, jnp.int32),
        k=jnp.array(0, jnp.int32),
        rounds=jnp.array(0, jnp.int32),
    )
    if instrument:
        # the counter-initialization scan (one pass over every induced
        # edge, the AC-4 init) is round-0 work
        names = _STAT_NAMES + (("r_sparse",) if sparse else ())
        stats0 = obs.stats_init(max_rounds, names)
        init["stats"] = obs.stats_record(stats0, jnp.int32(0),
                                         r_edges=jnp.sum(deg))
    out = jax.lax.while_loop(cond, body, init)
    coreness = out["coreness"]
    if k_stop is not None:
        # survivors of a bounded run are exactly the k_stop-core
        coreness = jnp.where(out["alive"], jnp.int32(k_stop), coreness)
    return (coreness, out["peel_round"], out["rounds"],
            out["stats"] if instrument else None)


def _run_bucket(graph_arrays, transpose_arrays, active, *, k_stop,
                use_kernel, frontier=FrontierPlan(), instrument=False,
                max_rounds=0):
    indptr, indices = graph_arrays
    t_indptr, t_indices, t_rows = transpose_arrays
    return peel_bucket_kernel(indptr, indices, t_indptr, t_indices, t_rows,
                              active, k_stop=k_stop, use_kernel=use_kernel,
                              frontier=frontier, instrument=instrument,
                              max_rounds=max_rounds)


register_kernel(KernelSpec(name="bucket", run=_run_bucket,
                           needs_transpose=True), family="peel")


@functools.lru_cache(maxsize=None)
def _peel_runner(method: str, k_stop, use_kernel, batched: bool,
                 fplan: FrontierPlan = FrontierPlan(),
                 instrument: bool = False, max_rounds: int = 0):
    """Shared jitted adapter, cached process-wide on the static
    configuration (DESIGN.md §1); each distinct ``k`` bound is its own
    compiled variant (the early-exit condition is static).
    ``fplan`` (DESIGN.md §12) bakes the sparse-frontier capacities in;
    the engine hands the dense plan in when ``batched`` (vmap lowers the
    direction cond to a select that would run both bodies).
    ``instrument``/``max_rounds`` select the stats-carrying variant."""
    spec = get_kernel(method, family="peel")

    def call(garrs, tarrs, active):
        _TRACE_COUNT[0] += 1  # runs at trace time only
        return spec.run(garrs, tarrs, active, k_stop=k_stop,
                        use_kernel=use_kernel, frontier=fplan,
                        instrument=instrument, max_rounds=max_rounds)

    return jit_named(call, f"peel_{method}",
                     (None, None, 0) if batched else None)


# -- results -------------------------------------------------------------------

class PeelResult:
    """Output of a peeling run — device-resident, lazily materialized.

    coreness:   (n,) int32 for ``run`` / (B, n) for ``run_batch`` — peel
                value per vertex: the largest k with v in the k-core.
                Inactive vertices hold -1; a bounded ``run(k=j)`` clamps
                survivors at ``j`` (they are in the j-core; their exact
                coreness was not computed).
    peel_round: (n,) / (B, n) int32 — fixpoint round at which the vertex
                peeled; -1 for survivors of a bounded run and inactive
                vertices.
    rounds:     fixpoint rounds executed (scalar / (B,)); transfers to
                the host on first access and is cached.
    round_stats: per-round :class:`repro.obs.RoundStats` (frontier size,
                Gᵀ edges traversed, bucket level); None unless the plan
                had ``instrument=True``.
    """

    __slots__ = ("_coreness", "_peel_round", "_rounds", "_k_stop",
                 "_round_stats")

    def __init__(self, coreness, peel_round, rounds, k_stop=None,
                 round_stats=None):
        self._coreness = coreness
        self._peel_round = peel_round
        self._rounds = rounds
        self._k_stop = k_stop
        self._round_stats = round_stats

    @property
    def coreness(self):
        return self._coreness

    @property
    def round_stats(self):
        return self._round_stats

    @property
    def peel_round(self):
        return self._peel_round

    @property
    def rounds(self):
        r = self._rounds
        if r is not None and not isinstance(r, (int, np.ndarray)):
            arr = np.asarray(r)
            self._rounds = int(arr) if arr.ndim == 0 else arr
        return self._rounds

    @property
    def k_stop(self):
        return self._k_stop

    # -- derived masks -----------------------------------------------------
    def k_core(self, k: int):
        """(n,) / (B, n) bool — vertices of the k-core (the maximal
        induced subgraph of min live out-degree >= k).  ``k_core(0)`` is
        the active set; ``k_core(1)`` is the trimmed live mask.  A bounded
        run only answers ``k <= k_stop``."""
        if self._k_stop is not None and k > self._k_stop:
            raise ValueError(
                f"this result was peeled with k={self._k_stop}; cores "
                f"above it were not computed (asked for k={k})")
        return self._coreness >= k

    @property
    def status(self):
        """(n,) / (B, n) int32 LIVE/DEAD mask of the (``k_stop`` or 1)-core
        — the :class:`~repro.core.graph.TrimResult` ``status`` convention,
        bit-identical to AC-4 trimming for ``k = 1``."""
        import jax.numpy as jnp
        k = 1 if self._k_stop is None else self._k_stop
        return self.k_core(k).astype(jnp.int32)

    @property
    def max_core(self):
        """Largest coreness present (host int for ``run``, (B,) int64 per
        row for ``run_batch``); 0 when nothing is active."""
        arr = np.asarray(self._coreness)
        if arr.shape[-1] == 0:
            z = np.zeros(arr.shape[:-1], np.int64)
            return int(z) if z.ndim == 0 else z
        mx = np.maximum(arr, 0).max(axis=-1).astype(np.int64)
        return int(mx) if mx.ndim == 0 else mx

    def degeneracy_order(self) -> np.ndarray:
        """Peel-order permutation (host): active vertices sorted stably by
        peel round.  Every vertex has at most ``coreness(v)`` out-neighbors
        peeled in its own round or later — its counter at peel time is the
        certificate.  Survivors of a bounded run (never peeled) are
        omitted; only defined for single-graph results."""
        rounds = np.asarray(self._peel_round)
        if rounds.ndim != 1:
            raise ValueError("degeneracy_order is per-graph; index a "
                             "batched result row first")
        order = np.argsort(rounds, kind="stable")
        return order[rounds[order] >= 0]

    def materialize(self) -> "PeelResult":
        """Force every field to the host (numpy arrays, python ints)."""
        self._coreness = np.asarray(self._coreness).astype(np.int32)
        self._peel_round = np.asarray(self._peel_round).astype(np.int32)
        _ = self.rounds
        return self

    def __repr__(self):  # no device sync: report only static facts
        kind = "numpy" if isinstance(self._coreness, np.ndarray) else "device"
        return (f"PeelResult(shape={tuple(self._coreness.shape)}, {kind}, "
                f"k_stop={self._k_stop})")


# -- the engine ----------------------------------------------------------------

def plan_peel(graph: CSRGraph, method: str = "bucket", *,
              use_kernel: bool | None = None,
              transpose: CSRGraph | None = None, frontier: str = "auto",
              instrument: bool = False,
              max_rounds: int | None = None) -> "PeelEngine":
    """Build a :class:`PeelEngine` for ``graph``.

    ``transpose`` pre-seeds the Gᵀ cache (shared with a
    :class:`~repro.core.engine.TrimEngine` over the same graph, whose
    AC-4 pass needs the identical arrays).  ``use_kernel`` forces the
    bucket-extraction Pallas kernel on/off (default: on iff a TPU is
    attached, like every ``kernels.ops`` wrapper).  ``frontier``
    (DESIGN.md §12) selects the sparse-frontier substrate — "auto"
    (default) switches per round on device; ``run_batch`` always executes
    dense (vmap lowers the switch to a select).  ``instrument`` attaches
    per-round stats to every result (DESIGN.md §11; zero cost when off).
    Full-coreness peels can take up to n rounds — pass ``max_rounds`` to
    widen the stat buffers past the 1024-slot default if the per-round
    breakdown of a deep peel matters (totals are exact either way).
    """
    return PeelEngine(graph, method=method, use_kernel=use_kernel,
                      transpose=transpose, frontier=frontier,
                      instrument=instrument, max_rounds=max_rounds)


class PeelEngine(EngineBase):
    """Compile-once k-core peeling over one graph.  Build with
    :func:`plan_peel`."""

    family = "peel"

    def __init__(self, graph, *, method, use_kernel, transpose,
                 frontier="auto", instrument=False, max_rounds=None):
        self.spec = get_kernel(method, family="peel")  # raises on unknown
        super().__init__(graph, transpose=transpose)
        self.method = method
        self.use_kernel = use_kernel
        self.fplan = frontier_plan(frontier, graph.n, graph.m)
        self.instrument = instrument
        self.max_rounds = (obs.round_capacity(graph.n, max_rounds)
                           if instrument else 0)
        self._tarrs = None

    def plan_signature(self) -> str:
        sig = (f"peel[{self.method}]"
               f"(n={self.graph.n},m={self.graph.m})"
               f"+frontier[{self.fplan.mode}]")
        return sig + "+stats" if self.instrument else sig

    # -- checkpoint/resume (DESIGN.md §14) ---------------------------------
    def _plan_kwargs(self):
        return {"method": self.method, "use_kernel": self.use_kernel,
                "frontier": self.fplan.mode, "instrument": self.instrument,
                "max_rounds": (self.max_rounds if self.instrument
                               else None)}

    def _invalidate_caches(self):
        self._tarrs = None

    # -- cached resources --------------------------------------------------
    def _transpose_arrays(self):
        if self._tarrs is None:
            gt = self.transpose
            self._tarrs = (gt.indptr, gt.indices, row_ids(gt.indptr, gt.m))
        return self._tarrs

    @staticmethod
    def _check_k(k):
        if k is not None and (not isinstance(k, (int, np.integer))
                              or isinstance(k, (bool, np.bool_)) or k < 0):
            raise ValueError(f"k must be None (full coreness) or an int "
                             f">= 0, got {k!r}")
        return None if k is None else int(k)

    # -- execution ---------------------------------------------------------
    def run(self, k: int | None = None, active=None) -> PeelResult:
        """Peel (the ``active``-induced subgraph of) the planned graph.

        ``k=None`` computes the full coreness of every vertex in one
        dispatch.  ``k=j`` peels only buckets below ``j`` and exits as
        soon as the j-core remains — ``run(k=1)`` does exactly AC-4
        trimming's work, and its ``status`` is bit-identical to
        :class:`~repro.core.engine.TrimEngine` AC-4.
        """
        import jax.numpy as jnp
        k = self._check_k(k)
        n, m = self.graph.n, self.graph.m
        if active is not None and np.shape(active) != (n,):
            raise ValueError(f"active mask must have shape ({n},), got "
                             f"{np.shape(active)}")
        act = (jnp.ones((n,), bool) if active is None
               else jnp.asarray(active, bool))
        if n == 0 or m == 0:
            return self._degenerate(act, k, batched=False)
        fn = _peel_runner(self.method, k, self.use_kernel, batched=False,
                          fplan=self.fplan, instrument=self.instrument,
                          max_rounds=self.max_rounds)
        core, rnd, rounds, stats = self._dispatch(
            fn, (self.graph.indptr, self.graph.indices),
            self._transpose_arrays(), act)
        return PeelResult(core, rnd, rounds, k_stop=k,
                          round_stats=self._wrap_stats(rounds, stats))

    def run_batch(self, active_masks, k: int | None = None) -> PeelResult:
        """Peel B induced subgraphs in one vmapped dispatch.

        ``active_masks``: (B, n) bool.  Returns one :class:`PeelResult`
        with stacked (B, n) ``coreness``/``peel_round`` and (B,) rounds,
        equal row-wise to sequential ``run()`` calls.
        """
        import jax.numpy as jnp
        k = self._check_k(k)
        n, m = self.graph.n, self.graph.m
        masks = jnp.asarray(active_masks, bool)
        if masks.ndim != 2 or masks.shape[1] != n:
            raise ValueError(f"active_masks must be (B, {n}) bool, got "
                             f"{masks.shape}")
        if n == 0 or m == 0:
            return self._degenerate(masks, k, batched=True)
        # vmap lowers the per-round direction cond to a select that runs
        # BOTH bodies every round, so batched peels always execute dense
        fn = _peel_runner(self.method, k, self.use_kernel, batched=True,
                          fplan=FrontierPlan(), instrument=self.instrument,
                          max_rounds=self.max_rounds)
        core, rnd, rounds, stats = self._dispatch(
            fn, (self.graph.indptr, self.graph.indices),
            self._transpose_arrays(), masks)
        return PeelResult(core, rnd, rounds, k_stop=k,
                          round_stats=self._wrap_stats(rounds, stats))

    def _wrap_stats(self, rounds, stats):
        if not self.instrument:
            return None
        rs = obs.RoundStats(rounds, stats, max_rounds=self.max_rounds)
        self._publish_round_stats(rs)
        return rs

    def nbytes_breakdown(self):
        # _tarrs[0:2] alias the cached transpose (accounted by the base)
        out = super().nbytes_breakdown()
        if self._tarrs is not None:
            out["row_ids"] = obs.array_nbytes(self._tarrs[2])
        return out

    # -- degenerate paths (no kernel dispatch, still device-resident) ------
    def _degenerate(self, act, k, *, batched):
        """n == 0 or m == 0: every active vertex has out-degree 0, so the
        whole graph is the zero bucket — coreness 0 in one round (or no
        rounds for k == 0, where nothing peels).  Device-resident jnp with
        the kernel path's dtypes, mirroring ``TrimEngine._degenerate``."""
        import jax.numpy as jnp
        lead = act.shape[:-1]
        core = jnp.where(act, jnp.int32(0), jnp.int32(-1))
        if k == 0:
            rnd = jnp.full(act.shape, -1, jnp.int32)
            rounds = jnp.zeros(lead, jnp.int32)
            peeled = jnp.zeros(lead + (1,), jnp.int32)
        else:
            rnd = jnp.where(act, jnp.int32(0), jnp.int32(-1))
            rounds = jnp.ones(lead, jnp.int32)
            peeled = act.sum(axis=-1, dtype=jnp.int32)[..., None]
        if not batched:
            rounds = rounds.reshape(())
        rs = None
        if self.instrument:
            R = self.max_rounds
            pad = [(0, 0)] * (peeled.ndim - 1) + [(0, R - 1)]
            frontier = jnp.pad(peeled, pad)
            zeros = jnp.zeros_like(frontier)
            rs = obs.RoundStats(
                rounds, {"r_frontier": frontier, "r_edges": zeros,
                         "r_k": zeros}, max_rounds=R)
        return PeelResult(core, rnd, rounds, k_stop=k, round_stats=rs)


# -- host oracle ---------------------------------------------------------------

def coreness_oracle(indptr: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """Matula–Beck out-degree coreness (numpy/python) — the test oracle.

    Repeatedly removes a single minimum-live-out-degree vertex; the
    running maximum of removal degrees is the removed vertex's coreness.
    Structurally different from the engine's bucketed cascade (one vertex
    at a time, no buckets), hence a real cross-check.
    """
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    n = len(indptr) - 1
    deg = np.diff(indptr).astype(np.int64)
    preds: list[list[int]] = [[] for _ in range(n)]
    for v in range(n):
        for e in range(indptr[v], indptr[v + 1]):
            preds[int(indices[e])].append(v)
    alive = np.ones(n, bool)
    core = np.full(n, -1, np.int64)
    k = 0
    for _ in range(n):
        cand = np.nonzero(alive)[0]
        v = cand[np.argmin(deg[cand])]
        k = max(k, int(deg[v]))
        core[v] = k
        alive[v] = False
        for u in preds[v]:
            if alive[u]:
                deg[u] -= 1
    return core


__all__ = ["plan_peel", "PeelEngine", "PeelResult", "peel_bucket_kernel",
           "coreness_oracle"]
