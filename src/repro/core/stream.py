"""Compile-once incremental trimming over edge-update batches (DESIGN.md §9).

The paper's central observation — trimming *is* arc-consistency — makes
AC-4's support counters (paper §5) persistent state: a long-lived service
can absorb edge deletions/insertions in O(1) amortized counter work per
arc and re-trim in time proportional to the *delta*, not the graph.
:class:`StreamEngine` is the third engine family (``"stream"`` in the
kernel registry), built on the same :class:`~repro.core.enginebase.EngineBase`
lifecycle as trim and reach::

    engine = plan_stream(graph, capacity=1024)
    res = engine.apply(deletions=(du, dv), insertions=(iu, iv))
    result = engine.retrim()            # current fixpoint, zero dispatch
    result = engine.retrim(full=True)   # from-scratch rebuild, 1 dispatch
    g_now  = engine.snapshot()          # materialized CSRGraph

Execution model (all static shapes, one device dispatch per ``apply``):

1. The batch is resolved on the host against the :class:`~repro.core.graph.
   DeltaCSR` overlay (tombstone ids / insert slots, multiset semantics)
   and pow2-padded.
2. A jitted step scatters the structural updates into the device overlay,
   adjusts the AC-4 live-out-degree counters of the touched sources with
   the ``kernels.counter_scatter`` Pallas kernel (one dispatch emits the
   newly-dead frontier), and
3. runs an *incremental* fixpoint: the AC-4 propagation body of
   ``core/ac4.py`` — bulk counter decrements through Gᵀ — extended with
   the overlay (tombstoned transpose edges masked out, insert-buffer arcs
   segment-summed in) and seeded from the delta frontier instead of all
   vertices.

**Insertions and revival.**  Deleting edges is monotone: continuing from
the previous fixpoint reaches exactly the from-scratch fixpoint.  An
inserted arc whose source is currently dead can *revive* vertices (it may
give a dead vertex a live successor, or close a new cycle among dead
vertices), which counter maintenance cannot express.  The step detects
that case on device (``dirty``) and — inside the same dispatch, via a
``where``-select on the loop's initial state — falls back to the
from-scratch initialization (all vertices live, counters = live
out-degree over the overlay).  Either way ``retrim()`` is bit-identical
to a from-scratch :meth:`~repro.core.engine.TrimEngine.run` on the
materialized graph; insertions between live endpoints and all deletions
stay on the cheap incremental path.
"""
from __future__ import annotations

import functools

import numpy as np

from .. import obs
from ..fault.plane import get_fault_plane
from .common import FrontierPlan, frontier_plan
from .enginebase import _TRACE_COUNT, EngineBase, jit_named
from .graph import CSRGraph, DeltaCSR, TrimResult, _pow2, \
    _stable_counting_order, check_edge_ids
from .registry import KernelSpec, get_kernel, register_kernel

STREAM_BACKENDS = ("dense",)

_STAT_NAMES = ("r_frontier", "r_edges", "r_decrements")


def _batch_tier(plan: FrontierPlan, width: int, n: int,
                m: int) -> FrontierPlan | None:
    """The sparse tier an apply tries before ``plan``'s own, sized from
    the batch: a batch of ``width`` padded updates seeds at most that many
    dead sources, and its cascade rounds stay near that size.  ``cap`` is
    the width (at least 128), ``ecap`` four mean in-degrees a member;
    ``None`` where that is no smaller than ``plan``."""
    cap = min(_pow2(max(width, 128)), plan.cap)
    ecap = min(_pow2(4 * cap * max(m // max(n, 1), 1)), plan.ecap)
    if (cap, ecap) == (plan.cap, plan.ecap):
        return None
    return FrontierPlan(plan.mode, cap, ecap)


# -- the stream kernel (family "stream") ---------------------------------------

def _run_stream_ac4(tarrs, overlay, state, updates, *, use_kernel,
                    full: bool, revivable: bool = True,
                    frontier: FrontierPlan = FrontierPlan(),
                    instrument: bool = False, max_rounds: int = 0):
    """One apply step: structural overlay updates + counter maintenance +
    (incremental or from-scratch) AC-4 fixpoint, all in one dispatch.

    tarrs:   (t_indptr, t_indices, t_rows, perm) — base Gᵀ plus the
             permutation mapping Gᵀ edge order back to base edge order
             (``perm``), through which the base tombstone mask is read in
             transpose order: whole, once per step, where every round
             reads all of it (a dense plan, or ``full``); otherwise only
             in the branches that read it — at a sparse round's expanded
             positions, or whole inside a dense round or the revival
             rebuild.
    overlay: (tomb, ins_src, ins_dst, ins_alive) — device overlay arrays.
    state:   (status bool (n,), counters int32 (n,)) — the persistent
             AC-4 state; ``counters[v]`` = number of live out-arcs of a
             live vertex v (DESIGN.md §9).
    updates: (del_src, del_dst, del_eid, del_slot, add_src, add_dst,
             add_slot) — pow2-padded int32 batches; sentinel ids (n for
             endpoints, m for edge ids, capacity for slots) are dropped
             by the ``mode="drop"`` scatters / the counter kernel.
    full:    static — ignore the incremental state and rebuild the
             fixpoint from scratch over the overlay (plan-time init,
             ``retrim(full=True)``, and the bit-identity oracle).
    revivable: static — the batch contains insertions, so the revival
             fallback must be compiled in (a ``lax.cond`` that rebuilds
             from scratch when an inserted arc leaves a dead source).
             Deletion-only batches are monotone and compile the fallback
             — including its counter re-initialization — out entirely.
    frontier: static sparse-frontier plan (DESIGN.md §12).  Fixpoint
             rounds whose delta frontier fits ``cap`` members and ``ecap``
             Gᵀ edges compact the frontier, expand only its transpose
             rows (tombstones masked through the expansion's edge
             positions), and scatter-add the bounded buffer; the small
             insert-buffer contribution stays a dense segment-sum either
             way.  A round that fits the smaller tier sized from the
             batch (:func:`_batch_tier`) takes it first, so a cascade's
             round costs in proportion to the batch, not to m.  The
             decrement vector — and therefore the fixpoint and every
             stat — is bit-identical to the dense path.
    instrument: static — thread per-round fixpoint telemetry (processed
             frontier size, live arcs traversed, counter decrements
             applied to live vertices; DESIGN.md §11) through the loop
             carry as ``(max_rounds,)`` int32 buffers.  ``False``
             compiles the stats out entirely — the returned stats slot is
             ``None`` and the jaxpr is identical to the uninstrumented
             kernel.
    """
    import jax
    import jax.numpy as jnp

    from ..kernels import ops as kops

    t_indptr, t_indices, t_rows, perm = tarrs
    tomb, ins_src, ins_dst, ins_alive = overlay
    status, counters = state
    del_src, del_dst, del_eid, del_slot, add_src, add_dst, add_slot = updates
    n = status.shape[0]
    hi = max(n - 1, 0)

    # 1. structural updates (pow2-padding sentinels fall off the end)
    tomb = tomb.at[del_eid].set(True, mode="drop")
    ins_alive = ins_alive.at[del_slot].set(False, mode="drop")
    ins_src = ins_src.at[add_slot].set(add_src, mode="drop")
    ins_dst = ins_dst.at[add_slot].set(add_dst, mode="drop")
    ins_alive = ins_alive.at[add_slot].set(True, mode="drop")
    # tombstones in Gᵀ edge order: an m-wide gather, hoisted out of the
    # loop only where every round pays it anyway; a sparse round reads
    # its ecap positions through ``perm`` instead
    hoist = full or frontier.mode == "dense"
    if hoist:
        tomb_t = tomb[perm]

    def tomb_all():
        return tomb_t if hoist else tomb[perm]

    def stat(ids):
        return status[jnp.clip(ids, 0, hi)] & (ids < n)

    # 2. counter deltas w.r.t. the pre-batch fixpoint: an arc contributes
    # to its source's counter iff both endpoints are live
    del_live = stat(del_src) & stat(del_dst)
    add_live = stat(add_src) & stat(add_dst)
    upd_src = jnp.concatenate([del_src, add_src])
    upd_delta = jnp.concatenate([-del_live.astype(jnp.int32),
                                 add_live.astype(jnp.int32)])
    new_counters, newly = kops.counter_scatter(
        counters, status, upd_src, upd_delta, use_kernel=use_kernel)

    def scratch_init(_):
        # from-scratch: all vertices live, counters = live out-degree
        # over the overlay (two segment-sums)
        deg0 = jax.ops.segment_sum((~tomb_all()).astype(jnp.int32),
                                   t_indices, num_segments=n)
        deg0 = deg0 + jax.ops.segment_sum(ins_alive.astype(jnp.int32),
                                          jnp.clip(ins_src, 0, hi),
                                          num_segments=n)
        return ~(deg0 == 0), deg0, deg0 == 0

    def incr_init(_):
        return status & ~newly, new_counters, newly

    if full:
        dirty = jnp.array(False)
        status0, counters0, frontier0 = scratch_init(None)
    elif revivable:
        # revival: an inserted arc out of a dead source can resurrect
        # vertices (new support, or a new cycle among dead vertices) —
        # restart the fixpoint from scratch inside this same dispatch
        dirty = jnp.any((add_src < n) & ~status[jnp.clip(add_src, 0, hi)])
        status0, counters0, frontier0 = jax.lax.cond(
            dirty, scratch_init, incr_init, None)
    else:
        # deletion-only batches are monotone: no revival, and the
        # from-scratch re-initialization is compiled out entirely
        dirty = jnp.array(False)
        status0, counters0, frontier0 = incr_init(None)

    # 3. AC-4 propagation (core/ac4.py's body over the overlay): each Gᵀ
    # arc whose dead propagator is on the frontier decrements its
    # predecessor — base arcs masked by tombstones, insert-buffer arcs
    # segment-summed in
    ins_tgt = jnp.clip(ins_dst, 0, hi)
    ins_own = jnp.clip(ins_src, 0, hi)
    sparse = frontier.mode != "dense"
    if sparse:
        t_deg = t_indptr[1:] - t_indptr[:-1]
        mt = t_indices.shape[0]
        # smallest first: fitting a tier implies fitting every larger one
        tiers = [p for p in (_batch_tier(frontier, del_src.shape[0]
                                         + add_src.shape[0], n, mt),
                             frontier) if p is not None]

    def base_dec_dense(f):
        return jax.ops.segment_sum(
            (f[t_rows] & ~tomb_all()).astype(jnp.int32), t_indices,
            num_segments=n)

    def base_dec_sparse(tier, f):
        # expand only the frontier's Gᵀ rows; a tombstoned base arc is
        # masked through its expanded edge *position* (Gᵀ order), exactly
        # the arcs ``~tomb_all()`` drops from the dense segment-sum
        ids, _ = kops.frontier_compact(f, tier.cap)
        _, tgt, pos, valid = kops.sparse_expand(t_indptr, t_indices, ids,
                                                tier.ecap)
        if mt:          # an edgeless base (everything compacted away or
            # inserted) expands to no valid slots — nothing to tombstone
            valid = valid & ~tomb[perm[jnp.clip(pos, 0, mt - 1)]]
        return jnp.zeros((n,), jnp.int32).at[
            jnp.where(valid, tgt, n)].add(1, mode="drop")

    def cond(s):
        return jnp.any(s["frontier"])

    def body(s):
        f = s["frontier"]
        if sparse:
            count = jnp.sum(f)
            tedges = jnp.sum(jnp.where(f, t_deg, 0))
            # the index of the smallest tier the round fits, else dense
            tier = sum(((count > p.cap) | (tedges > p.ecap)).astype(
                jnp.int32) for p in tiers)
            sparse_ok = tier < len(tiers)
            dec = jax.lax.switch(
                tier, [functools.partial(base_dec_sparse, p) for p in tiers]
                + [base_dec_dense], f)
        else:
            dec = base_dec_dense(f)
        dec = dec + jax.ops.segment_sum(
            (f[ins_tgt] & ins_alive).astype(jnp.int32), ins_own,
            num_segments=n)
        c = s["counters"] - dec
        newly_ = s["status"] & (c <= 0)
        new = dict(status=s["status"] & ~newly_, counters=c,
                   frontier=newly_, rounds=s["rounds"] + 1)
        if instrument:
            vals = dict(
                r_frontier=jnp.sum(f),
                r_edges=jnp.sum(dec),
                r_decrements=jnp.sum(jnp.where(s["status"], dec, 0)))
            if sparse:
                vals["r_sparse"] = sparse_ok.astype(jnp.int32)
            new["stats"] = obs.stats_record(s["stats"], s["rounds"], **vals)
        return new

    state0 = dict(status=status0, counters=counters0, frontier=frontier0,
                  rounds=jnp.array(0, jnp.int32))
    if instrument:
        # attribute the from-scratch counter re-initialization (a scan of
        # every overlay arc) to round slot 0 when it actually ran
        init_scan = jnp.array(t_rows.shape[0] + ins_alive.shape[0],
                              jnp.int32)
        if not full:
            init_scan = jnp.where(dirty, init_scan, 0)
        names = _STAT_NAMES + (("r_sparse",) if sparse else ())
        state0["stats"] = obs.stats_record(
            obs.stats_init(max_rounds, names), jnp.int32(0),
            r_edges=init_scan)
    out = jax.lax.while_loop(cond, body, state0)
    return ((tomb, ins_src, ins_dst, ins_alive),
            (out["status"], out["counters"]), out["rounds"], dirty,
            out["stats"] if instrument else None)


register_kernel(KernelSpec(name="ac4", run=_run_stream_ac4,
                           needs_transpose=True), family="stream")


@functools.lru_cache(maxsize=None)
def _stream_runner(method: str, use_kernel, full: bool, revivable: bool,
                   fplan: FrontierPlan = FrontierPlan(),
                   instrument: bool = False, max_rounds: int = 0):
    """Jitted apply step, cached process-wide on the static configuration
    (per method: from-scratch, deletion-only, and with-insertions
    variants; ``fplan`` bakes the sparse-frontier capacities in,
    DESIGN.md §12)."""
    spec = get_kernel(method, family="stream")

    def call(tarrs, overlay, state, updates):
        _TRACE_COUNT[0] += 1  # runs at trace time only
        return spec.run(tarrs, overlay, state, updates,
                        use_kernel=use_kernel, full=full,
                        revivable=revivable, frontier=fplan,
                        instrument=instrument, max_rounds=max_rounds)

    return jit_named(call, f"stream_{method}")


# -- results -------------------------------------------------------------------

class StreamResult:
    """Outcome of one ``apply`` batch — device-resident, lazily
    materialized (the ``TrimResult`` conventions).

    status:  (n,) bool fixpoint liveness after the batch
    rounds:  incremental propagation rounds this batch ran
    dirty:   the batch contained a reviving insertion and fell back to the
             from-scratch initialization (still one dispatch)
    resolve_s: host seconds of the batch's ``stream.resolve`` span
             (overlay resolution and padded upload)
    """

    __slots__ = ("_status", "_rounds", "_dirty", "_round_stats",
                 "resolve_s")

    def __init__(self, status, rounds, dirty, round_stats=None,
                 resolve_s=0.0):
        self._status = status
        self._rounds = rounds
        self._dirty = dirty
        self._round_stats = round_stats
        self.resolve_s = resolve_s

    @property
    def status(self):
        return self._status

    @property
    def rounds(self) -> int:
        if self._rounds is not None and not isinstance(self._rounds, int):
            self._rounds = int(self._rounds)
        return self._rounds

    @property
    def dirty(self) -> bool:
        if not isinstance(self._dirty, bool):
            self._dirty = bool(self._dirty)
        return self._dirty

    @property
    def n_trimmed(self) -> int:
        return int((~np.asarray(self._status)).sum())

    @property
    def round_stats(self):
        """Per-round fixpoint telemetry (:class:`repro.obs.RoundStats`)
        for this batch, or ``None`` when the engine was planned without
        ``instrument=True``."""
        return self._round_stats

    def __repr__(self):  # no device sync: report only static facts
        return f"StreamResult(n={self._status.shape[0]})"


# -- the engine ----------------------------------------------------------------

def plan_stream(graph, method: str = "ac4", backend: str = "dense", *,
                capacity: int | None = None,
                load_factor: float | None = None,
                use_kernel: bool | None = None,
                frontier: str = "auto",
                instrument: bool = False,
                max_rounds: int | None = None) -> "StreamEngine":
    """Build a :class:`StreamEngine` over ``graph`` (a :class:`CSRGraph`
    or a pre-built :class:`DeltaCSR` overlay).

    ``capacity`` (default 256) sizes the insert buffer (rounded up to a
    power of two; the engine compacts or doubles it when a batch would
    overflow).  ``load_factor`` (default 0.5) is the overlay fraction —
    (tombstones + consumed insert slots) / base edges — beyond which
    ``apply`` folds the overlay into a fresh base CSR via
    :meth:`DeltaCSR.compact`.  A pre-built :class:`DeltaCSR` carries its
    own sizing, so passing either kwarg with one raises rather than
    silently ignoring it.

    ``frontier`` (DESIGN.md §12) selects the sparse-frontier substrate
    for the incremental fixpoint — "auto" (default) switches per round on
    device, so small delta cascades expand only the frontier's transpose
    rows instead of segment-summing the whole overlay.  Capacities are
    sized once from the base graph at plan time and survive compaction.

    ``instrument=True`` threads per-round fixpoint telemetry through
    every dispatch (DESIGN.md §11): each :class:`StreamResult` (and the
    ``retrim`` :class:`TrimResult`) carries a ``round_stats``
    :class:`repro.obs.RoundStats`.  ``max_rounds`` caps the static round
    buffer; rounds past it fold into the last slot (totals stay exact).
    The default keeps stats compiled out — zero extra work, bit-identical
    results.
    """
    return StreamEngine(graph, method=method, backend=backend,
                        capacity=capacity, load_factor=load_factor,
                        use_kernel=use_kernel, frontier=frontier,
                        instrument=instrument, max_rounds=max_rounds)


class StreamEngine(EngineBase):
    """Compile-once incremental trimming over one mutating graph.  Build
    with :func:`plan_stream`."""

    family = "stream"

    def __init__(self, graph, *, method, backend, capacity, load_factor,
                 use_kernel, frontier="auto", instrument=False,
                 max_rounds=None):
        self.spec = get_kernel(method, family="stream")
        if backend not in STREAM_BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; expected one "
                             f"of {STREAM_BACKENDS}")
        if isinstance(graph, DeltaCSR):
            if capacity is not None or load_factor is not None:
                raise ValueError(
                    "capacity/load_factor are fixed by the DeltaCSR you "
                    "passed; construct it with the sizing you want")
            delta = graph
        else:
            delta = DeltaCSR(graph,
                             capacity=256 if capacity is None else capacity,
                             load_factor=(0.5 if load_factor is None
                                          else load_factor))
        super().__init__(delta.base)
        self.delta = delta
        self.method = method
        self.backend = backend
        self.use_kernel = use_kernel
        # sized once from the base graph; compaction changes the
        # representation, not the graph, so the plan stays valid
        self.fplan = frontier_plan(frontier, delta.n, delta.m_base)
        self.instrument = bool(instrument)
        self.max_rounds = (obs.round_capacity(delta.n, max_rounds)
                           if self.instrument else 0)
        self._tarrs = None
        self._state = None          # (status bool (n,), counters int32 (n,))
        self._rounds_total = None   # device scalar, accumulated lazily
        self._last_stats = None     # stats buffers of the latest dispatch
        self._compactions = 0
        if delta.n:
            self.retrim(full=True)  # establish the fixpoint at plan time
        else:
            import jax.numpy as jnp
            self._state = (jnp.zeros((0,), bool), jnp.zeros((0,), jnp.int32))
            self._rounds_total = jnp.array(0, jnp.int32)

    def plan_signature(self) -> str:
        sig = (f"stream[{self.method}/{self.backend}]"
               f"(n={self.delta.n},m={self.delta.m_base},"
               f"cap={self.delta.capacity})"
               f"+frontier[{self.fplan.mode}]")
        return sig + "+stats" if self.instrument else sig

    # -- cached resources --------------------------------------------------
    def _transpose_arrays(self):
        """Base Gᵀ arrays plus the base-edge→transpose-edge permutation
        (int32), rebuilt only at compaction: a host counting sort of the
        overlay's mirrors and one upload, spanned as ``stream.transpose``.
        Any Gᵀ the caller holds is not used."""
        if self._tarrs is None:
            import jax.numpy as jnp
            base = self.delta.base
            n, m = base.n, base.m
            with obs.span("transpose", cat="stream"):
                indices = self.delta._dst_np
                src = self.delta._src_np  # edge sources, held by the overlay
                perm = _stable_counting_order(indices, n)
                t_counts = (np.bincount(indices, minlength=n) if m
                            else np.zeros(n, np.int64))
                t_indptr = np.zeros(n + 1, dtype=np.int32)
                np.cumsum(t_counts, out=t_indptr[1:])
                t_indices = src[perm]
                t_rows = np.repeat(np.arange(n, dtype=np.int64), t_counts)
                self._tarrs = tuple(
                    jnp.asarray(a, jnp.int32)
                    for a in (t_indptr, t_indices, t_rows, perm))
            # seed the EngineBase cache so .transpose is consistent
            if self._transpose is None:
                self._transpose = CSRGraph(self._tarrs[0], self._tarrs[1])
                self._transpose_builds += 1
        return self._tarrs

    def _overlay_arrays(self):
        d = self.delta
        return (d.tomb, d.ins_src, d.ins_dst, d.ins_alive)

    # -- host-side batch plumbing ------------------------------------------
    @staticmethod
    def _pairs(edges):
        if edges is None:
            return (np.zeros(0, np.int64),) * 2
        src, dst = edges
        return (np.asarray(src, np.int64).reshape(-1),
                np.asarray(dst, np.int64).reshape(-1))

    def _padded_updates(self, dsrc, ddst, eids, slots_del, isrc, idst,
                        slots_ins):
        import jax.numpy as jnp
        n, m, cap = self.delta.n, self.delta.m_base, self.delta.capacity
        bd, bi = _pow2(max(dsrc.size, 1)), _pow2(max(isrc.size, 1))

        def pad(a, width, fill):
            out = np.full(width, fill, np.int64)
            out[:a.size] = a
            return jnp.asarray(out, jnp.int32)

        return (pad(dsrc, bd, n), pad(ddst, bd, n), pad(eids, bd, m),
                pad(slots_del, bd, cap), pad(isrc, bi, n),
                pad(idst, bi, n), pad(slots_ins, bi, cap))

    def _write_back(self, overlay, state, rounds):
        d = self.delta
        d.tomb, d.ins_src, d.ins_dst, d.ins_alive = overlay
        self._state = state
        self._rounds_total = (rounds if self._rounds_total is None
                              else self._rounds_total + rounds)

    def _wrap_stats(self, rounds, stats):
        """RoundStats for the latest dispatch (also kept as
        ``_last_stats`` so zero-dispatch ``retrim()`` can report the
        telemetry of the batch that produced the current fixpoint)."""
        if not self.instrument:
            return None
        rs = (obs.RoundStats(rounds, stats, max_rounds=self.max_rounds)
              if stats is not None else
              obs.RoundStats(0, obs.stats_init(self.max_rounds,
                                               _STAT_NAMES),
                             max_rounds=self.max_rounds))
        self._last_stats = rs
        if stats is not None:
            self._publish_round_stats(rs)
        return rs

    def nbytes_breakdown(self):
        # _tarrs[0:2] seed the base transpose cache (already accounted);
        # the transpose row ids + base-edge permutation and the DeltaCSR
        # overlay (tombstones, insert buffers, host index) are new bytes
        out = super().nbytes_breakdown()
        for k, v in self.delta.nbytes_breakdown().items():
            out[f"delta_{k}"] = v
        if self._tarrs is not None:
            out["transpose_perm"] = obs.array_nbytes(self._tarrs[2:])
        if self._state is not None:
            out["state"] = obs.array_nbytes(self._state)
        return out

    # -- execution ---------------------------------------------------------
    def apply(self, deletions=None, insertions=None) -> StreamResult:
        """Apply one edge-update batch and advance the fixpoint.

        ``deletions`` / ``insertions``: ``(src, dst)`` array pairs.
        Deleting an edge that is not present raises ``ValueError`` (and
        leaves the batch unapplied).  One device dispatch; the update
        arrays are pow2-padded so repeated batch sizes never retrace.
        The batch's resolution against the overlay and its padded upload
        are the ``stream.resolve`` span, whose seconds the result keeps
        as ``resolve_s``; a compaction that frees the insert buffer runs
        before it.
        """
        dsrc, ddst = self._pairs(deletions)
        isrc, idst = self._pairs(insertions)
        d = self.delta
        if d.n == 0:
            if dsrc.size or isrc.size:
                raise ValueError("cannot update an empty (n=0) graph")
            return StreamResult(self._state[0], 0, False,
                                round_stats=self._wrap_stats(0, None))
        # validate the whole batch before anything commits: a bad
        # insertion must not leave the deletions half-applied
        isrc, idst = check_edge_ids(d.n, isrc, idst)
        # fault point "mid-update-batch" (DESIGN.md §14): the batch is
        # validated but nothing — host mirror or device — has committed,
        # so a fault here is retry-safe with the same batch.  Past this
        # point the host mirrors mutate before the dispatch, and recovery
        # must restore from a checkpoint instead.
        fplane = get_fault_plane()
        if fplane.enabled:
            fplane.arm("mid-update-batch", family=self.family,
                       deletions=int(dsrc.size), insertions=int(isrc.size))
        if d.n_ins + isrc.size > d.capacity:
            self.compact()          # free the insert buffer first
            if isrc.size > d.capacity:
                d.grow(isrc.size)
        resolve = obs.span("resolve", cat="stream")
        with resolve:
            eids, slots_del = d.resolve_deletions(dsrc, ddst)
            slots_ins = d.stage_inserts(isrc, idst)
            updates = self._padded_updates(dsrc, ddst, eids, slots_del,
                                           isrc, idst, slots_ins)
        fn = _stream_runner(self.method, self.use_kernel, full=False,
                            revivable=bool(isrc.size), fplan=self.fplan,
                            instrument=self.instrument,
                            max_rounds=self.max_rounds)
        overlay, state, rounds, dirty, stats = self._dispatch(
            fn, self._transpose_arrays(), self._overlay_arrays(),
            self._state, updates)
        self._write_back(overlay, state, rounds)
        res = StreamResult(state[0], rounds, dirty,
                           round_stats=self._wrap_stats(rounds, stats),
                           resolve_s=resolve.seconds)
        if d.needs_compact:
            self.compact()
        return res

    def retrim(self, full: bool = False) -> TrimResult:
        """The current trimming fixpoint as a :class:`TrimResult`,
        bit-identical to a from-scratch ``TrimEngine.run()`` on
        :meth:`snapshot` (the acceptance oracle).

        ``full=False`` (default) returns the incrementally-maintained
        fixpoint — zero dispatches.  ``full=True`` discards the state and
        rebuilds it from scratch over the overlay in one dispatch (the
        from-scratch baseline an incremental apply is compared with).
        """
        import jax.numpy as jnp
        if full and self.delta.n:
            fn = _stream_runner(self.method, self.use_kernel, full=True,
                                revivable=False, fplan=self.fplan,
                                instrument=self.instrument,
                                max_rounds=self.max_rounds)
            z = np.zeros(0, np.int64)
            state_in = (self._state if self._state is not None else (
                jnp.zeros((self.delta.n,), bool),
                jnp.zeros((self.delta.n,), jnp.int32)))
            overlay, state, rounds, _, stats = self._dispatch(
                fn, self._transpose_arrays(), self._overlay_arrays(),
                state_in, self._padded_updates(z, z, z, z, z, z, z))
            self.delta.tomb, self.delta.ins_src, self.delta.ins_dst, \
                self.delta.ins_alive = overlay
            self._state = state
            self._rounds_total = rounds
            self._wrap_stats(rounds, stats)
        status, _ = self._state
        return TrimResult(status=status.astype(jnp.int32),
                          rounds=self._rounds_total,
                          round_stats=self._last_stats)

    # -- checkpoint/resume (DESIGN.md §14) ---------------------------------
    def state_dict(self):
        """DeltaCSR overlay (base + tombstones + insert buffers) plus the
        persistent AC-4 fixpoint state.  The base's ``graph_*``/transpose
        keys are replaced by the overlay's own serialization — the base
        CSR *is* the graph, and the transpose/permutation caches are
        rebuilt deterministically from the restored host mirrors."""
        out = dict(self.delta.state_dict())
        out["status"] = self._state[0]
        out["counters"] = self._state[1]
        out["rounds_total"] = self._rounds_total
        return out

    def state_meta(self):
        meta = super().state_meta()
        meta["delta"] = self.delta.state_meta()
        meta["compactions"] = self._compactions
        return meta

    def _plan_kwargs(self):
        return {"method": self.method, "backend": self.backend,
                "capacity": self.delta.capacity,
                "load_factor": self.delta.load_factor,
                "use_kernel": self.use_kernel,
                "frontier": self.fplan.mode, "instrument": self.instrument,
                "max_rounds": (self.max_rounds if self.instrument
                               else None)}

    def load_state(self, tree, meta):
        """Overwrite overlay + fixpoint state with a checkpoint's exact
        arrays.  The AC-4 counters are path-dependent on dead vertices
        (a dead vertex's counter freezes wherever propagation left it),
        so they are restored verbatim rather than recomputed — resume is
        bit-identical to the uninterrupted engine, counters included."""
        import jax.numpy as jnp
        if meta.get("family") != self.family:
            raise ValueError(f"checkpoint family {meta.get('family')!r} "
                             f"does not match engine family "
                             f"{self.family!r}")
        self.delta.load_state(tree, meta["delta"])
        self.graph = self.delta.base
        self._state = (jnp.asarray(np.asarray(tree["status"], bool)),
                       jnp.asarray(np.asarray(tree["counters"]),
                                   jnp.int32))
        self._rounds_total = jnp.asarray(
            np.asarray(tree["rounds_total"]), jnp.int32)
        self._dispatches = int(meta.get("dispatches", 0))
        self._traces = int(meta.get("traces", 0))
        self._transpose_builds = int(meta.get("transpose_builds", 0))
        self._compactions = int(meta.get("compactions", 0))
        self._last_stats = None
        self._transpose = None
        self._invalidate_caches()

    def _invalidate_caches(self):
        self._tarrs = None

    def snapshot(self) -> CSRGraph:
        """Materialize the current graph (base minus tombstones plus live
        inserts) as a standalone :class:`CSRGraph`; the overlay is kept."""
        return self.delta.materialize()

    def compact(self):
        """Fold the overlay into a fresh base CSR (O(n+m) counting sort)
        and rebuild the transpose/permutation caches.  The fixpoint state
        is untouched — compaction changes the representation, not the
        graph.  Spanned as ``stream.compact``; the transpose is rebuilt
        at the next dispatch."""
        with obs.span("compact", cat="stream"):
            self.graph = self.delta.compact()
        self._transpose = None
        self._tarrs = None
        self._compactions += 1

    @property
    def compactions(self) -> int:
        return self._compactions

    @property
    def status(self):
        """The persistent (n,) bool liveness fixpoint, device-resident."""
        return self._state[0]


__all__ = ["plan_stream", "StreamEngine", "StreamResult", "STREAM_BACKENDS"]
