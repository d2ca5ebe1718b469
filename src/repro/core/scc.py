"""SCC decomposition by Forward-Backward (FW-BW) search with graph trimming
— the paper's flagship application (§1.1, refs [30,29,54,32,11]) — as a
batched, device-resident multi-pivot driver.

Trimming removes size-1 SCCs in bulk *before* pivot searches: a vertex with
no live successor (or, symmetrically, no live predecessor) cannot lie on a
cycle, so it is its own SCC.  FW-BW then peels off one SCC per pivot:
SCC(pivot) = FW(pivot) ∩ BW(pivot), and recurses on the three remaining
regions.  BFS reachability is a frontier sweep over CSR — parallelizable
without difficulty, unlike DFS (paper §1.1).

The driver advances the worklist in *generations*: all pending regions
(pairwise disjoint by construction) are stacked into (B, n) masks and
drained at once —

* one batched :meth:`TrimEngine.run_batch_stacked` for the trim phase
  (forward on odd generations, backward on even ones, so both directions
  contribute over the run),
* one batched **trim-2** dispatch eliminating size-1 and size-2 SCCs that
  trimming cannot remove (self-loop singletons and mutually-captive
  2-cycles; Wang et al., "Parallel Strong Connectivity Based on Faster
  Reachability") before any pivot is spent on them,
* one batched :meth:`ReachEngine.run_batch` each for FW and BW, so B
  pivots advance in one vmapped dispatch per direction.

Worklists wider than ``max_batch`` regions are drained in equal pow2
chunks — one dispatch per chunk — so a single dispatch's device
footprint stays bounded on branchy SCC trees.

No host-side edge traversal remains: reachability runs inside the same
compiled substrate as trimming (``core.reach``, DESIGN.md §8), labels stay
device-resident until the single materialization at the end, and the host
only steers (region bookkeeping, pivot picking — O(Bn) mask work).

The four engines (trim FW/BW, reach FW/BW) share the driver's one
transpose build: the forward engines are pre-seeded with Gᵀ, the backward
engines sweep Gᵀ with their own caches pre-seeded with G, and Gᵀ
has G's exact array shapes, so each kernel is traced once per batch width
— except when G's max in-degree and max out-degree fall on opposite sides
of the reach window, where the two directions compile different pull
bodies (see ``reach.py``) and trace separately.
Per worklist generation the driver issues exactly one batched trim
dispatch and two batched reach dispatches (asserted against the engines'
``dispatches`` counters in the tests).
"""
from __future__ import annotations

import contextlib
import functools

import numpy as np

from .. import obs
from .engine import plan
from .enginebase import jit_named
from .graph import CSRGraph
from .reach import plan_reach

#: ``stats`` entries that time one call (seconds)
_CALL_SECONDS = ("plan_s", "transpose_s", "sync_s")
#: ``stats`` entries of one call, never checkpointed: its seconds and
#: where it built Gᵀ
_CALL_STATS = _CALL_SECONDS + ("transpose_on_device",)


def _pad_pow2(masks: np.ndarray) -> np.ndarray:
    """Pad a (B, n) mask stack with all-False rows up to the next power of
    two.  Batch width is a compile-time shape under vmap, so padding bounds
    the number of distinct executables per graph shape to log2(max B)
    instead of one per worklist width; the padded rows are empty regions
    and flow through trim/reach as no-ops."""
    b = masks.shape[0]
    bp = 1 << (b - 1).bit_length()
    if bp == b:
        return masks
    return np.concatenate(
        [masks, np.zeros((bp - b, masks.shape[1]), dtype=masks.dtype)])


def _chunks(masks, max_batch: int):
    """Split a pow2-padded (B, n) stack into at most ``max_batch``-row
    chunks.  B is a power of two, so every chunk is exactly ``max_batch``
    rows (or the single whole stack): the number of distinct compiled
    batch widths stays bounded, and so does the device memory of one
    vmapped dispatch (the per-round intermediates scale with the chunk's
    B, not the worklist's)."""
    b = masks.shape[0]
    if b <= max_batch:
        return [masks]
    return [masks[i:i + max_batch] for i in range(0, b, max_batch)]


@functools.lru_cache(maxsize=None)
def _trim2_runner():
    """Jitted, vmapped size-≤2 SCC detector — one device dispatch per
    worklist generation (per ``max_batch`` chunk).

    A live vertex pair {u, v} is a size-2 SCC *detectable locally* when
    the two are mutually captive (Wang et al.'s trim-2): every live
    out-edge of u goes to v and vice versa (any cycle through either must
    be the 2-cycle), or symmetrically every live in-edge (any cycle must
    enter through the 2-cycle).  With u == v the same predicate finds
    self-loop singletons — vertices whose only live out-edge (or in-edge)
    is their own loop, which trimming can never remove.  One-sided
    captivity is *not* sound (a fully-captive u merges into SCC(v), which
    may be larger), so only the two symmetric forms are used.

    Degrees/neighbors come scatter-free from cumsum-difference row
    reductions over G and Gᵀ (XLA CPU lowers a vmapped segment reduction
    to B per-edge scatters, an order of magnitude slower than the two
    prefix sums this needs): the live out/in degree is a row count, and
    the unique live successor/predecessor falls out of the *sum* of live
    targets per row — exact whenever the degree is 1, the only case it is
    read (int32 wrap-around on fatter rows is never observed).  Returns
    ``(detected, partner)``: (B, n) bool and (B, n) int32 (partner ==
    index for singletons and undetected rows).
    """
    import jax.numpy as jnp

    def rowsum(indptr, per_edge):
        csum = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(per_edge)])
        return csum[indptr[1:]] - csum[indptr[:-1]]

    def detect(indptr, indices, t_indptr, t_indices, live):
        n = live.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        # row v's live target count / target sum; the source-liveness
        # factor of the original per-edge mask folds into the row-level
        # ``live &`` below (rows of dead sources are never read)
        lt = live[indices]
        cnt_out = rowsum(indptr, lt.astype(jnp.int32))
        succ = rowsum(indptr, jnp.where(lt, indices, 0))
        ts = live[t_indices]
        cnt_in = rowsum(t_indptr, ts.astype(jnp.int32))
        pred = rowsum(t_indptr, jnp.where(ts, t_indices, 0))
        cap_out = live & (cnt_out == 1)
        s = jnp.clip(succ, 0, n - 1)
        pair_out = cap_out & cap_out[s] & (succ[s] == idx)
        cap_in = live & (cnt_in == 1)
        p = jnp.clip(pred, 0, n - 1)
        pair_in = cap_in & cap_in[p] & (pred[p] == idx)
        detected = pair_out | pair_in
        partner = jnp.where(pair_out, succ, jnp.where(pair_in, pred, idx))
        return detected, partner.astype(jnp.int32)

    return jit_named(detect, "scc_trim2", (None, None, None, None, 0))


def scc_decompose(graph: CSRGraph, use_trim: bool = True,
                  trim_method: str = "ac6", trim_transpose: bool = True,
                  max_pivots: int = 1_000_000, trim_backend: str = "dense",
                  reach_backend: str = "windowed", window: int = 16,
                  counters: bool = False, max_batch: int = 1024,
                  active=None, trim2: bool = True, workers: int = 1,
                  chunk: int = 4096, frontier: str = "auto",
                  instrument: bool = False,
                  max_rounds: int | None = None,
                  checkpoint_dir: str | None = None,
                  checkpoint_every: int = 0, checkpointer=None,
                  resume: bool = False):
    """Return (labels, stats). labels: (n,) int64 component ids (dense).

    ``active`` restricts decomposition to an induced subgraph: only
    vertices inside the (n,) bool mask are labeled (everything else
    returns -1).  The incremental driver uses this to re-decompose only
    the regions an update batch dirtied.

    ``trim_transpose=False`` restricts trimming to the forward direction
    on every generation.  ``counters=True`` additionally accumulates
    ``stats["trim_edges_traversed"]`` (the paper's traversal metric) at
    the cost of counter accumulation inside the trim kernels.
    ``stats["trim_passes"]`` counts per-region directional trim passes
    executed — each pending region gets exactly one pass per generation,
    in that generation's alternating direction (the old region-at-a-time
    driver ran up to two directions per region, so the two metrics are
    not comparable).

    ``reach_backend`` defaults to "windowed" (the pull sweep through the
    ``frontier_expand`` kernel): it is gather-based, which measures
    uniformly faster than the push scatter on CPU XLA and is the
    block-skipping Pallas path on TPU.  The transpose it needs is the one
    the driver already shares with the backward engines, so the choice
    costs no extra build.

    ``max_batch`` caps the batch width of a single device dispatch: a
    generation whose worklist outgrows it is drained in ``B/max_batch``
    equal chunks (B is pow2-padded), bounding the vmapped sweep's
    per-round intermediates — without it a branchy SCC tree could stack
    tens of thousands of (n,) regions into one dispatch.  Worklists up to
    ``max_batch`` regions keep the one-trim-two-reach dispatch contract
    per generation.

    ``trim2`` (default on) runs a size-≤2 SCC elimination between the
    trim and pivot phases of every generation: self-loop singletons and
    mutually-captive 2-cycles — which trimming can never remove and which
    would otherwise each consume a pivot (one FW-BW generation apiece
    when they chain through a region) — are detected in one batched
    dispatch and labeled directly.  Generations whose worklist dies in
    the trim phase skip it entirely, so fully-trimmable graphs pay
    nothing.  ``stats`` gains ``trim2_removed`` (vertices), ``trim2_sccs``
    (labels assigned), and ``trim2_dispatches``.

    ``workers`` partitions vertices over virtual workers inside the trim
    kernels (the paper's per-worker accounting; ``chunk`` is the paper's
    ``schedule(dynamic, 4096)`` chunk size — lower it below ``n/workers``
    or the whole graph lands on worker 0); with ``counters=True`` the
    driver additionally accumulates ``stats["per_worker_edges"]`` — an
    int64 ``(workers,)`` vector of traversed edges per worker summed
    over every trim pass, the quantity behind the paper's Fig. 4-style
    load-balance comparison.

    ``frontier`` (DESIGN.md §12) is threaded to all four engine plans.
    The driver's own dispatches are batched and therefore execute dense
    regardless (vmap lowers the per-round direction cond to a select),
    but the plans stay frontier-consistent with any single-region engines
    the caller shares.

    ``instrument=True`` plans all four engines with round-level telemetry
    (DESIGN.md §11): ``stats["trim_rounds"]`` / ``stats["reach_rounds"]``
    accumulate total fixpoint rounds.

    Every call is traced as ``obs`` spans (cat ``"scc"``), which a
    profiler trace holds as ``scc.<name>`` annotations and an
    ``obs.recording()`` around the call as records: ``transpose``
    (``where="device"|"host"``: on an accelerator the dispatch of Gᵀ's
    device sort, whose device time the first ``sync`` waits on; else the
    host counting sort of Gᵀ and its upload), ``plan`` (the four
    engines), one ``generation`` per worklist generation (its region
    count, and its pivots once chosen) and inside it ``trim``, ``trim2``,
    one ``reach`` per direction (``dir="fw"|"bw"``) and a ``sync`` around
    each blocking device→host read (the worklist and label-counter
    blobs, the children masks and the final labels).  The same spans
    fill three float ``stats`` entries on every call:
    ``stats["transpose_s"]``, ``stats["plan_s"]`` and ``stats["sync_s"]``,
    the seconds spent in those spans, and the int
    ``stats["transpose_on_device"]``, 1 where Gᵀ was built on the device.
    They describe this call alone and are not checkpointed.

    ``checkpoint_dir`` + ``checkpoint_every=k`` (DESIGN.md §14) save the
    generation-level driver state — labels, the pending region worklist,
    the label counter, and the stats scalars — every k completed
    generations plus once at the end, through the manifest-based
    ``fault.checkpoint`` writer (``checkpointer`` hands the IO to an
    ``AsyncCheckpointer``).  ``resume=True`` restores the latest
    checkpoint and continues; generations are atomic and deterministic
    from (labels, regions, next_label, generation parity — the trim
    direction alternates by generation), so a resumed run's labels are
    bit-identical to an uninterrupted run with the same arguments.
    """
    import jax.numpy as jnp

    n = graph.n
    stats = {"generations": 0, "trim_passes": 0, "trimmed_total": 0,
             "pivots": 0, "trim_dispatches": 0, "reach_dispatches": 0,
             "trim2_removed": 0, "trim2_sccs": 0, "trim2_dispatches": 0,
             "trim_edges_traversed": 0 if counters else None,
             "per_worker_edges": (np.zeros(workers, np.int64)
                                  if counters else None),
             "trim_rounds": 0 if instrument else None,
             "reach_rounds": 0 if instrument else None,
             "engine_traces": 0, "transpose_builds": 1,
             "transpose_on_device": 0,
             **dict.fromkeys(_CALL_SECONDS, 0.0)}
    if n == 0:
        return np.zeros(0, np.int64), stats
    if trim_backend == "sharded":
        raise ValueError(
            "the batched SCC driver needs a batchable trim backend "
            "('dense' or 'windowed'); shard at the region level instead")
    if max_batch < 1 or max_batch & (max_batch - 1):
        raise ValueError(f"max_batch must be a positive power of two, "
                         f"got {max_batch}")

    @contextlib.contextmanager
    def span(name, seconds=None, **attrs):
        """One ``scc.<name>`` span; ``seconds`` names the ``stats`` entry
        its duration adds to."""
        scope = obs.span(name, cat="scc", **attrs)
        try:
            with scope as sp:
                yield sp
        finally:
            if seconds is not None:
                stats[seconds] += scope.seconds

    def sync(x) -> np.ndarray:
        """A blocking device→host read, timed as ``scc.sync``."""
        with span("sync", "sync_s"):
            return np.asarray(x)

    def sweep(reach, direction, seeds, live_host, B):
        # all B pivots advance together: one vmapped dispatch per
        # direction (per max_batch chunk)
        with span("reach", dir=direction):
            outs = [reach.run_batch(s, a)
                    for s, a in zip(_chunks(seeds, max_batch),
                                    _chunks(live_host, max_batch))]
            mask = jnp.concatenate([o.mask for o in outs])[:B]
        if instrument:
            stats["reach_rounds"] += int(sum(sync(o.rounds).sum()
                                             for o in outs))
        return mask

    # four engines, one transpose build: the forward engines are
    # pre-seeded with Gᵀ, the backward pair sweeps Gᵀ with its transpose
    # cache pre-seeded with G itself
    on_device = graph.on_accelerator
    stats["transpose_on_device"] = int(on_device)
    with span("transpose", "transpose_s",
              where="device" if on_device else "host"):
        gt = graph.transpose()                # the one and only build
    with span("plan", "plan_s"):
        fw_trim = bw_trim = None
        if use_trim:
            fw_trim = plan(graph, method=trim_method, backend=trim_backend,
                           window=window, transpose=gt, workers=workers,
                           chunk=chunk, frontier=frontier,
                           instrument=instrument, max_rounds=max_rounds)
            bw_trim = plan(gt, method=trim_method, backend=trim_backend,
                           window=window, transpose=graph, workers=workers,
                           chunk=chunk, frontier=frontier,
                           instrument=instrument, max_rounds=max_rounds)
        fw_reach = plan_reach(graph, backend=reach_backend, window=window,
                              transpose=gt, frontier=frontier,
                              instrument=instrument, max_rounds=max_rounds)
        bw_reach = plan_reach(gt, backend=reach_backend, window=window,
                              transpose=graph, frontier=frontier,
                              instrument=instrument, max_rounds=max_rounds)
        if trim2:
            # G and Gᵀ CSR arrays for the size-≤2 detector
            # (device-resident, shared across every generation)
            t2_arrs = (graph.indptr, graph.indices, gt.indptr, gt.indices)
            t2_fn = _trim2_runner()

    labels = jnp.full((n,), -1, jnp.int32)   # device-resident until the end
    next_label = 0
    region0 = (np.ones(n, dtype=bool) if active is None
               else np.asarray(active, bool).copy())
    if region0.shape != (n,):
        raise ValueError(f"active mask must have shape ({n},), got "
                         f"{region0.shape}")
    regions = [region0] if region0.any() else []

    # -- generation-level checkpoint/resume (DESIGN.md §14) ----------------
    ckpt_on = checkpoint_dir is not None and checkpoint_every > 0
    last_saved = -1

    def _save_gen(gens):
        from ..fault.ckpt import save_tree
        tree = {"labels": labels,
                "regions": (np.stack(regions) if regions
                            else np.zeros((0, n), bool))}
        if counters:
            tree["per_worker_edges"] = stats["per_worker_edges"]
        drv_stats = {k: v for k, v in stats.items()
                     if k != "per_worker_edges" and k not in _CALL_STATS}
        save_tree(checkpoint_dir, gens, tree,
                  {"driver": {"kind": "scc", "next_label": next_label,
                              "stats": drv_stats}},
                  checkpointer=checkpointer)

    if resume and checkpoint_dir is not None:
        from ..fault import checkpoint as _ckpt
        last = _ckpt.latest_step(checkpoint_dir)
        if last is not None:
            tree, _, meta = _ckpt.load_flat(checkpoint_dir, last)
            drv = meta["driver"]
            labels = jnp.asarray(np.asarray(tree["labels"]), jnp.int32)
            regions = [r.copy() for r in np.asarray(tree["regions"], bool)
                       if r.any()]
            next_label = int(drv["next_label"])
            stats.update(drv["stats"])
            if counters:
                stats["per_worker_edges"] = np.asarray(
                    tree["per_worker_edges"], np.int64).copy()
            last_saved = last

    while regions:
        if ckpt_on and stats["generations"] > max(last_saved, 0) \
                and stats["generations"] % checkpoint_every == 0:
            last_saved = stats["generations"]
            _save_gen(last_saved)
        stats["generations"] += 1
        n_regions = len(regions)
        live_host = _pad_pow2(np.stack(regions))          # (B, n), disjoint
        regions = []
        with span("generation", gen=stats["generations"],
                  regions=n_regions) as gen_sp:
            if use_trim:
                # one batched dispatch (per max_batch chunk) trims every
                # pending region; directions alternate by generation so
                # source- and sink-like trivial SCCs both peel without a
                # second dispatch
                with span("trim"):
                    engine = (fw_trim if stats["generations"] % 2 == 1
                              or not trim_transpose else bw_trim)
                    parts = [engine.run_batch_stacked(jnp.asarray(c),
                                                      counters=counters)
                             for c in _chunks(live_host, max_batch)]
                    status = jnp.concatenate([p[0] for p in parts]) != 0
                    live = jnp.asarray(live_host)
                    dead = live & ~status
                    live = live & status
                    # regions are disjoint, so the union keeps one label
                    # per vertex
                    dead_union = jnp.any(dead, axis=0)
                stats["trim_passes"] += n_regions
                if counters:
                    # one (B, workers) transfer per generation (int32, the
                    # kernels' own accumulator width); cross-region and
                    # cross-worker sums in int64 on the host
                    pw = sync(jnp.concatenate(
                        [p[1] for p in parts])[:n_regions]).astype(np.int64)
                    stats["trim_edges_traversed"] += int(pw.sum())
                    stats["per_worker_edges"] += pw.sum(axis=0)
                if instrument:
                    stats["trim_rounds"] += int(sync(jnp.concatenate(
                        [p[2] for p in parts])[:n_regions]).sum())
                # one device->host transfer serves both the label counter
                # and the worklist bookkeeping below
                blob = sync(jnp.concatenate([dead_union[None], live]))
                dead_host, live_host = blob[0], blob[1:]
                k = int(dead_host.sum())
                if k:
                    rank = jnp.cumsum(dead_union.astype(jnp.int32)) - 1
                    labels = jnp.where(dead_union, next_label + rank, labels)
                    next_label += k
                    stats["trimmed_total"] += k

            if trim2 and live_host.any():
                # one batched dispatch (per max_batch chunk) detects
                # size-≤2 SCCs across every pending region; each
                # pair/singleton gets one label keyed by its
                # representative (min endpoint) and leaves the worklist
                # before any pivot is spent on it
                with span("trim2"):
                    parts2 = [t2_fn(*t2_arrs, jnp.asarray(c))
                              for c in _chunks(live_host, max_batch)]
                    stats["trim2_dispatches"] += len(parts2)
                    det = jnp.concatenate([p[0] for p in parts2])
                    # regions are disjoint, so the per-vertex
                    # partner/detected unions keep one value per vertex
                    partner = jnp.max(
                        jnp.concatenate([jnp.where(p[0], p[1], -1)
                                         for p in parts2]), axis=0)
                    det_union = jnp.any(det, axis=0)
                    idx = jnp.arange(n, dtype=jnp.int32)
                    is_rep = det_union & (idx <= partner)
                    rep = jnp.where(det_union, jnp.minimum(idx, partner),
                                    idx)
                    rank2 = jnp.cumsum(is_rep.astype(jnp.int32)) - 1
                # one device->host transfer serves the label counter, the
                # removal stat, and the worklist bookkeeping
                blob2 = sync(jnp.concatenate(
                    [is_rep[None], det_union[None],
                     jnp.asarray(live_host) & ~det]))
                n_sccs = int(blob2[0].sum())
                if n_sccs:
                    labels = jnp.where(det_union,
                                       next_label + rank2[rep], labels)
                    next_label += n_sccs
                    stats["trim2_sccs"] += n_sccs
                    stats["trim2_removed"] += int(blob2[1].sum())
                    live_host = blob2[2:]

            keep = np.nonzero(live_host.any(axis=1))[0]
            if keep.size == 0:
                continue
            live_host = _pad_pow2(live_host[keep])
            B = keep.size                   # real regions; the rest is pad

            # one pivot per surviving region: its first live vertex
            pivots = live_host[:B].argmax(axis=1)
            stats["pivots"] += B
            if stats["pivots"] > max_pivots:
                raise RuntimeError("scc_decompose: pivot budget exceeded")
            seeds = np.zeros_like(live_host)
            seeds[np.arange(B), pivots] = True

            fw = sweep(fw_reach, "fw", seeds, live_host, B)
            bw = sweep(bw_reach, "bw", seeds, live_host, B)
            live = jnp.asarray(live_host[:B])
            scc = fw & bw
            scc_ids = next_label + jnp.arange(B, dtype=jnp.int32)
            owner = jnp.max(jnp.where(scc, scc_ids[:, None], -1), axis=0)
            labels = jnp.where(owner >= 0, owner, labels)
            next_label += B

            children = sync(jnp.concatenate(
                [fw & ~scc, bw & ~scc, live & ~fw & ~bw]))
            regions = [m for m in children if m.any()]
            if gen_sp is not None:
                gen_sp.attrs["pivots"] = B

    if ckpt_on and stats["generations"] != last_saved:
        # final state: empty worklist, all labels assigned — a resumed
        # run restores it and returns without replaying any generation
        _save_gen(stats["generations"])

    labels = sync(labels).astype(np.int64)       # the one materialization
    assert ((labels >= 0) | ~region0).all()
    engines = [e for e in (fw_trim, bw_trim, fw_reach, bw_reach)
               if e is not None]
    stats["engine_traces"] = sum(e.traces for e in engines)
    stats["transpose_builds"] = 1 + sum(e.transpose_builds for e in engines)
    if use_trim:
        stats["trim_dispatches"] = fw_trim.dispatches + bw_trim.dispatches
    stats["reach_dispatches"] = fw_reach.dispatches + bw_reach.dispatches
    return labels, stats


def scc_decompose_incremental(graph: CSRGraph, prev_labels,
                              deletions=None, insertions=None,
                              reach_backend: str = "windowed",
                              window: int = 16, **scc_kwargs):
    """Re-decompose only the regions an edge-update batch dirtied.

    ``graph`` is the *updated* graph (e.g. ``StreamEngine.snapshot()``
    after an ``apply`` batch); ``prev_labels`` is a valid SCC labeling of
    the graph before the batch; ``deletions`` / ``insertions`` are the
    batch's ``(src, dst)`` pairs.  Returns ``(labels, stats)`` with
    labels valid for ``graph``: clean components keep their previous
    label, dirtied regions get fresh ids.

    Dirty-region construction (sound, not merely heuristic):

    * a deletion can only split the SCC that contained it, so only
      *intra-component* deletions dirty their component — cross edges
      are condensation-only and change no SCC;
    * an insertion ``(u, v)`` merges exactly the vertices on new cycles
      through it: ``FW(v) ∩ BW(u)`` on the updated graph — computed with
      two batched :class:`~repro.core.reach.ReachEngine` dispatches (one
      per direction for the whole batch), sharing one transpose build.
      Every old component intersecting a merge set is re-decomposed
      (merge sets are unions of old components); intra-component
      insertions change nothing and are skipped.

    The re-decomposition itself is one :func:`scc_decompose` call with
    ``active=dirty`` — the batched FW-BW driver confined to the dirty
    induced subgraph, trimming included.
    """
    from .graph import check_edge_ids

    n = graph.n
    prev = np.asarray(prev_labels, np.int64)
    if prev.shape != (n,):
        raise ValueError(f"prev_labels must have shape ({n},), got "
                         f"{prev.shape}")
    stats = {"dirty_vertices": 0, "dirty_components": 0,
             "reach_dispatches": 0, "recompute": None}

    def pairs(edges):
        if edges is None:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        return check_edge_ids(n, *edges)

    du, dv = pairs(deletions)
    iu, iv = pairs(insertions)
    dirty = np.zeros(n, bool)

    # deletions: only an intra-component deletion can split its SCC
    same = prev[du] == prev[dv]
    if same.any():
        dirty |= np.isin(prev, np.unique(prev[du[same]]))

    # insertions: merge set = FW(v) ∩ BW(u) on the updated graph; batch
    # every cross-component insertion into one dispatch per direction
    cross = prev[iu] != prev[iv]
    if cross.any():
        cu, cv = iu[cross], iv[cross]
        fw_engine = plan_reach(graph, backend=reach_backend, window=window)
        bw_engine = plan_reach(fw_engine.transpose, backend=reach_backend,
                               window=window, transpose=graph)
        b = cu.size
        fw_seeds = np.zeros((b, n), bool)
        bw_seeds = np.zeros((b, n), bool)
        fw_seeds[np.arange(b), cv] = True
        bw_seeds[np.arange(b), cu] = True
        fw = fw_engine.run_batch(_pad_pow2(fw_seeds)).mask
        bw = bw_engine.run_batch(_pad_pow2(bw_seeds)).mask
        merged = np.asarray(fw[:b] & bw[:b]).any(axis=0)
        stats["reach_dispatches"] = (fw_engine.dispatches
                                     + bw_engine.dispatches)
        if merged.any():
            dirty |= np.isin(prev, np.unique(prev[merged]))

    stats["dirty_vertices"] = int(dirty.sum())
    stats["dirty_components"] = int(np.unique(prev[dirty]).size)
    if not dirty.any():
        stats["recompute"] = None
        return prev.copy(), stats

    sub_labels, sub_stats = scc_decompose(
        graph, reach_backend=reach_backend, window=window,
        active=dirty, **scc_kwargs)
    labels = prev.copy()
    labels[dirty] = (prev.max() + 1) + sub_labels[dirty]
    stats["recompute"] = sub_stats
    return labels, stats


def tarjan_oracle(indptr: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """Iterative Tarjan SCC (numpy/python) — the test oracle."""
    n = len(indptr) - 1
    index = np.full(n, -1, dtype=np.int64)
    low = np.zeros(n, dtype=np.int64)
    on_stack = np.zeros(n, dtype=bool)
    comp = np.full(n, -1, dtype=np.int64)
    stack: list[int] = []
    counter = 0
    n_comp = 0
    for root in range(n):
        if index[root] != -1:
            continue
        # iterative DFS: (vertex, next-edge-offset)
        work = [(root, indptr[root])]
        index[root] = low[root] = counter
        counter += 1
        stack.append(root)
        on_stack[root] = True
        while work:
            v, ei = work[-1]
            if ei < indptr[v + 1]:
                work[-1] = (v, ei + 1)
                w = int(indices[ei])
                if index[w] == -1:
                    index[w] = low[w] = counter
                    counter += 1
                    stack.append(w)
                    on_stack[w] = True
                    work.append((w, indptr[w]))
                elif on_stack[w]:
                    low[v] = min(low[v], index[w])
            else:
                work.pop()
                if work:
                    pv = work[-1][0]
                    low[pv] = min(low[pv], low[v])
                if low[v] == index[v]:
                    while True:
                        w = stack.pop()
                        on_stack[w] = False
                        comp[w] = n_comp
                        if w == v:
                            break
                    n_comp += 1
    return comp


def same_partition(a: np.ndarray, b: np.ndarray) -> bool:
    """Do two labelings induce the same partition of vertices?"""
    a, b = np.asarray(a), np.asarray(b)
    pairs = set(zip(a.tolist(), b.tolist()))
    return len(pairs) == len(set(a.tolist())) == len(set(b.tolist()))
