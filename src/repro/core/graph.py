"""CSR graph container used by all trimming algorithms.

The paper stores explicit graphs in CSR (compressed sparse row) format
(paper §2.1): an O(n) index array (``indptr``) and an O(m) adjacency array
(``indices``).  We keep both arrays as device arrays so every algorithm is
jit-able with static (n, m).

Construction is a true O(n + m) counting sort on the host, mirroring the
paper's assumption that AC-4 pays the full O(n+m) space — but only linear
time — for reverse edges.  The transpose is built where G lives: on an
accelerator by one jitted stable sort of the arcs by target (program
``jit_csr_transpose``), so nothing crosses to the host; for a numpy- or
CPU-backed graph by the same host counting sort as construction, which
is faster there.  Both give the same arrays.  The transpose is built at
most once per :class:`repro.core.engine.TrimEngine` and cached for every
subsequent run (DESIGN.md §1).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs

LIVE = np.int32(1)
DEAD = np.int32(0)


def check_edge_ids(n: int, src: np.ndarray, dst: np.ndarray):
    """Validate an edge batch: matching lengths, endpoints in [0, n).
    Out-of-range ids would silently corrupt counting-sort indptrs
    (negative ids wrap, ids >= n scatter past the last row), so every
    construction/update path rejects them with the offending count.

    Returns canonical integer views — int32 whenever ``n`` fits (after
    validation every id is < n, so the downcast is lossless), int64 only
    for genuinely huge graphs.  Keeping edge lists narrow halves host-side
    edge memory; ``repro.analysis`` lints the same contract at the
    generator boundary."""
    src = np.asarray(src).reshape(-1)
    dst = np.asarray(dst).reshape(-1)
    if not np.issubdtype(src.dtype, np.integer):
        src = src.astype(np.int64)
    if not np.issubdtype(dst.dtype, np.integer):
        dst = dst.astype(np.int64)
    if src.shape != dst.shape:
        raise ValueError(f"src/dst length mismatch: {src.shape} vs "
                         f"{dst.shape}")
    bad = int(((src < 0) | (src >= n)).sum() + ((dst < 0) | (dst >= n)).sum())
    if bad:
        raise ValueError(f"{bad} edge endpoint(s) out of range [0, {n})")
    dt = np.int32 if n <= np.iinfo(np.int32).max else np.int64
    return src.astype(dt, copy=False), dst.astype(dt, copy=False)


def _stable_counting_order(src: np.ndarray, n: int) -> np.ndarray:
    """Permutation that stably groups edge ids by source vertex, O(n + m).

    scipy's coo→csr conversion is the textbook counting sort (one counting
    pass, one prefix sum, one scatter — all in C).  Using the edge id as
    the column key keeps duplicate (u, v) edges distinct and makes the
    within-row order (ascending column = ascending edge id) exactly the
    stable input order.  Data is stored 1-based so an explicit-zero pruning
    pass can never drop an entry.
    """
    m = src.shape[0]
    if m == 0:
        return np.zeros(0, dtype=np.int64)
    try:
        from scipy import sparse
    except ImportError:
        # numpy dispatches stable integer sorts to LSD radix sort — still
        # linear in m, just not the explicit counting sort.
        return np.argsort(src, kind="stable")
    csr = sparse.coo_matrix(
        (np.arange(1, m + 1, dtype=np.int64),
         (src, np.arange(m, dtype=np.int64))),
        shape=(n, m)).tocsr()
    return csr.data - 1


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Directed graph in CSR form. ``indptr``: (n+1,), ``indices``: (m,)."""

    indptr: jax.Array   # int32 (n+1,)
    indices: jax.Array  # int32 (m,)

    # -- pytree plumbing -------------------------------------------------
    def tree_flatten(self):
        return (self.indptr, self.indices), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- basic properties ------------------------------------------------
    @property
    def n(self) -> int:
        return int(self.indptr.shape[0]) - 1

    @property
    def m(self) -> int:
        return int(self.indices.shape[0])

    def out_degrees(self) -> jax.Array:
        return self.indptr[1:] - self.indptr[:-1]

    def edge_sources(self) -> jax.Array:
        """Source vertex of every edge ("row ids"), shape (m,)."""
        return row_ids(self.indptr, self.m)

    # -- constructors ------------------------------------------------------
    @staticmethod
    def from_edges(n: int, src: np.ndarray, dst: np.ndarray) -> "CSRGraph":
        src, dst = check_edge_ids(n, src, dst)
        m = src.shape[0]
        counts = np.bincount(src, minlength=n) if m else np.zeros(n, np.int64)
        indptr = np.zeros(n + 1, dtype=np.int32)
        np.cumsum(counts, out=indptr[1:])
        if m:
            dst = dst[_stable_counting_order(src, n)]
        return CSRGraph(jnp.asarray(indptr, jnp.int32),
                        jnp.asarray(dst, jnp.int32))

    @property
    def on_accelerator(self) -> bool:
        """Whether ``indices`` is a device array on a non-CPU backend:
        the case in which :meth:`transpose` builds Gᵀ on the device."""
        return isinstance(self.indices, jax.Array) and all(
            d.platform != "cpu" for d in self.indices.devices())

    def transpose(self) -> "CSRGraph":
        """Gᵀ, with each row's sources in ascending edge-id order.

        On an accelerator, :func:`csr_transpose` on G's device: dispatched
        asynchronously, nothing read to the host or uploaded.  Otherwise
        the host counting sort, O(n + m)."""
        if self.on_accelerator:
            return CSRGraph(*csr_transpose(self.indptr, self.indices))
        indptr = np.asarray(self.indptr)
        indices = np.asarray(self.indices)
        n = self.n
        src = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
        return CSRGraph.from_edges(n, indices.astype(np.int64), src)

    def to_numpy(self):
        return np.asarray(self.indptr), np.asarray(self.indices)


def row_ids(indptr: jax.Array, m: int) -> jax.Array:
    """Edge→source-vertex map from indptr, computed on device.

    Classic trick: scatter 1s at row starts, cumsum, subtract 1.
    """
    n = indptr.shape[0] - 1
    marks = jnp.zeros((m,), jnp.int32).at[indptr[1:-1]].add(1)
    # vertices with zero degree contribute stacked marks at the same index;
    # cumsum handles that correctly.
    return jnp.cumsum(marks)


@jax.jit
def csr_transpose(indptr: jax.Array, indices: jax.Array):
    """Gᵀ's ``(indptr, indices)`` from G's, on the device: a stable sort
    of the arcs by target keeps each row's sources in edge-id order, and
    Gᵀ's row starts are the prefix sums of the in-degrees.  The same
    int32 arrays as the host counting sort of :meth:`CSRGraph.transpose`.
    """
    n, m = indptr.shape[0] - 1, indices.shape[0]
    _, src = jax.lax.sort((indices, row_ids(indptr, m)), num_keys=1,
                          is_stable=True)
    in_deg = jnp.bincount(indices, length=n).astype(jnp.int32)
    indptr_t = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(in_deg)])
    return indptr_t, src


class TrimResult:
    """Output of a trimming run — device-resident, lazily materialized.

    ``status`` stays wherever the producer left it (a device array for
    ``TrimEngine.run``, numpy for the ``trim()`` shim).  Scalar counters
    transfer to the host only on first attribute access and are cached, so
    a pipeline that chains engine runs never blocks on device→host syncs
    it does not need (DESIGN.md §5).

    status:        (n,) int32, LIVE=1 / DEAD=0 at fixpoint
    rounds:        BSP rounds executed (≈ the paper's peeling steps / |Q| bound)
    edges_traversed: total adjacency entries examined (the paper's key
                   metric); None when the run disabled counters
    max_frontier:  max per-round frontier size (|Qp| analogue); None when
                   the run disabled counters
    per_worker_edges: (P,) traversed-edge counts attributed to static vertex
                   partitions of P workers (paper Fig.4/Table 8 analogue);
                   None unless counters were requested (``counters=True``,
                   the default)
    round_stats:   :class:`repro.obs.RoundStats` with the per-round stat
                   buffers (frontier size, traversed edges, ...); None
                   unless the plan had ``instrument=True`` (DESIGN.md §11)
    """

    __slots__ = ("_status", "_rounds", "_edges", "_max_frontier", "_pw",
                 "_round_stats")

    def __init__(self, status, rounds, edges_traversed=None,
                 max_frontier=None, per_worker_edges=None,
                 round_stats=None):
        self._status = status
        self._rounds = rounds
        self._edges = edges_traversed
        self._max_frontier = max_frontier
        self._pw = per_worker_edges
        self._round_stats = round_stats

    # -- lazy host materialization ----------------------------------------
    @property
    def status(self):
        return self._status

    @property
    def rounds(self) -> int:
        if self._rounds is not None and not isinstance(self._rounds, int):
            self._rounds = int(self._rounds)
        return self._rounds

    @property
    def edges_traversed(self):
        if self._edges is None and self._pw is not None:
            self._edges = int(np.asarray(self.per_worker_edges).sum())
        elif self._edges is not None and not isinstance(self._edges, int):
            self._edges = int(self._edges)
        return self._edges

    @property
    def max_frontier(self):
        if self._max_frontier is not None \
                and not isinstance(self._max_frontier, int):
            self._max_frontier = int(self._max_frontier)
        return self._max_frontier

    @property
    def per_worker_edges(self):
        if self._pw is not None and not (
                isinstance(self._pw, np.ndarray)
                and self._pw.dtype == np.int64):
            self._pw = np.asarray(self._pw).astype(np.int64)
        return self._pw

    @property
    def per_worker_edges_device(self):
        """Per-worker counters wherever the producer left them — no host
        sync, no caching.  ``None`` when the run disabled counters.  The
        batched SCC driver reduces these on device and transfers one
        scalar per generation instead of one array per region."""
        return self._pw

    @property
    def round_stats(self):
        """Per-round fixpoint stats (``None`` unless the producing plan
        had ``instrument=True``)."""
        return self._round_stats

    def materialize(self) -> "TrimResult":
        """Force every field to the host (numpy status, python ints)."""
        self._status = np.asarray(self._status).astype(np.int32)
        _ = (self.rounds, self.edges_traversed, self.max_frontier,
             self.per_worker_edges)
        return self

    # -- derived ----------------------------------------------------------
    @property
    def n_trimmed(self) -> int:
        return int((np.asarray(self.status) == 0).sum())

    @property
    def trimmed_fraction(self) -> float:
        n = self.status.shape[0]
        return self.n_trimmed / n if n else 0.0

    def __repr__(self):  # no device sync: report only static facts
        kind = "numpy" if isinstance(self._status, np.ndarray) else "device"
        return (f"TrimResult(n={self._status.shape[0]}, {kind}, "
                f"counters={'on' if self._pw is not None else 'off'})")


def worker_of(n: int, workers: int, chunk: int = 4096) -> np.ndarray:
    """Static chunked round-robin partition of vertices onto P workers.

    Mirrors the paper's ``schedule(dynamic, 4096)`` chunking closely enough
    for attribution of per-worker work: chunk c goes to worker c mod P.
    """
    v = np.arange(n, dtype=np.int64)
    return ((v // chunk) % workers).astype(np.int32)


def _pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length() if x > 0 else 1


class DeltaCSR:
    """Mutable edge-update overlay over an immutable base CSR (DESIGN.md §9).

    The device-resident overlay is a tombstone mask over base edges plus a
    fixed-capacity append buffer for inserted edges.  All overlay arrays
    have static shapes — the buffer is pow2-padded with sentinel entries —
    so the :class:`~repro.core.stream.StreamEngine` kernels never retrace
    across update batches.  Host mirrors of the same state provide the
    edge lookup for deletions (multiset semantics: duplicate arcs are
    distinct instances) and the compaction path; the device copies are
    updated inside the engine's jitted apply step with the same O(B)
    scatters, so the two views never diverge (property-tested).

    ``compact()`` folds the overlay into a fresh base CSR through the
    existing O(n+m) counting-sort constructor once
    ``overlay_fraction`` crosses ``load_factor`` (the engine triggers it).
    Building the host mirrors and the sorted key index, at construction
    and at each compaction, is the ``stream.index`` span.
    """

    def __init__(self, base: CSRGraph, *, capacity: int = 256,
                 load_factor: float = 0.5):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if not 0.0 < load_factor:
            raise ValueError(f"load_factor must be > 0, got {load_factor}")
        self.capacity = _pow2(capacity)
        self.load_factor = float(load_factor)
        self._rebase(base)

    # -- (re)initialization ------------------------------------------------
    def _rebase(self, base: CSRGraph):
        self.base = base
        n, m = base.n, base.m
        with obs.span("index", cat="stream"):
            indptr, indices = base.to_numpy()
            self._src_np = np.repeat(np.arange(n, dtype=np.int64),
                                     np.diff(indptr))
            self._dst_np = indices.astype(np.int64)
            # O(m log m) one-time index for (u, v) -> edge-id lookup;
            # duplicate arcs occupy a contiguous key range and are
            # resolved instance-wise
            keys = self._src_np * max(n, 1) + self._dst_np
            self._key_order = np.argsort(keys, kind="stable")
            self._keys_sorted = keys[self._key_order]
        self._tomb_np = np.zeros(m, bool)
        cap = self.capacity
        self._ins_src_np = np.full(cap, n, np.int64)   # n = empty sentinel
        self._ins_dst_np = np.full(cap, n, np.int64)
        self._ins_alive_np = np.zeros(cap, bool)
        self.n_ins = 0          # append high-water mark (slots consumed)
        self.n_tomb = 0         # tombstoned base edges
        # device overlay (kept in sync by the engine's jitted apply step)
        self.tomb = jnp.zeros((m,), bool)
        self.ins_src = jnp.full((cap,), n, jnp.int32)
        self.ins_dst = jnp.full((cap,), n, jnp.int32)
        self.ins_alive = jnp.zeros((cap,), bool)

    # -- basic properties --------------------------------------------------
    @property
    def n(self) -> int:
        return self.base.n

    @property
    def m_base(self) -> int:
        return self.base.m

    @property
    def m_live(self) -> int:
        """Edges in the materialized graph right now."""
        return (self.m_base - self.n_tomb
                + int(self._ins_alive_np[:self.n_ins].sum()))

    @property
    def overlay_fraction(self) -> float:
        """Overlay load: (tombstones + consumed insert slots) / base m."""
        return (self.n_tomb + self.n_ins) / max(self.m_base, 1)

    @property
    def needs_compact(self) -> bool:
        return self.overlay_fraction > self.load_factor

    # -- memory accounting (nbytes protocol, DESIGN.md §13) ----------------
    def nbytes_breakdown(self) -> dict:
        """Overlay bytes by component (device overlay, insert buffers, and
        the host mirrors that drive resolution), excluding the base graph
        — the owning engine accounts that as its ``graph`` component."""
        from ..obs.memory import array_nbytes
        return {
            "tombstones": array_nbytes(self.tomb) + self._tomb_np.nbytes,
            "insert_buffers": (
                array_nbytes((self.ins_src, self.ins_dst, self.ins_alive))
                + self._ins_src_np.nbytes + self._ins_dst_np.nbytes
                + self._ins_alive_np.nbytes),
            "host_index": (self._src_np.nbytes + self._dst_np.nbytes
                           + self._key_order.nbytes
                           + self._keys_sorted.nbytes),
        }

    def nbytes(self) -> int:
        """Total overlay bytes (base graph excluded)."""
        return sum(self.nbytes_breakdown().values())

    # -- checkpoint/resume (DESIGN.md §14) ---------------------------------
    def state_dict(self) -> dict:
        """The overlay's checkpointable arrays: base CSR, tombstone mask,
        and insert buffers (device copies — the host mirrors are kept in
        sync by construction, property-tested, and are rebuilt from these
        on :meth:`load_state`)."""
        return {"base_indptr": self.base.indptr,
                "base_indices": self.base.indices,
                "tomb": self.tomb, "ins_src": self.ins_src,
                "ins_dst": self.ins_dst, "ins_alive": self.ins_alive}

    def state_meta(self) -> dict:
        """JSON side of :meth:`state_dict` (sizing + slot accounting)."""
        return {"capacity": self.capacity, "load_factor": self.load_factor,
                "n_ins": self.n_ins, "n_tomb": self.n_tomb}

    def load_state(self, tree: dict, meta: dict) -> None:
        """Overwrite this overlay with a checkpoint's exact state: the
        base is rebuilt from the saved CSR arrays (no re-sort — edge
        order, and therefore every derived permutation, is preserved),
        the host mirrors are reconstructed from the saved device arrays,
        and the slot accounting comes from ``meta``."""
        base = CSRGraph(jnp.asarray(np.asarray(tree["base_indptr"]),
                                    jnp.int32),
                        jnp.asarray(np.asarray(tree["base_indices"]),
                                    jnp.int32))
        self.capacity = int(meta["capacity"])
        self.load_factor = float(meta["load_factor"])
        self._rebase(base)              # empty overlay at saved capacity
        tomb = np.asarray(tree["tomb"], bool)
        ins_src = np.asarray(tree["ins_src"])
        ins_dst = np.asarray(tree["ins_dst"])
        ins_alive = np.asarray(tree["ins_alive"], bool)
        if tomb.shape != (base.m,) or ins_src.shape != (self.capacity,):
            raise ValueError("checkpoint overlay shapes do not match the "
                             "saved base/capacity")
        self._tomb_np = tomb.copy()
        self._ins_src_np = ins_src.astype(np.int64)
        self._ins_dst_np = ins_dst.astype(np.int64)
        self._ins_alive_np = ins_alive.copy()
        self.n_ins = int(meta["n_ins"])
        self.n_tomb = int(meta["n_tomb"])
        self.tomb = jnp.asarray(tomb)
        self.ins_src = jnp.asarray(ins_src, jnp.int32)
        self.ins_dst = jnp.asarray(ins_dst, jnp.int32)
        self.ins_alive = jnp.asarray(ins_alive)

    # -- host-side bookkeeping (the engine drives these) -------------------
    def resolve_deletions(self, src, dst):
        """Resolve a deletion batch to concrete edge instances and mark the
        host mirrors.  Returns ``(eids, slots)``: per deletion either a base
        edge id (``slots`` holds the sentinel ``capacity``) or an insert
        slot (``eids`` holds the sentinel ``m_base``).  Duplicate arcs are
        a multiset: each deletion claims a distinct not-yet-deleted
        instance.  Atomic: assignments are validated before anything is
        marked, so a phantom deletion raises ``ValueError`` with the batch
        unapplied."""
        src, dst = check_edge_ids(self.n, src, dst)
        b = src.shape[0]
        eids = np.full(b, self.m_base, np.int64)
        slots = np.full(b, self.capacity, np.int64)
        # key arithmetic needs the full int64 range (n * n overflows the
        # int32 the validated batch arrives in)
        keys = src.astype(np.int64) * max(self.n, 1) + dst
        lo = np.searchsorted(self._keys_sorted, keys, "left")
        hi = np.searchsorted(self._keys_sorted, keys, "right")
        # group the batch by key; within a group, claim untombed base
        # instances first, then live insert slots — all without mutating,
        # so failure needs no rollback
        order = np.argsort(keys, kind="stable")
        ks = keys[order]
        starts = (np.nonzero(np.r_[True, ks[1:] != ks[:-1]])[0] if b
                  else np.zeros(0, np.int64))
        ins_live = self._ins_alive_np[:self.n_ins]
        ins_keys = (self._ins_src_np[:self.n_ins] * max(self.n, 1)
                    + self._ins_dst_np[:self.n_ins])
        # vectorized fast path: singleton groups whose key matches exactly
        # one untombed base instance (all of them, on a simple graph with
        # an all-distinct batch) assign without the per-group loop
        pending = np.ones(len(starts), bool)
        if self.m_base and b:
            sizes = np.diff(np.r_[starts, b])
            g0 = order[starts]                 # one member per group
            rng1 = (hi[g0] - lo[g0]) == 1
            cand0 = self._key_order[np.where(rng1, lo[g0], 0)]
            easy = (sizes == 1) & rng1 & ~self._tomb_np[cand0]
            eids[g0[easy]] = cand0[easy]
            pending &= ~easy
        for gi in np.nonzero(pending)[0]:
            s0 = starts[gi]
            s1 = starts[gi + 1] if gi + 1 < len(starts) else b
            members = order[s0:s1]
            i0 = members[0]
            cand = self._key_order[lo[i0]:hi[i0]]
            avail = cand[~self._tomb_np[cand]]
            t = min(members.size, avail.size)
            eids[members[:t]] = avail[:t]
            extra = members[t:]
            if extra.size:
                cand2 = np.nonzero(ins_live & (ins_keys == keys[i0]))[0]
                if cand2.size < extra.size:
                    raise ValueError(
                        f"cannot delete edge ({src[i0]}, {dst[i0]}): "
                        "not present in the graph")
                slots[extra] = cand2[:extra.size]
        # commit
        from_base = eids < self.m_base
        self._tomb_np[eids[from_base]] = True
        self.n_tomb += int(from_base.sum())
        self._ins_alive_np[slots[slots < self.capacity]] = False
        return eids, slots

    def stage_inserts(self, src, dst):
        """Claim contiguous insert-buffer slots for a batch and mark the
        host mirrors.  The caller (engine) guarantees capacity."""
        src, dst = check_edge_ids(self.n, src, dst)
        k = src.shape[0]
        if self.n_ins + k > self.capacity:
            raise RuntimeError(
                f"insert buffer overflow: {self.n_ins} + {k} > "
                f"{self.capacity} (the engine compacts/grows first)")
        slots = np.arange(self.n_ins, self.n_ins + k, dtype=np.int64)
        self._ins_src_np[slots] = src
        self._ins_dst_np[slots] = dst
        self._ins_alive_np[slots] = True
        self.n_ins += k
        return slots

    def grow(self, min_capacity: int):
        """Double the insert buffer to a pow2 >= min_capacity (new static
        shape: the engine's apply step retraces once per capacity)."""
        new_cap = _pow2(max(2 * self.capacity, min_capacity))
        pad = new_cap - self.capacity
        n = self.n
        self._ins_src_np = np.concatenate(
            [self._ins_src_np, np.full(pad, n, np.int64)])
        self._ins_dst_np = np.concatenate(
            [self._ins_dst_np, np.full(pad, n, np.int64)])
        self._ins_alive_np = np.concatenate(
            [self._ins_alive_np, np.zeros(pad, bool)])
        self.ins_src = jnp.concatenate(
            [self.ins_src, jnp.full((pad,), n, jnp.int32)])
        self.ins_dst = jnp.concatenate(
            [self.ins_dst, jnp.full((pad,), n, jnp.int32)])
        self.ins_alive = jnp.concatenate(
            [self.ins_alive, jnp.zeros((pad,), bool)])
        self.capacity = new_cap

    # -- materialization ---------------------------------------------------
    def _live_edges(self):
        live_base = ~self._tomb_np
        ins_live = self._ins_alive_np[:self.n_ins]
        src = np.concatenate([self._src_np[live_base],
                              self._ins_src_np[:self.n_ins][ins_live]])
        dst = np.concatenate([self._dst_np[live_base],
                              self._ins_dst_np[:self.n_ins][ins_live]])
        return src, dst

    def materialize(self) -> CSRGraph:
        """Fold the overlay into a standalone CSR (the overlay is kept)."""
        src, dst = self._live_edges()
        return CSRGraph.from_edges(self.n, src, dst)

    def compact(self) -> CSRGraph:
        """Fold the overlay into a fresh base CSR (O(n+m) counting sort)
        and reset the overlay to empty.  Returns the new base."""
        src, dst = self._live_edges()
        base = CSRGraph.from_edges(self.n, src, dst)
        self._rebase(base)
        return base

    def __repr__(self):
        return (f"DeltaCSR(n={self.n}, m_base={self.m_base}, "
                f"tomb={self.n_tomb}, ins={self.n_ins}/{self.capacity}, "
                f"load={self.overlay_fraction:.2f})")
