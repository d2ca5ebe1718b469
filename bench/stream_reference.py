"""The arc-deletion stream of the ``stream`` entry, its plain host
reference and its control.

The stream deletes G's arcs in the order of one permutation of their ids
(CSR positions) drawn from the run's seed: batch k (k = 1, 2, ...) is
arcs (k-1)·B … k·B-1 of the permutation, so each batch is uniform over
the arcs not yet deleted, parallel copies of an arc drawn apart; at the
end of the permutation the batches run short, then empty.

The reference imports nothing of the program under test.  Deletions are
monotone, so the greatest trimming fixpoint of G minus the first k
batches is the fixpoint of k-1 peeled further: each batch takes its arcs
off their sources' counts of live successors, and peeling rounds then
kill every live vertex whose count is zero and take its in-arcs (through
the original Gᵀ, with the deleted ones added back) off their sources'
counts, as ``bench/reference.host_trim`` does from scratch.  ``verify``
checks a state against ``host_trim`` of the materialised graph.

The control is the fixpoint after the first batch whose cascade takes at
least two peeling rounds, with the last round left out: the step an apply
that updates the counters of the batch's sources without propagating
their deaths would skip.
"""
from __future__ import annotations

import numpy as np

from bench import reference


def arc_order(seed: int, m: int) -> np.ndarray:
    """G's arc ids in the order the stream deletes them: one permutation
    drawn from the run's seed, int32."""
    return np.random.default_rng(seed).permutation(m).astype(np.int32)


def batch_arcs(order, indptr, indices, k: int, batch: int):
    """(src, dst) of batch k of the stream, numpy int32."""
    ids = order[(k - 1) * batch:k * batch]
    src = np.searchsorted(indptr, ids, side="right") - 1
    return src.astype(np.int32), indices[ids]


class Fixpoints:
    """The fixpoints of one run's stream, advanced batch by batch.

    ``live(k)`` is the greatest trimming fixpoint of G minus the first k
    batches; asked for a k below the one it holds, it starts again from
    G's own fixpoint.  ``rounds`` and ``last_round`` describe the cascade
    of the latest batch: its peeling rounds and the vertices the last of
    them killed."""

    def __init__(self, graph, transpose, seed: int, batch: int):
        self.indptr, self.indices = graph
        self.t_indptr, self.t_indices = transpose
        self.batch = batch
        self.order = arc_order(seed, len(self.indices))
        live, _ = reference.host_trim(*graph, *transpose)
        counts = np.concatenate(
            [[0], np.cumsum(live[self.indices], dtype=np.int64)])
        # live successors of each vertex, over the arcs not deleted
        live_out = counts[self.indptr[1:]] - counts[self.indptr[:-1]]
        self._start = (live, live_out)
        self._reset()

    def _reset(self):
        self.k = 0
        self._live, self._live_out = (a.copy() for a in self._start)
        self._del_src = np.zeros(0, np.int32)
        self._del_dst = np.zeros(0, np.int32)
        self.rounds, self.last_round = 0, np.zeros(0, np.int64)

    def live(self, k: int) -> np.ndarray:
        if k < self.k:
            self._reset()
        while self.k < k:
            self._advance()
        return self._live

    def _advance(self):
        live, live_out = self._live, self._live_out
        src, dst = batch_arcs(self.order, self.indptr, self.indices,
                              self.k + 1, self.batch)
        self.k += 1
        self._del_src = np.concatenate([self._del_src, src])
        self._del_dst = np.concatenate([self._del_dst, dst])
        np.subtract.at(live_out, src[live[dst]], 1)
        front = np.unique(src[live[src] & (live_out[src] == 0)])
        self.rounds, self.last_round = 0, np.zeros(0, np.int64)
        while front.size:
            self.rounds += 1
            self.last_round = front
            live[front] = False
            lo = self.t_indptr[front]
            lens = self.t_indptr[front + 1] - lo
            arcs = (np.repeat(lo - np.cumsum(lens) + lens, lens)
                    + np.arange(lens.sum()))
            preds = self.t_indices[arcs]
            # deleted in-arcs left the counts when they were deleted, their
            # targets being live then: add them back
            np.subtract.at(live_out, preds, 1)
            np.add.at(live_out, self._del_src[np.isin(self._del_dst,
                                                      front)], 1)
            touched = np.unique(preds)
            front = touched[live[touched] & (live_out[touched] == 0)]

    def verify(self):
        """Raise unless the state held equals ``host_trim`` of G minus the
        deleted arcs, from scratch."""
        from scipy.sparse import csr_matrix
        n, m = len(self.indptr) - 1, len(self.indices)
        keep = np.ones(m, bool)
        keep[self.order[:self.k * self.batch]] = False
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.diff(self.indptr)
                  - np.bincount(self._del_src, minlength=n),
                  out=indptr[1:])
        indices = self.indices[keep]
        gt = csr_matrix((np.ones(len(indices), np.int8), indices, indptr),
                        shape=(n, n)).tocsc()
        scratch, _ = reference.host_trim(indptr, indices, gt.indptr,
                                         gt.indices)
        if not np.array_equal(scratch, self._live):
            raise AssertionError(
                f"incremental reference disagrees with host_trim after "
                f"{self.k} batches in {int((scratch != self._live).sum())} "
                f"vertices")


def control(graph, transpose, seed: int, batch: int):
    """(k, live): the fixpoint after the first batch k whose cascade takes
    two or more peeling rounds, with that cascade's last round undone."""
    fix = Fixpoints(graph, transpose, seed, batch)
    batches = -(-len(fix.indices) // batch)
    while fix.k < batches:
        live = fix.live(fix.k + 1)
        if fix.rounds >= 2:
            live = live.copy()
            live[fix.last_round] = True
            return fix.k, live
    raise ValueError("no batch of the stream cascades over two rounds")
