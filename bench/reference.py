"""Plain host references, their controls, and the comparisons that decide
``correct``.

The references are numpy and scipy, and import nothing of the program
under test: the trimming fixpoint is peeled round by round from the CSR
and its transpose, and the strongly connected components come from
scipy's ``connected_components``.  Each comparison is exact, so its limit,
kept by the entry under ``bench/entries/`` that uses it, is 0 (PERF.md
gives the readings behind each limit).

Each control breaks the one guarantee its configuration states by
stopping the iteration one step before its fixpoint, the step a faster
program would be tempted to skip: the trimming fixpoint one peeling round
early, and the strong components as a forward-backward search whose two
searches each stop one level short would leave them.
"""
from __future__ import annotations

import numpy as np


def host_transpose(indptr, indices):
    """Gᵀ by a stable counting sort on the host."""
    n = len(indptr) - 1
    src = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
    order = np.argsort(indices, kind="stable")
    t_indptr = np.concatenate(
        [[0], np.cumsum(np.bincount(indices, minlength=n))])
    return t_indptr, src[order]


def host_trim(indptr, indices, t_indptr, t_indices, rounds=None):
    """(live mask, rounds) of the trimming fixpoint: a vertex dies once
    none of its out-arcs leads to a live vertex.  Each round kills every
    live vertex whose live out-degree is zero and takes its in-arcs off
    their sources' counts.  ``rounds`` stops the peeling after that many
    rounds (the control)."""
    n = len(indptr) - 1
    live_out = np.diff(indptr).astype(np.int64)
    live = np.ones(n, bool)
    front = live_out == 0
    done = 0
    while front.any() and (rounds is None or done < rounds):
        live &= ~front
        ids = np.flatnonzero(front)
        lo, lens = t_indptr[ids], t_indptr[ids + 1] - t_indptr[ids]
        arcs = (np.repeat(lo - np.cumsum(lens) + lens, lens)
                + np.arange(lens.sum()))
        live_out -= np.bincount(t_indices[arcs], minlength=n)
        front = live & (live_out == 0)
        done += 1
    return live, done


def host_scc(indptr, indices):
    """Strongly connected component labels (scipy)."""
    from scipy.sparse.csgraph import connected_components
    return connected_components(_scipy(indptr, indices), directed=True,
                                connection="strong")[1]


def _scipy(indptr, indices):
    from scipy.sparse import csr_matrix
    n = len(indptr) - 1
    return csr_matrix((np.ones(len(indices), np.int8), indices, indptr),
                      shape=(n, n))


def trim_control(indptr, indices, t_indptr, t_indices):
    """The fixpoint stopped one peeling round early."""
    _, rounds = host_trim(indptr, indices, t_indptr, t_indices)
    return host_trim(indptr, indices, t_indptr, t_indices,
                     rounds=max(rounds - 1, 0))[0]


def scc_control(indptr, indices, t_indptr, t_indices):
    """Strong components with the largest one cut as a forward-backward
    search from its first vertex leaves it when both searches stop one
    level short: the vertices of the last forward or the last backward
    level become singletons."""
    from scipy.sparse.csgraph import shortest_path
    labels = host_scc(indptr, indices).astype(np.int64)
    giant = np.bincount(labels).argmax()
    members = labels == giant
    pivot = int(np.flatnonzero(members)[0])
    cut = np.zeros(len(labels), bool)
    for ptr, idx in ((indptr, indices), (t_indptr, t_indices)):
        dist = shortest_path(_scipy(ptr, idx), indices=pivot,
                             unweighted=True)
        depth = dist[members].max()
        cut |= members & (dist == depth)
    labels[cut] = labels.max() + 1 + np.arange(int(cut.sum()))
    return labels


def status_mismatch(status, live) -> int:
    """Vertices whose status differs from the reference's live mask."""
    return int(((np.asarray(status) != 0) != live).sum())


def partition_mismatch(labels, ref) -> int:
    """Vertices whose component is not exactly the reference's: a vertex
    counts when its label class meets two reference classes or its
    reference class meets two label classes.  0 iff the partitions are
    equal."""
    a = np.asarray(labels, np.int64)
    b = np.asarray(ref, np.int64)
    a = a - a.min()
    b = b - b.min()
    width = int(b.max()) + 1
    pairs = np.unique(a * width + b)
    per_a = np.bincount(pairs // width, minlength=int(a.max()) + 1)
    per_b = np.bincount(pairs % width, minlength=width)
    return int(((per_a[a] > 1) | (per_b[b] > 1)).sum())


def unlabelled(labels) -> int:
    """Vertices without a component id in [0, n)."""
    labels = np.asarray(labels)
    return int(((labels < 0) | (labels >= len(labels))).sum())
