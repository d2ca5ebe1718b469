"""Numpy host references for the BSP trimming methods (DESIGN.md §2).

Each ``host_*_rounds(indptr, indices)`` replays one method round by round
and returns a list with one ``(dead, examined)`` pair a round: the mask
of vertices that round kills, and the arcs each vertex examined in it.
Per-round telemetry sums them (``round_totals``); per-worker counters
add them up by owner.
"""
from functools import partial

import numpy as np


def _host_scan(live, indptr, indices, start, scanning):
    """Per scanning vertex, the first position at or after ``start`` whose
    successor is in the round-start snapshot ``live``: (found, position
    (the degree when exhausted), arcs examined including the hit)."""
    deg = np.diff(indptr)
    found = np.zeros(len(deg), bool)
    pos = deg.copy()
    examined = np.zeros(len(deg), np.int64)
    for v in np.nonzero(scanning)[0]:
        row = indices[indptr[v]:indptr[v + 1]]
        p = first = min(start[v], deg[v])
        while p < deg[v] and not live[row[p]]:
            p += 1
        found[v] = p < deg[v]
        pos[v] = p
        examined[v] = p - first + found[v]
    return found, pos, examined


def host_ac3_rounds(indptr, indices):
    """AC-3: every live vertex re-scans from its pointer (its last
    support, not past it); a vertex with no successor live at the round's
    start dies.  The round that kills nothing is the last, and is
    counted."""
    n = len(indptr) - 1
    live = np.ones(n, bool)
    ptr = np.zeros(n, np.int64)
    rounds = []
    while True:
        found, pos, examined = _host_scan(live, indptr, indices, ptr, live)
        dead = live & ~found
        rounds.append((dead, examined))
        ptr = np.where(live, pos, ptr)
        live &= found
        if not dead.any():
            return rounds


def host_ac4_rounds(indptr, indices, count_init_scan=True):
    """AC-4: synchronous rounds, frontier = newly-zero counters; a
    frontier vertex examines its in-list, and the counter-init scan (each
    vertex's out-arcs) is charged to round 0 when the method counts it."""
    n = len(indptr) - 1
    outdeg = np.diff(indptr).astype(np.int64)
    indeg = np.zeros(n, np.int64)
    np.add.at(indeg, indices, 1)
    order = np.argsort(indices, kind="stable")
    t_indptr = np.zeros(n + 1, np.int64)
    np.cumsum(indeg, out=t_indptr[1:])
    t_indices = np.repeat(np.arange(n), outdeg)[order]

    c = outdeg.copy()
    dead = np.zeros(n, bool)
    frontier = c == 0
    rounds = []
    while frontier.any():
        examined = np.where(frontier, indeg, 0)
        if not rounds and count_init_scan:
            examined = examined + outdeg
        rounds.append((frontier, examined))
        dead |= frontier
        dec = np.zeros(n, np.int64)
        for v in np.nonzero(frontier)[0]:
            np.add.at(dec, t_indices[t_indptr[v]:t_indptr[v + 1]], 1)
        c = c - dec
        frontier = (c == 0) & ~dead
    if not rounds and count_init_scan:
        rounds.append((frontier, outdeg))
    return rounds


def host_ac6_rounds(indptr, indices):
    """AC-6: only vertices whose support died scan, strictly past it
    (every vertex in round 0, from position 0); the rounds run until no
    live vertex has a dead support."""
    n = len(indptr) - 1
    deg = np.diff(indptr)
    live = np.ones(n, bool)
    ptr = np.full(n, -1, np.int64)
    affected = live.copy()
    rounds = []
    while affected.any():
        found, pos, examined = _host_scan(live, indptr, indices, ptr + 1,
                                          affected)
        dead = affected & ~found
        rounds.append((dead, examined))
        ptr = np.where(affected, pos, ptr)
        live &= ~dead
        held = np.nonzero(live & (deg > 0))[0]
        affected = np.zeros(n, bool)
        affected[held] = ~live[indices[indptr[held] + ptr[held]]]
    return rounds


HOST_ROUNDS = {
    "ac3": host_ac3_rounds,
    "ac4": partial(host_ac4_rounds, count_init_scan=True),
    "ac4*": partial(host_ac4_rounds, count_init_scan=False),
    "ac6": host_ac6_rounds,
}


def round_totals(rounds):
    """(r_frontier, r_edges): deaths and arcs examined, one entry a
    round."""
    return (np.asarray([int(d.sum()) for d, _ in rounds]),
            np.asarray([int(e.sum()) for _, e in rounds]))
