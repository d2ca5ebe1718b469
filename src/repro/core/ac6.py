"""AC-6-based graph trimming (paper Algorithm 7/8) — the paper's novel
contribution — in BSP formulation.

Each vertex v keeps ONE support: the adjacency position ``ptr[v]`` of a live
successor.  When the support dies, v scans strictly *after* its pointer for
a replacement (``DoPost``, paper Alg. 7 lines 9-12); failure kills v and the
death propagates.  Pointers never retreat, so every adjacency entry is
examined at most once — total edge traversals ≤ m (paper Theorem 12), the
property that makes AC-6 the right algorithm for implicit/on-the-fly graphs.

TPU adaptation of the supporting sets (paper Definition 3): instead of
mutating per-vertex sets v.S under locks, we store only the forward choice
``support(v) = indices[indptr[v] + ptr[v]]`` and *lazily invert* it each
round with one dense gather::

    affected = live(v)  &  ¬status[support(v)]

This is race-free by construction (BSP snapshot), needs O(n) space like the
paper's S-sets, and preserves the ≤ m traversal bound.  The trade is an
O(n) vectorized mask per round instead of O(|S(w)|) pointer chasing — the
depth/work trade documented in DESIGN.md §2.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import obs
from .common import FrontierPlan, per_worker_add, probe_first_live_ids, \
    resolve_probe, worker_counts
from .registry import KernelSpec, register_kernel

CHUNK = 64  # chunked-frontier granularity (DESIGN.md §12)


@partial(jax.jit, static_argnames=("workers", "probe", "window",
                                   "use_kernel", "counters", "frontier",
                                   "instrument", "max_rounds"))
def ac6_kernel(indptr, indices, worker_ids, workers: int, active=None, *,
               probe: str = "dense", window: int = 16,
               use_kernel: bool | None = None, counters: bool = True,
               frontier: FrontierPlan = FrontierPlan(),
               instrument: bool = False, max_rounds: int = 0):
    """``active``: optional (n,) bool — trim the induced subgraph (vertices
    outside are treated as already DEAD).  Used by the SCC application.

    ``probe``/``window``/``use_kernel`` select the scan implementation
    (see ``common.resolve_probe``); ``counters=False`` skips per-worker
    counter accumulation entirely (the serving fast path) and returns
    ``None`` in the counter slots.  ``frontier`` (DESIGN.md §12) selects
    the sparse-frontier substrate: state is padded to 64-aligned chunks,
    and rounds whose affected set spans few enough chunks compact the
    *chunk* set — an any-reduce plus a rank search over the (n/64,) chunk
    mask, no per-vertex scatter — probe only those rows
    (``common.probe_first_live_ids``), and scatter whole chunk rows back.
    Bit-identical to the dense round including every counter.
    ``instrument=True`` (DESIGN.md §11) threads ``(max_rounds,)`` per-round
    buffers — deaths and probed edges per round — through the carry,
    returned as a fifth output.
    """
    n = indptr.shape[0] - 1
    m = indices.shape[0]
    deg = indptr[1:] - indptr[:-1]
    row_base = indptr[:-1]
    probe_fn = resolve_probe(probe, window, use_kernel)
    if active is None:
        active = jnp.ones((n,), bool)

    sparse = frontier.mode != "dense"
    if sparse:
        K = -(-n // CHUNK)
        Cc = max(1, min(frontier.cap // CHUNK, K))
        pad = K * CHUNK - n
        # pad rows are dead: deg 0, never active, never scanned
        deg = jnp.pad(deg, (0, pad))
        row_base = jnp.pad(row_base, (0, pad))
        active = jnp.pad(active, (0, pad))
        worker_ids = jnp.pad(worker_ids, (0, pad))
        indptr = jnp.pad(indptr, (0, pad), mode="edge")
        n_state = K * CHUNK
        deg2 = deg.reshape(K, CHUNK)
        rb2 = row_base.reshape(K, CHUNK)
        wk2 = worker_ids.reshape(K, CHUNK)
    else:
        n_state = n
    has_deg = deg > 0
    zero_pw = jnp.zeros((workers,), jnp.int32)

    def support_of(ptr):
        addr = jnp.clip(row_base + ptr, 0, max(m - 1, 0))
        return indices[addr]

    def cond(state):
        return jnp.any(state["affected"])

    def body(state):
        status, affected = state["status"], state["affected"]

        # scan strictly after the (dead) support; round 0 starts at 0
        # (ptr=-1).  In sparse mode both rounds additionally return the
        # per-vertex support (``indices[row_base + ptr]``) so the lazy
        # inversion reads a carried array instead of re-gathering it.
        def dense_round(aff):
            found, pos, probes = probe_fn(
                status, indptr, indices, state["ptr"] + 1, scanning=aff)
            new_status = status & ~(aff & ~found)
            ptr = jnp.where(aff, jnp.where(found, pos, deg), state["ptr"])
            pw = (per_worker_add(zero_pw, probes, worker_ids, workers)
                  if counters else zero_pw)
            ps = jnp.sum(probes) if instrument else jnp.int32(0)
            if not sparse:
                return new_status, ptr, pw, ps
            return new_status, ptr, support_of(ptr), pw, ps

        if sparse:
            chmask = jnp.any(affected.reshape(K, CHUNK), axis=1)
            sparse_ok = jnp.sum(chmask) <= Cc

        def sparse_round(aff):
            # compact the *chunk* set (rank search over the (K,) chunk
            # mask), probe the selected Cc*CHUNK rows through gathered CSR
            # descriptors, scatter whole chunk rows back (sentinel chunk
            # id K drops)
            aff2 = aff.reshape(K, CHUNK)
            ccs = jnp.cumsum(chmask.astype(jnp.int32))
            cids = jnp.searchsorted(
                ccs, jnp.arange(1, Cc + 1, dtype=jnp.int32),
                side="left").astype(jnp.int32)
            okc = cids < K
            rowc = jnp.minimum(cids, K - 1)
            scan2 = aff2[rowc] & okc[:, None]               # (Cc, CHUNK)
            scan = scan2.reshape(-1)
            rb_rows = rb2[rowc]
            dg_rows = deg2[rowc]
            dg = jnp.where(scan, dg_rows.reshape(-1), 0)
            ptr2 = state["ptr"].reshape(K, CHUNK)
            ptr_rows = ptr2[rowc]
            start = jnp.where(scan, ptr_rows.reshape(-1) + 1, 0)
            found, pos, probes = probe_first_live_ids(
                status, indices, rb_rows.reshape(-1), dg, start,
                scanning=scan)
            found2 = found.reshape(Cc, CHUNK)
            new_ptr_rows = jnp.where(
                scan2,
                jnp.where(found2, pos.reshape(Cc, CHUNK), dg_rows),
                ptr_rows)
            ptr = ptr2.at[cids].set(new_ptr_rows, mode="drop").reshape(-1)
            # refresh the carried support for the touched rows only
            supp_rows = indices[jnp.clip(rb_rows + new_ptr_rows,
                                         0, max(m - 1, 0))]
            supp = state["supp"].reshape(K, CHUNK).at[cids].set(
                supp_rows, mode="drop").reshape(-1)
            st2 = status.reshape(K, CHUNK)
            new_st_rows = st2[rowc] & ~(scan2 & ~found2)
            new_status = st2.at[cids].set(new_st_rows,
                                          mode="drop").reshape(-1)
            # probes is 0 off ``scan``: pad slots and repeated rows add 0
            pw = (per_worker_add(zero_pw, probes, wk2[rowc].reshape(-1),
                                 workers)
                  if counters else zero_pw)
            ps = jnp.sum(probes) if instrument else jnp.int32(0)
            return new_status, ptr, supp, pw, ps

        if sparse:
            new_status, ptr, supp, pw_delta, probes_sum = jax.lax.cond(
                sparse_ok, sparse_round, dense_round, affected)
        else:
            new_status, ptr, pw_delta, probes_sum = dense_round(affected)
            supp = support_of(ptr)
        frontier_ = status & ~new_status       # newly dead this round
        # lazy supporting-set inversion: whose support died?
        supp_live = new_status[supp]
        next_affected = new_status & ~supp_live & has_deg
        new = dict(
            status=new_status,
            ptr=ptr,
            affected=next_affected,
            rounds=state["rounds"] + 1,
        )
        if sparse:
            new["supp"] = supp
        if counters:
            fsz = worker_counts(frontier_, worker_ids, workers)
            new["per_worker"] = state["per_worker"] + pw_delta
            new["max_qp"] = jnp.maximum(state["max_qp"], jnp.max(fsz))
        if instrument:
            vals = dict(r_frontier=jnp.sum(frontier_),
                        r_edges=probes_sum)
            if sparse:
                vals["r_sparse"] = sparse_ok.astype(jnp.int32)
            new["stats"] = obs.stats_record(
                state["stats"], state["rounds"], **vals)
        return new

    init = dict(
        status=active,
        ptr=jnp.full((n_state,), -1, jnp.int32),
        affected=active,
        rounds=jnp.array(0, jnp.int32),
    )
    if sparse:
        # round 1 processes every live row (affected0 = active), so both
        # branches overwrite the support of every row that can ever be
        # read — zeros here are never observed
        init["supp"] = jnp.zeros((n_state,), jnp.int32)
    if counters:
        init["per_worker"] = jnp.zeros((workers,), jnp.int32)
        init["max_qp"] = jnp.array(0, jnp.int32)
    if instrument:
        names = ("r_frontier", "r_edges") + (("r_sparse",) if sparse else ())
        init["stats"] = obs.stats_init(max_rounds, names)
    out = jax.lax.while_loop(cond, body, init)
    status_out = out["status"][:n] if sparse else out["status"]
    return (status_out, out["rounds"],
            out["per_worker"] if counters else None,
            out["max_qp"] if counters else None,
            out["stats"] if instrument else None)


def _run_ac6(graph_arrays, transpose_arrays, worker_ids, workers, active, *,
             probe, window, use_kernel, counters,
             frontier=FrontierPlan(), instrument=False, max_rounds=0):
    indptr, indices = graph_arrays
    return ac6_kernel(
        indptr, indices, worker_ids, workers, active=active, probe=probe,
        window=window, use_kernel=use_kernel, counters=counters,
        frontier=frontier, instrument=instrument, max_rounds=max_rounds)


register_kernel(KernelSpec(
    name="ac6", run=_run_ac6, needs_transpose=False,
    supports_windowed=True, sharded_method="ac6"))
