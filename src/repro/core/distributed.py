"""Distributed (multi-device / multi-pod) graph trimming via ``shard_map``.

The paper's P multicore workers become the P devices of a JAX mesh; the
paper's shared-memory status array becomes a replicated status vector that
is re-assembled once per BSP round with one ``all_gather`` (AC-3/AC-6) or
``psum_scatter`` (AC-4's bulk counter decrement).  Per-device private state
(scan pointers, waiting-set masks, traversal counters) never leaves the
device — the analogue of the paper's private Q_p sets, with the collectives
playing the role of the atomics.

Per-round communication volume:
  AC-3/AC-6:  all_gather of n/P status bytes per device  (O(n) per round)
  AC-4:       psum_scatter of an (n,) int32 decrement vector

This module provides the shard_map *bodies* and partitioners; callers go
through the engine (``plan(graph, backend="sharded")``) or the
:func:`trim_distributed` convenience wrapper, which is now a thin shim over
a throwaway engine.  It is exercised three ways: (1) correctness tests on 8
virtual CPU devices (subprocess), (2) the 512-chip production-mesh dry-run
(`launch/trim.py --dryrun`), (3) the scaling benchmark.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import obs
from .common import probe_first_live
from .graph import CSRGraph, TrimResult

from ..jaxcompat import mark_varying as _mark_varying
from ..jaxcompat import shard_map as _shard_map


def shard_map_compat(body, mesh, in_specs: int, out_specs: int, axis):
    """shard_map ``body`` over ``mesh`` with ``in_specs``/``out_specs``
    counts of ``P(axis)``-sharded operands/results, on whichever shard_map
    this jax release ships (see ``repro.jaxcompat``)."""
    return _shard_map(body, mesh=mesh,
                      in_specs=(P(axis),) * in_specs,
                      out_specs=(P(axis),) * out_specs)


def build_partition(graph: CSRGraph, num_parts: int, sharding):
    """Host-side contiguous row partition of a CSR graph, placed once on
    the mesh: row ``d`` of each operand lands on device ``d`` of
    ``sharding`` (a ``NamedSharding(mesh, P(axis))``), so no device ever
    holds the whole partition.

    Returns (local_indptr (P, nl+1), local_indices (P, ml_max), n_pad).
    ``local_indices`` keeps GLOBAL vertex ids (the status vector is global);
    ``local_indptr`` is rebased per device.  Padded rows have degree 0.
    """
    indptr, indices = graph.to_numpy()
    n = graph.n
    nl = math.ceil(max(n, 1) / num_parts)
    nl = -(-nl // 32) * 32          # 32-align for the packed-bitmap variant
    n_pad = nl * num_parts
    ml_max = 1
    parts = []
    for d in range(num_parts):
        lo, hi = d * nl, min((d + 1) * nl, n)
        if lo >= n:
            lip = np.zeros(nl + 1, np.int32)
            lix = np.zeros(0, np.int32)
        else:
            base = indptr[lo]
            lip = np.zeros(nl + 1, np.int32)
            lip[: hi - lo + 1] = indptr[lo : hi + 1] - base
            lip[hi - lo + 1 :] = lip[hi - lo]   # padded rows: degree 0
            lix = indices[indptr[lo] : indptr[hi]]
        ml_max = max(ml_max, len(lix))
        parts.append((lip, lix))
    local_indptr = np.stack([p[0] for p in parts])
    local_indices = np.zeros((num_parts, ml_max), np.int32)
    for d, (_, lix) in enumerate(parts):
        local_indices[d, : len(lix)] = lix
    return (jax.device_put(local_indptr, sharding),
            jax.device_put(local_indices, sharding), n_pad)


def _axis_size(mesh, axis):
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    return int(np.prod([mesh.shape[a] for a in names]))


def _pack_bits(status_bool):
    """(n,) bool -> (n/32,) uint32 bitmap (n divisible by 32)."""
    b = status_bool.reshape(-1, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(b << shifts, axis=1, dtype=jnp.uint32)


def _unpack_bits(packed):
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (((packed[:, None] >> shifts) & 1) > 0).reshape(-1)


def _ac6_body_packed(axis, instrument: bool = False, max_rounds: int = 0):
    """§Perf variant: the per-round status all_gather exchanges a packed
    uint32 bitmap (n/8 bytes) instead of a bool array (n bytes) — an 8×
    collective-traffic cut for the paper's technique at pod scale.
    Requires n/P divisible by 32 (pad_to=32 in build_partition).

    ``instrument`` (DESIGN.md §11): every body maker here optionally
    threads per-SHARD ``(max_rounds,)`` round buffers — deaths and
    traversed edges this shard did per round — through the carry,
    returning them as two extra ``(1, R)`` sharded outputs (the engine
    stacks them to ``(P, R)``: per-worker per-round stats, the quantity
    the paper's imbalance experiments plot)."""
    def run(lip, lix, act):
        lip, lix, act = lip[0], lix[0], act[0]
        nl = lip.shape[0] - 1
        deg = lip[1:] - lip[:-1]
        ml = lix.shape[0]

        def cond(s):
            return s["go"]

        def body(s):
            status_g = _unpack_bits(s["status_pg"])
            found, pos, probes = probe_first_live(
                status_g, lip, lix, s["ptr"] + 1, s["affected"])
            frontier = s["affected"] & ~found
            status_l = s["status_l"] & ~frontier
            ptr = jnp.where(s["affected"],
                            jnp.where(found, pos, deg), s["ptr"])
            status_pg = jax.lax.all_gather(_pack_bits(status_l), axis,
                                           tiled=True)
            status_gn = _unpack_bits(status_pg)
            supp = lix[jnp.clip(lip[:-1] + ptr, 0, max(ml - 1, 0))]
            affected = status_l & ~status_gn[supp] & (deg > 0)
            go = jax.lax.pmax(jnp.any(affected), axis)
            new = dict(
                status_l=status_l, status_pg=status_pg, ptr=ptr,
                affected=affected, go=go, rounds=s["rounds"] + 1,
                edges=s["edges"] + jnp.sum(probes),
                max_qp=jnp.maximum(s["max_qp"],
                                   jnp.sum(frontier.astype(jnp.int32))))
            if instrument:
                new["stats"] = obs.stats_record(
                    s["stats"], s["rounds"],
                    r_frontier=jnp.sum(frontier),
                    r_edges=jnp.sum(probes))
            return _mark_varying(new, axis)

        init = dict(status_l=act,
                    status_pg=jax.lax.all_gather(_pack_bits(act), axis,
                                                 tiled=True),
                    ptr=jnp.full((nl,), -1, jnp.int32),
                    affected=act,
                    go=jnp.array(True),
                    rounds=jnp.array(0, jnp.int32),
                    edges=jnp.array(0, jnp.int32),
                    max_qp=jnp.array(0, jnp.int32))
        if instrument:
            init["stats"] = obs.stats_init(max_rounds,
                                           ("r_frontier", "r_edges"))
        out = jax.lax.while_loop(cond, body, _mark_varying(init, axis))
        res = (out["status_l"][None], out["edges"][None],
               out["rounds"][None], out["max_qp"][None])
        if instrument:
            res += (out["stats"]["r_frontier"][None],
                    out["stats"]["r_edges"][None])
        return res
    return run


def _ac6_body(axis, instrument: bool = False, max_rounds: int = 0):
    def run(lip, lix, act):
        lip, lix, act = lip[0], lix[0], act[0]
        nl = lip.shape[0] - 1
        deg = lip[1:] - lip[:-1]
        ml = lix.shape[0]

        def cond(s):
            return s["go"]

        def body(s):
            status_g = s["status_g"]
            found, pos, probes = probe_first_live(
                status_g, lip, lix, s["ptr"] + 1, s["affected"])
            frontier = s["affected"] & ~found
            status_l = s["status_l"] & ~frontier
            ptr = jnp.where(s["affected"],
                            jnp.where(found, pos, deg), s["ptr"])
            status_g = jax.lax.all_gather(status_l, axis, tiled=True)
            supp = lix[jnp.clip(lip[:-1] + ptr, 0, max(ml - 1, 0))]
            affected = status_l & ~status_g[supp] & (deg > 0)
            go = jax.lax.pmax(jnp.any(affected), axis)
            new = dict(
                status_l=status_l, status_g=status_g, ptr=ptr,
                affected=affected, go=go,
                rounds=s["rounds"] + 1,
                edges=s["edges"] + jnp.sum(probes),
                max_qp=jnp.maximum(s["max_qp"],
                                   jnp.sum(frontier.astype(jnp.int32))))
            if instrument:
                new["stats"] = obs.stats_record(
                    s["stats"], s["rounds"],
                    r_frontier=jnp.sum(frontier),
                    r_edges=jnp.sum(probes))
            return _mark_varying(new, axis)

        init = dict(status_l=act,
                    status_g=jax.lax.all_gather(act, axis, tiled=True),
                    ptr=jnp.full((nl,), -1, jnp.int32),
                    affected=act,
                    go=jnp.array(True),
                    rounds=jnp.array(0, jnp.int32),
                    edges=jnp.array(0, jnp.int32),
                    max_qp=jnp.array(0, jnp.int32))
        if instrument:
            init["stats"] = obs.stats_init(max_rounds,
                                           ("r_frontier", "r_edges"))
        out = jax.lax.while_loop(cond, body, _mark_varying(init, axis))
        res = (out["status_l"][None], out["edges"][None],
               out["rounds"][None], out["max_qp"][None])
        if instrument:
            res += (out["stats"]["r_frontier"][None],
                    out["stats"]["r_edges"][None])
        return res
    return run


def _ac3_body(axis, instrument: bool = False, max_rounds: int = 0):
    def run(lip, lix, act):
        lip, lix, act = lip[0], lix[0], act[0]
        nl = lip.shape[0] - 1
        deg = lip[1:] - lip[:-1]

        def cond(s):
            return s["go"]

        def body(s):
            status_g, status_l = s["status_g"], s["status_l"]
            found, pos, probes = probe_first_live(
                status_g, lip, lix, s["ptr"], status_l)
            frontier = status_l & ~found
            status_l = status_l & found
            ptr = jnp.where(s["status_l"], jnp.where(found, pos, deg), s["ptr"])
            status_g = jax.lax.all_gather(status_l, axis, tiled=True)
            go = jax.lax.pmax(jnp.any(frontier), axis)
            new = dict(
                status_l=status_l, status_g=status_g, ptr=ptr,
                go=go, rounds=s["rounds"] + 1,
                edges=s["edges"] + jnp.sum(probes),
                max_qp=jnp.maximum(s["max_qp"],
                                   jnp.sum(frontier.astype(jnp.int32))))
            if instrument:
                new["stats"] = obs.stats_record(
                    s["stats"], s["rounds"],
                    r_frontier=jnp.sum(frontier),
                    r_edges=jnp.sum(probes))
            return _mark_varying(new, axis)

        init = dict(status_l=act,
                    status_g=jax.lax.all_gather(act, axis, tiled=True),
                    ptr=jnp.zeros((nl,), jnp.int32),
                    go=jnp.array(True),
                    rounds=jnp.array(0, jnp.int32),
                    edges=jnp.array(0, jnp.int32),
                    max_qp=jnp.array(0, jnp.int32))
        if instrument:
            init["stats"] = obs.stats_init(max_rounds,
                                           ("r_frontier", "r_edges"))
        out = jax.lax.while_loop(cond, body, _mark_varying(init, axis))
        res = (out["status_l"][None], out["edges"][None],
               out["rounds"][None], out["max_qp"][None])
        if instrument:
            res += (out["stats"]["r_frontier"][None],
                    out["stats"]["r_edges"][None])
        return res
    return run


def build_ac4_operands(graph: CSRGraph, num: int, sharding):
    """AC-4's sharded state: Gᵀ partition + out-degree counters, built once
    and placed on the mesh with ``sharding`` (see :func:`build_partition`).

    Returns ``(operands, n_pad)``: the three (P, ...) sharded arrays
    :func:`_ac4_body` consumes.  The engine caches them.
    """
    gt = graph.transpose()
    ltip, ltix, n_pad = build_partition(gt, num, sharding)
    nl = n_pad // num
    # deg_out of owned vertices, padded, shaped (P, nl)
    deg_out = np.zeros(n_pad, np.int32)
    deg_out[: graph.n] = np.asarray(graph.out_degrees())
    deg_out = jax.device_put(deg_out.reshape(num, nl), sharding)
    return (ltip, ltix, deg_out), n_pad


def _ac4_body(axis, instrument: bool = False, max_rounds: int = 0):
    def run(ltip, ltix, deg_out_l):
        ltip, ltix, deg_out_l = ltip[0], ltix[0], deg_out_l[0]
        nl = ltip.shape[0] - 1
        deg_in = ltip[1:] - ltip[:-1]
        psize = jax.lax.psum(1, axis)
        n_pad = nl * psize
        mlt = ltix.shape[0]
        marks = jnp.zeros((mlt,), jnp.int32).at[ltip[1:-1]].add(1)
        lrows = jnp.cumsum(marks)
        valid = jnp.arange(mlt, dtype=jnp.int32) < ltip[nl]

        # padding vertices have deg_out 0 -> they die in round 0 but have no
        # Gᵀ edges, so they are inert.
        frontier0 = deg_out_l == 0
        status0 = ~frontier0

        def cond(s):
            return s["go"]

        def body(s):
            frontier = s["frontier"]
            contrib = jnp.where(valid, frontier[lrows].astype(jnp.int32), 0)
            dec_partial = jax.ops.segment_sum(contrib, ltix,
                                              num_segments=n_pad)
            dec_local = jax.lax.psum_scatter(dec_partial, axis,
                                             scatter_dimension=0, tiled=True)
            counters = s["counters"] - dec_local
            newly = s["status_l"] & (counters <= 0)
            status_l = s["status_l"] & ~newly
            go = jax.lax.pmax(jnp.any(newly), axis)
            round_edges = jnp.sum(jnp.where(frontier, deg_in, 0))
            new = dict(
                status_l=status_l, counters=counters, frontier=newly,
                go=go, rounds=s["rounds"] + 1,
                edges=s["edges"] + round_edges,
                max_qp=jnp.maximum(s["max_qp"],
                                   jnp.sum(newly.astype(jnp.int32))))
            if instrument:
                new["stats"] = obs.stats_record(
                    s["stats"], s["rounds"],
                    r_frontier=jnp.sum(frontier),
                    r_edges=round_edges)
            return _mark_varying(new, axis)

        init = dict(status_l=status0, counters=deg_out_l.astype(jnp.int32),
                    frontier=frontier0,
                    go=jax.lax.pmax(jnp.any(frontier0), axis),
                    rounds=jnp.array(0, jnp.int32),
                    edges=jnp.array(0, jnp.int32),
                    max_qp=jnp.sum(frontier0.astype(jnp.int32)))
        if instrument:
            init["stats"] = obs.stats_init(max_rounds,
                                           ("r_frontier", "r_edges"))
        out = jax.lax.while_loop(cond, body, _mark_varying(init, axis))
        res = (out["status_l"][None], out["edges"][None],
               out["rounds"][None], out["max_qp"][None])
        if instrument:
            res += (out["stats"]["r_frontier"][None],
                    out["stats"]["r_edges"][None])
        return res
    return run


def trim_distributed(graph: CSRGraph, method: str = "ac6",
                     mesh: jax.sharding.Mesh | None = None,
                     axis="workers") -> TrimResult:
    """Run distributed trimming on ``mesh`` (default: all local devices).

    Compatibility shim over a throwaway sharded-backend engine; long-lived
    callers should hold ``plan(graph, method=..., backend="sharded")`` and
    reuse it across runs.
    """
    from .engine import plan
    packed = method == "ac6_packed"
    eng = plan(graph, method="ac6" if packed else method, backend="sharded",
               mesh=mesh, axis=axis, packed=packed, unmasked=True)
    return eng.run().materialize()
