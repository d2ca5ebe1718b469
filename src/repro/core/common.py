"""Shared machinery for the BSP (bulk-synchronous) trimming algorithms.

The paper's multicore algorithms advance per-vertex *scan pointers*
(``edge_index``, paper §8 "Traverse Edges") so that the adjacency list of a
vertex is never re-scanned from the beginning.  On TPU we keep the pointer
array and advance *all* unresolved vertices in lockstep micro-steps inside a
``lax.while_loop``; each micro-step is one dense gather (one "probe") per
scanning vertex.  This preserves the paper's traversal bounds:

* AC-3: each live vertex re-probes from its pointer every peeling round
  (work O(α(n+m))), pointer skips the known-dead prefix.
* AC-6: a vertex probes only when its single support died; the pointer
  strictly advances past dead targets, so every adjacency entry is examined
  at most once (work O(n+m), the paper's Theorem 12).

Counters (traversed edges, per-worker attribution, frontier sizes) are
carried inside the loop state so benchmarks read exact, deterministic values
— the paper's primary experimental metric (§9.3).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

FRONTIER_MODES = ("auto", "dense", "sparse")


def _pow2(x: int) -> int:
    # local copy (core.graph and obs carry one too): common sits below both
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


class FrontierPlan(NamedTuple):
    """Static sparse-frontier configuration, resolved once at plan time
    (DESIGN.md §12) and baked into the compiled fixpoint.

    mode: "dense"  — every round runs the existing dense O(n)/O(m) body.
          "auto"   — each round switches on-device (``lax.cond``): rounds
                     whose frontier fits ``cap`` members and ``ecap``
                     expanded edges take the compacted path, the rest stay
                     dense.  Results are bit-identical either way.
          "sparse" — capacities cover the whole graph, so every round
                     compacts (the parity-test configuration).
    cap:  static member capacity of the compacted id buffer (pow2).
    ecap: static capacity of the expanded edge buffer (pow2).

    The tuple is hashable, so it keys the engines' lru-cached runners and
    rides into ``jax.jit`` as a static argument — switching direction
    never changes carry shapes and never retraces.
    """

    mode: str = "dense"
    cap: int = 0
    ecap: int = 0


def frontier_plan(mode: str, n: int, m: int) -> FrontierPlan:
    """Resolve a ``frontier=`` argument into a static :class:`FrontierPlan`.

    "auto" sizes the member capacity at ~n/64 (clamped to [128, n],
    pow2-padded) — the compacted round's cost scales with the *capacity*,
    not the live frontier, so the buffer must stay far below n for the
    sparse path to win — and the edge capacity at ~m/8: the expansion
    path is scatter-bound on both sides, so an 8x smaller buffer is an
    ~8x cheaper round whenever it triggers.  Degenerate graphs (no
    vertices or no edges) never reach a kernel, so they plan dense.
    """
    if mode not in FRONTIER_MODES:
        raise ValueError(f"unknown frontier mode {mode!r}; expected one of "
                         f"{FRONTIER_MODES}")
    if mode == "dense" or n == 0 or m == 0:
        return FrontierPlan("dense", 0, 0)
    if mode == "sparse":
        return FrontierPlan("sparse", _pow2(n), _pow2(m))
    cap = _pow2(min(max(n // 64, 128), n))
    ecap = _pow2(min(max(m // 8, 128), m))
    return FrontierPlan("auto", cap, ecap)


def probe_first_live(status, indptr, indices, start, scanning):
    """Advance scan pointers until a live target is found or the list ends.

    Args:
      status:   (n,) bool — snapshot of liveness for this round. Probes read
                this snapshot only (BSP: no intra-round races by construction).
      indptr:   (n+1,) int32 CSR row pointers.
      indices:  (m,) int32 CSR adjacency.
      start:    (n,) int32 — relative scan position to probe first.
      scanning: (n,) bool — which vertices participate.

    Returns:
      found:  (n,) bool — a live target exists at position >= start.
      pos:    (n,) int32 — relative position of the found live target
              (undefined where not found).
      probes: (n,) int32 — number of adjacency entries examined ("traversed
              edges", paper §9.3). Zero for non-scanning vertices.
    """
    n = indptr.shape[0] - 1
    m = indices.shape[0]
    deg = indptr[1:] - indptr[:-1]
    start = jnp.minimum(start, deg)

    def cond(state):
        ptr, active, found = state
        return jnp.any(active)

    def body(state):
        ptr, active, found = state
        in_range = ptr < deg
        addr = jnp.clip(indptr[:-1] + ptr, 0, max(m - 1, 0))
        target = indices[addr]
        hit = active & in_range & status[target]
        # live target found: stop, keep ptr at the hit position
        found = found | hit
        # dead target: advance; exhausted: deactivate
        advance = active & in_range & ~hit
        ptr = jnp.where(advance, ptr + 1, ptr)
        active = active & ~hit & (ptr < deg)
        return ptr, active, found

    ptr0 = jnp.where(scanning, start, deg)
    active0 = scanning & (ptr0 < deg)
    # derive found0 from `scanning` (not a fresh constant) so its varying-axis
    # type matches the loop body's output under shard_map
    found0 = jnp.logical_and(scanning, False)
    ptr, _, found = jax.lax.while_loop(cond, body, (ptr0, active0, found0))
    # entries examined: positions start..ptr inclusive when found,
    # start..deg-1 when exhausted  ->  (ptr - start) + found
    probes = jnp.where(scanning, ptr - start + found.astype(jnp.int32), 0)
    return found, ptr, probes


def probe_first_live_ids(status, indices, row_base, deg, start, scanning):
    """Compacted-row variant of :func:`probe_first_live`: probe only the
    ``C`` rows a frontier compaction selected, through *gathered* CSR row
    descriptors instead of the full (n,) arrays.

    Args:
      status:   (n,) bool liveness snapshot (gathers stay n-wide).
      indices:  (m,) int32 CSR adjacency.
      row_base: (C,) int32 — ``indptr[v]`` of each compacted row.
      deg:      (C,) int32 — degree of each compacted row (0 for the
                sentinel slots a short frontier leaves unused).
      start:    (C,) int32 relative scan position to probe first.
      scanning: (C,) bool — which compacted slots participate.

    Same contract as :func:`probe_first_live` (found/pos/probes, pointers
    never retreat, every entry examined at most once), so a sparse round
    built on it is bit-identical to the dense round — including the
    traversed-edge counters.
    """
    m = indices.shape[0]
    start = jnp.minimum(start, deg)

    def cond(state):
        ptr, active, found = state
        return jnp.any(active)

    def body(state):
        ptr, active, found = state
        in_range = ptr < deg
        addr = jnp.clip(row_base + ptr, 0, max(m - 1, 0))
        target = indices[addr]
        hit = active & in_range & status[target]
        found = found | hit
        advance = active & in_range & ~hit
        ptr = jnp.where(advance, ptr + 1, ptr)
        active = active & ~hit & (ptr < deg)
        return ptr, active, found

    ptr0 = jnp.where(scanning, start, deg)
    active0 = scanning & (ptr0 < deg)
    found0 = jnp.logical_and(scanning, False)
    ptr, _, found = jax.lax.while_loop(cond, body, (ptr0, active0, found0))
    probes = jnp.where(scanning, ptr - start + found.astype(jnp.int32), 0)
    return found, ptr, probes


def probe_first_live_windowed(status, indptr, indices, start, scanning,
                              window: int = 16,
                              use_kernel: bool | None = None):
    """Window-batched probe: materialize each scanning vertex's next
    ``window`` adjacency entries, reduce them with the
    ``kernels.first_live_scan`` Pallas kernel (block-level frontier skip on
    TPU), and fall back to per-step probing only for vertices whose live
    target lies beyond the window.  Identical results to
    ``probe_first_live`` including the traversal counters.

    This is the TPU-native execution path of the trimming hot loop: one
    XLA gather builds the (n, W) liveness tile, the kernel fuses the row
    scan (DESIGN.md §6).  ``use_kernel=None`` (the default) lets
    ``kernels.ops`` pick: Pallas on TPU, the jnp reference elsewhere.
    """
    from ..kernels import ops as kops

    n = indptr.shape[0] - 1
    m = indices.shape[0]
    deg = indptr[1:] - indptr[:-1]
    start = jnp.minimum(start, deg)

    offs = jnp.arange(window, dtype=jnp.int32)
    pos = start[:, None] + offs[None, :]                     # (n, W)
    valid = pos < deg[:, None]
    addr = jnp.clip(indptr[:-1, None] + pos, 0, max(m - 1, 0))
    flags = status[indices[addr]]                            # (n, W)

    first, found_w = kops.first_live_scan(flags, valid, scanning,
                                          use_kernel=use_kernel)
    pos_w = start + first
    # exhausted within the window <=> no live found AND window covers deg
    covered = (start + window) >= deg
    resolved = found_w | covered
    # window probes: min(first-live-or-window-end) entries examined
    examined_w = jnp.where(
        scanning,
        jnp.where(found_w, first + 1,
                  jnp.minimum(window, jnp.maximum(deg - start, 0))),
        0)

    # rare continuation: live target beyond the window
    rest = scanning & ~resolved
    found_r, pos_r, probes_r = probe_first_live(
        status, indptr, indices, start + window, rest)

    found = jnp.where(rest, found_r, found_w & scanning)
    pos_out = jnp.where(rest, pos_r, pos_w)
    probes = jnp.where(rest, examined_w + probes_r, examined_w)
    return found, pos_out, probes


def resolve_probe(kind: str = "dense", window: int = 16,
                  use_kernel: bool | None = None):
    """Map an engine backend's probe kind to a concrete probe function.

    "dense"    — per-step lockstep probing (``probe_first_live``)
    "windowed" — window-batched probing through the ``first_live_scan``
                 Pallas kernel (``probe_first_live_windowed``)

    Both are interchangeable inside the AC-3/AC-6 while-loops: identical
    results including the traversal counters (DESIGN.md §6).
    """
    if kind == "dense":
        return probe_first_live
    if kind == "windowed":
        return partial(probe_first_live_windowed, window=window,
                       use_kernel=use_kernel)
    raise ValueError(f"unknown probe kind {kind!r}; "
                     "expected 'dense' or 'windowed'")


def per_worker_add(acc, values, worker_ids, workers: int):
    """acc[p] += sum of values over vertices owned by worker p.

    One masked int32 reduction per worker, stacked: no scatter into the
    (workers,) slots, and exact for any ``worker_ids`` (integer sums do
    not depend on order; ids outside [0, workers) count nowhere)."""
    values = values.astype(jnp.int32)
    return acc + jnp.stack([
        jnp.sum(jnp.where(worker_ids == p, values, 0), dtype=jnp.int32)
        for p in range(workers)])


def worker_counts(mask, worker_ids, workers: int):
    """(workers,) int32: members of ``mask`` owned by each worker."""
    return per_worker_add(jnp.zeros((workers,), jnp.int32), mask,
                          worker_ids, workers)
