"""Frontier compaction — the sparse-frontier substrate's device primitive.

Every fixpoint in this repo advances a ``lax.while_loop`` over dense (n,)
masks, so a round costs O(n) (or O(m)) even when three vertices changed.
Work-efficient frontier processing (direction-optimizing BFS; Dhulipala
et al.'s compacted vertexSubsets) instead *compacts* a small frontier
into an index list and expands only ``Σ deg(frontier)`` edges.  Two
primitives implement that here:

``prefix_positions``
    exclusive cumulative sum over an int32 vector, tiled as a sequential
    Pallas grid with an SMEM carry — the scan that turns a frontier mask
    into scatter positions (and CSR degree runs into edge offsets).

``frontier_compact``
    mask -> (ids, count): the frontier's vertex ids compacted into a
    *static-capacity* pow2 buffer (unused slots hold the sentinel ``n``)
    plus the member count.  Static capacity keeps the while-loop carry
    fixed-shape, so switching between dense and sparse rounds never
    retraces.

``sparse_expand``
    (csr, ids) -> per-edge (src, tgt, pos, valid): gathers the CSR
    adjacency slices of the compacted rows into a static ``ecap``-wide
    edge buffer.  Row ownership comes from a boundary-marker scan — +1
    scattered at each row's exclusive edge offset, inclusive-cumsummed —
    which lands zero-degree rows on no edge and needs no searchsorted.

The dynamic gathers/scatters stay in XLA (TPUs have hardware gather
support; Pallas TPU dynamic gathers don't — the ``frontier_expand``
precedent); the Pallas kernel owns the scan, where the sequential grid +
SMEM carry maps onto the TPU's tiled memory cleanly.  ``kernels/ref.py``
holds the pure-jnp twins; ``kernels/ops.py`` picks per backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TILE = 8 * LANES            # one (8, 128) int32 vreg tile
DEFAULT_BLOCK = 8 * TILE    # elements per sequential grid step


def _scan_kernel(x_ref, out_ref, carry_ref):
    """One grid step of the sequential exclusive scan over a (rows, 128)
    block in row-major order: emit the running prefix for this block and
    push the block total into the SMEM carry.

    Mosaic has no cumsum, so the block scan is two Hillis–Steele passes
    of shift-and-add (``pltpu.roll`` + lane/sublane masks, int32-exact):
    log2(128) steps along the lanes of each row, then log2(rows) steps
    over the row totals down the sublanes."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[0] = 0

    x = x_ref[...]                                   # (rows, 128) int32
    rows = x.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    incl = x                                         # within-row inclusive
    s = 1
    while s < LANES:
        incl = incl + jnp.where(lane >= s, pltpu.roll(incl, s, 1), 0)
        s *= 2
    tot = jnp.broadcast_to(jnp.sum(x, axis=1, keepdims=True), x.shape)
    above = tot                                      # inclusive over rows
    s = 1
    while s < rows:
        above = above + jnp.where(row >= s, pltpu.roll(above, s, 0), 0)
        s *= 2
    base = carry_ref[0]
    out_ref[...] = base + (above - tot) + (incl - x)  # exclusive positions
    carry_ref[0] = base + jnp.sum(x)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def prefix_positions(x, block: int = DEFAULT_BLOCK, interpret: bool = True):
    """Exclusive prefix sum of an (n,) int32 vector as a sequential-grid
    Pallas scan (SMEM scalar carry between blocks).  Returns
    ``(positions, total)`` with ``positions[i] = sum(x[:i])`` and
    ``total = sum(x)``.

    The vector is laid out as (rows, 128) — the TPU's native int32 tile —
    padded to whole (8, 128) tiles; ``block`` (elements per grid step) is
    rounded up to whole tiles the same way."""
    n = x.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32)
    x = x.astype(jnp.int32)
    n_tiles = -(-n // TILE)
    block_rows = 8 * min(max(-(-block // TILE), 1), n_tiles)
    rows = -(-(n_tiles * 8) // block_rows) * block_rows
    n_pad = rows * LANES
    if n_pad != n:
        x = jnp.pad(x, (0, n_pad - n))

    pos = pl.pallas_call(
        _scan_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        name="prefix_positions",
        interpret=interpret,
    )(x.reshape(rows, LANES)).reshape(n_pad)
    total = pos[n - 1] + x[n - 1]
    return pos[:n], total


@functools.partial(jax.jit, static_argnames=("capacity", "block",
                                             "interpret"))
def frontier_compact_pallas(mask, capacity: int, block: int = DEFAULT_BLOCK,
                            interpret: bool = True):
    """mask: (n,) bool -> (ids, count): the True positions compacted into
    a (capacity,) int32 buffer (sentinel ``n`` beyond ``count``; members
    past ``capacity`` are dropped — callers gate on ``count <= capacity``
    before taking the sparse path) and the scalar member count."""
    n = mask.shape[0]
    if n == 0:
        return jnp.full((capacity,), 0, jnp.int32), jnp.zeros((), jnp.int32)
    pos, count = prefix_positions(mask.astype(jnp.int32), block=block,
                                  interpret=interpret)
    slot = jnp.where(mask, pos, capacity)   # overflow/off-frontier: dropped
    ids = jnp.full((capacity,), n, jnp.int32).at[slot].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    return ids, count


@functools.partial(jax.jit, static_argnames=("ecap", "block", "interpret"))
def sparse_expand_pallas(indptr, indices, ids, ecap: int,
                         block: int = DEFAULT_BLOCK, interpret: bool = True):
    """Expand the CSR rows of the compacted ``ids`` into a static
    (ecap,)-wide edge buffer.

    indptr/indices: the CSR to expand (G or Gᵀ).
    ids: (C,) int32 compacted row ids, sentinel ``n`` in unused slots.

    Returns ``(src, tgt, pos, valid)``, all (ecap,):
      src   — the compacted row (frontier vertex) owning edge slot e
              (clamped into range; masked by ``valid``),
      tgt   — ``indices[pos]``, the edge's endpoint,
      pos   — the edge's position in ``indices`` (edge id),
      valid — slot e holds a real edge (e < Σ deg over ids).

    Rows whose total degree exceeds ``ecap`` lose their tail — callers
    gate on ``Σ deg <= ecap`` before taking the sparse path.
    """
    n = indptr.shape[0] - 1
    m = indices.shape[0]
    C = ids.shape[0]
    if n == 0 or m == 0:                   # nothing to expand, statically
        z = jnp.zeros((ecap,), jnp.int32)
        return z, z, z, jnp.zeros((ecap,), bool)
    ok = ids < n
    row = jnp.where(ok, ids, 0)
    row_base = jnp.where(ok, indptr[row], 0)
    deg = jnp.where(ok, indptr[jnp.minimum(row + 1, n)] - row_base, 0)
    excl, total = prefix_positions(deg, block=block, interpret=interpret)

    # boundary-marker ownership: +1 at each row's exclusive offset, then an
    # inclusive scan — zero-degree rows bump the counter in place, so the
    # rank cumsum skips them (deg [2,0,3] -> owners [0,0,2,2,2])
    marker = jnp.zeros((ecap,), jnp.int32).at[
        jnp.minimum(excl, ecap)].add(1, mode="drop")
    mpos, _ = prefix_positions(marker, block=block, interpret=interpret)
    owner = jnp.clip(mpos + marker - 1, 0, C - 1)   # inclusive scan - 1

    e = jnp.arange(ecap, dtype=jnp.int32)
    valid = e < total
    src = jnp.where(ok[owner], ids[owner], 0)
    pos = jnp.clip(row_base[owner] + (e - excl[owner]), 0, max(m - 1, 0))
    tgt = indices[pos]
    return src, tgt, pos, valid
