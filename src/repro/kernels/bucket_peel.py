"""Bucket extraction — one peeling round's frontier as a Pallas kernel.

One round of the bucketed k-core fixpoint (``core.peel``, DESIGN.md §10)
asks, per *alive* vertex, whether its live-out-degree support counter has
fallen into the current peel bucket:

    frontier[v] = alive[v] & (counters[v] <= k)

The comparison itself is trivial; what the kernel buys is *block-level
peel skipping*, reusing the ``frontier_expand`` layout: vertex blocks with
no alive vertex are skipped entirely (``@pl.when``) — late in the peel,
when most of the graph is already assigned a coreness, most blocks cost
nothing.  The bucket level ``k`` is a traced scalar (it advances inside
the fixpoint's ``while_loop``), so it rides along as a (1,) operand
broadcast to every grid cell rather than a compile-time constant.

Layout: lanes = vertices within a block (×128), grid = vertex blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiling import vertex_block

DEFAULT_BLOCK_V = 1024


def _bucket_kernel(counters_ref, alive_ref, k_ref, frontier_ref):
    alive = alive_ref[...]                          # (block_v,)

    @pl.when(jnp.any(alive))
    def _extract():
        frontier_ref[...] = alive & (counters_ref[...] <= k_ref[0])

    @pl.when(~jnp.any(alive))
    def _skip():
        frontier_ref[...] = jnp.zeros_like(frontier_ref)


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def bucket_peel_pallas(counters, alive, k, block_v: int = DEFAULT_BLOCK_V,
                       interpret: bool = True):
    """counters: (n,) int32 — live-out-degree support counters.
    alive:    (n,) bool — not yet peeled (and inside the active subgraph).
    k:        scalar int32 (traced) — current bucket level.

    Returns frontier: (n,) bool — alive vertices whose counter sits at or
    below the bucket level (they peel this round with coreness ``k``).
    """
    n = counters.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.bool_)
    k = jnp.asarray(k, jnp.int32).reshape(1)
    block_v = vertex_block(block_v, n)
    n_pad = -(-n // block_v) * block_v
    if n_pad != n:
        counters = jnp.pad(counters, (0, n_pad - n))
        alive = jnp.pad(alive, (0, n_pad - n))      # padding is never alive

    frontier = pl.pallas_call(
        _bucket_kernel,
        grid=(n_pad // block_v,),
        in_specs=[
            pl.BlockSpec((block_v,), lambda i: (i,)),
            pl.BlockSpec((block_v,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_v,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.bool_),
        name="bucket_peel",
        interpret=interpret,
    )(counters, alive, k)
    return frontier[:n]
