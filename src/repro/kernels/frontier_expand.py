"""Frontier expansion — one BFS pull round as a Pallas kernel.

One round of the reachability sweep (``core.reach``, pull mode) asks, per
*pending* vertex (active, not yet visited), whether ANY of its windowed
in-neighbors sits on the current frontier:

    hit[i] = pending[i] & OR over j of (flags[i, j] & valid[i, j])

The frontier-membership gather stays in XLA (TPUs have hardware gather
support; Pallas TPU dynamic gathers don't); the kernel fuses the masked
row OR-reduction with *block-level frontier skipping*, reusing the
``first_live_scan`` layout: vertex blocks with no pending vertex are
skipped entirely (``@pl.when``) — once most of the graph is visited, most
blocks cost nothing.

Layout: rows = vertices (sublanes ×8), lanes = window offsets (×128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiling import vertex_block

DEFAULT_BLOCK_V = 1024


def _expand_kernel(flags_ref, valid_ref, pending_ref, hit_ref):
    pending = pending_ref[...]                      # (block_v,)

    @pl.when(jnp.any(pending))
    def _compute():
        flags = flags_ref[...] & valid_ref[...]     # (block_v, W) bool
        hit_ref[...] = pending & jnp.any(flags, axis=1)

    @pl.when(~jnp.any(pending))
    def _skip():
        hit_ref[...] = jnp.zeros_like(hit_ref)


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def frontier_expand(flags, valid, pending, block_v: int = DEFAULT_BLOCK_V,
                    interpret: bool = True):
    """flags:   (n, W) bool — frontier membership of the j-th windowed
    in-neighbor of vertex i.
    valid:   (n, W) bool — window position exists (within in-degree).
    pending: (n,) bool — vertex is active and not yet visited.

    Returns hit: (n,) bool — pending vertex with a frontier in-neighbor
    inside the window.
    """
    n, window = flags.shape
    if n == 0:
        return jnp.zeros((0,), jnp.bool_)
    block_v = vertex_block(block_v, n)
    n_pad = -(-n // block_v) * block_v
    if n_pad != n:
        pad = n_pad - n
        flags = jnp.pad(flags, ((0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
        pending = jnp.pad(pending, (0, pad))

    hit = pl.pallas_call(
        _expand_kernel,
        grid=(n_pad // block_v,),
        in_specs=[
            pl.BlockSpec((block_v, window), lambda i: (i, 0)),
            pl.BlockSpec((block_v, window), lambda i: (i, 0)),
            pl.BlockSpec((block_v,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block_v,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.bool_),
        name="frontier_expand",
        interpret=interpret,
    )(flags, valid, pending)
    return hit[:n]
