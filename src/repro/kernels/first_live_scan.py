"""First-live-neighbor scan — the trimming hot loop as a Pallas kernel.

One BSP probe round of AC-3/AC-6 reduces, per scanning vertex, a window of
its adjacency to the offset of the first LIVE target.  The liveness gather
stays in XLA (TPUs have hardware gather support; Pallas TPU dynamic gathers
don't); the kernel fuses the masked row scan:

    first[i] = min over j of (j where flags[i, j] else W)

with *block-level frontier skipping*: vertex blocks with no scanning vertex
are skipped entirely (``@pl.when``) — the BSP analogue of the paper's
work-efficiency (only affected vertices pay), at tile granularity.

Layout: rows = vertices (sublanes ×8), lanes = window offsets (×128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiling import vertex_block

DEFAULT_BLOCK_V = 1024


def _scan_kernel(flags_ref, valid_ref, active_ref, first_ref, found_ref,
                 *, window: int):
    active = active_ref[...]                        # (block_v,)

    @pl.when(jnp.any(active))
    def _compute():
        flags = flags_ref[...] & valid_ref[...]     # (block_v, W) bool
        offs = jax.lax.broadcasted_iota(jnp.int32, flags.shape, 1)
        first = jnp.min(jnp.where(flags, offs, window), axis=1)
        first_ref[...] = jnp.where(active, first, window)
        found_ref[...] = active & (first < window)

    @pl.when(~jnp.any(active))
    def _skip():
        first_ref[...] = jnp.full_like(first_ref, window)
        found_ref[...] = jnp.zeros_like(found_ref)


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def first_live_scan(flags, valid, active, block_v: int = DEFAULT_BLOCK_V,
                    interpret: bool = True):
    """flags:  (n, W) bool — liveness of the j-th window target of vertex i.
    valid:  (n, W) bool — window position exists (within degree).
    active: (n,) bool — vertex is scanning this round.

    Returns (first, found): first (n,) int32 offset of first live target
    (W when none), found (n,) bool.
    """
    n, window = flags.shape
    block_v = vertex_block(block_v, n)
    n_pad = -(-n // block_v) * block_v
    if n_pad != n:
        pad = n_pad - n
        flags = jnp.pad(flags, ((0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
        active = jnp.pad(active, (0, pad))

    first, found = pl.pallas_call(
        functools.partial(_scan_kernel, window=window),
        grid=(n_pad // block_v,),
        in_specs=[
            pl.BlockSpec((block_v, window), lambda i: (i, 0)),
            pl.BlockSpec((block_v, window), lambda i: (i, 0)),
            pl.BlockSpec((block_v,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block_v,), lambda i: (i,)),
            pl.BlockSpec((block_v,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad,), jnp.int32),
            jax.ShapeDtypeStruct((n_pad,), jnp.bool_),
        ],
        name="first_live_scan",
        interpret=interpret,
    )(flags, valid, active)
    return first[:n], found[:n]
