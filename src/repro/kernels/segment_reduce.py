"""Segment-sum scatter as one-hot × MXU matmul — the TPU-native bulk
"fetch-and-add" (paper §2.5 adaptation, DESIGN.md §2).

``out[seg_ids[e]] += values[e]`` has no TPU atomic; instead each
(edge-block × vertex-block) grid cell builds the one-hot matrix
``onehot[e, v] = (seg_ids[e] == v)`` in VREGs and feeds the MXU:

    out_block += onehotᵀ @ values_block        # (bn, be) @ (be, d)

This one kernel serves three substrates: GNN message aggregation,
EmbeddingBag reduction (recsys), and AC-4's frontier counter decrements.

Block sizes are MXU-aligned (multiples of 128 lanes / 8 sublanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_E = 512
DEFAULT_BLOCK_N = 512


def _segsum_kernel(vals_ref, ids_ref, o_ref, *, block_n: int):
    ni = pl.program_id(0)
    ei = pl.program_id(1)

    @pl.when(ei == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    vals = vals_ref[...].astype(jnp.float32)        # (block_e, d)
    ids = ids_ref[...]                              # (block_e,)
    local = ids - ni * block_n                      # position in this n-block
    onehot = (local[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (ids.shape[0], block_n), 1)).astype(jnp.float32)
    o_ref[...] += jax.lax.dot_general(
        onehot, vals, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "num_segments", "block_e", "block_n", "interpret"))
def segment_sum_pallas(values, seg_ids, num_segments: int,
                       block_e: int = DEFAULT_BLOCK_E,
                       block_n: int = DEFAULT_BLOCK_N,
                       interpret: bool = True):
    """values: (m, d) float; seg_ids: (m,) int32 in [0, num_segments).

    Returns (num_segments, d) float32 segment sums.
    Out-of-range ids (e.g. padding = num_segments) are dropped naturally
    (their one-hot row is all zeros).
    """
    m, d = values.shape
    block_e = min(block_e, m)
    # pad m to a block multiple with out-of-range ids
    m_pad = -(-m // block_e) * block_e
    if m_pad != m:
        values = jnp.pad(values, ((0, m_pad - m), (0, 0)))
        seg_ids = jnp.pad(seg_ids, (0, m_pad - m),
                          constant_values=num_segments)
    block_n = min(block_n, num_segments)
    n_pad = -(-num_segments // block_n) * block_n
    ne, nn = m_pad // block_e, n_pad // block_n

    out = pl.pallas_call(
        functools.partial(_segsum_kernel, block_n=block_n),
        grid=(nn, ne),
        in_specs=[
            pl.BlockSpec((block_e, d), lambda ni, ei: (ei, 0)),
            pl.BlockSpec((block_e,), lambda ni, ei: (ei,)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda ni, ei: (ni, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        name="segment_reduce",
        interpret=interpret,
    )(values, seg_ids.astype(jnp.int32))
    return out[:num_segments]
