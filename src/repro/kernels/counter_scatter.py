"""Counter maintenance — AC-4 support-counter updates as a Pallas kernel.

One update batch of the streaming engine (``core.stream``, DESIGN.md §9)
adjusts the live-out-degree counters of the sources touched by a (B,)-batch
of edge updates and reports which live vertices just lost their last
support:

    new[v]  = counters[v] + sum over b of (delta[b] where src[b] == v)
    dead[v] = status[v] & (new[v] <= 0)

``out[src[b]] += delta[b]`` has no TPU atomic, so each (vertex-block ×
update-block) grid cell builds the membership matrix
``hit[b, v] = (src[b] == v)`` in VREGs and reduces it with an integer
masked sum (counters are int32-exact), with *block-level update
skipping*: vertex blocks that no update touches keep their counters
verbatim (``@pl.when``), so a small delta batch costs one pass over the
counter array and nothing else.

Layout: lanes = vertices within a block (×128), update batch on sublanes
(a (B, 1) column per operand).
Out-of-range sources (the engine's pow2-padding sentinel ``src = n``) fall
in no vertex block and contribute nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiling import vertex_block

DEFAULT_BLOCK_V = 1024
DEFAULT_BLOCK_U = 256


def _counter_kernel(counters_ref, status_ref, src_ref, delta_ref,
                    out_ref, dead_ref, *, block_v: int):
    vi = pl.program_id(0)
    ui = pl.program_id(1)
    nu = pl.num_programs(1)

    @pl.when(ui == 0)
    def _seed():
        out_ref[...] = counters_ref[...]

    src = src_ref[...]                               # (block_u, 1) int32
    local = src - vi * block_v
    hit = local == jax.lax.broadcasted_iota(
        jnp.int32, (src.shape[0], block_v), 1)       # (block_u, block_v)
    contrib = jnp.where(hit, delta_ref[...], 0)      # int32, lane-broadcast

    @pl.when(jnp.any(contrib != 0))
    def _accumulate():
        out_ref[...] += jnp.sum(contrib, axis=0).astype(out_ref.dtype)

    @pl.when(ui == nu - 1)
    def _deaths():
        dead_ref[...] = status_ref[...] & (out_ref[...] <= 0)


@functools.partial(jax.jit, static_argnames=("block_v", "block_u",
                                             "interpret"))
def counter_scatter_pallas(counters, status, upd_src, upd_delta,
                           block_v: int = DEFAULT_BLOCK_V,
                           block_u: int = DEFAULT_BLOCK_U,
                           interpret: bool = True):
    """counters: (n,) int32 — live-out-degree support counters.
    status:   (n,) bool — LIVE mask (dead vertices never re-die).
    upd_src:  (B,) int32 — source vertex per update; out-of-range entries
              (the pow2-padding sentinel n) contribute nothing.
    upd_delta:(B,) int32 — counter adjustment per update (+1 insert of a
              live arc, -1 delete, 0 no-op).

    The update batch enters as (B, 1) int32 columns, so the hit matrix is
    built by broadcasting along lanes — no vector reshape inside the
    kernel.  ``block_v`` is rounded up to whole vertex tiles
    (``kernels.tiling``) and ``block_u`` to whole 8-row sublane groups.

    Returns ``(new_counters, newly_dead)``: (n,) int32 and (n,) bool.
    """
    n = counters.shape[0]
    b = upd_src.shape[0]
    if n == 0:
        return counters, jnp.zeros((0,), jnp.bool_)
    if b == 0:
        return counters, status & (counters <= 0)
    block_v = vertex_block(block_v, n)
    block_u = min(-(-max(block_u, 1) // 8) * 8, b)
    n_pad = -(-n // block_v) * block_v
    b_pad = -(-b // block_u) * block_u
    if n_pad != n:
        counters = jnp.pad(counters, (0, n_pad - n))
        status = jnp.pad(status, (0, n_pad - n))
    upd_src = upd_src.astype(jnp.int32)
    upd_delta = upd_delta.astype(jnp.int32)
    if b_pad != b:
        # pad sources beyond every vertex block so they never hit
        upd_src = jnp.pad(upd_src, (0, b_pad - b), constant_values=n_pad)
        upd_delta = jnp.pad(upd_delta, (0, b_pad - b))

    out, dead = pl.pallas_call(
        functools.partial(_counter_kernel, block_v=block_v),
        grid=(n_pad // block_v, b_pad // block_u),
        in_specs=[
            pl.BlockSpec((block_v,), lambda vi, ui: (vi,)),
            pl.BlockSpec((block_v,), lambda vi, ui: (vi,)),
            pl.BlockSpec((block_u, 1), lambda vi, ui: (ui, 0)),
            pl.BlockSpec((block_u, 1), lambda vi, ui: (ui, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_v,), lambda vi, ui: (vi,)),
            pl.BlockSpec((block_v,), lambda vi, ui: (vi,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad,), counters.dtype),
            jax.ShapeDtypeStruct((n_pad,), jnp.bool_),
        ],
        name="counter_scatter",
        interpret=interpret,
    )(counters, status, upd_src[:, None], upd_delta[:, None])
    return out[:n], dead[:n]
