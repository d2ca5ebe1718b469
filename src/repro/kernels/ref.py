"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def counter_scatter_ref(counters, status, upd_src, upd_delta):
    """Segment-sum twin of ``kernels.counter_scatter``: scatter-add the
    update deltas into the support counters and report newly-dead
    vertices.  Out-of-range sources (the pow2-padding sentinel) are
    dropped, matching the kernel."""
    n = counters.shape[0]
    if n == 0:
        return counters, jnp.zeros((0,), jnp.bool_)
    ok = (upd_src >= 0) & (upd_src < n)
    ids = jnp.where(ok, upd_src, 0)
    delta = jnp.where(ok, upd_delta, 0)
    new = counters + jax.ops.segment_sum(delta.astype(counters.dtype), ids,
                                         num_segments=n)
    return new, status & (new <= 0)


def bucket_peel_ref(counters, alive, k):
    """Bucket extraction — the jnp twin of ``kernels.bucket_peel``: alive
    vertices whose support counter sits at or below the bucket level."""
    return alive & (counters <= jnp.asarray(k, counters.dtype))


def frontier_expand_ref(flags, valid, pending):
    """Row-wise masked OR — the jnp twin of ``kernels.frontier_expand``."""
    return pending & jnp.any(flags & valid, axis=1)


def frontier_compact_ref(mask, capacity: int):
    """Compaction twin of ``kernels.frontier_compact``: the True positions
    of ``mask`` packed into a (capacity,) int32 buffer (sentinel ``n`` in
    unused slots; members past ``capacity`` dropped) plus the count."""
    n = mask.shape[0]
    if n == 0:
        return jnp.full((capacity,), 0, jnp.int32), jnp.zeros((), jnp.int32)
    m32 = mask.astype(jnp.int32)
    csum = jnp.cumsum(m32)
    # rank search instead of position scatter: ids[j] = index of the
    # (j+1)-th member (searchsorted returns n past the last member — the
    # sentinel — and XLA CPU lowers it as a vectorized binary search,
    # ~8x cheaper than an n-update scatter)
    q = jnp.arange(1, capacity + 1, dtype=csum.dtype)
    ids = jnp.searchsorted(csum, q, side="left").astype(jnp.int32)
    return ids, csum[-1]


def sparse_expand_ref(indptr, indices, ids, ecap: int):
    """Expansion twin of ``kernels.sparse_expand``: CSR rows of the
    compacted ``ids`` gathered into a static (ecap,) edge buffer.  Row
    ownership is a rank search over the inclusive degree cumsum —
    ``side='right'`` lands each edge on the first row whose cumsum
    exceeds it, which skips zero-degree rows, exactly the Pallas twin's
    boundary-marker scan — avoiding the ecap-update marker scatter."""
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
    csum = jnp.cumsum(deg)
    excl = csum - deg
    total = csum[-1] if C else jnp.zeros((), jnp.int32)

    e = jnp.arange(ecap, dtype=jnp.int32)
    owner = jnp.clip(jnp.searchsorted(csum, e, side="right"),
                     0, max(C - 1, 0)).astype(jnp.int32)
    valid = e < total
    src = jnp.where(ok[owner], ids[owner], 0)
    pos = jnp.clip(row_base[owner] + (e - excl[owner]), 0, max(m - 1, 0))
    tgt = indices[pos]
    return src, tgt, pos, valid


def first_live_ref(flags, valid, active):
    n, window = flags.shape
    f = flags & valid
    offs = jnp.arange(window, dtype=jnp.int32)[None, :]
    first = jnp.min(jnp.where(f, offs, window), axis=1)
    first = jnp.where(active, first, window)
    found = active & (first < window)
    return first.astype(jnp.int32), found
