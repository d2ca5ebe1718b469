"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (the TPU target contract)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bucket_peel import bucket_peel_pallas
from repro.kernels.counter_scatter import counter_scatter_pallas
from repro.kernels.first_live_scan import first_live_scan
from repro.kernels.frontier_compact import (frontier_compact_pallas,
                                            prefix_positions,
                                            sparse_expand_pallas)
from repro.kernels.frontier_expand import frontier_expand

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("n,W,bv", [(333, 16, 128), (64, 8, 64),
                                    (1024, 32, 256), (3000, 16, 1024)])
def test_first_live_scan(n, W, bv):
    flags = jnp.asarray(RNG.random((n, W)) < 0.3)
    valid = jnp.asarray(RNG.random((n, W)) < 0.8)
    active = jnp.asarray(RNG.random(n) < 0.5)
    f1, d1 = first_live_scan(flags, valid, active, block_v=bv,
                             interpret=True)
    f2, d2 = ref.first_live_ref(flags, valid, active)
    assert (f1 == f2).all() and (d1 == d2).all()


@pytest.mark.parametrize("n,b,bv,bu", [
    (333, 16, 128, 8),
    (64, 4, 64, 4),
    (1024, 256, 256, 64),
    (7, 3, 512, 256),      # smaller than one block
    (50, 1, 512, 256),     # single update
    (3000, 300, 1024, 64),  # 3 x 5 grid, both axes padded
])
def test_counter_scatter(n, b, bv, bu):
    counters = jnp.asarray(RNG.integers(0, 5, n), jnp.int32)
    status = jnp.asarray(RNG.random(n) < 0.7)
    # sources include the out-of-range padding sentinel n (dropped)
    src = jnp.asarray(RNG.integers(0, n + 1, b), jnp.int32)
    delta = jnp.asarray(RNG.integers(-2, 3, b), jnp.int32)
    got_c, got_d = counter_scatter_pallas(counters, status, src, delta,
                                          block_v=bv, block_u=bu,
                                          interpret=True)
    want_c, want_d = ref.counter_scatter_ref(counters, status, src, delta)
    assert got_c.dtype == want_c.dtype == jnp.int32
    assert got_d.dtype == want_d.dtype == jnp.bool_
    assert (got_c == want_c).all() and (got_d == want_d).all()
    # block skipping: an all-zero delta batch keeps counters verbatim and
    # kills nothing new beyond counters already <= 0
    same_c, same_d = counter_scatter_pallas(counters, status, src,
                                            jnp.zeros_like(delta),
                                            block_v=bv, block_u=bu,
                                            interpret=True)
    assert (same_c == counters).all()
    assert (same_d == (status & (counters <= 0))).all()


@pytest.mark.parametrize("n,b,bv,bu", [(64, 32, 64, 8), (333, 64, 128, 16)])
def test_counter_scatter_duplicate_sources(n, b, bv, bu):
    """B updates landing on the SAME vertex in one batch must all
    accumulate (the membership-matrix reduction sums every hit row, not
    just one) — on top of a background of mixed random updates."""
    counters = jnp.asarray(RNG.integers(1, 6, n), jnp.int32)
    status = jnp.ones(n, bool)
    hot = int(RNG.integers(0, n))
    # half the batch hits `hot`, the rest is random (duplicates likely)
    src = np.where(np.arange(b) % 2 == 0, hot, RNG.integers(0, n, b))
    delta = RNG.integers(-2, 3, b)
    got_c, got_d = counter_scatter_pallas(
        jnp.asarray(counters), status, jnp.asarray(src, jnp.int32),
        jnp.asarray(delta, jnp.int32), block_v=bv, block_u=bu,
        interpret=True)
    # independent numpy oracle (not the jnp ref twin)
    want = np.asarray(counters).copy()
    np.add.at(want, src, delta)
    assert np.array_equal(np.asarray(got_c), want)
    assert np.array_equal(np.asarray(got_d), want <= 0)
    # all-duplicates batch: every entry adjusts one vertex
    src1 = jnp.full((b,), hot, jnp.int32)
    delta1 = jnp.asarray(RNG.integers(-2, 3, b), jnp.int32)
    one_c, _ = counter_scatter_pallas(jnp.asarray(counters), status, src1,
                                      delta1, block_v=bv, block_u=bu,
                                      interpret=True)
    want1 = np.asarray(counters).copy()
    want1[hot] += int(np.asarray(delta1).sum())
    assert np.array_equal(np.asarray(one_c), want1)


@pytest.mark.parametrize("n,bv", [(333, 128), (64, 64), (1024, 256),
                                  (7, 512), (513, 512), (2500, 1024)])
def test_bucket_peel(n, bv):
    counters = jnp.asarray(RNG.integers(-2, 8, n), jnp.int32)
    alive = jnp.asarray(RNG.random(n) < 0.6)
    for k in (0, 1, 3, 7):
        got = bucket_peel_pallas(counters, alive, jnp.int32(k), block_v=bv,
                                 interpret=True)
        want = ref.bucket_peel_ref(counters, alive, k)
        assert got.dtype == want.dtype == jnp.bool_
        assert (got == want).all()
    # block skipping: an all-dead bucket (no alive vertex) is all-False
    none = bucket_peel_pallas(counters, jnp.zeros(n, bool), jnp.int32(5),
                              block_v=bv, interpret=True)
    assert not bool(none.any())


def test_bucket_peel_empty():
    got = bucket_peel_pallas(jnp.zeros((0,), jnp.int32),
                             jnp.zeros((0,), bool), jnp.int32(0),
                             interpret=True)
    assert got.shape == (0,) and got.dtype == jnp.bool_
    want = ref.bucket_peel_ref(jnp.zeros((0,), jnp.int32),
                               jnp.zeros((0,), bool), 0)
    assert want.shape == (0,)


@pytest.mark.parametrize("n,W,bv", [(333, 16, 128), (64, 8, 64),
                                    (1024, 32, 256), (7, 4, 256),
                                    (3000, 16, 1024)])
def test_frontier_expand(n, W, bv):
    flags = jnp.asarray(RNG.random((n, W)) < 0.2)
    valid = jnp.asarray(RNG.random((n, W)) < 0.8)
    pending = jnp.asarray(RNG.random(n) < 0.5)
    got = frontier_expand(flags, valid, pending, block_v=bv, interpret=True)
    want = ref.frontier_expand_ref(flags, valid, pending)
    assert got.dtype == want.dtype == jnp.bool_
    assert (got == want).all()
    # block skipping: a fully non-pending input produces all-False
    none = frontier_expand(flags, valid, jnp.zeros(n, bool), block_v=bv,
                           interpret=True)
    assert not bool(none.any())


# -- frontier compaction (the sparse-frontier substrate, DESIGN.md §12) ------

def _compact_oracle(mask, capacity):
    n = len(mask)
    members = np.flatnonzero(mask).astype(np.int32)
    ids = np.full(capacity, n, np.int32)
    kept = members[:capacity]
    ids[: len(kept)] = kept
    return ids, np.int32(len(members))


@pytest.mark.parametrize("n,cap,block", [(0, 8, 512), (1, 1, 512),
                                         (333, 64, 64), (1024, 1024, 512),
                                         (700, 16, 128), (5000, 4096, 1024)])
@pytest.mark.parametrize("fill", ["none", "some", "all"])
def test_frontier_compact(n, cap, block, fill):
    """Pallas scan vs jnp ref vs numpy oracle — including the all-dead
    (empty) and full-frontier masks, and capacity overflow (n=700,cap=16
    with fill="all": overflow members drop, callers gate on count)."""
    mask = {"none": np.zeros(n, bool), "all": np.ones(n, bool),
            "some": RNG.random(n) < 0.3}[fill]
    mask = jnp.asarray(mask)
    want_ids, want_cnt = _compact_oracle(np.asarray(mask), cap)
    for got_ids, got_cnt in (
            frontier_compact_pallas(mask, cap, block=block, interpret=True),
            ref.frontier_compact_ref(mask, cap)):
        assert np.array_equal(np.asarray(got_ids), want_ids), (n, cap, fill)
        assert int(got_cnt) == int(want_cnt)


@pytest.mark.parametrize("n,m,cap,ecap", [(0, 0, 8, 16), (5, 0, 8, 16),
                                          (64, 256, 16, 512),
                                          (333, 1000, 64, 2048),
                                          (2000, 12000, 512, 16384)])
def test_sparse_expand(n, m, cap, ecap):
    """Expansion of compacted CSR rows vs a numpy oracle, zero-degree rows
    and the degenerate n=0/m=0 shapes included."""
    src = RNG.integers(0, max(n, 1), m)
    dst = RNG.integers(0, max(n, 1), m)
    order = np.argsort(src, kind="stable")
    indptr = jnp.asarray(np.searchsorted(src[order], np.arange(n + 1)),
                         jnp.int32)
    indices = jnp.asarray(dst[order], jnp.int32)
    mask = RNG.random(n) < 0.2 if n else np.zeros(0, bool)
    ids = jnp.asarray(_compact_oracle(mask, cap)[0])

    ip = np.asarray(indptr)
    w_src, w_tgt, w_pos = [], [], []
    for v in np.flatnonzero(mask)[:cap]:
        for p in range(ip[v], ip[v + 1]):
            w_src.append(v), w_tgt.append(dst[order][p]), w_pos.append(p)
    total = len(w_src)

    for fn in (lambda: sparse_expand_pallas(indptr, indices, ids, ecap,
                                            interpret=True),
               lambda: ref.sparse_expand_ref(indptr, indices, ids, ecap)):
        s, t, p, valid = map(np.asarray, fn())
        assert valid.sum() == min(total, ecap)
        assert np.array_equal(s[:total][valid[:total]],
                              np.asarray(w_src)[valid[:total]])
        assert np.array_equal(t[:total][valid[:total]],
                              np.asarray(w_tgt)[valid[:total]])
        assert np.array_equal(p[:total][valid[:total]],
                              np.asarray(w_pos)[valid[:total]])


@pytest.mark.parametrize("n,block", [(1, 1024), (1023, 1024),
                                     (5000, 1024), (20000, 8192),
                                     (9000, 100)])
def test_prefix_positions(n, block):
    """The shift-and-add block scan + SMEM carry vs numpy, across single,
    multi-block and padded grids (a block request below one (8, 128) tile
    rounds up to one), with prefixes far past 2**24, where a float32
    accumulation would lose int32 exactness (totals stay below 2**31)."""
    x = RNG.integers(0, 1 << 16, n).astype(np.int32)
    pos, total = prefix_positions(jnp.asarray(x), block=block,
                                  interpret=True)
    want = np.concatenate([[0], np.cumsum(x, dtype=np.int64)[:-1]])
    assert np.array_equal(np.asarray(pos), want)
    assert int(total) == int(x.sum(dtype=np.int64))


def test_vertex_block_rounds_to_tiles():
    from repro.kernels.tiling import VERTEX_TILE, vertex_block
    assert vertex_block(256, 1 << 22) == VERTEX_TILE
    assert vertex_block(1500, 1 << 22) == 2 * VERTEX_TILE
    assert vertex_block(4096, 1 << 22) == 4096
    assert vertex_block(1024, 700) == 700          # one block = whole array


def test_frontier_compact_no_retrace():
    """One trace serves every mask shape-alike: all-dead, full, partial
    (the direction switch flips per round — retracing would kill the
    compile-once contract)."""
    traces = 0

    def counted(mask):
        nonlocal traces
        traces += 1
        ids, cnt = ref.frontier_compact_ref(mask, 16)
        s, t, p, v = ref.sparse_expand_ref(
            jnp.arange(65, dtype=jnp.int32), jnp.zeros(64, jnp.int32),
            ids, 64)
        return cnt + v.sum()

    jitted = jax.jit(counted)
    for mask in (np.zeros(64, bool), np.ones(64, bool),
                 RNG.random(64) < 0.5):
        jitted(jnp.asarray(mask)).block_until_ready()
    assert traces == 1
