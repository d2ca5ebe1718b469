"""Deterministic coverage for the stream engine family (DESIGN.md §9):
delta-CSR overlay bookkeeping, incremental-vs-scratch bit-identity
(including across a compact() boundary), the revival fallback, dispatch
accounting, incremental SCC, and the satellite fixes (from_edges
validation, erdos_renyi simple=True)."""
import numpy as np
import pytest

from repro.core import CSRGraph, DeltaCSR, plan, plan_stream
from repro.core.ref import trim_oracle
from repro.core.scc import (same_partition, scc_decompose,
                            scc_decompose_incremental, tarjan_oracle)
from repro.graphs import generators


def _random_graph(n=40, m=120, seed=0):
    rng = np.random.default_rng(seed)
    return CSRGraph.from_edges(n, rng.integers(0, n, m),
                               rng.integers(0, n, m))


def _scratch_status(engine):
    """The acceptance oracle: a from-scratch TrimEngine.run on the
    materialized graph."""
    return np.asarray(plan(engine.snapshot(), method="ac4").run().status)


def _edges(engine):
    d = engine.delta
    live = ~d._tomb_np
    return d._src_np[live], d._dst_np[live]


# -- bit-identity: retrim() == from-scratch TrimEngine.run -------------------

def test_retrim_matches_scratch_over_deletions():
    g = _random_graph(seed=1)
    engine = plan_stream(g, capacity=16)
    assert np.array_equal(np.asarray(engine.retrim().status),
                          _scratch_status(engine))
    rng = np.random.default_rng(2)
    for _ in range(4):
        src, dst = _edges(engine)
        ids = rng.choice(src.size, 6, replace=False)
        engine.apply(deletions=(src[ids], dst[ids]))
        got = np.asarray(engine.retrim().status)
        want = _scratch_status(engine)
        assert got.dtype == want.dtype == np.int32
        assert np.array_equal(got, want)


def test_retrim_matches_scratch_with_insertions():
    g = _random_graph(seed=3)
    engine = plan_stream(g, capacity=64)
    rng = np.random.default_rng(4)
    n = g.n
    for _ in range(4):
        ins = (rng.integers(0, n, 3), rng.integers(0, n, 3))
        src, dst = _edges(engine)
        ids = rng.choice(src.size, 3, replace=False)
        engine.apply(deletions=(src[ids], dst[ids]), insertions=ins)
        assert np.array_equal(np.asarray(engine.retrim().status),
                              _scratch_status(engine))


def test_retrim_full_resets_to_same_fixpoint():
    g = _random_graph(seed=5)
    engine = plan_stream(g)
    src, dst = _edges(engine)
    engine.apply(deletions=(src[:5], dst[:5]))
    incr = np.asarray(engine.retrim().status)
    full = np.asarray(engine.retrim(full=True).status)
    assert np.array_equal(incr, full)


def test_identity_across_compact_boundary():
    g = _random_graph(n=30, m=90, seed=6)
    # load_factor tiny: the engine compacts after (almost) every batch
    engine = plan_stream(g, capacity=16, load_factor=0.05)
    rng = np.random.default_rng(7)
    for i in range(3):
        src, dst = _edges(engine)
        ids = rng.choice(src.size, 4, replace=False)
        engine.apply(deletions=(src[ids], dst[ids]),
                     insertions=(rng.integers(0, g.n, 2),
                                 rng.integers(0, g.n, 2)))
        assert np.array_equal(np.asarray(engine.retrim().status),
                              _scratch_status(engine))
    assert engine.compactions >= 2
    # after compaction the overlay is empty and the base carries everything
    assert engine.delta.n_tomb == 0 and engine.delta.n_ins == 0


def test_revival_via_dead_source_insertion():
    # chain: everything trims away; inserting a back-edge creates a cycle
    # among dead vertices, which only the from-scratch fallback can revive
    g = generators.chain(10)
    engine = plan_stream(g, capacity=8)
    assert engine.retrim().n_trimmed == 10
    res = engine.apply(insertions=([5], [2]))      # 2->..->5->2 cycle
    assert res.dirty
    status = np.asarray(engine.retrim().status)
    assert np.array_equal(status, _scratch_status(engine))
    # the cycle {2..5} revives, and so does the 0->1 tail feeding into it
    assert status[:6].all() and status.sum() == 6


def test_live_insertions_stay_incremental():
    g = generators.cycle(8)                        # nothing trims
    engine = plan_stream(g, capacity=8)
    res = engine.apply(insertions=([0], [4]))      # live -> live
    assert not res.dirty
    assert np.array_equal(np.asarray(engine.retrim().status),
                          _scratch_status(engine))


def test_empty_base_with_insertions():
    # base has no edges (everything dead); a batch inserting a 2-cycle
    # must revive exactly that pair
    g = CSRGraph.from_edges(4, np.zeros(0, np.int64), np.zeros(0, np.int64))
    engine = plan_stream(g, capacity=8)
    res = engine.apply(insertions=([1, 2], [2, 1]))
    assert res.dirty
    status = np.asarray(engine.retrim().status).astype(bool)
    assert (status == np.array([False, True, True, False])).all()
    assert np.array_equal(np.asarray(engine.retrim().status),
                          _scratch_status(engine))


# -- overlay bookkeeping -----------------------------------------------------

def test_delete_missing_edge_raises_and_rolls_back():
    g = CSRGraph.from_edges(4, [0, 1, 2], [1, 2, 3])
    engine = plan_stream(g, capacity=8)
    with pytest.raises(ValueError, match="not present"):
        engine.apply(deletions=([0, 3], [1, 0]))   # (3, 0) does not exist
    # the batch rolled back atomically: (0, 1) is still deletable
    assert engine.delta.n_tomb == 0
    engine.apply(deletions=([0], [1]))
    assert np.array_equal(np.asarray(engine.retrim().status),
                          _scratch_status(engine))


def test_duplicate_arcs_are_distinct_instances():
    # two copies of (0, 1): deleting twice works, a third raises
    g = CSRGraph.from_edges(3, [0, 0, 1], [1, 1, 2])
    engine = plan_stream(g, capacity=8)
    engine.apply(deletions=([0], [1]))
    engine.apply(deletions=([0], [1]))
    with pytest.raises(ValueError, match="not present"):
        engine.apply(deletions=([0], [1]))
    assert np.array_equal(np.asarray(engine.retrim().status),
                          _scratch_status(engine))


def test_delete_inserted_edge():
    g = generators.cycle(4)
    engine = plan_stream(g, capacity=8)
    engine.apply(insertions=([0], [2]))
    engine.apply(deletions=([0], [2]))             # resolves to the slot
    assert engine.delta.n_tomb == 0
    assert np.array_equal(np.asarray(engine.retrim().status),
                          _scratch_status(engine))


def test_insert_buffer_growth():
    g = generators.cycle(8)
    engine = plan_stream(g, capacity=2, load_factor=100.0)  # never compact
    iu = np.zeros(5, np.int64)
    iv = np.full(5, 1, np.int64)
    engine.apply(insertions=(iu, iv))              # 5 > 2: compact + grow
    assert engine.delta.capacity >= 5
    assert engine.snapshot().m == 8 + 5
    assert np.array_equal(np.asarray(engine.retrim().status),
                          _scratch_status(engine))


def test_update_out_of_range_raises():
    engine = plan_stream(generators.cycle(4), capacity=8)
    with pytest.raises(ValueError, match="out of range"):
        engine.apply(insertions=([0], [4]))
    with pytest.raises(ValueError, match="out of range"):
        engine.apply(deletions=([-1], [0]))


def test_failed_batch_applies_nothing():
    # valid deletions + an out-of-range insertion: the whole batch must
    # be rejected without committing the deletions (host and device views
    # would otherwise diverge and break the bit-identity oracle)
    engine = plan_stream(generators.cycle(4), capacity=8)
    with pytest.raises(ValueError, match="out of range"):
        engine.apply(deletions=([0], [1]), insertions=([99], [0]))
    assert engine.delta.n_tomb == 0 and engine.delta.n_ins == 0
    assert engine.snapshot().m == 4
    engine.apply(deletions=([0], [1]))         # still deletable
    assert np.array_equal(np.asarray(engine.retrim().status),
                          _scratch_status(engine))


def test_host_device_overlay_never_diverge():
    g = _random_graph(n=20, m=60, seed=8)
    engine = plan_stream(g, capacity=16)
    rng = np.random.default_rng(9)
    for _ in range(3):
        src, dst = _edges(engine)
        ids = rng.choice(src.size, 3, replace=False)
        engine.apply(deletions=(src[ids], dst[ids]),
                     insertions=(rng.integers(0, g.n, 2),
                                 rng.integers(0, g.n, 2)))
        d = engine.delta
        assert np.array_equal(np.asarray(d.tomb), d._tomb_np)
        assert np.array_equal(np.asarray(d.ins_alive), d._ins_alive_np)
        assert np.array_equal(np.asarray(d.ins_src)[d._ins_alive_np],
                              d._ins_src_np[d._ins_alive_np])


# -- engine contracts --------------------------------------------------------

def test_stream_dispatch_accounting():
    g = _random_graph(seed=10)
    engine = plan_stream(g)
    base = engine.dispatches                       # plan-time init = 1
    assert base == 1 and engine.transpose_builds == 1
    src, dst = _edges(engine)
    engine.apply(deletions=(src[:2], dst[:2]))
    assert engine.dispatches == base + 1
    engine.retrim()                                # fixpoint read: free
    assert engine.dispatches == base + 1
    engine.retrim(full=True)
    assert engine.dispatches == base + 2


def test_apply_same_batch_shape_never_retraces():
    g = _random_graph(seed=11)
    engine = plan_stream(g)
    src, dst = _edges(engine)
    engine.apply(deletions=(src[:4], dst[:4]))
    traces = engine.traces
    src, dst = _edges(engine)
    engine.apply(deletions=(src[:4], dst[:4]))     # same pow2 width
    engine.apply(deletions=(src[10:13], dst[10:13]))  # 3 pads to 4
    assert engine.traces == traces


# -- the batch-sized sparse tier ---------------------------------------------

def test_batch_tier_sizes():
    from repro.core.common import FrontierPlan, frontier_plan
    from repro.core.stream import _batch_tier
    n, m = 1 << 22, 1 << 26            # kron-s22: 1024 deletions + insert pad
    plan22 = frontier_plan("auto", n, m)
    assert plan22 == FrontierPlan("auto", 65536, 8388608)
    assert _batch_tier(plan22, 1025, n, m) == FrontierPlan("auto", 2048,
                                                           131072)
    assert _batch_tier(plan22, 2, n, m) == FrontierPlan("auto", 128, 8192)
    # a plan no larger than the batch's tier keeps its one tier
    small = frontier_plan("auto", 1024, 16384)
    assert _batch_tier(small, 1025, 1024, 16384) is None


def _fan_graph(fans, leaves, fan_in, seed=5):
    """``generators.rmat(14, 1 << 17)`` plus ``fans`` fans on new vertices.
    Fan j's hub has one arc into G's live core and ``leaves`` in-arcs, one
    out of each of its leaves; each leaf is fed by ``fan_in`` arcs out of
    live core vertices.  Deleting the hub's arc kills the hub (round 1),
    then its leaves (round 2), whose Gᵀ rows hold ``fan_in`` arcs each.
    Returns the graph, and per fan its hub's arc and its leaves' in-arcs,
    each as a (2, k) array of sources over targets."""
    g = generators.rmat(14, 1 << 17, seed=seed)
    indptr, indices = g.to_numpy()
    core = np.flatnonzero(trim_oracle(indptr, indices))
    rng = np.random.default_rng(seed)
    arcs = [np.stack([np.repeat(np.arange(g.n), np.diff(indptr)), indices])]
    hub_arcs, leaf_in = [], []
    for j in range(fans):
        hub = g.n + j * (leaves + 1)
        leaf = hub + 1 + np.arange(leaves)
        hub_arcs.append(np.array([[hub], [rng.choice(core)]]))
        leaf_in.append(np.stack([rng.choice(core, leaves * fan_in),
                                 np.repeat(leaf, fan_in)]))
        arcs += [hub_arcs[-1], np.stack([leaf, np.full(leaves, hub)]),
                 leaf_in[-1]]
    arcs = np.concatenate(arcs, axis=1)
    return (CSRGraph.from_edges(g.n + fans * (leaves + 1), *arcs),
            hub_arcs, leaf_in)


def _assert_counters_live_out(engine):
    """A live vertex's AC-4 counter is its number of live successors."""
    indptr, indices = engine.snapshot().to_numpy()
    live = trim_oracle(indptr, indices)
    src = np.repeat(np.arange(live.size), np.diff(indptr))
    live_out = np.bincount(src[live[indices]], minlength=live.size)
    counters = np.asarray(engine._state[1])
    assert np.array_equal(counters[live], live_out[live])


# tier: the tier a cascade's second round takes, from the batch's width and
# the fans' sizes (the first round, the hub alone, takes the batch tier)
@pytest.mark.parametrize("tier,batch,leaves,fan_in", [
    ("batch", 64, 32, 8), ("batch", 512, 64, 16),
    ("plan", 64, 256, 4), ("dense", 64, 600, 2)])
def test_batch_tier_stream_matches_dense_and_oracle(tier, batch, leaves,
                                                    fan_in):
    """An ``auto`` plan with both sparse tiers equals the dense-frontier
    engine (status, counters, rounds) and the oracle in every apply, and
    takes the case's tier.  Apply j kills fan j and deletes arcs into the
    leaves of fans j and j + 1: the leaves' Gᵀ rows, which the second
    round expands, hold tombstones of this batch and of the one before,
    so a tombstone read at the wrong position shows in the counters."""
    from repro.core.stream import _batch_tier
    fans = 3
    g, hub_arcs, leaf_in = _fan_graph(fans, leaves, fan_in)
    auto = plan_stream(g, frontier="auto", instrument=True)
    dense = plan_stream(g, frontier="dense")
    tiers = [_batch_tier(auto.fplan, batch + 1, g.n, g.m), auto.fplan]
    assert tiers[0] is not None

    def tier_of(count, tedges):
        return sum((count > p.cap) | (tedges > p.ecap) for p in tiers)

    want = tier_of(leaves, leaves * fan_in)
    assert want == ["batch", "plan", "dense"].index(tier)
    assert tier_of(1, leaves) == 0
    assert all(f.shape[1] >= batch for f in leaf_in)
    half = (batch - 1) // 2
    for j in range(fans):
        # the next fan's leaves die in the next apply, over these tombstones
        ahead = leaf_in[(j + 1) % fans]
        dels = tuple(np.concatenate([hub_arcs[j], leaf_in[j][:, :half],
                                     ahead[:, half + 1 - batch:]], axis=1))
        a = auto.apply(deletions=dels)
        d = dense.apply(deletions=dels)
        assert a.rounds == d.rounds == 2
        assert np.array_equal(np.asarray(a.status), np.asarray(d.status))
        assert np.array_equal(np.asarray(auto._state[1]),
                              np.asarray(dense._state[1]))
        assert np.array_equal(np.asarray(a.status),
                              trim_oracle(*auto.snapshot().to_numpy()))
        _assert_counters_live_out(auto)
        stats = a.round_stats
        assert list(stats.per_round("r_frontier")[:2]) == [1, leaves]
        assert list(stats.per_round("r_sparse")[:2]) == [1, int(want < 2)]


def test_revival_after_deletions_matches_dense_and_oracle():
    """A mixed batch whose insertion revives a dead hub rebuilds the
    fixpoint from scratch inside the apply, over the tombstones of the
    batch before; it equals the dense-frontier engine and the oracle, and
    a ``full=True`` retrim reads the same state."""
    g, hub_arcs, leaf_in = _fan_graph(1, 32, 8)
    auto = plan_stream(g, frontier="auto")
    dense = plan_stream(g, frontier="dense")
    kill = tuple(np.concatenate([hub_arcs[0], leaf_in[0][:, :15]], axis=1))
    revive = dict(deletions=tuple(leaf_in[0][:, -4:]),
                  insertions=tuple(hub_arcs[0]))
    for batch in (dict(deletions=kill), revive):
        a, d = auto.apply(**batch), dense.apply(**batch)
        assert a.dirty == d.dirty == ("insertions" in batch)
        assert a.rounds == d.rounds
        assert np.array_equal(np.asarray(a.status), np.asarray(d.status))
        assert np.array_equal(np.asarray(auto._state[1]),
                              np.asarray(dense._state[1]))
        assert np.array_equal(np.asarray(a.status),
                              trim_oracle(*auto.snapshot().to_numpy()))
        _assert_counters_live_out(auto)
    status = np.asarray(a.status)
    assert status[hub_arcs[0][0, 0]:].all()   # the hub and its leaves
    counters = np.asarray(auto._state[1])
    full = auto.retrim(full=True)
    assert np.array_equal(np.asarray(full.status).astype(bool), status)
    assert np.array_equal(np.asarray(auto._state[1]), counters)
    assert full.rounds == a.rounds


def _m_gathers(jaxpr, m, loops):
    """The gathers of all of an ``m``-element array into ``m`` elements
    (the tombstones into Gᵀ order) in ``jaxpr``; inside ``while`` and
    ``cond`` bodies too only if ``loops``."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            count += (eqn.invars[0].aval.size == eqn.outvars[0].aval.size
                      == m)
        if eqn.primitive.name in ("while", "cond") and not loops:
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    count += _m_gathers(sub, m, loops)
    return count


@pytest.mark.parametrize("frontier,full,revivable,top,anywhere", [
    ("auto", False, False, 0, 1),       # a batch: only the dense round's
    ("auto", False, True, 0, 2),        # ... and the revival rebuild's
    ("dense", False, False, 1, 1),      # every round dense: once a step
    ("auto", True, False, 1, 1)])       # a rebuild reads all of it first
def test_stream_step_gathers_tombstones_where_read(frontier, full, revivable,
                                                   top, anywhere):
    """The m-wide gather of the tombstones into Gᵀ order runs outside the
    fixpoint loop only where every round reads the whole mask; a batch's
    sparse rounds read it through the permutation at their positions."""
    import jax
    from repro.core.common import frontier_plan
    from repro.core.stream import _stream_runner
    g = _random_graph(n=40, m=120, seed=15)
    engine = plan_stream(g, frontier=frontier)
    fn = _stream_runner("ac4", False, full=full, revivable=revivable,
                        fplan=frontier_plan(frontier, g.n, g.m))
    updates = engine._padded_updates(*(np.zeros(0, np.int64),) * 7)
    jaxpr = jax.make_jaxpr(fn)(engine._transpose_arrays(),
                               engine._overlay_arrays(), engine._state,
                               updates).jaxpr
    assert _m_gathers(jaxpr, g.m, loops=False) == top
    assert _m_gathers(jaxpr, g.m, loops=True) == anywhere


def test_plan_stream_rejects_unknown_configs():
    g = generators.cycle(4)
    with pytest.raises(ValueError, match="unknown method"):
        plan_stream(g, method="ac9000")
    with pytest.raises(ValueError, match="unknown backend"):
        plan_stream(g, backend="sharded")


def test_delta_csr_standalone():
    g = _random_graph(n=10, m=30, seed=12)
    d = DeltaCSR(g, capacity=4)
    assert d.m_live == 30 and not d.needs_compact
    src, dst = d._src_np.copy(), d._dst_np.copy()
    d.resolve_deletions(src[:2], dst[:2])
    assert d.m_live == 28 and d.n_tomb == 2
    snap = d.materialize()
    assert snap.m == 28
    d.compact()
    assert d.m_base == 28 and d.n_tomb == 0
    engine = plan_stream(d)                        # adopt a pre-built overlay
    assert np.array_equal(
        np.asarray(engine.retrim().status).astype(bool),
        trim_oracle(*snap.to_numpy()))
    # a pre-built overlay carries its own sizing: conflicting kwargs raise
    with pytest.raises(ValueError, match="fixed by the DeltaCSR"):
        plan_stream(d, capacity=64)


# -- incremental SCC ---------------------------------------------------------

def test_scc_incremental_split_and_merge():
    # two 3-cycles joined by a bridge
    src = [0, 1, 2, 3, 4, 5, 0]
    dst = [1, 2, 0, 4, 5, 3, 3]
    g = CSRGraph.from_edges(6, src, dst)
    labels, _ = scc_decompose(g, window=4)
    assert same_partition(labels, tarjan_oracle(*g.to_numpy()))

    # split: delete an edge of the first cycle
    g1 = CSRGraph.from_edges(6, src[1:], dst[1:])
    l1, st1 = scc_decompose_incremental(g1, labels,
                                        deletions=([0], [1]), window=4)
    assert same_partition(l1, tarjan_oracle(*g1.to_numpy()))
    assert st1["dirty_vertices"] == 3              # only the split cycle

    # merge: a back-edge 3 -> 0 closes a big cycle through the bridge
    g2 = CSRGraph.from_edges(6, src + [3], dst + [0])
    l2, st2 = scc_decompose_incremental(g2, labels,
                                        insertions=([3], [0]), window=4)
    assert same_partition(l2, tarjan_oracle(*g2.to_numpy()))
    assert st2["reach_dispatches"] == 2            # one FW + one BW batch

    # cross-component deletion: nothing dirtied, labels reused verbatim
    g3 = CSRGraph.from_edges(6, src[:-1], dst[:-1])
    l3, st3 = scc_decompose_incremental(g3, labels,
                                        deletions=([0], [3]), window=4)
    assert st3["dirty_vertices"] == 0
    assert np.array_equal(l3, np.asarray(labels))


def test_scc_incremental_random_batches():
    rng = np.random.default_rng(13)
    n, m = 25, 70
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    g = CSRGraph.from_edges(n, src, dst)
    labels, _ = scc_decompose(g, window=4)
    for _ in range(3):
        ids = rng.choice(src.size, 4, replace=False)
        keep = np.ones(src.size, bool)
        keep[ids] = False
        iu, iv = rng.integers(0, n, 2), rng.integers(0, n, 2)
        nsrc = np.concatenate([src[keep], iu])
        ndst = np.concatenate([dst[keep], iv])
        g2 = CSRGraph.from_edges(n, nsrc, ndst)
        labels, _ = scc_decompose_incremental(
            g2, labels, deletions=(src[ids], dst[ids]),
            insertions=(iu, iv), window=4)
        assert same_partition(labels, tarjan_oracle(*g2.to_numpy()))
        src, dst = nsrc, ndst


def test_scc_decompose_active_mask():
    g = _random_graph(n=20, m=50, seed=14)
    active = np.zeros(20, bool)
    active[:10] = True
    labels, _ = scc_decompose(g, active=active, window=4)
    assert (labels[10:] == -1).all() and (labels[:10] >= 0).all()


# -- satellite fixes ---------------------------------------------------------

def test_from_edges_rejects_out_of_range():
    with pytest.raises(ValueError, match="2 edge endpoint"):
        CSRGraph.from_edges(4, [0, 5, 1], [1, 2, -1])
    with pytest.raises(ValueError, match="length mismatch"):
        CSRGraph.from_edges(4, [0, 1], [1])


def test_erdos_renyi_simple():
    g = generators.erdos_renyi(100, 600, seed=3, simple=True)
    indptr, indices = g.to_numpy()
    src = np.repeat(np.arange(100), np.diff(indptr))
    assert (src != indices).all()                  # no self-loops
    keys = src * 100 + indices
    assert np.unique(keys).size == keys.size       # no duplicate arcs
    # the default path is untouched (historical baselines preserved)
    g_default = generators.erdos_renyi(100, 600, seed=3)
    assert g_default.m == 600
