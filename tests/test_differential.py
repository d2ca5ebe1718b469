"""Cross-engine differential harness: every trimming execution path must
produce the same live mask on the same graph.

One parametrized matrix runs {ac3, ac4, ac4*, ac6} × {dense, windowed,
sharded-unmasked} over adversarial fixtures, and the counter-substrate
engines that *reuse* the trimming fixpoint — ``PeelEngine`` (whose
``k = 1`` run is AC-4 by construction) and ``StreamEngine.retrim()``
(the incrementally-maintained AC-4 state at plan time) — ride in the same
matrix.  Every cell is asserted against the one numpy ``trim_oracle``,
which makes all cells pairwise identical.

The fixtures are the shapes that break trimming code in practice: the
n = 0 graph (degenerate dispatch paths), an edgeless graph (everything is
the zero bucket), a single self-loop (a cycle trimming must never
remove), a long chain (α = n, the AC-3 worst case crossing every block
boundary), a star (one frontier round killing almost everything), two
2-cycles bridged by a dead tail (live SCCs upstream of trimmable mass —
the trim-2 shape), and the benchmark graphs' shapes at small scale: a
Kronecker graph with scrambled labels (duplicate arcs, self-loops, hub
skew) and uniform random arcs of degree 16.
"""
import numpy as np
import pytest

from repro.core import CSRGraph, plan, plan_peel, plan_stream, trim_oracle
from repro.core.reach import plan_reach
from repro.graphs import generators


def _graph(n, src=(), dst=()):
    return CSRGraph.from_edges(n, np.asarray(src, np.int64),
                               np.asarray(dst, np.int64))


def _kron(scale, m, seed=1):
    """R-MAT with Graph500's A/B/C, labels scrambled by a seeded
    permutation (duplicate arcs and self-loops kept)."""
    ip, ix = generators.rmat(scale, m, seed=seed).to_numpy()
    perm = np.random.default_rng(seed).permutation(len(ip) - 1)
    src = perm[np.repeat(np.arange(len(ip) - 1), np.diff(ip))]
    return _graph(len(ip) - 1, src, perm[ix])


FIXTURES = {
    "n0": _graph(0),
    "edgeless": _graph(5),
    "self_loop": _graph(3, [1], [1]),
    "long_chain": _graph(700, np.arange(699), np.arange(1, 700)),
    "star": _graph(9, [0] * 8, np.arange(1, 9)),
    # 0<->1 -> 2<->3 -> 4 -> 5   (two 2-cycles bridged by a dead tail)
    "bridged_2cycles": _graph(6, [0, 1, 1, 2, 3, 3, 4],
                              [1, 0, 2, 3, 2, 4, 5]),
    "kron": _kron(9, 16 * 512),
    "urand": generators.erdos_renyi(512, 16 * 512, seed=1),
}
METHODS = ("ac3", "ac4", "ac4*", "ac6")
BACKENDS = ("dense", "windowed", "sharded")


@pytest.fixture(scope="module")
def oracles():
    return {name: trim_oracle(*g.to_numpy()) for name, g in FIXTURES.items()}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("name", list(FIXTURES))
def test_trim_matrix(name, method, backend, oracles):
    g = FIXTURES[name]
    # sharded AC-4 is maskless-only; this matrix never passes masks, so
    # declare it uniformly (the point is the execution path, not the API)
    engine = plan(g, method=method, backend=backend, unmasked=True)
    got = np.asarray(engine.run().status).astype(bool)
    assert np.array_equal(got, oracles[name]), (name, method, backend)


@pytest.mark.parametrize("k_mode", ["bounded", "full"])
@pytest.mark.parametrize("name", list(FIXTURES))
def test_peel_k1_matches_trim(name, k_mode, oracles):
    """peel(k=1) — and the k_core(1) slice of a full-coreness run — are
    bit-identical to the AC-4 live mask on every fixture."""
    g = FIXTURES[name]
    engine = plan_peel(g)
    res = engine.run(k=1) if k_mode == "bounded" else engine.run()
    got = np.asarray(res.status).astype(bool)
    assert np.array_equal(got, oracles[name]), (name, k_mode)
    want_i32 = np.asarray(plan(g, method="ac4").run().status)
    assert np.array_equal(np.asarray(res.status), want_i32)  # bit-identical


@pytest.mark.parametrize("name", list(FIXTURES))
def test_stream_retrim_matches(name, oracles):
    """The StreamEngine's plan-time fixpoint sits in the same matrix."""
    g = FIXTURES[name]
    got = np.asarray(plan_stream(g).retrim().status).astype(bool)
    assert np.array_equal(got, oracles[name]), name


@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("name", ["self_loop", "long_chain",
                                  "bridged_2cycles"])
def test_masked_cells_agree(name, method):
    """The maskable cells (dense × windowed) also agree on an induced
    subgraph, against the oracle of the materialized subgraph."""
    g = FIXTURES[name]
    rng = np.random.default_rng(3)
    act = rng.random(g.n) < 0.7
    ip, ix = g.to_numpy()
    src = np.repeat(np.arange(g.n), np.diff(ip))
    keep = act[src] & act[ix]
    sub = CSRGraph.from_edges(g.n, src[keep], ix[keep])
    want = trim_oracle(*sub.to_numpy()) & act
    for backend in ("dense", "windowed"):
        got = np.asarray(plan(g, method=method, backend=backend)
                         .run(active=act).status).astype(bool)
        assert np.array_equal(got, want), (name, method, backend)
    got_peel = np.asarray(plan_peel(g).run(k=1, active=act).status)
    assert np.array_equal(got_peel.astype(bool), want), name


# -- the frontier axis: sparse/auto rounds are bit-identical to dense ---------
#
# Every fixpoint engine grew a per-round direction switch (DESIGN.md §12):
# rounds whose frontier fits the compaction capacities run compacted, the
# rest dense.  The contract is bit-identity — same status/masks AND same
# instrumented counters — so the whole frontier-mode axis collapses into
# this one differential block.

def _trim_outputs(g, fr):
    res = plan(g, method="ac6", frontier=fr, instrument=True).run()
    return {"status": np.asarray(res.status),
            "r_frontier": res.round_stats.per_round("r_frontier")}


def _reach_outputs(g, fr):
    out = {}
    for backend in ("dense", "windowed"):
        eng = plan_reach(g, backend=backend, frontier=fr, instrument=True)
        res = eng.run(np.arange(g.n) % 3 == 0)   # multi-seed mask, n=0-safe
        out[backend] = np.asarray(res.mask)
        # r_frontier is exact on both paths; r_edges of a sparse-taken
        # *pull* round is push-charged (DESIGN.md §12), so it is asserted
        # only for the push backend
        out[backend + "/r_frontier"] = res.round_stats.per_round("r_frontier")
        if backend == "dense":
            out[backend + "/r_edges"] = res.round_stats.per_round("r_edges")
    return out


def _peel_outputs(g, fr):
    res = plan_peel(g, frontier=fr, instrument=True).run()
    return {"status": np.asarray(res.status),
            "coreness": np.asarray(res.coreness),
            "r_edges": res.round_stats.per_round("r_edges")}


def _stream_outputs(g, fr):
    eng = plan_stream(g, frontier=fr, instrument=True)
    out = {"retrim": np.asarray(eng.retrim(full=True).status)}
    ip, ix = g.to_numpy()
    if g.m:                                      # one delete + one insert
        src = np.repeat(np.arange(g.n), np.diff(ip))
        res = eng.apply(deletions=([src[0]], [ix[0]]))
        out["status"] = np.asarray(res.status)
        out["rounds"] = np.asarray(res.rounds)
        res = eng.apply(insertions=([src[0]], [ix[0]]))
        out["status2"] = np.asarray(res.status)
    return out


ENGINE_OUTPUTS = {"trim": _trim_outputs, "reach": _reach_outputs,
                  "peel": _peel_outputs, "stream": _stream_outputs}


@pytest.mark.parametrize("engine", list(ENGINE_OUTPUTS))
@pytest.mark.parametrize("name", list(FIXTURES))
def test_frontier_modes_bit_identical(name, engine):
    g = FIXTURES[name]
    fn = ENGINE_OUTPUTS[engine]
    dense = fn(g, "dense")
    for fr in ("sparse", "auto"):
        got = fn(g, fr)
        assert got.keys() == dense.keys()
        for key in dense:
            assert np.array_equal(got[key], dense[key]), (name, engine,
                                                          fr, key)


# -- the resume axis: checkpointed/restored cells sit in the same matrix -----
#
# Every engine grew a ``state_dict()/load_state()`` checkpoint protocol
# (DESIGN.md §14).  The contract is the same as the frontier axis: a
# restored engine is bit-identical to the original — same masks, same
# counters, same accounting — and its outputs still match the one numpy
# oracle.  Stream checkpoints mid-update-sequence (the path-dependent
# AC-4 counters must be restored verbatim, never recomputed).

def _resume_trim(g, d):
    import repro.fault as flt
    e = plan(g, method="ac6")
    want = np.asarray(e.run().status)
    flt.save_engine(d, e, 0)
    r, *_ = flt.restore_engine(d)
    assert r.dispatches == e.dispatches and r.traces == e.traces
    got = np.asarray(r.run().status)
    assert np.array_equal(got, want)
    return got.astype(bool)


def _resume_reach(g, d):
    import repro.fault as flt
    e = plan_reach(g)
    seeds = np.arange(g.n) % 3 == 0
    want = np.asarray(e.run(seeds).mask)
    flt.save_engine(d, e, 0)
    r, *_ = flt.restore_engine(d)
    assert r.dispatches == e.dispatches
    assert np.array_equal(np.asarray(r.run(seeds).mask), want)
    return None                              # reach has no trim oracle


def _resume_peel(g, d):
    import repro.fault as flt
    e = plan_peel(g)
    res = e.run()
    flt.save_engine(d, e, 0)
    r, *_ = flt.restore_engine(d)
    res2 = r.run()
    assert np.array_equal(np.asarray(res2.coreness),
                          np.asarray(res.coreness))
    assert np.array_equal(np.asarray(res2.status), np.asarray(res.status))
    return np.asarray(r.run(k=1).status).astype(bool)


def _resume_stream(g, d):
    import repro.fault as flt
    e = plan_stream(g)
    ip, ix = g.to_numpy()
    src = np.repeat(np.arange(g.n), np.diff(ip))
    if g.m:                                  # one committed update batch
        e.apply(deletions=([src[0]], [ix[0]]))
    flt.save_engine(d, e, 0)                 # checkpoint mid-sequence
    r, *_ = flt.restore_engine(d)
    if g.m > 1:                              # both engines continue
        e.apply(deletions=([src[1]], [ix[1]]))
        r.apply(deletions=([src[1]], [ix[1]]))
    assert np.array_equal(np.asarray(r._state[0]), np.asarray(e._state[0]))
    assert np.array_equal(np.asarray(r._state[1]), np.asarray(e._state[1]))
    assert r.delta.n_tomb == e.delta.n_tomb
    got = np.asarray(r.retrim().status).astype(bool)
    assert np.array_equal(got, trim_oracle(*e.snapshot().to_numpy()))
    return None                              # oracle asserted in-place


RESUME_ENGINES = {"trim": _resume_trim, "reach": _resume_reach,
                  "peel": _resume_peel, "stream": _resume_stream}


@pytest.mark.parametrize("engine", sorted(RESUME_ENGINES))
@pytest.mark.parametrize("name", ["self_loop", "long_chain",
                                  "bridged_2cycles"])
def test_resumed_cells_agree(name, engine, oracles, tmp_path):
    got = RESUME_ENGINES[engine](FIXTURES[name], str(tmp_path / "ck"))
    if got is not None:
        assert np.array_equal(got, oracles[name]), (name, engine)


def test_frontier_auto_matches_dense_property():
    """Randomized auto-vs-dense bit-identity (needs optional hypothesis;
    the deterministic fixture matrix above runs regardless)."""
    pytest.importorskip(
        "hypothesis",
        reason="property-based case needs the optional hypothesis dep "
               "(pip install -e .[test]); the deterministic frontier "
               "matrix above covers the fixture shapes")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 60), st.integers(0, 180),
           st.integers(0, 2**31 - 1))
    def prop(n, m, seed):
        rng = np.random.default_rng(seed)
        g = _graph(n, rng.integers(0, n, m), rng.integers(0, n, m))
        a = plan(g, method="ac6", frontier="auto").run().status
        d = plan(g, method="ac6", frontier="dense").run().status
        assert np.array_equal(np.asarray(a), np.asarray(d))

    prop()
