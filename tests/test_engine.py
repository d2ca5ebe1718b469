"""Compile-once TrimEngine: plan/run lifecycle, kernel registry, backends.

Deterministic (no hypothesis) so this coverage survives even when the
optional property-testing dep is absent.  Trace-count assertions use the
engine's own accounting (bumped only inside traced functions); the jit
cache is process-wide, so tests that assert an exact count use graph
shapes no other test produces.
"""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CSRGraph, available_methods, get_kernel,
                        peeling_alpha_oracle, plan, trim, trim_oracle,
                        worker_of)
from repro.core.ac6 import ac6_kernel
from repro.core.common import frontier_plan
from repro.core.engine import BACKENDS
from repro.core.scc import same_partition, scc_decompose, tarjan_oracle
from repro.graphs import barabasi_albert

from host_rounds import HOST_ROUNDS

METHODS = ("ac3", "ac4", "ac4*", "ac6")


def random_graph(seed, n, factor=3):
    rng = np.random.default_rng(seed)
    m = factor * n
    return CSRGraph.from_edges(n, rng.integers(0, n, m),
                               rng.integers(0, n, m))


def induced_oracle(g, active):
    ip, ix = g.to_numpy()
    src = np.repeat(np.arange(g.n), np.diff(ip))
    keep = active[src] & active[ix]
    sub = CSRGraph.from_edges(g.n, src[keep], ix[keep])
    return trim_oracle(*sub.to_numpy()) & active


# -- registry -----------------------------------------------------------------

def test_registry_has_paper_methods():
    assert set(METHODS) <= set(available_methods())


def test_unknown_method_and_backend_raise():
    g = random_graph(0, n=10)
    with pytest.raises(ValueError, match="unknown method"):
        plan(g, method="ac99")
    with pytest.raises(ValueError, match="unknown backend"):
        plan(g, method="ac6", backend="gpu-farm")


# -- compile-once contract ----------------------------------------------------

def test_compile_cache_reuse_across_runs():
    # unique shape (n=103, m=309) so no other test warms this cache entry
    g = random_graph(1, n=103)
    engine = plan(g, method="ac6")
    rng = np.random.default_rng(1)
    for i in range(5):
        mask = rng.random(g.n) < 0.7
        res = engine.run(active=mask)
        assert (np.asarray(res.status).astype(bool)
                == induced_oracle(g, mask)).all()
    assert engine.traces == 1   # 5 runs, one trace


def test_transpose_built_once_and_shareable():
    g = random_graph(2, n=50)
    engine = plan(g, method="ac4")
    for _ in range(3):
        engine.run()
    assert engine.transpose_builds == 1
    # pre-seeding skips the build entirely
    engine2 = plan(g, method="ac4", transpose=engine.transpose)
    engine2.run()
    assert engine2.transpose_builds == 0


def test_run_batch_matches_sequential():
    g = random_graph(3, n=71)
    rng = np.random.default_rng(3)
    masks = np.stack([rng.random(g.n) < p for p in (0.9, 0.6, 0.3, 1.0)])
    for method in METHODS:
        engine = plan(g, method=method, workers=3, chunk=8)
        seq = [engine.run(active=m) for m in masks]
        bat = engine.run_batch(masks)
        for a, b in zip(seq, bat):
            assert (np.asarray(a.status) == np.asarray(b.status)).all()
            assert a.rounds == b.rounds
            assert a.edges_traversed == b.edges_traversed
            assert a.max_frontier == b.max_frontier
            assert (a.per_worker_edges == b.per_worker_edges).all()


# -- counters fast path -------------------------------------------------------

def test_counters_false_skips_accumulation():
    g = random_graph(4, n=64)
    for method in METHODS:
        engine = plan(g, method=method)
        full = engine.run()
        fast = engine.run(counters=False)
        assert (np.asarray(full.status) == np.asarray(fast.status)).all()
        assert fast.per_worker_edges is None
        assert fast.edges_traversed is None
        assert fast.max_frontier is None
        assert fast.rounds == full.rounds
        # docstring contract: counters requested => populated
        assert full.per_worker_edges is not None
        assert full.per_worker_edges.sum() == full.edges_traversed


def test_counters_false_batch():
    g = random_graph(5, n=40)
    masks = np.ones((2, g.n), bool)
    engine = plan(g, method="ac6")
    for res in engine.run_batch(masks, counters=False):
        assert res.per_worker_edges is None
        assert (np.asarray(res.status).astype(bool)
                == trim_oracle(*g.to_numpy())).all()


# -- per-worker counters against a numpy replay ------------------------------

def counter_graph(seed=13, n=1009, chain=40):
    """A sparse random digraph that trims over a few wide rounds, plus a
    chain of ``chain`` vertices scattered over the id range (chain vertex
    i's only arc goes to i+1, the last is a sink), so its tail takes
    rounds of one death each: rounds an ``auto`` plan compacts."""
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, n, 2 * n), rng.integers(0, n, 2 * n)
    ch = rng.permutation(n)[:chain]
    keep = ~np.isin(src, ch)
    return CSRGraph.from_edges(n, np.concatenate([src[keep], ch[:-1]]),
                               np.concatenate([dst[keep], ch[1:]]))


@pytest.mark.parametrize("method,frontier", [
    (meth, f) for meth in METHODS for f in ("dense", "auto", "sparse")
    if (meth, f) != ("ac3", "sparse")])     # AC-3 has no sparse rounds
@pytest.mark.parametrize("workers", (1, 3, 16))
def test_worker_counters_match_bincount_reference(method, frontier,
                                                  workers):
    # n = 1009 is no multiple of chunk * workers (nor of AC-6's 64-row
    # chunks), so the last worker chunk and the compaction pad are partial
    chunk = 16
    g = counter_graph()
    ip, ix = g.to_numpy()
    wid = worker_of(g.n, workers, chunk)
    res = plan(g, method=method, workers=workers, chunk=chunk,
               frontier=frontier).run()
    rounds = HOST_ROUNDS[method](ip, ix)
    edges = sum(examined for _, examined in rounds)
    want = np.bincount(wid, weights=edges, minlength=workers)
    assert np.array_equal(res.per_worker_edges, want.astype(np.int64))
    assert res.max_frontier == max(
        np.bincount(wid[dead], minlength=workers).max()
        for dead, _ in rounds)


@pytest.mark.parametrize("workers", (1, 16))
def test_ac6_counters_add_no_scatter(workers):
    """The per-worker counters are reductions: compiled with and without
    them, AC-6 under an ``auto`` plan has the same scatters."""
    n, m = 1 << 12, 1 << 16
    args = (jax.ShapeDtypeStruct((n + 1,), jnp.int32),
            jax.ShapeDtypeStruct((m,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32))

    def scatter_shapes(counters):
        text = ac6_kernel.lower(
            *args, workers, counters=counters,
            frontier=frontier_plan("auto", n, m)).compile().as_text()
        return collections.Counter(re.findall(r"= (\S+) scatter\(", text))

    plain = scatter_shapes(False)
    assert plain        # the sparse round's row write-backs are scatters
    assert scatter_shapes(True) == plain


# -- edge cases across methods and backends -----------------------------------

@pytest.mark.parametrize("backend", ("dense", "windowed"))
@pytest.mark.parametrize("method", METHODS)
def test_empty_graphs(method, backend):
    # n == 0
    g0 = CSRGraph.from_edges(0, [], [])
    engine = plan(g0, method=method, backend=backend)
    res = engine.run()
    assert res.status.shape == (0,) and res.rounds == 0
    assert res.edges_traversed == 0
    # m == 0: every active vertex is a sink
    g1 = CSRGraph.from_edges(5, [], [])
    engine = plan(g1, method=method, backend=backend, workers=2)
    res = engine.run()
    assert res.n_trimmed == 5 and res.rounds == 2
    assert res.per_worker_edges.shape == (2,)
    res = engine.run(active=np.array([1, 0, 1, 0, 0], bool))
    assert res.max_frontier == 2
    for r in engine.run_batch(np.ones((2, 5), bool)):
        assert r.n_trimmed == 5
    # counters off on the degenerate path too
    assert engine.run(counters=False).per_worker_edges is None


@pytest.mark.parametrize("backend", ("dense", "windowed"))
@pytest.mark.parametrize("method", METHODS)
def test_active_mask_all_backends(method, backend):
    g = random_graph(6, n=60)
    rng = np.random.default_rng(6)
    active = rng.random(g.n) < 0.6
    engine = plan(g, method=method, backend=backend, window=4)
    res = engine.run(active=active)
    assert (np.asarray(res.status).astype(bool)
            == induced_oracle(g, active)).all()


def test_windowed_counters_match_dense():
    g = random_graph(7, n=90)
    for method in ("ac3", "ac6"):
        dense = plan(g, method=method, workers=4).run()
        windowed = plan(g, method=method, backend="windowed", window=4,
                        workers=4).run()
        assert (np.asarray(dense.status) == np.asarray(windowed.status)).all()
        assert dense.edges_traversed == windowed.edges_traversed
        assert (dense.per_worker_edges == windowed.per_worker_edges).all()


def test_sharded_backend_matches_oracle():
    # runs on however many devices the test process sees (1 by default)
    g = random_graph(8, n=77)
    oracle = trim_oracle(*g.to_numpy())
    for method in METHODS:
        unmasked = get_kernel(method).sharded_method == "ac4"
        engine = plan(g, method=method, backend="sharded",
                      unmasked=unmasked)
        res = engine.run()
        assert (np.asarray(res.status).astype(bool) == oracle).all(), method
    # active masks on the status-exchange methods
    rng = np.random.default_rng(8)
    active = rng.random(g.n) < 0.5
    engine = plan(g, method="ac6", backend="sharded")
    res = engine.run(active=active)
    assert (np.asarray(res.status).astype(bool)
            == induced_oracle(g, active)).all()
    assert engine.traces == 1
    with pytest.raises(NotImplementedError):
        engine.run_batch(np.ones((2, g.n), bool))


# -- fail-fast config validation ----------------------------------------------

def test_plan_fails_fast_on_unmaskable_config():
    """plan(method='ac4', backend='sharded') can never run an active mask —
    it must raise at plan() time, not mid-worklist at run(active=...)."""
    g = random_graph(8, n=77)
    for method in ("ac4", "ac4*"):
        with pytest.raises(ValueError, match="cannot trim induced"):
            plan(g, method=method, backend="sharded")
    # the unmasked=True escape hatch keeps the maskless path working but
    # turns a masked run() into an immediate error
    engine = plan(g, method="ac4", backend="sharded", unmasked=True)
    with pytest.raises(ValueError, match="unmasked=True"):
        engine.run(active=np.ones(g.n, bool))
    # the shim infers the promise from its own arguments
    from repro.core import trim
    with pytest.raises(ValueError, match="cannot trim induced"):
        trim(g, method="ac4", backend="sharded", active=np.ones(g.n, bool))


# -- degenerate paths are device-resident -------------------------------------

@pytest.mark.parametrize("method", METHODS)
def test_degenerate_results_device_resident(method):
    """n=0 / m=0 shortcuts must return the same types/dtypes as the kernel
    path: device-resident jnp status, so downstream code never branches on
    provenance."""
    import jax

    # kernel path reference: masked-empty on a real graph
    g = random_graph(10, n=24)
    kernel_res = plan(g, method=method, workers=2).run(
        active=np.zeros(g.n, bool))
    assert isinstance(kernel_res.status, jax.Array)
    for gd in (CSRGraph.from_edges(0, [], []),
               CSRGraph.from_edges(7, [], [])):
        engine = plan(gd, method=method, workers=2)
        res = engine.run()
        assert isinstance(res.status, jax.Array)
        assert res.status.dtype == kernel_res.status.dtype
        assert res.status.shape == (gd.n,)
        assert isinstance(res.per_worker_edges, np.ndarray)  # lazy host view
        assert (res.per_worker_edges
                == np.zeros(2, np.int64)).all()
        assert res.per_worker_edges.dtype \
            == kernel_res.per_worker_edges.dtype
        assert type(res.rounds) is type(kernel_res.rounds) is int
        assert engine.dispatches == 0    # no kernel ran
        fast = engine.run(counters=False)
        assert fast.per_worker_edges is None
        assert fast.edges_traversed is None


# -- shim compatibility -------------------------------------------------------

def test_shim_matches_engine_and_oracle():
    g = random_graph(9, n=83)
    oracle = trim_oracle(*g.to_numpy())
    alpha = peeling_alpha_oracle(*g.to_numpy())
    for method in METHODS:
        res = trim(g, method=method, workers=3, chunk=4)
        assert isinstance(res.status, np.ndarray)
        assert res.status.dtype == np.int32
        assert (res.status.astype(bool) == oracle).all()
        assert res.per_worker_edges.dtype == np.int64
        assert res.per_worker_edges.sum() == res.edges_traversed
        eng = plan(g, method=method, workers=3, chunk=4).run().materialize()
        assert (res.status == eng.status).all()
        assert res.rounds == eng.rounds
    from repro.core import peeling_alpha
    assert peeling_alpha(g) == alpha


def test_backends_constant():
    assert BACKENDS == ("dense", "windowed", "sharded")


# -- SCC acceptance: one transpose build, one trace per (method, shape) -------

def test_scc_single_transpose_and_trace(monkeypatch):
    calls = []
    orig = CSRGraph.transpose

    def counting(self):
        calls.append(1)
        return orig(self)

    monkeypatch.setattr(CSRGraph, "transpose", counting)
    g = barabasi_albert(10_000, 5, seed=3)
    labels, stats = scc_decompose(g, use_trim=True, trim_method="ac6")
    assert len(calls) == 1                  # one transpose across the worklist
    assert stats["transpose_builds"] == 1
    assert stats["engine_traces"] <= 1      # one jit trace per (method, shape)
    assert stats["trimmed_total"] == 10_000  # BA construction graph is a DAG
    assert stats["pivots"] == 0              # ...so no reach dispatch ran
    assert stats["reach_dispatches"] == 0
    assert stats["trim_dispatches"] == stats["generations"] == 1
    assert (np.unique(labels) == np.arange(10_000)).all()


def test_scc_matches_tarjan_deterministic():
    rng = np.random.default_rng(12)
    for _ in range(4):
        n = int(rng.integers(2, 60))
        m = int(rng.integers(0, 3 * n))
        g = CSRGraph.from_edges(n, rng.integers(0, n, m),
                                rng.integers(0, n, m))
        for use_trim in (True, False):
            labels, _ = scc_decompose(g, use_trim=use_trim)
            assert same_partition(labels, tarjan_oracle(*g.to_numpy()))
