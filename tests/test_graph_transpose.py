"""Gᵀ built where G lives: the device transpose against the host counting
sort, and which of the two ``CSRGraph.transpose`` takes."""
import numpy as np
import pytest

from repro import obs
from repro.core import graph as graph_mod
from repro.core import CSRGraph
from repro.core.graph import csr_transpose
from repro.core.scc import same_partition, scc_decompose, tarjan_oracle
from repro.graphs import barabasi_albert


def _edges(n, src, dst):
    return CSRGraph.from_edges(n, np.asarray(src, np.int64),
                               np.asarray(dst, np.int64))


def _random(n, m, seed):
    rng = np.random.default_rng(seed)
    return _edges(n, rng.integers(0, n, m), rng.integers(0, n, m))


GRAPHS = {
    "empty": lambda: _edges(1, [], []),
    "self-loop": lambda: _edges(1, [0], [0]),
    # duplicate arcs stay distinct instances, in edge-id order
    "duplicates-and-loops": lambda: _edges(
        5, [1, 1, 1, 2, 2, 3, 1], [2, 2, 1, 1, 2, 2, 2]),
    # vertices 0, 1 and 5, 6 have no arcs at all
    "zero-degree-ends": lambda: _edges(7, [2, 3, 2, 4, 3], [3, 2, 4, 4, 2]),
    "random-1000": lambda: _random(1000, 16_000, seed=5),
    "barabasi-albert": lambda: barabasi_albert(2000, 5, seed=1),
}


@pytest.mark.parametrize("name", GRAPHS)
def test_device_transpose_matches_host_counting_sort(name):
    g = GRAPHS[name]()
    assert not g.on_accelerator          # the CPU backend: the host path
    host = g.transpose()
    indptr, indices = csr_transpose(g.indptr, g.indices)
    assert indptr.dtype == indices.dtype == np.int32
    np.testing.assert_array_equal(np.asarray(indptr), np.asarray(host.indptr))
    np.testing.assert_array_equal(np.asarray(indices),
                                  np.asarray(host.indices))


def _spy_device(monkeypatch):
    calls = []
    real = graph_mod.csr_transpose

    def spy(indptr, indices):
        calls.append(1)
        return real(indptr, indices)

    monkeypatch.setattr(graph_mod, "csr_transpose", spy)
    return calls


@pytest.mark.parametrize("backing", ["numpy", "cpu"])
def test_host_backed_graph_takes_the_host_path(monkeypatch, backing):
    g = _random(300, 1200, seed=2)
    if backing == "numpy":
        g = CSRGraph(*g.to_numpy())
    calls = _spy_device(monkeypatch)
    assert not g.on_accelerator
    g.transpose()
    assert calls == []


def test_accelerator_resident_graph_takes_the_device_path(monkeypatch):
    g = _random(300, 1200, seed=3)
    host = g.transpose()
    calls = _spy_device(monkeypatch)
    monkeypatch.setattr(CSRGraph, "on_accelerator",
                        property(lambda self: True))
    gt = g.transpose()
    assert calls == [1]
    np.testing.assert_array_equal(np.asarray(gt.indptr),
                                  np.asarray(host.indptr))
    np.testing.assert_array_equal(np.asarray(gt.indices),
                                  np.asarray(host.indices))
    # scc_decompose reports the path and still builds Gᵀ once
    with obs.recording() as rec:
        labels, stats = scc_decompose(g)
    assert calls == [1, 1]
    assert stats["transpose_on_device"] == 1
    assert stats["transpose_builds"] == 1
    (sp,) = rec.select("transpose", cat="scc")
    assert sp.attrs["where"] == "device"
    assert same_partition(labels, tarjan_oracle(*g.to_numpy()))
