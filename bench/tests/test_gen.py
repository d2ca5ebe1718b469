"""``bench/gen.py`` and ``bench/generators/``: graphs made on the device
from the seed."""
import jax
import numpy as np
import pytest

from bench import find, gen, reference
from bench.tests.conftest import ROOT

KRON = {"generator": "kronecker", "scale": 10, "edge_factor": 16,
        "initiator": {"a": 0.57, "b": 0.19, "c": 0.19}, "structure_seed": 1}
URAND = {"generator": "uniform", "scale": 10, "edge_factor": 16,
         "structure_seed": 1}


def sizes(config):
    n = 1 << config["scale"]
    return n, n * config["edge_factor"]


def build(config, seed):
    return gen.build(find.module(ROOT, "generators", config["generator"]),
                     config, seed)


def host(config, seed):
    g, gt = build(config, seed)
    return gen.to_host(g), gen.to_host(gt)


@pytest.mark.parametrize("config", [KRON, URAND], ids=["kron", "urand"])
def test_csr_and_transpose(config):
    (indptr, indices), (t_indptr, t_indices) = host(config, 5)
    n, m = sizes(config)
    assert (len(indptr), len(indices)) == (n + 1, m)
    assert indptr[0] == 0 and indptr[-1] == m
    assert (np.diff(indptr) >= 0).all()
    assert indices.min() >= 0 and indices.max() < n
    want = reference.host_transpose(indptr, indices)
    assert np.array_equal(t_indptr, want[0])
    # the transpose is stable by target, so rows agree as multisets
    src = np.repeat(np.arange(n), np.diff(t_indptr))
    assert sorted(zip(src, t_indices)) == sorted(zip(src, want[1]))


@pytest.mark.parametrize("config", [KRON, URAND], ids=["kron", "urand"])
def test_seed_relabels_the_graph(config):
    """One seed gives one graph; another seed (high bits count too) gives
    the same arcs relabelled: the labels differ, the degree sequence and
    the structure seed's arcs stay."""
    a, b = host(config, 2**33 + 7), host(config, 2**33 + 7)
    assert all(np.array_equal(x, y) for x, y in zip(a[0], b[0]))
    for other in (7, 2**33 + 8):
        c = host(config, other)
        assert not np.array_equal(a[0][1], c[0][1])
        assert np.array_equal(np.sort(np.diff(a[0][0])),
                              np.sort(np.diff(c[0][0])))
    d = host(dict(config, structure_seed=2), 7)
    assert not np.array_equal(np.sort(np.diff(a[0][0])),
                              np.sort(np.diff(d[0][0])))


def test_kronecker_quadrants():
    """Before scrambling the top bit of a source is 1 with probability
    C + D and of a target with B + D; scrambling keeps the degrees and
    moves the hubs off the lowest labels."""
    config = dict(KRON, edge_factor=64)
    kron = find.module(ROOT, "generators", "kronecker")
    # the key gen.build gives the arcs of structure seed 1
    n, src, dst = kron.arcs(config, jax.random.fold_in(gen.seed_key(1), 0))
    src, dst = np.asarray(src), np.asarray(dst)
    assert abs((src >= n // 2).mean() - 0.24) < 0.01
    assert abs((dst >= n // 2).mean() - 0.24) < 0.01
    (indptr, _), _ = host(config, 3)
    plain = np.bincount(src, minlength=n)
    assert np.array_equal(np.sort(plain), np.sort(np.diff(indptr)))
    assert not np.array_equal(plain, np.diff(indptr))


def test_uniform_has_no_skew():
    (indptr, _), _ = host(URAND, 3)
    deg = np.diff(indptr)
    assert abs(deg.mean() - 16) < 1e-9 and deg.max() < 40


def test_seed_range():
    with pytest.raises(ValueError):
        gen.seed_key(-1)


def test_missing_generator():
    with pytest.raises(SystemExit, match="bench/generators/nope.py"):
        find.module(ROOT, "generators", "nope")
