"""``bench/reference.py``: the plain references agree with the repo's own
oracles, the comparisons count what they say, and each control fails."""
import numpy as np
import pytest

from bench import find, reference
from bench.tests.conftest import ROOT
from bench.tests.test_gen import KRON, URAND, host


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_references_match_oracles(seed):
    from repro.core import trim_oracle
    from repro.core.scc import same_partition, tarjan_oracle
    (indptr, indices), t = host(dict(KRON, scale=9), seed)
    live, rounds = reference.host_trim(indptr, indices, *t)
    assert np.array_equal(live, trim_oracle(indptr, indices))
    assert rounds >= 1
    assert same_partition(reference.host_scc(indptr, indices),
                          tarjan_oracle(indptr, indices))


def test_partition_mismatch_counts_vertices():
    ref = np.array([0, 0, 0, 1, 1, 2])
    assert reference.partition_mismatch(np.array([5, 5, 5, 7, 7, 9]), ref) \
        == 0
    # vertex 2 split off its class: the three vertices of class 0 count
    assert reference.partition_mismatch(np.array([5, 5, 6, 7, 7, 9]), ref) \
        == 3
    # classes 1 and 2 merged: their three vertices count
    assert reference.partition_mismatch(np.array([5, 5, 5, 7, 7, 7]), ref) \
        == 3
    assert reference.unlabelled(np.array([0, -1, 6, 2, 1, 0])) == 2
    assert reference.status_mismatch(np.array([1, 0, 1]),
                                     np.array([True, True, True])) == 1


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("entry,config", [("trim", KRON), ("scc", URAND)])
def test_control_is_not_correct(entry, config, seed):
    """The control in the program's place fails a limit on every seed."""
    graph, transpose = host(dict(config, scale=12), seed)
    module = find.module(ROOT, "entries", entry)
    got = module.compare(module.control(graph, transpose),
                         module.reference_answer(graph, transpose))
    assert any(v > module.LIMITS[k] for k, v in got.items())
