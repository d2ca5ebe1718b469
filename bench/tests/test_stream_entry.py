"""The ``stream`` entry (``bench/entries/stream.py``), its deletion stream,
incremental reference and control (``bench/stream_reference.py``), and the
readers of the ``kron-s22.stream`` cell's per-layer metrics."""
import json

import numpy as np
import pytest

from bench import find, reference, stream_reference
from bench.tests.conftest import ROOT
from bench.tests.test_gen import KRON, host
from bench.tests.test_metrics import read, summary
from bench.tests.test_run import run_cell

CELL = "kron-s22.stream"
MIX = json.loads((ROOT / "bench" / "traffic" / "stream_loop.json")
                 .read_text())
BATCH = MIX["batch"]


def entry():
    return find.module(ROOT, "entries", "stream")


def scratch(graph, order, k, batch):
    """``host_trim`` of G minus the first k batches, built from scratch."""
    indptr, indices = graph
    keep = np.ones(len(indices), bool)
    keep[order[:k * batch]] = False
    src = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))[keep]
    dst = indices[keep]
    order_ = np.argsort(src, kind="stable")
    g = (np.concatenate([[0], np.cumsum(np.bincount(
        src, minlength=len(indptr) - 1))]), dst[order_])
    return reference.host_trim(*g, *reference.host_transpose(*g))[0]


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 11])
def test_incremental_reference_equals_scratch(seed):
    """Every k of the whole stream at scale 12, batches of 1024 (64 of
    them, the last ones deleting everything)."""
    graph, transpose = host(dict(KRON, scale=12), seed)
    fix = stream_reference.Fixpoints(graph, transpose, seed, BATCH)
    batches = len(graph[1]) // BATCH
    cascades = []
    for k in range(batches + 1):
        assert np.array_equal(fix.live(k), scratch(graph, fix.order, k,
                                                   BATCH)), k
        cascades.append(fix.rounds)
    fix.verify()
    assert not fix.live(batches).any()
    assert max(cascades) >= 2
    # asked for an earlier k, it starts again
    assert np.array_equal(fix.live(3), scratch(graph, fix.order, 3, BATCH))


def test_verify_catches_a_wrong_state():
    graph, transpose = host(dict(KRON, scale=10), 5)
    fix = stream_reference.Fixpoints(graph, transpose, 5, BATCH)
    live = fix.live(2)
    fix.verify()
    live[np.flatnonzero(live)[0]] = False
    with pytest.raises(AssertionError, match="after 2 batches in 1 "):
        fix.verify()


def test_stream_is_a_permutation_in_batches():
    graph, _ = host(dict(KRON, scale=10), 6)
    m = len(graph[1])
    order = stream_reference.arc_order(2**40 + 3, m)
    assert order.dtype == np.int32
    assert np.array_equal(np.sort(order), np.arange(m))
    assert np.array_equal(order, stream_reference.arc_order(2**40 + 3, m))
    assert not np.array_equal(order, stream_reference.arc_order(2**40 + 4,
                                                                m))
    src, dst = stream_reference.batch_arcs(order, *graph, 2, 1000)
    ids = order[1000:2000]
    assert np.array_equal(graph[1][ids], dst)
    assert ((graph[0][src] <= ids) & (ids < graph[0][src + 1])).all()
    last = stream_reference.batch_arcs(order, *graph, m // 1000 + 1, 1000)
    assert len(last[0]) == m % 1000
    assert len(stream_reference.batch_arcs(order, *graph, m, 1000)[0]) == 0


def test_batches_draw_parallel_arcs_apart():
    """Each arc id is drawn on its own: the copies of a duplicated arc
    fall in different batches, as a uniform draw over arcs has them."""
    graph, _ = host(dict(KRON, scale=12), 9)
    indptr, indices = graph
    m = len(indices)
    src = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))
    key = src.astype(np.int64) * len(indptr) + indices
    _, first, copies = np.unique(key, return_index=True, return_counts=True)
    assert (copies > 1).any()           # the configuration keeps duplicates
    batch_of = np.empty(m, np.int64)
    batch_of[stream_reference.arc_order(9, m)] = np.arange(m) // BATCH
    same = np.isin(key, key[first[copies > 1]])
    pairs = {}
    for arc in np.flatnonzero(same):
        pairs.setdefault(key[arc], set()).add(batch_of[arc])
    assert any(len(b) > 1 for b in pairs.values())


def test_answer_carries_seeds_batch_and_k():
    module = entry()
    status = np.array([1, 0, 1], np.uint8)
    answer = np.concatenate([module.header(2**33 + 5, 1024, 17), status])
    seed, batch, k, got = module.parse(answer)
    assert (seed, batch, k) == (2**33 + 5, 1024, 17)
    assert np.array_equal(got, status)


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 11])
def test_control_is_not_correct(seed):
    """The control in the program's place fails its limit at scale 12,
    at a batch whose cascade takes two rounds or more."""
    graph, transpose = host(dict(KRON, scale=12), seed)
    module = entry()
    module.CLIENTS[seed] = (BATCH, 1)
    control = module.control(graph, transpose)
    _, _, k, _ = module.parse(control)
    fix = stream_reference.Fixpoints(graph, transpose, seed, BATCH)
    fix.live(k)
    assert fix.rounds >= 2
    got = module.compare(control, module.reference_answer(graph, transpose))
    assert got["status_mismatch"] > module.LIMITS["status_mismatch"]
    assert got["status_mismatch"] == len(fix.last_round)


def test_reference_verifies_the_last_applied_batch(monkeypatch):
    graph, transpose = host(dict(KRON, scale=10), 8)
    module = entry()
    verified = []
    monkeypatch.setattr(stream_reference.Fixpoints, "verify",
                        lambda self: verified.append(self.k))
    ref = module.reference_answer(graph, transpose)
    module.CLIENTS[8] = (BATCH, 3)
    for k in (1, 2, 3):
        ref.live(8, BATCH, k)
    assert verified == [3]


def _stream_fault(kind):
    from repro.core import StreamEngine, StreamResult
    apply = StreamEngine.apply

    def broken(self, *args, **kwargs):
        before = np.asarray(self.status).copy()
        res = apply(self, *args, **kwargs)
        status = np.asarray(res.status).copy()
        if kind == "unchanged":         # the status before the batch
            status = before
        elif kind == "half":            # half the vertices never computed
            status[len(status) // 2:] = True
        else:                           # one answer altered
            status[np.flatnonzero(~status)[0]] = True
        return StreamResult(status, res.rounds, res.dirty)
    return StreamEngine, "apply", broken


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_fault_is_not_correct(off_chip, small_root, monkeypatch, kind):
    monkeypatch.setattr(*_stream_fault(kind))
    result = run_cell(off_chip, small_root, CELL, False, monkeypatch)
    assert not result["correct"]
    assert result["checks"]["status_mismatch"]["value"] > 0


def test_least_bytes():
    scope = {}
    exec((ROOT / "bench" / "metrics" / "stream_roofline.py").read_text(),
         scope)
    # each arc read once (source and target, 4 bytes each), n status bytes
    assert scope["least_bytes"](3, 5) == 8 * 5 + 3
    assert scope["least_bytes"](4194304, 1024) == 4202496


def test_stream_roofline():
    # 819 bytes take 1 ns at 819 GB/s; 2 calls in 4 ns busy: 2 ns a call
    counts = [{"arcs": 100}, {"arcs": 102}]     # 8 * 101 + 11 = 819
    share = read("stream_roofline", n=11, counts=counts, calls=2,
                 trace=summary(4e-9, 1e-8))
    assert share == pytest.approx(100 * 1e-9 / 2e-9)
    assert read("stream_roofline", n=11, counts=counts, calls=2) is None
    assert read("stream_roofline", n=11, calls=2,
                trace=summary(4e-9, 1e-8)) is None


def test_stream_readers():
    assert read("idle_share.stream", trace=summary(0.75, 1.0)) == \
        pytest.approx(25.0)
    assert read("idle_share.stream") is None
    assert read("rounds.stream", counts=[{"rounds": 1}, {"rounds": 2}]) \
        == 1.5
    assert read("resolve_s.stream",
                counts=[{"resolve_s": 0.002}, {"resolve_s": 0.004}]) == \
        pytest.approx(0.003)
    # a program whose results lack the counters reads nothing
    for name, key in (("rounds.stream", "rounds"),
                      ("resolve_s.stream", "resolve_s")):
        assert read(name, counts=[{key: None, "arcs": 1024}]) is None
        assert read(name) is None
