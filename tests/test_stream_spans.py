"""The stream engine's host path on the profiler clock (DESIGN.md §9, §11):
the ``stream.*`` spans, the per-apply ``resolve_s`` counter, results that
tracing leaves unchanged, and incremental trimming of a device-built
Graph500 Kronecker graph under uniform 1024-arc deletion batches, checked
against the host oracle after every batch."""
import glob
import os
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.core import plan_stream
from repro.core.enginebase import _TRACE_COUNT
from repro.core.ref import trim_oracle
from repro.graphs import generators

ROOT = Path(__file__).resolve().parents[1]
SPANS = ("stream.index", "stream.transpose", "stream.resolve",
         "stream.compact")


def _host_events(log_dir):
    """(name, start_ns, end_ns) of every host-plane event in the one
    ``.xplane.pb`` a profiler trace wrote under ``log_dir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for pl in ProfileData.from_file(path).planes
            if not pl.name.startswith("/device:")
            for line in pl.lines for ev in line.events]


def _batches(g, seed, batch, k):
    """The first ``k`` deletion batches of a uniform permutation of G's
    arc ids, as (src, dst) pairs."""
    indptr, indices = (np.asarray(a) for a in g.to_numpy())
    order = np.random.default_rng(seed).permutation(len(indices))
    for i in range(k):
        ids = order[i * batch:(i + 1) * batch]
        yield np.searchsorted(indptr, ids, side="right") - 1, indices[ids]


def test_stream_spans_reach_the_profiler_trace(tmp_path):
    import jax
    g = generators.erdos_renyi(150, 600, seed=21)
    (batch,) = _batches(g, seed=1, batch=20, k=1)
    with jax.profiler.trace(str(tmp_path)):
        engine = plan_stream(g, capacity=16)    # index, transpose
        engine.apply(deletions=batch)            # resolve
        engine.compact()                         # compact, index inside
        engine.apply(deletions=([], []))         # transpose again
    events = _host_events(tmp_path)
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[0], []).append(ev)
    assert {name: len(by_name.get(name, [])) for name in SPANS} == {
        "stream.index": 2, "stream.transpose": 2, "stream.resolve": 2,
        "stream.compact": 1}
    (compact,) = by_name["stream.compact"]
    assert any(compact[1] <= s and e <= compact[2]
               for _, s, e in by_name["stream.index"])
    # each resolve ends before its apply's dispatch begins
    dispatches = sorted(by_name["engine.dispatch"], key=lambda ev: ev[1])
    for _, _, end in by_name["stream.resolve"]:
        assert any(s >= end for _, s, _ in dispatches)


def test_resolve_s_is_the_resolve_span():
    g = generators.erdos_renyi(150, 600, seed=22)
    engine = plan_stream(g)
    batches = list(_batches(g, seed=2, batch=16, k=2))
    engine.apply(deletions=batches[0])          # compile outside
    with obs.recording() as rec:
        t0 = time.perf_counter()
        res = engine.apply(deletions=batches[1])
        np.asarray(res.status)
        wall = time.perf_counter() - t0
    (span,) = rec.select("resolve", cat="stream")
    assert isinstance(res.resolve_s, float)
    assert 0 <= res.resolve_s <= wall
    assert res.resolve_s == pytest.approx(span.dur, rel=1e-12, abs=1e-12)


def test_tracing_leaves_results_and_counts_unchanged(tmp_path):
    import jax
    g = generators.erdos_renyi(157, 640, seed=23)
    batches = list(_batches(g, seed=3, batch=32, k=6))

    def drive():
        before = _TRACE_COUNT[0]
        engine = plan_stream(g, capacity=16)
        results = [engine.apply(deletions=b) for b in batches]
        statuses = [np.asarray(r.status) for r in results]
        return (statuses, [r.rounds for r in results], engine.dispatches,
                engine.traces, engine.compactions, _TRACE_COUNT[0] - before)

    drive()                                      # warm the jit cache
    off = drive()
    with jax.profiler.trace(str(tmp_path)), obs.recording():
        on = drive()
    assert all(np.array_equal(a, b) for a, b in zip(off[0], on[0]))
    assert off[1:] == on[1:]
    assert off[2] == 1 + len(batches) and off[3] == off[5] == 0


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_device_built_kronecker_stream_matches_oracle(seed):
    """The benchmark's own Kronecker graph at scale 10 (n = 1024, m =
    16384), made on the device, under ten uniform 1024-arc deletion
    batches: the ninth crosses ``load_factor`` 0.5 and compacts."""
    sys.path.insert(0, str(ROOT))
    from bench import find, gen
    config = {"generator": "kronecker", "scale": 10, "edge_factor": 16,
              "initiator": {"a": 0.57, "b": 0.19, "c": 0.19},
              "structure_seed": 1}
    g, _ = gen.build(find.module(ROOT, "generators", "kronecker"), config,
                     seed)
    engine = plan_stream(g, method="ac4", frontier="auto")
    rounds = []
    for batch in _batches(g, seed=seed, batch=1024, k=10):
        res = engine.apply(deletions=batch)
        want = trim_oracle(*engine.snapshot().to_numpy())
        assert np.array_equal(np.asarray(res.status), want)
        rounds.append(res.rounds)
    assert engine.compactions == 1
    assert sum(rounds) > 0


def test_compaction_inside_apply_is_outside_resolve(tmp_path):
    """Insertions that overflow the insert buffer compact before the
    batch resolves: ``stream.compact`` and ``stream.resolve`` do not
    overlap, so ``resolve_s`` holds no compaction."""
    import jax
    g = generators.erdos_renyi(150, 600, seed=24)
    engine = plan_stream(g, capacity=16)
    rng = np.random.default_rng(4)
    with jax.profiler.trace(str(tmp_path)):
        engine.apply(insertions=(rng.integers(0, 150, 10),
                                 rng.integers(0, 150, 10)))
        engine.apply(insertions=(rng.integers(0, 150, 10),
                                 rng.integers(0, 150, 10)))
    assert engine.compactions == 1
    events = _host_events(tmp_path)
    (compact,) = [ev for ev in events if ev[0] == "stream.compact"]
    resolves = [ev for ev in events if ev[0] == "stream.resolve"]
    assert len(resolves) == 2
    assert all(e <= compact[1] or compact[2] <= s for _, s, e in resolves)
