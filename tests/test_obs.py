"""Observability subsystem coverage (DESIGN.md §11): the zero-overhead
invariant (``instrument=False`` is bit-identical, no extra dispatches, no
retrace), per-round telemetry of all four trimming methods against host
oracles on eight graph families, span recording with compile
attribution, and exporter round-trips."""
import json
import os

import numpy as np
import pytest

from repro import obs
from repro.core import CSRGraph, plan, plan_peel, plan_reach, plan_stream
from repro.core.ref import trim_oracle
from repro.core.scc import scc_decompose, same_partition, tarjan_oracle
from repro.graphs import generators

from host_rounds import HOST_ROUNDS, round_totals


def _kron(scale, m, seed=1):
    """R-MAT with Graph500's A/B/C (duplicate arcs and self-loops kept)
    and vertex labels scrambled by a seeded permutation, the shape of the
    ``kron`` benchmark graphs at small scale."""
    ip, ix = generators.rmat(scale, m, seed=seed).to_numpy()
    perm = np.random.default_rng(seed).permutation(len(ip) - 1)
    src = perm[np.repeat(np.arange(len(ip) - 1), np.diff(ip))]
    return CSRGraph.from_edges(len(ip) - 1, src, perm[ix])


def _families():
    return {
        "ER": generators.erdos_renyi(300, 360, seed=1),
        "BA": generators.barabasi_albert(200, 3, seed=1),
        "RMAT": generators.rmat(8, 320, seed=1),
        "chain": generators.chain(50),
        "layered": generators.layered_dag(200, 11, 4, seed=1),
        "sink_heavy": generators.sink_heavy(200, 800, 0.9, seed=1),
        "kron": _kron(9, 16 * 512),
        # uniform random arcs, degree 16 (the ``urand`` benchmark shape)
        "urand": generators.erdos_renyi(512, 16 * 512, seed=1),
    }


# -- zero-overhead invariant -------------------------------------------------

def test_instrument_off_bit_identical_no_retrace_no_extra_dispatch():
    g = generators.erdos_renyi(137, 400, seed=7)
    for method in ("ac4", "ac6"):
        plain = plan(g, method=method)
        inst = plan(g, method=method, instrument=True)
        with obs.recording() as rec_plain:
            r0 = plain.run()
        with obs.recording() as rec_inst:
            r1 = inst.run()
        # bit-identical results
        assert np.array_equal(np.asarray(r0.status), np.asarray(r1.status))
        assert int(r0.rounds) == int(r1.rounds)
        # telemetry only where requested
        assert r0.round_stats is None
        assert r1.round_stats is not None
        # identical dispatch counts, observed two ways
        assert plain.dispatches == inst.dispatches == 1
        assert len(rec_plain.select("dispatch", cat="engine")) == \
            len(rec_inst.select("dispatch", cat="engine")) == 1
        # the instrumented plan has its own cache entry: re-planning
        # un-instrumented hits the existing executable, zero retraces
        again = plan(g, method=method)
        r2 = again.run()
        assert again.traces == 0 and again.dispatches == 1
        assert np.array_equal(np.asarray(r0.status), np.asarray(r2.status))


# -- device round stats vs host oracle ---------------------------------------

@pytest.mark.parametrize("method", list(HOST_ROUNDS))
@pytest.mark.parametrize("family", ["ER", "BA", "RMAT", "chain", "layered",
                                    "sink_heavy", "kron", "urand"])
def test_ac4_round_stats_match_host_oracle(family, method):
    g = _families()[family]
    indptr, indices = g.to_numpy()
    rs = plan(g, method=method, instrument=True).run().round_stats
    hf, he = round_totals(HOST_ROUNDS[method](indptr, indices))
    pf, pe = rs.per_round("r_frontier"), rs.per_round("r_edges")
    r = len(hf)
    assert np.array_equal(pf[:r], hf), (family, method)
    assert np.array_equal(pe[:r], he), (family, method)
    assert pf[r:].sum() == 0 and pe[r:].sum() == 0, (family, method)
    if method == "ac6":                  # each arc examined at most once
        assert he.sum() <= g.m, family
    # status agrees with the trim oracle while we're here
    status = np.asarray(plan(g, method=method).run().status)
    assert np.array_equal(status.astype(bool), trim_oracle(indptr, indices))


def test_round_totals_agree_with_per_worker_counters():
    g = generators.layered_dag(400, 11, 4, seed=3)
    engine = plan(g, method="ac4", workers=8, chunk=1, instrument=True)
    res = engine.run(counters=True)
    pw = np.asarray(res.per_worker_edges).astype(np.int64)
    assert pw.shape == (8,)
    assert int(res.round_stats.total("r_edges")) == int(pw.sum())
    assert int(res.round_stats.total("r_frontier")) == int(res.n_trimmed)


def test_overflow_clamps_keep_totals_exact():
    g = generators.chain(60)                  # 60 rounds to the fixpoint
    full = plan(g, method="ac4", instrument=True).run().round_stats
    tiny = plan(g, method="ac4", instrument=True,
                max_rounds=4).run().round_stats
    assert not full.overflowed and tiny.overflowed
    assert tiny.max_rounds == 4
    for name in ("r_frontier", "r_edges"):
        assert int(tiny.total(name)) == int(full.total(name)), name
    # the tail is folded into the last slot
    pf = tiny.per_round("r_frontier")
    assert pf.shape == (4,) and pf[-1] == full.per_round(
        "r_frontier")[3:].sum()


# -- the other engine families -----------------------------------------------

def test_reach_peel_stream_instrumented_smoke():
    g = generators.erdos_renyi(200, 800, seed=5)

    reach = plan_reach(g, instrument=True)
    seeds = np.zeros(g.n, bool)
    seeds[0] = True
    rr = reach.run(seeds)
    visited = int(np.asarray(rr.mask).sum())
    assert int(rr.round_stats.total("r_frontier")) == visited
    plain = np.asarray(plan_reach(g).run(seeds).mask)
    assert np.array_equal(np.asarray(rr.mask), plain)

    peel = plan_peel(g, instrument=True)
    pr = peel.run(k=1)
    assert pr.round_stats is not None
    assert np.array_equal(np.asarray(pr.status),
                          np.asarray(plan(g, method="ac4").run().status))

    stream = plan_stream(g, capacity=64, instrument=True)
    first = stream.retrim(full=True)
    assert first.round_stats is not None
    assert int(first.round_stats.total("r_frontier")) == int(first.n_trimmed)
    d = stream.delta
    live = ~d._tomb_np
    src, dst = d._src_np[live], d._dst_np[live]
    stream.apply(deletions=(src[:5], dst[:5]))
    got = np.asarray(stream.retrim().status)
    want = np.asarray(plan(stream.snapshot(), method="ac4").run().status)
    assert np.array_equal(got, want)


def test_sharded_instrumented_smoke():
    g = generators.chain(50)                  # 1 device -> 1 shard lane
    engine = plan(g, method="ac6", backend="sharded", instrument=True)
    res = engine.run()
    assert np.array_equal(np.asarray(res.status).astype(bool),
                          trim_oracle(*g.to_numpy()))
    rs = res.round_stats
    assert rs is not None
    assert int(np.asarray(rs.total("r_frontier")).sum()) == int(res.n_trimmed)


def test_scc_decompose_instrumented():
    g = generators.sink_heavy(300, 1200, 0.9, seed=2)
    with obs.recording() as rec:
        labels, stats = scc_decompose(g, counters=True, workers=4, chunk=1,
                                      instrument=True)
    assert same_partition(labels, tarjan_oracle(*g.to_numpy()))
    pw = stats["per_worker_edges"]
    assert pw.shape == (4,)
    assert int(pw.sum()) == stats["trim_edges_traversed"]
    assert stats["trim_rounds"] > 0 and stats["reach_rounds"] >= 0
    gens = rec.select("generation", cat="scc")
    assert len(gens) == stats["generations"]
    assert all("pivots" in sp.attrs for sp in gens)
    assert len(rec.select("dispatch", cat="engine")) > 0
    # uninstrumented driver leaves the telemetry keys None
    _, stats0 = scc_decompose(g)
    assert stats0["trim_rounds"] is None and stats0["reach_rounds"] is None
    assert stats0["per_worker_edges"] is None


# -- span recorder + exporters -----------------------------------------------

def test_recorder_disabled_is_noop():
    rec = obs.get_recorder()
    assert not rec.enabled
    with obs.span("x", cat="t") as sp:
        assert sp is None
    assert obs.instant("y") is None


def test_dispatch_spans_carry_compile_attribution():
    g = generators.erdos_renyi(139, 420, seed=9)   # fresh shape -> compiles
    with obs.recording() as rec:
        engine = plan(g, method="ac4", instrument=True)
        engine.run()
        engine.run()
    spans = rec.select("dispatch", cat="engine", family="trim")
    assert len(spans) == engine.dispatches == 2
    assert spans[0].attrs["phase"] == "compile+execute"
    assert spans[0].attrs["traces"] >= 1
    assert spans[1].attrs["phase"] == "execute"
    assert spans[1].attrs["traces"] == 0
    assert "+stats" in spans[0].attrs["plan"]
    # kernel-selection notes are emitted at trace time only
    kernel_notes = rec.select(cat="kernel")
    assert all(sp.ph == "i" for sp in kernel_notes)


def test_exporters_round_trip(tmp_path):
    rec = obs.Recorder()
    with rec.span("outer", cat="a", k=1):
        with rec.span("inner", cat="b"):
            pass
    rec.instant("mark", cat="a", v="x")
    want = [sp.to_dict() for sp in rec.spans]

    jl = rec.to_jsonl(str(tmp_path / "spans.jsonl"))
    assert obs.read_jsonl(jl) == want

    ct = rec.to_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(ct))
    assert isinstance(doc["traceEvents"], list)
    got = obs.read_chrome_trace(ct)
    assert [(d["name"], d["cat"], d["ph"]) for d in got] == \
        [(d["name"], d["cat"], d["ph"]) for d in want]
    for g_, w in zip(got, want):
        assert g_["ts"] == pytest.approx(w["ts"], abs=1e-9)
        assert g_["dur"] == pytest.approx(w["dur"], abs=1e-9)
        assert g_["attrs"] == w["attrs"]


def test_round_capacity():
    assert obs.round_capacity(5) == 8          # pow2(5 + 2)
    assert obs.round_capacity(10**9) == 1024   # clamped to MAX_ROUND_SLOTS
    assert obs.round_capacity(100, max_rounds=3) == 4
    with pytest.raises(ValueError):
        obs.round_capacity(100, max_rounds=0)


# -- MetricsPlane: labeled metrics, exposition, snapshot ----------------------

def test_histogram_percentiles_exact_vs_numpy():
    plane = obs.MetricsPlane()
    hist = plane.histogram("t_seconds", "test latencies")
    rng = np.random.default_rng(11)
    samples = rng.lognormal(-6, 2, size=500)
    for s in samples:
        hist.observe(float(s), family="trim")
    child = hist.labels(family="trim")
    for q, attr in ((50, "p50"), (95, "p95"), (99, "p99")):
        assert getattr(child, attr) == pytest.approx(
            np.percentile(samples, q), rel=0, abs=0), q
    assert child.count == 500
    assert child.sum == pytest.approx(samples.sum())
    # bucket counts are complete: every sample landed somewhere
    assert sum(child.counts) == 500


def test_histogram_ring_is_bounded():
    plane = obs.MetricsPlane()
    hist = plane.histogram("t_seconds", "", ring=16)
    for i in range(100):
        hist.observe(float(i))
    child = hist.labels()
    assert child.count == 100                  # totals keep everything
    assert len(child.ring) == 16               # percentiles use the window
    assert child.p50 == pytest.approx(np.percentile(np.arange(84, 100), 50))


def test_label_cardinality_cap_folds_into_overflow():
    plane = obs.MetricsPlane()
    c = plane.counter("things", "")
    cap = obs.LABEL_CARDINALITY_CAP
    for i in range(cap + 6):
        c.inc(worker=str(i))
    # cap distinct children + the single overflow child
    assert len(c.children) == cap + 1
    assert c.labels(overflow="true").value == 6
    dropped = plane.families["repro_metric_labels_dropped"]
    assert dropped.labels(metric="things").value == 6


def test_counter_name_rejects_total_suffix():
    plane = obs.MetricsPlane()
    with pytest.raises(ValueError):
        plane.counter("things_total", "")
    with pytest.raises(ValueError):
        plane.counter("bad name", "")
    # kind mismatch on re-registration raises
    plane.counter("x", "")
    with pytest.raises(ValueError):
        plane.gauge("x", "")


def test_openmetrics_exposition_round_trips():
    plane = obs.MetricsPlane()
    plane.counter("repro_dispatches", "dispatch count").inc(
        3, family="trim")
    plane.gauge("repro_engine_live_bytes", "live").set(
        1024, family="trim", component="total")
    h = plane.histogram("repro_dispatch_latency_seconds", "lat")
    h.observe(0.002, family="trim", phase="execute")
    h.observe(3.5, family="trim", phase="compile")
    text = plane.to_openmetrics()
    doc = obs.parse_openmetrics(text)
    # counters are exposed with the _total suffix
    assert doc["repro_dispatches_total"]["type"] == "counter"
    [(s, labels, v)] = doc["repro_dispatches_total"]["samples"]
    assert (labels, v) == ({"family": "trim"}, 3.0)
    assert doc["repro_engine_live_bytes"]["type"] == "gauge"
    hist = doc["repro_dispatch_latency_seconds"]
    assert hist["type"] == "histogram"
    # per child: one _bucket line per bound + +Inf, then _sum and _count
    infs = [(s, labels, v) for s, labels, v in hist["samples"]
            if labels.get("le") == "+Inf"]
    assert [v for _, _, v in infs] == [1.0, 1.0]
    counts = [(labels, v) for s, labels, v in hist["samples"]
              if s.endswith("_count")]
    assert all(v == 1.0 for _, v in counts) and len(counts) == 2
    # bucket counts are cumulative and end at the total
    exec_buckets = [v for s, labels, v in hist["samples"]
                    if s.endswith("_bucket")
                    and labels.get("phase") == "execute"]
    assert exec_buckets == sorted(exec_buckets)


def test_snapshot_round_trip_is_exposition_identical():
    plane = obs.MetricsPlane()
    plane.counter("c", "help c").inc(7, k="v")
    plane.gauge("g", "help g").set(2.5)
    plane.histogram("h_seconds", "help h").observe(0.01, phase="execute")
    snap = json.loads(json.dumps(plane.snapshot()))   # through real JSON
    assert snap["metrics_schema"] == 1
    clone = obs.load_snapshot(snap)
    assert clone.to_openmetrics() == plane.to_openmetrics()
    # percentile state survives too (ring is serialized)
    assert clone.histogram("h_seconds").labels(phase="execute").p50 == \
        pytest.approx(0.01)
    with pytest.raises(ValueError):
        obs.load_snapshot({"metrics_schema": 99, "families": {}})


# -- MetricsPlane: engine integration -----------------------------------------

def test_disabled_plane_zero_overhead_bit_identical():
    """The default (disabled) plane changes nothing: identical status
    bits, identical dispatch/trace counters, zero extra retraces."""
    from repro.core.enginebase import _TRACE_COUNT
    g = generators.erdos_renyi(141, 420, seed=13)
    plan(g, method="ac4", instrument=True).run()   # warm the jit cache
    assert not obs.get_plane().enabled

    off = plan(g, method="ac4", instrument=True)
    before = _TRACE_COUNT[0]
    r_off = off.run()
    d_off = _TRACE_COUNT[0] - before

    with obs.collecting_metrics() as plane:
        on = plan(g, method="ac4", instrument=True)
        before = _TRACE_COUNT[0]
        r_on = on.run()
        d_on = _TRACE_COUNT[0] - before

    assert np.array_equal(np.asarray(r_off.status), np.asarray(r_on.status))
    assert int(r_off.rounds) == int(r_on.rounds)
    assert (off.dispatches, off.traces, d_off) == \
        (on.dispatches, on.traces, d_on) == (1, 0, 0)
    # the disabled path really recorded nothing; the enabled one did
    assert not obs.get_plane().families.get("repro_dispatches")
    assert plane.counter("repro_dispatches").labels(family="trim").value == 1


def test_enabled_plane_collects_dispatch_and_fixpoint_families():
    g = generators.erdos_renyi(143, 430, seed=17)    # fresh shape: compiles
    with obs.collecting_metrics() as plane:
        engine = plan(g, method="ac4", instrument=True)
        engine.run()
        engine.run()
    lat = plane.families["repro_dispatch_latency_seconds"]
    phases = {dict(k).get("phase") for k in lat.children}
    assert phases == {"compile", "execute"}
    assert plane.counter("repro_dispatches").labels(family="trim").value == 2
    assert plane.counter("repro_traces").labels(family="trim").value >= 1
    assert len(plane.families["repro_plan_compiles"].children) == 1
    # fixpoint telemetry folded from RoundStats
    assert plane.counter("repro_fixpoint_rounds").labels(
        family="trim").value > 0
    work = plane.families["repro_fixpoint_work"]
    stats = {dict(k)["stat"] for k in work.children}
    assert {"r_frontier", "r_edges"} <= stats
    # memory accounting: component gauges + a total
    mem = plane.families["repro_engine_live_bytes"]
    comps = {dict(k)["component"] for k in mem.children}
    assert "graph" in comps and "total" in comps
    total = mem.labels(family="trim", component="total").value
    assert total == engine.nbytes() > 0
    # XLA cost analysis stamped per plan
    flops = plane.families["repro_plan_cost_flops"]
    assert all(dict(k)["family"] == "trim" for k in flops.children)
    assert plane.families["repro_plan_cost_bytes"].labels(
        family="trim", plan=engine.plan_signature()).value > 0


def test_engine_nbytes_breakdown_components():
    g = generators.erdos_renyi(200, 800, seed=5)
    engine = plan(g, method="ac4", workers=4, chunk=1)
    engine.run(counters=True)
    bd = engine.nbytes_breakdown()
    assert {"graph", "transpose", "row_ids", "worker_ids"} <= set(bd)
    assert engine.nbytes() == sum(bd.values()) > 0

    stream = plan_stream(g, capacity=64)
    stream.retrim(full=True)
    sbd = stream.nbytes_breakdown()
    assert any(k.startswith("delta_") for k in sbd)
    assert sbd["delta_insert_buffers"] > 0
    assert stream.nbytes() == sum(sbd.values())


def test_retrace_storm_warns_once_and_counts():
    plane = obs.MetricsPlane(retrace_storm_threshold=3)
    plane.note_compile("trim", "p1")
    plane.note_compile("trim", "p1")
    with pytest.warns(obs.RetraceStormWarning):
        plane.note_compile("trim", "p1")
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")               # a second warn would raise
        plane.note_compile("trim", "p1")
    assert plane.counter("repro_retrace_storms").labels(
        family="trim").value == 1
    assert plane.counter("repro_plan_compiles").labels(
        family="trim", plan="p1").value == 4


def test_slo_tracker_breach_counting():
    plane = obs.MetricsPlane()
    slo = obs.SLOTracker(0.010, window=16, min_samples=4, name="tick",
                         plane=plane)
    for _ in range(8):
        assert slo.observe(0.001) is False
    assert slo.breaches == 0 and not slo.breached
    for _ in range(8):
        slo.observe(0.050)                     # p99 now over target
    assert slo.breached and slo.breaches > 0
    assert plane.gauge("repro_slo_p99_seconds").labels(
        slo="tick").value > 0.010
    assert plane.gauge("repro_slo_target_seconds").labels(
        slo="tick").value == pytest.approx(0.010)
    assert plane.counter("repro_slo_breaches").labels(
        slo="tick").value == slo.breaches


def test_metrics_server_serves_openmetrics_and_health():
    import urllib.request
    plane = obs.MetricsPlane()
    plane.counter("repro_dispatches", "").inc(family="trim")
    plane.histogram("repro_dispatch_latency_seconds", "").observe(
        0.001, family="trim", phase="execute")
    server = obs.MetricsServer(0, plane_getter=lambda: plane,
                               health_getter=lambda: {"status": "serving"})
    try:
        base = f"http://127.0.0.1:{server.port}"
        body = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "repro_dispatch_latency_seconds_bucket" in body
        assert "repro_dispatches_total" in body
        assert obs.parse_openmetrics(body)     # scrapeable
        health = json.loads(urllib.request.urlopen(
            f"{base}/healthz").read())
        assert health == {"status": "serving"}
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope")
    finally:
        server.close()


# -- recording(): exception restore + nested tee ------------------------------

def test_recording_restores_previous_recorder_on_exception():
    baseline = obs.get_recorder()
    with pytest.raises(RuntimeError):
        with obs.recording():
            assert obs.get_recorder() is not baseline
            raise RuntimeError("boom")
    assert obs.get_recorder() is baseline
    # nested scopes unwind in order under exceptions too
    with obs.recording() as outer:
        with pytest.raises(RuntimeError):
            with obs.recording():
                raise RuntimeError("inner boom")
        assert obs.get_recorder().spans is outer.spans
    assert obs.get_recorder() is baseline


def test_recording_nested_scopes_tee_spans_to_both():
    with obs.recording() as outer:
        with obs.span("before", cat="t"):
            pass
        with obs.recording() as inner:
            with obs.span("shared", cat="t", k=1):
                pass
            obs.instant("mark", cat="t")
        with obs.span("after", cat="t"):
            pass
    # the inner recorder saw only its own scope
    assert [sp.name for sp in inner.spans] == ["shared", "mark"]
    # the outer recorder saw everything, including the teed copies
    names = [sp.name for sp in outer.spans]
    assert names.count("shared") == 1 and names.count("mark") == 1
    assert "before" in names and "after" in names
    teed = next(sp for sp in outer.spans if sp.name == "shared")
    orig = next(sp for sp in inner.spans if sp.name == "shared")
    assert teed.attrs == orig.attrs
    assert teed.dur == pytest.approx(orig.dur, abs=1e-9)
    # timestamps stay on the outer epoch: ordered with its own spans
    b = next(sp for sp in outer.spans if sp.name == "before")
    a = next(sp for sp in outer.spans if sp.name == "after")
    assert b.ts <= teed.ts <= a.ts


def test_recording_tee_optout():
    with obs.recording() as outer:
        with obs.recording(tee=False) as inner:
            with obs.span("quiet", cat="t"):
                pass
    assert [sp.name for sp in inner.spans] == ["quiet"]
    assert [sp.name for sp in outer.spans] == []


# -- spans on the profiler clock; named device programs -----------------------

def _host_events(log_dir):
    """(name, start_ns, end_ns, stats) of every host-plane event in the one
    ``.xplane.pb`` a profiler trace wrote under ``log_dir``."""
    import glob

    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for pl in ProfileData.from_file(path).planes
            if not pl.name.startswith("/device:")
            for line in pl.lines for ev in line.events]


def test_span_is_a_profiler_annotation_without_a_recorder(tmp_path):
    import jax
    assert not obs.get_recorder().enabled
    with jax.profiler.trace(str(tmp_path)):
        scope = obs.span("x", cat="t", k=7)
        with scope as sp:
            assert sp is None
        with pytest.raises(ValueError):
            with obs.span("raised", cat="t"):
                raise ValueError("boom")
        with obs.span("after", cat="t"):
            pass
    assert scope.seconds > 0
    events = {name: (s, e, st) for name, s, e, st in _host_events(tmp_path)
              if name.startswith("t.")}
    assert set(events) == {"t.x", "t.raised", "t.after"}
    assert events["t.x"][2] == {"k": 7}
    # the span a raise left ends before the next one starts
    assert events["t.raised"][1] <= events["t.after"][0]


def test_disabled_span_formats_no_attribute():
    calls = []

    class Attr:
        def __str__(self):
            calls.append("str")
            return "attr"

        __repr__ = __format__ = __str__

    with obs.span("x", cat="t", a=Attr()):
        pass
    assert calls == []


def test_span_record_and_seconds_share_one_clock():
    with obs.recording() as rec:
        scope = obs.span("x", cat="t")
        with scope:
            pass
    (sp,) = rec.select("x", cat="t")
    assert sp.dur == scope.seconds


def _scc_trace_graph():
    # trimmed vertices, size-≤2 SCCs and pivots in more than one generation
    return generators.sink_heavy(300, 1200, 0.9, seed=2)


def test_scc_spans_reach_the_profiler_trace(tmp_path):
    import jax
    g = _scc_trace_graph()
    scc_decompose(g)                               # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        labels, stats = scc_decompose(g)
    assert same_partition(labels, tarjan_oracle(*g.to_numpy()))
    assert stats["pivots"] > 0 and stats["generations"] >= 2
    events = _host_events(tmp_path)
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[0], []).append(ev)
    for name in ("scc.plan", "scc.transpose", "scc.sync", "scc.trim",
                 "scc.trim2", "scc.reach", "engine.dispatch"):
        assert name in by_name, name
    gens = by_name["scc.generation"]
    assert len(gens) == stats["generations"]
    assert sorted(ev[3]["gen"] for ev in gens) == \
        list(range(1, stats["generations"] + 1))
    assert {ev[3]["dir"] for ev in by_name["scc.reach"]} == {"fw", "bw"}
    # a CPU-resident graph builds Gᵀ by the host counting sort
    assert [ev[3]["where"] for ev in by_name["scc.transpose"]] == ["host"]
    assert stats["transpose_on_device"] == 0

    def inside_generation(ev):
        return any(s <= ev[1] and ev[2] <= e for _, s, e, _ in gens)

    for name in ("scc.trim", "scc.trim2", "scc.reach"):
        assert all(inside_generation(ev) for ev in by_name[name]), name
    # every sync but the final labels read happens inside a generation
    outside = [ev for ev in by_name["scc.sync"] if not inside_generation(ev)]
    assert len(outside) == 1
    assert outside[0][1] >= max(e for _, _, e, _ in gens)
    assert all(inside_generation(ev) for ev in by_name["engine.dispatch"])


def test_scc_call_seconds_are_kept_on_every_call():
    import time
    g = _scc_trace_graph()
    t0 = time.perf_counter()
    _, stats = scc_decompose(g)
    wall = time.perf_counter() - t0
    seconds = [stats[k] for k in ("plan_s", "transpose_s", "sync_s")]
    assert all(isinstance(v, float) and v >= 0 for v in seconds)
    assert stats["transpose_s"] > 0 and stats["sync_s"] > 0
    assert sum(seconds) <= wall
    # the counters are the spans' own durations
    with obs.recording() as rec:
        _, stats = scc_decompose(g)
    for key, name in (("plan_s", "plan"), ("transpose_s", "transpose"),
                      ("sync_s", "sync")):
        spans = rec.select(name, cat="scc")
        assert spans
        assert stats[key] == pytest.approx(sum(sp.dur for sp in spans),
                                           rel=1e-12, abs=1e-12)
    _, empty = scc_decompose(generators.chain(0))
    assert (empty["plan_s"], empty["transpose_s"], empty["sync_s"]) == \
        (0.0, 0.0, 0.0)


@pytest.mark.parametrize("path", ["continue", "pivot-budget", "fault"])
def test_scc_generation_span_closes_on_every_path(path):
    from repro import fault as flt
    g = (generators.layered_dag(200, 11, 4, seed=1) if path == "continue"
         else _scc_trace_graph())
    with obs.recording() as rec:
        if path == "continue":
            # a DAG trims away whole: the first generation leaves by
            # `continue` before any pivot
            _, stats = scc_decompose(g)
            assert stats["pivots"] == 0
        elif path == "pivot-budget":
            with pytest.raises(RuntimeError, match="pivot budget"):
                scc_decompose(g, max_pivots=0)
        else:
            with flt.injecting_faults(
                    flt.FaultSchedule(0, at={"pre-dispatch": [1]})):
                with pytest.raises(flt.DeviceFault):
                    scc_decompose(g)
        with obs.span("after", cat="t"):
            pass
    gens = rec.select("generation", cat="scc")
    assert len(gens) == 1         # recorded, so closed, on the way out
    after = rec.select("after", cat="t")[0]
    assert gens[0].ts + gens[0].dur <= after.ts


def _dispatched_modules(monkeypatch, drive):
    """Module names of the jitted runners ``drive()`` dispatches."""
    import re

    from repro.core.enginebase import EngineBase
    seen = []
    real = EngineBase._dispatch

    def spy(self, fn, *args):
        seen.append((fn, args))
        return real(self, fn, *args)

    monkeypatch.setattr(EngineBase, "_dispatch", spy)
    drive()
    return {re.search(r"module @(\S+)", fn.lower(*args).as_text()).group(1)
            for fn, args in seen}


def _drive(path, g):
    masks = np.ones((2, g.n), bool)
    if path == "trim":                  # kron-s22.trim's call
        plan(g, method="ac6", transpose=g.transpose()).run()
    elif path == "trim-batch":
        plan(g, method="ac6").run_batch(masks)
    elif path == "scc":                 # urand-s18.scc's call
        scc_decompose(g, trim_method="ac6")
    elif path == "reach":
        plan_reach(g, backend="windowed").run(0)
    elif path == "reach-push-batch":
        plan_reach(g, backend="dense").run_batch(np.eye(2, g.n, dtype=bool))
    elif path == "peel":
        plan_peel(g).run()
    elif path == "peel-batch":
        plan_peel(g).run_batch(masks)
    elif path == "stream":
        indptr, indices = g.to_numpy()
        u = int(np.argmax(np.diff(indptr) > 0))     # delete u's first arc
        plan_stream(g).apply(deletions=(np.array([u]),
                                        indices[indptr[u]:indptr[u] + 1]))
    elif path == "sharded":
        plan(g, method="ac6", backend="sharded").run()


@pytest.mark.parametrize("path,modules", [
    ("trim", {"jit_trim_ac6"}),
    ("trim-batch", {"jit_trim_ac6_batch"}),
    ("scc", {"jit_trim_ac6_batch", "jit_reach_pull_batch"}),
    ("reach", {"jit_reach_pull"}),
    ("reach-push-batch", {"jit_reach_push_batch"}),
    ("peel", {"jit_peel_bucket"}),
    ("peel-batch", {"jit_peel_bucket_batch"}),
    ("stream", {"jit_stream_ac4"}),
    ("sharded", {"jit_trim_ac6_sharded"}),
])
def test_jitted_runners_name_their_modules(monkeypatch, path, modules):
    g = generators.erdos_renyi(200, 800, seed=3)
    assert _dispatched_modules(monkeypatch, lambda: _drive(path, g)) == \
        modules


def test_trim2_runner_names_its_module():
    import jax.numpy as jnp

    from repro.core.scc import _trim2_runner
    g = generators.erdos_renyi(200, 800, seed=3)
    gt = g.transpose()
    text = _trim2_runner().lower(g.indptr, g.indices, gt.indptr,
                                 gt.indices, jnp.ones((2, g.n), bool)
                                 ).as_text()
    assert "module @jit_scc_trim2_batch " in text
