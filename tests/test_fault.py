"""Chaos/conformance suite for the FaultPlane (DESIGN.md §14).

Three contracts, asserted across all four engine families:

1. **Zero overhead / zero perturbation.**  The disabled plane — and an
   installed-but-never-firing schedule — are bit-identical to a build
   without the plane: same results, same dispatch counts, same trace
   counts.
2. **Deterministic injection, bounded recovery.**  A seeded
   ``FaultSchedule`` fires the same faults on replay; every fault point
   has a recovery strategy (retry / restore-from-checkpoint / skip) that
   reproduces the uninterrupted run bit-identically — masks *and*
   counters — and retries are hard-bounded.
3. **Durable checkpoints.**  A fault (or kill) during a checkpoint write
   can never corrupt the latest good step: writes are atomic tmp-dir
   renames, ``latest_step`` ignores torn ``.tmp`` dirs, and the
   ``AsyncCheckpointer`` flushes on close/exit.
"""
import os
import signal
import tempfile
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import fault as flt
from repro.core import plan, plan_peel, plan_stream
from repro.core.reach import plan_reach
from repro.core.scc import scc_decompose
from repro.graphs import generators
from repro.fault import checkpoint as ckpt_lib


def _er(n=64, m=256, seed=3):
    return generators.erdos_renyi(n, m, seed=seed, simple=True)


# -- the schedule: deterministic, replayable ----------------------------------

def test_schedule_replayable_and_bounded():
    kw = dict(rate=0.5, points=("pre-dispatch", "checkpoint-write"))
    a = flt.FaultSchedule(7, **kw)
    b = flt.FaultSchedule(7, **kw)
    fires_a = [(p, c) for p in kw["points"] for c in range(1, 40)
               if a.should_fire(p, c)]
    fires_b = [(p, c) for p in kw["points"] for c in range(1, 40)
               if b.should_fire(p, c)]
    assert fires_a and fires_a == fires_b   # same seed -> same faults
    c = flt.FaultSchedule(8, **kw)
    fires_c = [(p, cnt) for p in kw["points"] for cnt in range(1, 40)
               if c.should_fire(p, cnt)]
    assert fires_a != fires_c               # different seed -> different
    d = flt.FaultSchedule(7, rate=1.0, max_faults=3)
    n = sum(d.should_fire("pre-dispatch", i) for i in range(1, 100))
    assert n == 3                           # budget is a hard cap


def test_fault_kinds():
    assert issubclass(flt.DeviceFault, RuntimeError)
    assert issubclass(flt.IOFault, OSError)
    for p in flt.FAULT_POINTS:
        kind = flt.fault_kind(p)
        assert kind is (flt.IOFault if p in flt.IO_POINTS
                        else flt.DeviceFault)
    with pytest.raises(ValueError):
        flt.FaultPlane(flt.FaultSchedule()).arm("no-such-point")


# -- contract 1: the disabled/inert plane perturbs nothing --------------------

def test_zero_perturbation_when_not_firing():
    g = _er()
    plan(g, method="ac4").run()          # warm the process jit cache
    base = plan(g, method="ac4")
    want = np.asarray(base.run().status)
    assert not flt.get_fault_plane().enabled
    with flt.injecting_faults() as plane:    # enabled, inert schedule
        assert plane.enabled
        armed = plan(g, method="ac4")
        got = np.asarray(armed.run().status)
    assert np.array_equal(got, want)
    assert armed.dispatches == base.dispatches
    assert armed.traces == base.traces
    # the armed run counted its armings but fired nothing
    assert plane.armings["pre-dispatch"] == 1
    assert plane.armings["post-dispatch"] == 1
    assert not plane.injected
    # and the global plane is restored on scope exit
    assert not flt.get_fault_plane().enabled


# -- contract 2: fault x family recovery matrix -------------------------------

def _run_trim(g):
    e = plan(g, method="ac4")
    return e, lambda: np.asarray(e.run().status)


def _run_reach(g):
    e = plan_reach(g)
    seeds = np.arange(g.n) % 3 == 0
    return e, lambda: np.asarray(e.run(seeds).mask)


def _run_peel(g):
    e = plan_peel(g)
    return e, lambda: np.asarray(e.run().coreness)


def _run_stream(g):
    e = plan_stream(g, capacity=64)
    return e, lambda: np.asarray(e.retrim(full=True).status)


PURE_FAMILIES = {"trim": _run_trim, "reach": _run_reach, "peel": _run_peel,
                 "stream": _run_stream}


@pytest.mark.parametrize("point", ["pre-dispatch", "post-dispatch"])
@pytest.mark.parametrize("family", sorted(PURE_FAMILIES))
def test_dispatch_fault_retry_bit_identical(family, point):
    """An injected dispatch fault, retried, reproduces the clean run
    bit-identically — result arrays AND the dispatch/trace accounting
    (post-dispatch arms before the counters commit, so a retried
    dispatch is indistinguishable from a fault-free one)."""
    g = _er(seed=11)
    PURE_FAMILIES[family](g)[1]()   # warm the process-wide jit cache
    clean_engine, clean_run = PURE_FAMILIES[family](g)
    want = clean_run()
    chaos_engine, chaos_run = PURE_FAMILIES[family](g)
    with flt.injecting_faults(
            flt.FaultSchedule(0, at={point: [1]})) as plane:
        got = flt.call_with_retries(chaos_run, retries=2,
                                    sleep=lambda _: None)
    assert np.array_equal(got, want), (family, point)
    assert plane.injected[point] == 1
    assert plane.recoveries[(point, "retry")] == 1
    assert chaos_engine.dispatches == clean_engine.dispatches
    assert chaos_engine.traces == clean_engine.traces


def test_retries_hard_bounded():
    g = _er()
    e = plan(g, method="ac4")
    calls = []
    with flt.injecting_faults(flt.FaultSchedule(0, rate=1.0)) as plane:
        with pytest.raises(flt.DeviceFault):
            flt.call_with_retries(lambda: (calls.append(1), e.run()),
                                  retries=3, sleep=lambda _: None)
    assert len(calls) == 4                  # retries + 1, not one more
    assert plane.armings["pre-dispatch"] == 4
    assert not plane.recoveries


def test_mid_update_batch_is_retry_safe():
    """``mid-update-batch`` fires after validation but before any host
    mirror moved, so simply re-calling ``apply`` with the same batch is a
    correct recovery — no checkpoint needed."""
    g = _er(seed=5)
    ref = plan_stream(g, capacity=64)
    chaos = plan_stream(g, capacity=64)
    src, dst = ref.delta._src_np.copy(), ref.delta._dst_np.copy()
    batches = [(src[:7], dst[:7]), (src[9:12], dst[9:12])]
    for s, d in batches:
        ref.apply(deletions=(s, d))
    with flt.injecting_faults(
            flt.FaultSchedule(0, at={"mid-update-batch": [2]})) as plane:
        for s, d in batches:
            flt.call_with_retries(
                lambda s=s, d=d: chaos.apply(deletions=(s, d)),
                retries=2, sleep=lambda _: None)
    assert plane.injected["mid-update-batch"] == 1
    assert np.array_equal(np.asarray(chaos._state[0]),
                          np.asarray(ref._state[0]))
    assert np.array_equal(np.asarray(chaos._state[1]),
                          np.asarray(ref._state[1]))
    assert chaos.delta.n_tomb == ref.delta.n_tomb


def test_stream_dispatch_fault_recovers_via_checkpoint(tmp_path):
    """A pre-dispatch fault on the stream engine is NOT retry-safe (host
    mirrors already moved): the recovery path is restore-from-checkpoint
    and re-apply, which is bit-identical to the uninterrupted engine —
    status, AC-4 counters, and overlay state."""
    g = _er(seed=8)
    ref = plan_stream(g, capacity=64)
    chaos = plan_stream(g, capacity=64)
    src, dst = ref.delta._src_np.copy(), ref.delta._dst_np.copy()
    ref.apply(deletions=(src[:9], dst[:9]))
    chaos.apply(deletions=(src[:9], dst[:9]))
    d = str(tmp_path / "ck")
    flt.save_engine(d, chaos, step=1)
    with flt.injecting_faults(
            flt.FaultSchedule(0, at={"pre-dispatch": [1]})) as plane:
        with pytest.raises(flt.DeviceFault):
            chaos.apply(deletions=(src[20:25], dst[20:25]))
    assert plane.injected["pre-dispatch"] == 1
    restored, step, _, _ = flt.restore_engine(d)
    assert step == 1
    ref.apply(deletions=(src[20:25], dst[20:25]))
    restored.apply(deletions=(src[20:25], dst[20:25]))
    for a, b in ((restored._state[0], ref._state[0]),
                 (restored._state[1], ref._state[1]),
                 (restored.delta.tomb, ref.delta.tomb)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(restored.retrim().status),
                          np.asarray(ref.retrim().status))
    assert restored.dispatches == ref.dispatches


# -- checkpoint protocol across families --------------------------------------

@pytest.mark.parametrize("family", sorted(PURE_FAMILIES))
def test_checkpoint_roundtrip_bit_identical(family, tmp_path):
    g = _er(seed=13)
    engine, run = PURE_FAMILIES[family](g)
    want = run()
    d = str(tmp_path / "ck")
    flt.save_engine(d, engine, step=3)
    restored, step, _, meta = flt.restore_engine(d)
    assert step == 3 and meta["engine"]["family"] == engine.family
    assert restored.dispatches == engine.dispatches
    assert restored.traces == engine.traces
    # run the restored engine through the family's entry point
    got = {"trim": lambda: np.asarray(restored.run().status),
           "reach": lambda: np.asarray(
               restored.run(np.arange(g.n) % 3 == 0).mask),
           "peel": lambda: np.asarray(restored.run().coreness),
           "stream": lambda: np.asarray(
               restored.retrim(full=True).status)}[family]()
    assert np.array_equal(got, want), family


def test_checkpoint_family_mismatch_rejected(tmp_path):
    g = _er()
    e = plan(g, method="ac4")
    with pytest.raises(ValueError, match="family"):
        e.load_state(e.state_dict(), {"family": "peel"})


def test_sharded_trim_not_checkpointable():
    g = _er()
    e = plan(g, method="ac4", backend="sharded", unmasked=True)
    if e.mesh is None:
        pytest.skip("no mesh on this host")
    with pytest.raises(ValueError, match="not checkpointable"):
        e.state_meta()


# -- contract 3: durable checkpoint writes ------------------------------------

def test_checkpoint_roundtrip_and_prune():
    with tempfile.TemporaryDirectory() as d:
        tree = {"a": jnp.arange(6.0).reshape(2, 3),
                "b": {"c": jnp.ones((4,), jnp.int32)}}
        for step in (1, 2, 3, 4):
            ckpt_lib.save(d, step, tree, metadata={"s": step})
        ckpt_lib.prune(d, keep=2)
        assert ckpt_lib.latest_step(d) == 4
        got, step, meta = ckpt_lib.restore(d, tree)
        assert step == 4 and meta["s"] == 4
        np.testing.assert_array_equal(got["a"], tree["a"])
        assert len([x for x in os.listdir(d) if x.startswith("step_")]) == 2


def test_async_checkpointer():
    with tempfile.TemporaryDirectory() as d:
        ac = ckpt_lib.AsyncCheckpointer(d, keep=2)
        tree = {"w": jnp.ones((8, 8))}
        ac.save(1, tree)
        ac.save(2, tree)
        ac.wait()
        assert ckpt_lib.latest_step(d) == 2
        ac.close()


def test_checkpoint_write_fault_preserves_latest(tmp_path):
    g = _er()
    e = plan(g, method="ac4")
    want = np.asarray(e.run().status)
    d = str(tmp_path / "ck")
    flt.save_engine(d, e, step=1)
    with flt.injecting_faults(
            flt.FaultSchedule(0, at={"checkpoint-write": [1]})):
        with pytest.raises(flt.IOFault):
            flt.save_engine(d, e, step=2)
    assert ckpt_lib.latest_step(d) == 1     # step 2 never became visible
    restored, step, _, _ = flt.restore_engine(d)
    assert step == 1
    assert np.array_equal(np.asarray(restored.run().status), want)


def test_torn_tmp_dir_is_invisible(tmp_path):
    """A ``step_*.tmp`` dir (a write killed mid-flight) is ignored by
    ``latest_step`` and cleaned up by the next save of that step."""
    g = _er()
    e = plan(g, method="ac4")
    d = str(tmp_path / "ck")
    flt.save_engine(d, e, step=1)
    torn = os.path.join(d, "step_00000002.tmp")
    os.makedirs(torn)
    with open(os.path.join(torn, "garbage.npy"), "w") as f:
        f.write("not a checkpoint")
    assert ckpt_lib.latest_step(d) == 1
    restored, step, _, _ = flt.restore_engine(d)
    assert step == 1
    flt.save_engine(d, e, step=2)           # overwrites the torn tmp
    assert ckpt_lib.latest_step(d) == 2
    assert not os.path.exists(torn)


def test_async_checkpointer_flushes_on_close(tmp_path):
    d = str(tmp_path / "ck")
    ck = ckpt_lib.AsyncCheckpointer(d)
    ck.save(1, {"x": np.arange(5)})
    ck.close()                              # must flush the queued write
    tree, step, _ = ckpt_lib.load_flat(d)
    assert step == 1 and np.array_equal(tree["x"], np.arange(5))
    ck.close()                              # idempotent
    with pytest.raises(RuntimeError):
        ck.save(2, {"x": np.arange(5)})     # closed writer refuses work


def test_async_checkpointer_error_surfaced_once(tmp_path):
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("file where the ckpt dir should go")
    ck = ckpt_lib.AsyncCheckpointer(str(blocker))
    ck.save(1, {"x": np.arange(3)})
    with pytest.raises(OSError):
        ck.wait()                           # the write error surfaces...
    ck.wait()                               # ...exactly once
    ck.close()


# -- the SCC driver: generation-level checkpoint/resume -----------------------

def _scc_graph():
    return generators.rmat(6, 400, seed=2)


def test_scc_checkpoint_resume_after_fault(tmp_path):
    g = _scc_graph()
    labels_clean, stats_clean = scc_decompose(g)
    assert stats_clean["generations"] >= 2  # resume needs a mid-point
    d = str(tmp_path / "ck")
    # probe how many dispatches a checkpointed run issues (inert plane
    # counts armings without firing), then fault the *last* one — by
    # then at least one generation checkpoint is on disk
    with flt.injecting_faults() as probe:
        scc_decompose(g, checkpoint_dir=str(tmp_path / "probe"),
                      checkpoint_every=1)
    total = probe.armings["pre-dispatch"]
    assert total >= 2
    fired = False
    with flt.injecting_faults(
            flt.FaultSchedule(0, at={"pre-dispatch": [total]})):
        try:
            scc_decompose(g, checkpoint_dir=d, checkpoint_every=1)
        except flt.DeviceFault:
            fired = True
    assert fired and ckpt_lib.latest_step(d) is not None
    labels, stats = scc_decompose(g, checkpoint_dir=d, checkpoint_every=1,
                                  resume=True)
    assert np.array_equal(labels, labels_clean)
    assert stats["generations"] == stats_clean["generations"]
    assert stats["pivots"] == stats_clean["pivots"]


def test_scc_checkpointing_does_not_change_labels(tmp_path):
    g = _scc_graph()
    labels_clean, _ = scc_decompose(g)
    d = str(tmp_path / "ck")
    labels, _ = scc_decompose(g, checkpoint_dir=d, checkpoint_every=1)
    assert np.array_equal(labels, labels_clean)
    assert ckpt_lib.latest_step(d) is not None   # final state was saved


# -- the serving loop: recovery tiers, SIGTERM drain, metrics faults ----------

def _serve(tmp_path, **kw):
    from repro.launch.serve import serve_trim_stream
    return serve_trim_stream("chain", batch=32, seed=0, **kw)


def test_serve_resume_bit_identical(tmp_path):
    """Stopping the serve loop and restarting from its checkpoint lands
    in exactly the state of an uninterrupted run — engine status, AC-4
    counters, overlay, and the feed's own RNG/alive/pending state."""
    da, db = str(tmp_path / "a"), str(tmp_path / "b")
    _serve(tmp_path, ticks=8, checkpoint_dir=da, checkpoint_every=100)
    _serve(tmp_path, ticks=3, checkpoint_dir=db, checkpoint_every=100)
    _serve(tmp_path, ticks=8, checkpoint_dir=db, checkpoint_every=100)
    ta, sa, ma = ckpt_lib.load_flat(da)
    tb, sb, mb = ckpt_lib.load_flat(db)
    assert sa == sb == 8
    assert ma["feed"]["dirty_ticks"] == mb["feed"]["dirty_ticks"]
    for key in ("status", "counters", "tomb", "ins_alive", "feed_alive",
                "feed_pending", "feed_pending_lens"):
        assert np.array_equal(ta[key], tb[key]), key
    assert ma["feed"]["rng_state"] == mb["feed"]["rng_state"]


def test_serve_chaos_run_survives_and_recovers(tmp_path):
    d = str(tmp_path / "ck")
    with flt.injecting_faults(
            flt.FaultSchedule(11, rate=0.08)) as plane:
        engine = _serve(tmp_path, ticks=8, checkpoint_dir=d,
                        checkpoint_every=2, retries=8)
    assert engine is not None
    assert sum(plane.injected.values()) > 0      # chaos actually happened
    assert sum(plane.recoveries.values()) > 0    # ...and was recovered
    assert ckpt_lib.latest_step(d) == 8          # final checkpoint


def test_serve_sigterm_drains_cleanly(tmp_path):
    """SIGTERM mid-feed: the loop breaks at a tick boundary, writes a
    final checkpoint, stops the metrics daemon thread, and returns."""
    d = str(tmp_path / "ck")

    def _kill_once_checkpointed():
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if ckpt_lib.latest_step(d) is not None:
                os.kill(os.getpid(), signal.SIGTERM)
                return
            time.sleep(0.02)

    killer = threading.Thread(target=_kill_once_checkpointed, daemon=True)
    killer.start()
    engine = _serve(tmp_path, ticks=10_000, checkpoint_dir=d,
                    checkpoint_every=2, metrics_port=0)
    killer.join(timeout=60)
    assert engine is not None                    # clean return, no raise
    last = ckpt_lib.latest_step(d)
    assert last is not None and last < 10_000    # drained early
    _, _, meta = ckpt_lib.load_flat(d)           # final ckpt is loadable
    assert meta["feed"]["tick"] == last
    assert not any(t.name == "repro-metrics"     # daemon stopped
                   for t in threading.enumerate())


def test_metrics_server_fault_returns_503():
    import urllib.error
    import urllib.request

    from repro import obs
    plane = obs.MetricsPlane()
    server = obs.MetricsServer(0, plane_getter=lambda: plane)
    base = f"http://127.0.0.1:{server.port}"
    try:
        with flt.injecting_faults(
                flt.FaultSchedule(0, at={"metrics-server": [1]})) as fp:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/metrics")
            assert ei.value.code == 503
            resp = urllib.request.urlopen(f"{base}/metrics")
            assert resp.status == 200            # next scrape succeeds
        assert fp.injected["metrics-server"] == 1
        assert fp.armings["metrics-server"] == 2
    finally:
        server.close()


@pytest.mark.slow
def test_serve_sigkill_subprocess_resumes_bit_identical(tmp_path):
    """The acceptance scenario: SIGKILL the serve process mid-soak, then
    restart it with the same ``--checkpoint-dir`` — the resumed process
    finishes the feed and its final checkpoint is bit-identical to an
    uninterrupted process run."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")

    def cmd(d, ticks):
        return [sys.executable, "-m", "repro.launch.serve",
                "--graph", "chain", "--ticks", str(ticks),
                "--update-batch", "32", "--checkpoint-dir", d,
                "--checkpoint-every", "2"]

    da, db = str(tmp_path / "a"), str(tmp_path / "b")
    subprocess.run(cmd(da, 8), env=env, check=True, timeout=300,
                   capture_output=True)
    proc = subprocess.Popen(cmd(db, 8), env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            step = ckpt_lib.latest_step(db)
            if step is not None and 0 < step < 8:
                break
            if proc.poll() is not None:
                break
            time.sleep(0.05)
        proc.kill()                              # SIGKILL: no cleanup
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert ckpt_lib.latest_step(db) is not None, "no checkpoint before kill"
    subprocess.run(cmd(db, 8), env=env, check=True, timeout=300,
                   capture_output=True)
    ta, sa, _ = ckpt_lib.load_flat(da)
    tb, sb, _ = ckpt_lib.load_flat(db)
    assert sa == sb == 8
    for key in ("status", "counters", "feed_alive"):
        assert np.array_equal(ta[key], tb[key]), key
