"""``bench/run.py`` driven end to end on the CPU at scale 10, with the
Pallas kernels in interpret mode: every cell as committed, a cell made of
files the harness has never seen, the refusals, and the faults that
``correct`` must catch."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import trace
from bench.tests.conftest import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
SUMMARY = trace.Summary(window_s=2.0, busy_s=1.5, devices=1,
                        device_ops=[["fusion.1", 1.5]],
                        idle_gaps=[["bench.pull", 0.5]])


def run_cell(run, root, cell, traced, monkeypatch, seed=2**31 + 11):
    if traced:
        monkeypatch.setattr(trace, "summarize", lambda path: SUMMARY)
    return run.main(["--workload", cell, "--seed", str(seed),
                     "--seconds", "0.5", "--trace", str(int(traced))],
                    root=root)


def expected(cell, traced):
    if traced:
        return {m["name"] for m in SPEC["per_layer"]
                if cell in m["workloads"]}
    return {m["name"] for m in SPEC["end_to_end"]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(off_chip, small_root, monkeypatch, cell,
                                  traced, capsys):
    from repro import obs
    with obs.recording() as rec:
        result = run_cell(off_chip, small_root, cell, traced, monkeypatch)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == expected(cell, traced)
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in result["checks"].values())
    # dense AC-6 calls no Pallas kernel; scc_decompose's run interpreted
    assert {sp.attrs.get("interpret") for sp in rec.select(cat="kernel")} \
        <= {True}
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert err.strip().splitlines()[-1].startswith("check ")
    if traced:
        assert result["device"]["busy_s"] == SUMMARY.busy_s
        assert result["breakdown"] == SUMMARY.breakdown()


GENERATOR = '''"""Test generator: a directed ring on half the vertices,
each with one arc out to a vertex of the other half, which dies."""
import jax.numpy as jnp


def arcs(config, key):
    n = 1 << int(config["scale"])
    ring = jnp.arange(n // 2, dtype=jnp.int32)
    return (n, jnp.concatenate([ring, ring]),
            jnp.concatenate([(ring + 1) % (n // 2), ring + n // 2]))
'''

ENTRY = '''"""Test entry: AC-4 trim, counting the vertices it settles."""
import numpy as np

from bench import reference

LIMITS = {"live_mismatch": 0}


class Loop:
    def __init__(self, g, gt, mix, seed):
        from repro.core import plan
        self.engine = plan(g, transpose=gt, method=mix["method"])
        self.n = g.n

    def call(self):
        return np.asarray(self.engine.run().status), {"vertices": self.n}


def reference_answer(graph, transpose):
    return reference.host_trim(*graph, *transpose)[0]


def control(graph, transpose):
    return reference.trim_control(*graph, *transpose)


def compare(answer, ref):
    return {"live_mismatch": reference.status_mismatch(answer, ref)}
'''

RATE = '''def read(ctx):
    return sum(c["vertices"] for c in ctx.counts) / ctx.window_s
'''


def test_new_cell_from_files_alone(off_chip, small_root, monkeypatch):
    """A generator, a configuration, an entry, a mix, an end-to-end rate
    and a per-layer metric added as files, with their entries in
    BENCHMARK.json, run with no change to any file the harness has."""
    bench = small_root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "generators" / "ring_tail.py").write_text(GENERATOR)
    (bench / "entries" / "settle.py").write_text(ENTRY)
    (bench / "configs" / "ring-s9.json").write_text(json.dumps(
        {"generator": "ring_tail", "scale": 9, "structure_seed": 1}))
    (bench / "traffic" / "settle_loop.json").write_text(json.dumps(
        {"entry": "settle", "method": "ac4"}))
    (bench / "metrics" / "settled_per_s.py").write_text(RATE)
    (bench / "metrics" / "dummy_calls.py").write_text(
        "def read(ctx):\n    return float(ctx.calls)\n")
    spec = json.loads((small_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "ring-s9", "source": "test",
                            "file": "bench/configs/ring-s9.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "ring-s9.settle", "config": "ring-s9",
                              "traffic": "settle_loop", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "settled_per_s", "unit": "vertices/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["ring-s9.settle"]})
    spec["per_layer"].append({"name": "dummy_calls", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "settled_per_s"})
    (small_root / "BENCHMARK.json").write_text(json.dumps(spec))
    plain = run_cell(off_chip, small_root, "ring-s9.settle", False,
                     monkeypatch)
    assert plain["correct"]
    assert plain["checks"] == {"live_mismatch": {"value": 0, "limit": 0}}
    assert set(plain["metrics"]) == {"settled_per_s", "setup_s"}
    assert plain["metrics"]["settled_per_s"]["unit"] == "vertices/s"
    assert plain["metrics"]["settled_per_s"]["value"] > 512
    traced = run_cell(off_chip, small_root, "ring-s9.settle", True,
                      monkeypatch)
    assert traced["correct"]
    assert traced["metrics"] == {"dummy_calls": {
        "value": traced["attempted"], "unit": "calls"}}
    assert all(p.read_bytes() == data for p, data in before.items())


def test_missing_metric_file(small_root):
    """A metric of BENCHMARK.json without its reader is refused before
    anything runs."""
    from bench import run
    spec = json.loads((small_root / "BENCHMARK.json").read_text())
    spec["end_to_end"].append({"name": "no_reader_s", "unit": "s",
                               "better": "lower", "bound": 0.05,
                               "source": "host_clock"})
    (small_root / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(SystemExit, match="bench/metrics/no_reader_s.py"):
        run.load_cell(small_root, CELLS[0])


@pytest.mark.parametrize("cell", CELLS)
def test_readings(off_chip, small_root, cell, capsys):
    from bench import readings
    readings.main(["--workload", cell, "--seeds", "3,4"], root=small_root)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["seed"] for x in lines] == [3, 4]
    for x in lines:
        assert all(v == 0 for v in x["program"].values())
        assert any(v > 0 for v in x["control"].values())


def test_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_refuses_unknown_device_kind(small_root):
    from bench import run
    with pytest.raises(SystemExit, match="not in bench/peaks.json"):
        run.peak_of(small_root, "TPU v9 imaginary")


def test_refuses_unknown_workload(small_root):
    from bench import run
    with pytest.raises(SystemExit, match="no workload"):
        run.load_cell(small_root, "no-such.cell")


# -- faults planted under the timed path: each must make `correct` false --

def _trim_fault(kind):
    from repro.core import TrimEngine, TrimResult
    run = TrimEngine.run

    def broken(self, *args, **kwargs):
        res = run(self, *args, **kwargs)
        status = np.asarray(res.status).copy()
        if kind == "unchanged":         # the input state, all live
            status[:] = 1
        elif kind == "half":            # half the vertices never computed
            status[len(status) // 2:] = 1
        else:                           # one answer altered
            status[np.flatnonzero(status == 0)[0]] = 1
        return TrimResult(status=status, rounds=res.rounds,
                          per_worker_edges=res.per_worker_edges)
    return TrimEngine, "run", broken


def _scc_fault(kind):
    from repro.core import scc
    decompose = scc.scc_decompose

    def broken(*args, **kwargs):
        labels, stats = decompose(*args, **kwargs)
        labels = labels.copy()
        if kind == "unchanged":         # scc_decompose's initial labels
            labels[:] = -1
        elif kind == "half":
            labels[len(labels) // 2:] = -1
        else:                           # one vertex moved to its own class
            labels[0] = labels.max() + 1
        return labels, stats
    return scc, "scc_decompose", broken


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(off_chip, small_root, monkeypatch, cell, kind):
    traffic = next(w["traffic"] for w in SPEC["workloads"]
                   if w["name"] == cell)
    entry = json.loads((ROOT / "bench" / "traffic"
                        / f"{traffic}.json").read_text())["entry"]
    target, attr, broken = {"trim": _trim_fault,
                            "scc": _scc_fault}[entry](kind)
    monkeypatch.setattr(target, attr, broken)
    result = run_cell(off_chip, small_root, cell, False, monkeypatch)
    assert not result["correct"]
    assert any(c["value"] > c["limit"] for c in result["checks"].values())
