"""The metric readers under ``bench/metrics/``, the roofline byte model and
the table of peaks."""
import json
from types import SimpleNamespace

import pytest

from bench import find, trace
from bench.tests.conftest import ROOT

V5E = json.loads((ROOT / "bench" / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


def read(name, **ctx):
    base = dict(counts=[], calls=0, probe=None, trace=None, n=0, m=0,
                peak=V5E, setup_s=0.0, window_s=0.0)
    return find.module(ROOT, "metrics", name).read(
        SimpleNamespace(**{**base, **ctx}))


def summary(busy_s, window_s):
    return trace.Summary(window_s=window_s, busy_s=busy_s, devices=1,
                         device_ops=[], idle_gaps=[])


def test_peaks_table():
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["bf16_flops_per_s"] == 197e12


@pytest.mark.parametrize("name,n,m,want", [
    # read indptr (n+1) and indices (m) at 4 bytes, write n status bytes
    ("trim_roofline", 3, 5, 4 * 4 + 4 * 5 + 3),
    ("trim_roofline", 4194304, 67108864, 289406980),
    # read G and Gᵀ, each 4(n+1) + 4m bytes, write 4-byte labels
    ("scc_roofline", 3, 5, 2 * (16 + 20) + 12),
    ("scc_roofline", 4194304, 67108864, 587202568),
])
def test_least_bytes(name, n, m, want):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    scope = {}
    exec(path.read_text(), scope)
    assert scope["least_bytes"](n, m) == want


def test_roofline_share():
    # 819 bytes take 1 ns at 819 GB/s; 2 calls in 4 ns busy: 2 ns a call
    n, m = 0, 204     # trim: 4 + 816 + 0 = 820 bytes
    share = read("trim_roofline", n=n, m=m, calls=2,
                 trace=summary(4e-9, 1e-8))
    assert share == pytest.approx(100 * (820 / 819e9) / 2e-9)
    assert read("trim_roofline", n=n, m=m, calls=2) is None
    assert read("scc_roofline", n=n, m=m, calls=0,
                trace=summary(4e-9, 1e-8)) is None


@pytest.mark.parametrize("name", ["idle_share.trim", "idle_share.scc"])
def test_idle_share(name):
    assert read(name, trace=summary(0.75, 1.0)) == pytest.approx(25.0)
    assert read(name) is None


@pytest.mark.parametrize("name,key", [("rounds.trim", "rounds"),
                                      ("edges.trim", "edges")])
def test_counts(name, key):
    assert read(name, counts=[{key: 4}, {key: 6}]) == 5
    assert read(name, counts=[{key: None}]) is None
    assert read(name) is None


def test_reach_rounds_from_the_probe():
    """Read from the one instrumented call after the window, never from
    the timed calls' counts."""
    assert read("reach_rounds.scc", probe={"reach_rounds": 15}) == 15
    assert read("reach_rounds.scc", counts=[{"reach_rounds": 3}]) is None
    assert read("reach_rounds.scc", probe={"reach_rounds": None}) is None


@pytest.mark.parametrize("name", ["trim_s", "scc_s"])
def test_seconds_per_call(name):
    """The window's whole wall time over the calls completed in it."""
    assert read(name, window_s=30.5, calls=10) == pytest.approx(3.05)
    assert read(name, window_s=30.5, calls=0) == pytest.approx(30.5)


def test_setup():
    assert read("setup_s", setup_s=8.9) == 8.9
