"""The benchmark's own tests, on the CPU: ``python -m pytest -q bench/tests``
from the root of the checkout."""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")


class FakeTpu:
    """A CPU device dressed as one v5e, for driving a run off the chip."""

    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


@pytest.fixture
def small_root(tmp_path):
    """A copy of the benchmark with every configuration cut to scale 10."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in (tmp_path / "bench" / "configs").glob("*.json"):
        config = json.loads(path.read_text())
        config["scale"] = 10
        path.write_text(json.dumps(config))
    return tmp_path


@pytest.fixture
def off_chip(monkeypatch):
    """Runs ``bench.run`` on the CPU: a fake v5e, the Pallas kernels forced
    on in interpret mode, and no persistent compile cache."""
    from bench import run
    from repro.kernels import ops
    from repro.launch import compile_cache

    def interpreted(kernel, use_kernel):
        ops.obs.note_kernel(kernel, use_kernel=True, interpret=True)
        return True, True

    monkeypatch.setattr(run, "accelerator", lambda chips: [FakeTpu()] * chips)
    monkeypatch.setattr(ops, "_select", interpreted)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    return run
