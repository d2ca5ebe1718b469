"""``chip_smoke.py`` off the chip: it must refuse to run without a TPU,
and its plain host references must agree with the repo's own oracles."""
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


SMALL = {
    "ER": lambda g: g.erdos_renyi(400, 900, seed=5),
    "BA": lambda g: g.barabasi_albert(300, deg=3, seed=5),
    "RMAT": lambda g: g.rmat(9, 4096, seed=5),
    "chain": lambda g: g.chain(200),
    "layered": lambda g: g.layered_dag(400, layers=9, seed=5),
    "sink_heavy": lambda g: g.sink_heavy(400, 1600, sink_frac=0.8, seed=5),
}


@pytest.mark.parametrize("family", sorted(SMALL))
def test_host_trim_matches_oracle(family):
    from repro.core import trim_oracle
    from repro.graphs import generators
    g = SMALL[family](generators)
    indptr, indices = g.to_numpy()
    t_indptr, t_indices = chip_smoke.host_transpose(indptr, indices)
    got = chip_smoke.host_trim(indptr, indices, t_indptr, t_indices)
    assert np.array_equal(got, np.asarray(trim_oracle(indptr, indices), bool))
