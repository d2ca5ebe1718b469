"""Distributed (shard_map) trimming on 8 virtual CPU devices — run in a
subprocess so the device-count flag never leaks into other tests.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, sys
    sys.path.insert(0, %r)
    from repro.core import CSRGraph, trim_oracle
    from repro.core.distributed import trim_distributed
    from repro.graphs import chain

    rng = np.random.default_rng(11)
    for trial in range(4):
        n = int(rng.integers(5, 250))
        m = int(rng.integers(0, 5 * n))
        g = CSRGraph.from_edges(n, rng.integers(0, n, m),
                                rng.integers(0, n, m))
        oracle = trim_oracle(*g.to_numpy())
        for meth in ("ac3", "ac4", "ac6", "ac6_packed"):
            r = trim_distributed(g, method=meth)
            assert (r.status.astype(bool) == oracle).all(), (trial, meth)
            assert r.per_worker_edges.shape == (8,)
    # chain crossing partitions + AC-6 bound
    g = chain(97)
    r = trim_distributed(g, method="ac6")
    assert r.n_trimmed == 97 and r.edges_traversed <= g.m + 97
    print("DISTRIBUTED_OK")
""")


def test_distributed_trim_8dev():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT % src],
                         capture_output=True, text=True, timeout=600)
    assert "DISTRIBUTED_OK" in out.stdout, out.stderr[-2000:]


PLACEMENT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, %r)
    from repro.core import plan, trim_oracle
    from repro.graphs import rmat

    g = rmat(10, 8 << 10, seed=3)
    oracle = trim_oracle(*g.to_numpy())
    for method in ("ac3", "ac4", "ac6"):
        eng = plan(g, method=method, backend="sharded", unmasked=True)
        assert (eng.run().materialize().status.astype(bool) == oracle).all()
        # each device holds its own row of every partition operand
        for op in eng._shard["operands"]:
            assert len(op.sharding.device_set) == 4, (method, op.sharding)
            assert {s.data.shape[0] for s in op.addressable_shards} == {1}
    print("PLACEMENT_OK")
""")


def test_sharded_operands_placed_per_device():
    """The partition is placed once, row d on device d — not gathered onto
    device 0 and resharded every run."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", PLACEMENT % src],
                         capture_output=True, text=True, timeout=600)
    assert "PLACEMENT_OK" in out.stdout, out.stderr[-2000:]
