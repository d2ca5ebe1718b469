"""Compile every graph kernel for a described TPU v5e chip, at the shapes
the engines use on a scale-22 Graph500-style graph (n = 2**22 vertices,
m = 16 n arcs, window 16, frontier capacities from ``frontier_plan``).

Nothing runs: the TPU compiler, which ships with jax, compiles for a
chip that is described, not attached.  A kernel Mosaic refuses (a block
off the TPU tiling, an op it cannot lower) fails here instead of on the
chip.  The topology is described only inside the module fixture, so
importing or collecting this file never loads the TPU library, and the
tests skip where no v5e can be described.
"""
from __future__ import annotations

import pytest

N = 1 << 22
M = 16 * N
WINDOW = 16
UPDATES = 1024 + 1   # a stream batch: 1024 deletions + the empty insert pad


@pytest.fixture(scope="module")
def one_chip():
    import os

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from a persistent
    # cache, so keep it out of the way
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _kernel_case(name: str, sds):
    """(kernel fn with interpret=False, abstract args) at engine shapes."""
    import functools

    import jax.numpy as jnp

    from repro.core.common import frontier_plan
    from repro.kernels.bucket_peel import bucket_peel_pallas
    from repro.kernels.counter_scatter import counter_scatter_pallas
    from repro.kernels.first_live_scan import first_live_scan
    from repro.kernels.frontier_compact import (frontier_compact_pallas,
                                                sparse_expand_pallas)
    from repro.kernels.frontier_expand import frontier_expand
    fp = frontier_plan("auto", N, M)
    i32, b = jnp.int32, jnp.bool_
    tile = (sds((N, WINDOW), b), sds((N, WINDOW), b), sds((N,), b))
    cases = {
        "first_live_scan": (first_live_scan, {}, tile),
        "frontier_expand": (frontier_expand, {}, tile),
        "bucket_peel": (bucket_peel_pallas, {},
                        (sds((N,), i32), sds((N,), b), sds((), i32))),
        "counter_scatter": (counter_scatter_pallas, {},
                            (sds((N,), i32), sds((N,), b),
                             sds((UPDATES,), i32), sds((UPDATES,), i32))),
        "frontier_compact": (frontier_compact_pallas,
                             {"capacity": fp.cap}, (sds((N,), b),)),
        "sparse_expand": (sparse_expand_pallas, {"ecap": fp.ecap},
                          (sds((N + 1,), i32), sds((M,), i32),
                           sds((fp.cap,), i32))),
    }
    fn, kw, args = cases[name]
    return functools.partial(fn, interpret=False, **kw), args


#: the ``name=`` of the pallas_call each wrapper makes; frontier_compact
#: and sparse_expand reach Pallas through the prefix_positions scan
PALLAS_NAMES = {"first_live_scan": "first_live_scan",
                "frontier_expand": "frontier_expand",
                "bucket_peel": "bucket_peel",
                "counter_scatter": "counter_scatter",
                "frontier_compact": "prefix_positions",
                "sparse_expand": "prefix_positions",
                "prefix_positions": "prefix_positions"}


@pytest.mark.parametrize("kernel", [
    "first_live_scan", "frontier_expand", "bucket_peel", "counter_scatter",
    "frontier_compact", "sparse_expand"])
def test_graph_kernel_compiles_for_v5e(one_chip, kernel):
    import re

    import jax

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_case(kernel, sds)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's name= names its Mosaic custom call, which is what a
    # device trace's XLA Ops line shows
    name = PALLAS_NAMES[kernel]
    assert re.search(rf"%{name}(\.\d+)? = .*custom-call\(.*"
                     rf'custom_call_target="tpu_custom_call".*'
                     rf'op_name="[^"]*/{name}/pallas_call"', text)


@pytest.mark.parametrize("kernel", sorted(PALLAS_NAMES))
def test_graph_kernel_names_its_pallas_call(kernel):
    """Every graph kernel's pallas_call carries its ``name=``."""
    from repro.analysis.catalog import KERNEL_CATALOG
    entry = next(e for e in KERNEL_CATALOG if e.name == kernel)
    calls = entry.build(entry.points[0])
    assert calls
    assert {c.kwargs.get("name") for c in calls} == {PALLAS_NAMES[kernel]}
