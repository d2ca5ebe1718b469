#!/usr/bin/env python3
"""Chip smoke test: the graph system's main path on a TPU, with its Pallas
kernels compiled, checked against plain host references.

    python chip_smoke.py             # one chip, phases a-f below
    python chip_smoke.py --chips 4   # four chips: the sharded trim phase only

The graph is Graph500-style Kronecker (R-MAT A/B/C = 0.57/0.19/0.19, edge
factor 16, seed 1) at scale 22: 4,194,304 vertices, 67,108,864 arcs.

One chip:
  a. trim — dense AC-6, AC-4 and windowed AC-6 statuses vs a numpy fixpoint
  b. peel — full coreness; run(k=1) status vs AC-4
  c. stream — three batches of 1024 deletions; retrim() vs retrim(full=True)
     vs the numpy fixpoint of the edited graph
  d. SCC — batched FW-BW driver labels vs scipy; one windowed reach query
     vs scipy BFS
  e. kernels — all six graph kernels were traced compiled (use_kernel=True,
     interpret=False)
  f. last line: {"ok": true, "device": {"platform", "kind", "count"}}

Four chips: AC-3, AC-4, AC-6 and packed AC-6 on the sharded backend over a
mesh of the four chips, each status vs single-device dense AC-6.

Exits non-zero, and prints no result, when JAX finds no TPU.  Every phase
prints one line with its check and its first (compile included) and warm
wall times, except full-coreness peel, which runs once: at scale 22 it
takes about five minutes on one v5e (hundreds of bucket rounds), and a
second call would put the script near its 20-minute budget.  Everything
runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SCALE, SEED = 22, 1     # Graph500 scale 22: n = 2**22, m = 16 n
GRAPH_KERNELS = ("first_live_scan", "frontier_expand", "bucket_peel",
                 "counter_scatter", "frontier_compact", "sparse_expand")


def tpu_devices(count: int):
    """The attached TPUs; exits non-zero unless there are ``count``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) < count:
        sys.exit(f"chip_smoke: {count} TPUs needed, {len(devs)} attached")
    return devs[:count]


def timed(fn):
    """(result, first_s, warm_s): ``fn`` must return host data, so each
    timing ends only when the device work behind it has finished."""
    t0 = time.perf_counter()
    fn()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    return out, first, time.perf_counter() - t0


def cache_state(path: str) -> str:
    """Where the persistent compile cache is and whether it starts warm
    (first-call times include compilation only when it is cold)."""
    entries = len(os.listdir(path)) if os.path.isdir(path) else 0
    return f"compile cache {path} ({entries} entries at start)"


def check(ok: bool, what: str):
    if not ok:
        raise AssertionError(f"chip_smoke: {what}")


# -- plain host references (numpy/scipy, independent of the engines) ---------

def host_transpose(indptr, indices):
    import numpy as np
    n = len(indptr) - 1
    src = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
    order = np.argsort(indices, kind="stable")
    t_indptr = np.concatenate(
        [[0], np.cumsum(np.bincount(indices, minlength=n))])
    return t_indptr, src[order]


def host_trim(indptr, indices, t_indptr, t_indices):
    """Live mask of the trimming fixpoint: a vertex dies once none of its
    out-arcs leads to a live vertex.  Rounds kill every vertex whose live
    out-degree is zero and subtract its in-arcs from their sources."""
    import numpy as np
    n = len(indptr) - 1
    live_out = np.diff(indptr).astype(np.int64)
    live = np.ones(n, bool)
    front = live_out == 0
    while front.any():
        live &= ~front
        ids = np.flatnonzero(front)
        lo, lens = t_indptr[ids], t_indptr[ids + 1] - t_indptr[ids]
        arcs = (np.repeat(lo - np.cumsum(lens) + lens, lens)
                + np.arange(lens.sum()))
        live_out -= np.bincount(t_indices[arcs], minlength=n)
        front = live & (live_out == 0)
    return live


def scipy_csr(n, indptr, indices):
    import numpy as np
    from scipy.sparse import csr_matrix
    return csr_matrix((np.ones(len(indices), np.int8), indices, indptr),
                      shape=(n, n))


def host_reach(a, seed):
    import numpy as np
    from scipy.sparse.csgraph import breadth_first_order
    mask = np.zeros(a.shape[0], bool)
    mask[breadth_first_order(a, seed, directed=True,
                             return_predecessors=False)] = True
    return mask


# -- phases --------------------------------------------------------------------

def phase_trim(g, oracle):
    import numpy as np

    from repro.core import plan
    parts, statuses = [], {}
    for label, kw in (("ac6", dict(method="ac6")),
                      ("ac4", dict(method="ac4")),
                      ("ac6-windowed", dict(method="ac6",
                                            backend="windowed"))):
        eng = plan(g, **kw)
        status, first, warm = timed(lambda: np.asarray(eng.run().status) != 0)
        check(np.array_equal(status, oracle), f"{label} status != oracle")
        statuses[label] = status
        parts.append(f"{label} first={first:.3f}s warm={warm:.4f}s")
        del eng
    print(f"[a] trim: {int(oracle.sum()):,} of {g.n:,} live, all three "
          f"statuses == host fixpoint | " + " | ".join(parts), flush=True)
    return statuses["ac4"]


def phase_peel(g, ac4_status):
    import numpy as np

    from repro.core import plan_peel
    eng = plan_peel(g)
    t0 = time.perf_counter()
    res = eng.run().materialize()
    first = time.perf_counter() - t0
    k1, k1_first, k1_warm = timed(
        lambda: np.asarray(eng.run(k=1).status) != 0)
    check(np.array_equal(k1, ac4_status), "peel run(k=1) status != AC-4")
    check(np.array_equal(res.coreness >= 1, ac4_status),
          "full-coreness 1-core != AC-4")
    print(f"[b] peel: max coreness {res.max_core}, rounds {res.rounds}, "
          f"run(k=1) status == AC-4 | full (one call) first={first:.3f}s "
          f"| k=1 first={k1_first:.3f}s "
          f"warm={k1_warm:.4f}s", flush=True)


def phase_stream(g, indptr, indices, seed):
    import numpy as np

    from repro.core import plan_stream
    batch, batches = 1024, 3
    eng = plan_stream(g, capacity=batch)
    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(g.n, dtype=np.int32), np.diff(indptr))
    eids = rng.choice(g.m, batch * batches, replace=False)
    times = []
    for i in range(batches):
        ids = eids[i * batch:(i + 1) * batch]
        t0 = time.perf_counter()
        np.asarray(eng.apply(deletions=(src[ids], indices[ids])).status)
        times.append(time.perf_counter() - t0)
    incr = np.asarray(eng.retrim().status) != 0
    t0 = time.perf_counter()
    full = np.asarray(eng.retrim(full=True).status) != 0
    t_full = time.perf_counter() - t0
    check(np.array_equal(incr, full), "stream retrim() != retrim(full=True)")
    keep = np.ones(g.m, bool)
    keep[eids] = False
    k_indptr = np.concatenate(
        [[0], np.cumsum(np.bincount(src[keep], minlength=g.n))])
    k_indices = indices[keep]
    oracle = host_trim(k_indptr, k_indices,
                       *host_transpose(k_indptr, k_indices))
    check(np.array_equal(incr, oracle), "stream status != host fixpoint")
    print(f"[c] stream: {batches} batches of {batch} deletions, "
          f"{int(incr.sum()):,} live, retrim() == retrim(full=True) == host "
          f"fixpoint | apply first={times[0]:.3f}s "
          f"warm={times[1]:.4f}s,{times[2]:.4f}s | "
          f"retrim(full=True) {t_full:.4f}s", flush=True)


def phase_scc(g, a, scc_labels, device):
    import numpy as np

    from repro.core import plan_reach
    from repro.core.scc import same_partition, scc_decompose
    (labels, stats), first, warm = timed(
        lambda: scc_decompose(g, trim_method="ac6"))
    check(same_partition(labels, scc_labels), "SCC labels != scipy")
    # The batched driver pulls whole rows on a graph whose in-degrees
    # overflow the window, as R-MAT's do; a single query takes the
    # windowed pull (frontier_expand).  Seeded at the giant SCC.
    sizes = np.bincount(scc_labels)
    pivot = int(np.flatnonzero(scc_labels == sizes.argmax())[0])
    eng = plan_reach(g, backend="windowed")
    fw, r_first, r_warm = timed(lambda: np.asarray(eng.run(pivot).mask))
    check(np.array_equal(fw, host_reach(a, pivot)), "reach != scipy BFS")
    peak = device.memory_stats()["peak_bytes_in_use"]
    print(f"[d] scc: n={g.n:,} m={g.m:,}, "
          f"{len(sizes):,} SCCs, giant {int(sizes.max()):,}, "
          f"labels == scipy; generations={stats['generations']} "
          f"pivots={stats['pivots']} (max_batch 1024) | first={first:.3f}s "
          f"warm={warm:.4f}s | windowed reach from {pivot}: "
          f"{int(fw.sum()):,} reached == scipy BFS, first={r_first:.3f}s "
          f"warm={r_warm:.4f}s | peak HBM in use {peak:,} bytes", flush=True)


def phase_kernels(rec):
    notes = rec.select(cat="kernel")
    compiled = sorted({sp.name for sp in notes
                       if sp.attrs.get("use_kernel")
                       and not sp.attrs.get("interpret")})
    interpreted = sorted({sp.name for sp in notes
                          if sp.attrs.get("interpret")})
    missing = [k for k in GRAPH_KERNELS if k not in compiled]
    check(not missing, f"graph kernels never traced compiled: {missing}")
    check(not interpreted, f"kernels traced in interpret mode: "
                           f"{interpreted}")
    print(f"[e] kernels traced with use_kernel=True, interpret=False: "
          f"{', '.join(compiled)}", flush=True)


def one_chip():
    devs = tpu_devices(1)
    from scipy.sparse.csgraph import connected_components

    from repro import obs
    from repro.graphs import rmat
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[setup] device {devs[0].device_kind}; "
          f"{cache_state(enable_compile_cache())}", flush=True)

    t0 = time.perf_counter()
    g = rmat(SCALE, 16 << SCALE, seed=SEED)
    indptr, indices = g.to_numpy()
    oracle = host_trim(indptr, indices, *host_transpose(indptr, indices))
    a = scipy_csr(g.n, indptr, indices)
    _, scc_labels = connected_components(a, directed=True,
                                         connection="strong")
    print(f"[setup] R-MAT scale {SCALE}, edge factor 16, "
          f"A/B/C=0.57/0.19/0.19, seed {SEED}: n={g.n:,} m={g.m:,}; "
          f"deviation from Graph500: vertex labels are not scrambled | "
          f"graph + host references {time.perf_counter() - t0:.1f}s",
          flush=True)

    with obs.recording() as rec:
        ac4_status = phase_trim(g, oracle)
        phase_peel(g, ac4_status)
        phase_stream(g, indptr, indices, SEED)
        phase_scc(g, a, scc_labels, devs[0])
    phase_kernels(rec)
    return devs


def four_chips():
    devs = tpu_devices(4)
    import numpy as np

    from repro.core import plan
    from repro.graphs import rmat
    from repro.jaxcompat import make_mesh
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[setup] 4 x {devs[0].device_kind}; "
          f"{cache_state(enable_compile_cache())}", flush=True)
    g = rmat(SCALE, 16 << SCALE, seed=SEED)
    eng = plan(g, method="ac6")
    ref, first, warm = timed(lambda: np.asarray(eng.run().status) != 0)
    del eng
    print(f"[4] single-device dense ac6: {int(ref.sum()):,} of {g.n:,} "
          f"live | first={first:.3f}s warm={warm:.4f}s", flush=True)
    mesh = make_mesh((4,), ("workers",))
    for method, packed in (("ac3", False), ("ac4", False), ("ac6", False),
                           ("ac6", True)):
        eng = plan(g, method=method, backend="sharded", mesh=mesh,
                   packed=packed, unmasked=True)
        status, first, warm = timed(
            lambda: np.asarray(eng.run().status) != 0)
        label = method + ("-packed" if packed else "")
        check(np.array_equal(status, ref),
              f"sharded {label} status != single-device ac6")
        print(f"[4] sharded {label} over 4 chips: status == single-device "
              f"ac6 | first={first:.3f}s warm={warm:.4f}s", flush=True)
        del eng
    return devs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded trim phase on 4 chips")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    devs = four_chips() if args.chips == 4 else one_chip()
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
