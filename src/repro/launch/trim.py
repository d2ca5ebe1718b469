import os
if "--dryrun" in __import__("sys").argv:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""The paper's technique at production scale: distributed graph trimming.

    # run locally on this container (1 device):
    PYTHONPATH=src python -m repro.launch.trim --graph BA --method ac6
    # windowed Pallas probe path / sharded shard_map path:
    PYTHONPATH=src python -m repro.launch.trim --graph BA --backend windowed
    PYTHONPATH=src python -m repro.launch.trim --graph BA --backend sharded
    # production-mesh dry-run (512 virtual chips):
    PYTHONPATH=src python -m repro.launch.trim --dryrun --method ac6
    # the flagship application (batched device-resident FW-BW SCC driver):
    PYTHONPATH=src python -m repro.launch.trim --app scc --graph BA
    # incremental trimming over edge-update batches (StreamEngine):
    PYTHONPATH=src python -m repro.launch.trim --app stream --graph BA
    # bucketed k-core peeling on the AC-4 counter substrate (PeelEngine):
    PYTHONPATH=src python -m repro.launch.trim --app peel --graph BA
    # static analysis plane (race/purity/retrace lint; no graph runs):
    PYTHONPATH=src python -m repro.launch.trim --app check --strict

Serving goes through the compile-once engine: ``plan()`` once, then every
``run()`` reuses the cached transpose and compiled kernel — the first/steady
timing split below is the whole point (DESIGN.md §1).
"""
import argparse
import time


def run_local(graph_name: str, method: str, workers: int,
              backend: str = "dense"):
    from ..core.engine import plan
    from ..graphs import make
    g = make(graph_name)
    # this entrypoint never passes active masks, so declare it: sharded
    # AC-4 (maskless-only) stays servable here
    engine = plan(g, method=method, backend=backend, workers=workers,
                  unmasked=True)
    t0 = time.time()
    res = engine.run().materialize()
    t_first = time.time() - t0
    t0 = time.time()
    res = engine.run().materialize()     # compile-cache hit
    t_steady = time.time() - t0
    print(f"[trim] {graph_name} n={g.n} m={g.m} method={method} "
          f"backend={backend}: trimmed {res.n_trimmed} "
          f"({res.trimmed_fraction*100:.1f}%) rounds={res.rounds} "
          f"edges={res.edges_traversed} max|Qp|={res.max_frontier} | "
          f"first={t_first:.2f}s steady={t_steady*1e3:.1f}ms "
          f"traces={engine.traces}")
    return res


def run_scc(graph_name: str, method: str, backend: str = "dense",
            reach_backend: str = "windowed",
            checkpoint_dir: str | None = None, checkpoint_every: int = 0,
            retries: int = 3):
    """The paper's flagship application on the device-resident batched
    driver (DESIGN.md §8): per worklist generation one batched trim
    dispatch + two batched reach dispatches, labels materialized once.

    With ``--checkpoint-dir`` the driver saves its generation-level state
    (labels, pending regions, label counter, stats) every
    ``checkpoint_every`` generations through an async writer; a
    :class:`~repro.fault.DeviceFault`/``IOFault`` mid-decomposition is
    retried with exponential backoff, each retry resuming from the latest
    saved generation rather than replaying the whole worklist."""
    import numpy as np

    from ..core.scc import scc_decompose
    from ..graphs import make
    g = make(graph_name)
    if checkpoint_dir is not None:
        from .. import fault as flt
        from ..fault.checkpoint import AsyncCheckpointer
        checkpointer = AsyncCheckpointer(checkpoint_dir)
        t0 = time.time()
        try:
            att = 0
            while True:
                try:
                    labels, stats = scc_decompose(
                        g, trim_method=method, trim_backend=backend,
                        reach_backend=reach_backend,
                        checkpoint_dir=checkpoint_dir,
                        checkpoint_every=checkpoint_every,
                        checkpointer=checkpointer, resume=att > 0)
                    break
                except (flt.DeviceFault, flt.IOFault) as e:
                    att += 1
                    if att > retries:
                        raise
                    time.sleep(flt.backoff_delay(att - 1))
                    try:
                        checkpointer.wait()
                    except OSError:
                        pass
                    flt.get_fault_plane().record_recovery(
                        getattr(e, "point", "unknown"), "restore")
                    print(f"[scc] fault at "
                          f"{getattr(e, 'point', 'unknown')!r}: resuming "
                          f"from latest checkpoint (attempt {att})")
        finally:
            try:
                checkpointer.close()
            except OSError as e:
                print(f"[scc] checkpoint writer error at close: {e}")
        t_first = t_steady = time.time() - t0
    else:
        t0 = time.time()
        labels, stats = scc_decompose(g, trim_method=method,
                                      trim_backend=backend,
                                      reach_backend=reach_backend)
        t_first = time.time() - t0
        t0 = time.time()
        labels, stats = scc_decompose(g, trim_method=method,
                                      trim_backend=backend,
                                      reach_backend=reach_backend)
        t_steady = time.time() - t0   # jit caches are process-wide: warm
    print(f"[scc] {graph_name} n={g.n} m={g.m} trim={method}/{backend} "
          f"reach={reach_backend}: {len(np.unique(labels)):,} SCCs, "
          f"generations={stats['generations']} pivots={stats['pivots']} "
          f"trimmed={stats['trimmed_total']:,} "
          f"dispatches={stats['trim_dispatches']}+{stats['reach_dispatches']}"
          f" | first={t_first:.2f}s steady={t_steady*1e3:.1f}ms")
    return labels, stats


def run_stream(graph_name: str, batches: int = 3, batch_frac: float = 0.001,
               seed: int = 0):
    """Incremental trimming under a synthetic deletion feed (DESIGN.md §9):
    ``apply()`` absorbs each batch through the counter-scatter kernel and
    a delta-seeded fixpoint; ``retrim(full=True)`` is the from-scratch
    baseline on the same overlay."""
    import numpy as np

    from ..core.stream import plan_stream
    from ..graphs import make
    g = make(graph_name)
    engine = plan_stream(g)
    rng = np.random.default_rng(seed)
    src, dst = engine.delta._src_np, engine.delta._dst_np
    k = max(1, int(g.m * batch_frac))
    alive = np.ones(g.m, bool)
    t_incr, t_full = [], []
    for _ in range(batches):
        ids = rng.choice(np.nonzero(alive)[0], k, replace=False)
        alive[ids] = False
        t0 = time.time()
        res = engine.apply(deletions=(src[ids], dst[ids]))
        _ = res.rounds                         # host sync closes the timing
        t_incr.append(time.time() - t0)
        t0 = time.time()
        _ = engine.retrim(full=True).rounds
        t_full.append(time.time() - t0)
    inc, full = np.median(t_incr[1:] or t_incr), np.median(t_full[1:] or t_full)
    res = engine.retrim()
    print(f"[stream] {graph_name} n={g.n} m={g.m}: {batches} batches of "
          f"{k} deletions | incremental {inc*1e3:.1f}ms vs from-scratch "
          f"{full*1e3:.1f}ms ({full/max(inc, 1e-9):.1f}x) | trimmed "
          f"{res.n_trimmed} ({res.trimmed_fraction*100:.1f}%)")
    return engine


def run_peel(graph_name: str):
    """Full out-degree coreness in one dispatch on the peel engine
    (DESIGN.md §10), plus the k=1 ≡ AC-4 cross-check."""
    import numpy as np

    from ..core.engine import plan
    from ..core.peel import plan_peel
    from ..graphs import make
    g = make(graph_name)
    engine = plan_peel(g)
    t0 = time.time()
    res = engine.run().materialize()
    t_first = time.time() - t0
    t0 = time.time()
    res = engine.run().materialize()     # compile-cache hit
    t_steady = time.time() - t0
    core = res.coreness
    hist = np.bincount(core, minlength=res.max_core + 1)
    top = ", ".join(f"k={k}:{hist[k]:,}"
                    for k in range(min(res.max_core, 4) + 1))
    if res.max_core > 4:
        top += f", ..., k={res.max_core}:{hist[res.max_core]:,}"
    ac4 = np.asarray(plan(g, method="ac4").run().status)
    assert np.array_equal(np.asarray(res.status), ac4), "peel(1) != AC-4"
    print(f"[peel] {graph_name} n={g.n} m={g.m}: max coreness "
          f"{res.max_core}, 1-core {int((core >= 1).sum()):,} "
          f"({(core >= 1).mean()*100:.1f}%) [{top}] rounds={res.rounds} "
          f"| k=1 mask == AC-4 | first={t_first:.2f}s "
          f"steady={t_steady*1e3:.1f}ms traces={engine.traces}")
    return res


def run_dryrun(method: str):
    """Lower + compile distributed trimming for the 512-chip mesh."""
    import jax

    from ..core.distributed import _ac3_body, _ac6_body, shard_map_compat
    from .mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=True)
    num = 512
    axis = ("pod", "data", "model")
    # synthetic production-scale graph: shapes only matter for lowering,
    # so build a tiny host graph and lift the partition shapes
    n, m = 64_000_000, 512_000_000
    nl, ml = n // num, m // num  # balanced partition assumption
    lip = jax.ShapeDtypeStruct((num, nl + 1), jax.numpy.int32)
    lix = jax.ShapeDtypeStruct((num, 2 * ml), jax.numpy.int32)
    act = jax.ShapeDtypeStruct((num, nl), jax.numpy.bool_)
    body = {"ac3": _ac3_body, "ac6": _ac6_body}[method](axis)
    f = jax.jit(shard_map_compat(body, mesh, in_specs=3, out_specs=4,
                                 axis=axis))
    t0 = time.time()
    lowered = f.lower(lip, lix, act)
    compiled = lowered.compile()
    dt = time.time() - t0
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    n_ag = hlo.count("all-gather")
    print(f"[trim-dryrun] {method} on 2x16x16 (512 chips): compiled in "
          f"{dt:.1f}s; per-device args "
          f"{mem.argument_size_in_bytes/2**20:.1f} MiB, temps "
          f"{mem.temp_size_in_bytes/2**20:.1f} MiB, all-gather sites "
          f"{n_ag}")
    print(f"  graph: n={n:,} m={m:,} -> {nl:,} vertices/device; "
          f"status all_gather {n/8/2**20:.1f} MiB per round")
    return compiled


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="BA")
    ap.add_argument("--method", default="ac6")
    ap.add_argument("--workers", type=int, default=16)
    ap.add_argument("--backend", default="dense",
                    choices=("dense", "windowed", "sharded"))
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--app", default="trim", choices=("trim", "scc",
                                                      "stream", "peel",
                                                      "check"))
    ap.add_argument("--strict", action="store_true",
                    help="fail on warnings as well as errors (--app check)")
    ap.add_argument("--mutants", action="store_true",
                    help="run the analysis mutation corpus instead of the "
                         "real registry (--app check)")
    ap.add_argument("--reach-backend", default="windowed",
                    choices=("dense", "windowed"))
    ap.add_argument("--metrics-json", metavar="PATH",
                    help="collect MetricsPlane telemetry for the run and "
                         "dump the JSON snapshot to PATH (any --app; for "
                         "--app check this is the findings JSON)")
    ap.add_argument("--checkpoint-dir", metavar="DIR",
                    help="checkpoint the SCC driver's generation state "
                         "here and resume across faults (--app scc)")
    ap.add_argument("--checkpoint-every", type=int, default=5,
                    metavar="GENS",
                    help="generations between driver checkpoints (with "
                         "--checkpoint-dir)")
    ap.add_argument("--fault-seed", type=int, default=None, metavar="SEED",
                    help="install a deterministic FaultSchedule with this "
                         "seed (chaos testing; off by default)")
    ap.add_argument("--fault-rate", type=float, default=0.05,
                    help="per-arming fault probability for --fault-seed")
    ap.add_argument("--retries", type=int, default=3,
                    help="bound on resume-from-checkpoint attempts")
    args = ap.parse_args()
    if args.app == "check":
        # the static-analysis plane: no graph, no engines, no device work —
        # delegate to the repro.analysis.check CLI
        if args.fault_seed is not None or args.checkpoint_dir:
            ap.error("--app check is static analysis; fault injection and "
                     "checkpoints don't apply")
        from ..analysis.check import main as check_main
        argv = []
        if args.strict:
            argv.append("--strict")
        if args.mutants:
            argv.append("--mutants")
        if args.metrics_json:
            argv += ["--json", args.metrics_json]
        raise SystemExit(check_main(argv))
    if args.strict or args.mutants:
        ap.error("--strict/--mutants apply to --app check")
    if args.app == "scc" and args.backend == "sharded":
        ap.error("--app scc needs a batchable trim backend "
                 "(--backend dense or windowed); shard at the region level")
    if args.checkpoint_dir and args.app != "scc":
        ap.error("--checkpoint-dir applies to --app scc (for the serving "
                 "loop use repro.launch.serve --checkpoint-dir)")

    import contextlib

    from .. import obs
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.fault_seed is not None:
        from .. import fault as flt
        fault_scope = flt.injecting_faults(
            flt.FaultSchedule(args.fault_seed, rate=args.fault_rate))
    else:
        fault_scope = contextlib.nullcontext(None)
    scope = (obs.collecting_metrics() if args.metrics_json
             else contextlib.nullcontext(None))
    with fault_scope, scope as plane:
        if args.dryrun:
            run_dryrun(args.method)
        elif args.app == "scc":
            run_scc(args.graph, args.method, args.backend,
                    args.reach_backend,
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every,
                    retries=args.retries)
        elif args.app == "stream":
            run_stream(args.graph)
        elif args.app == "peel":
            run_peel(args.graph)
        else:
            run_local(args.graph, args.method, args.workers, args.backend)
    if plane is not None:
        import json
        with open(args.metrics_json, "w") as f:
            json.dump(plane.snapshot(), f, indent=1)
        print(f"[trim] metrics snapshot: {args.metrics_json} "
              f"({len(plane.families)} families)")


if __name__ == "__main__":
    main()
