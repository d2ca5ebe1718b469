#!/usr/bin/env python3
"""One cell of ``BENCHMARK.json``, run on the accelerator this process finds.

    python bench/run.py --workload kron-s22.trim --seed 7 --seconds 30 --trace 0

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in the file the entry names, its traffic mix in
``bench/traffic/<traffic>.json``, and by ``bench/find.py`` the mix's entry
``bench/entries/<entry>.py``, the configuration's generator
``bench/generators/<generator>.py`` and each metric's reader
``bench/metrics/<metric>.py``.  A run:

1. exits non-zero, printing no result, unless JAX finds as many TPUs as the
   cell asks for;
2. set-up (``setup_s``): turns on the persistent compile cache
   (``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set),
   makes the graph and its transpose on the device from the seed
   (``bench/gen.py``), builds the entry's closed-loop client and makes one
   call to compile and warm it;
3. the window: the client calls the entry until ``--seconds`` have passed;
   a call in flight at the end finishes and counts.  With ``--trace 1``
   the profiler records the window and ``bench/trace.py`` reduces it;
4. after the window: reads the peak device memory; in a traced run makes
   the client's ``probe()`` call, where it has one, for counts the timed
   call does not keep; frees the program's state, copies the graph to the
   host, and compares every answer of the window with the entry's plain
   reference (``bench/reference.py``);
5. reads each metric of the cell from what the run saw: the end-to-end
   ones with ``--trace 0``, the per-layer ones with ``--trace 1``.

The last lines of standard error give each number compared beside its
limit; the last line of standard output is the result as one JSON object.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import find, gen, trace  # noqa: E402


def load_cell(root: Path, name: str) -> SimpleNamespace:
    """The cell ``name`` with its configuration, mix, entry, generator and
    metric readers; exits when one of their files is missing."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    cfg = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((root / cfg["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return SimpleNamespace(
        name=name, chips=int(cell["chips"]), config=config, mix=mix,
        entry=find.module(root, "entries", mix["entry"]),
        generator=find.module(root, "generators", config["generator"]),
        end_to_end=e2e, per_layer=layer,
        readers={m["name"]: find.module(root, "metrics", m["name"]).read
                 for m in e2e + layer})


def accelerator(chips: int):
    """The first ``chips`` TPUs; exits non-zero when there are fewer."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < chips:
        raise SystemExit(f"bench: {chips} TPUs needed, {len(devices)} found")
    return devices[:chips]


def peak_of(root: Path, kind: str) -> dict:
    """The published peaks of ``kind`` from ``bench/peaks.json``."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"bench: device kind {kind!r} is not in "
                         f"bench/peaks.json")
    return table["devices"][kind]


class CompileEvents:
    """Counts JAX's compile and compile-cache events while ``on``."""

    def __init__(self):
        import jax.monitoring
        self.on, self.count = False, 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *args, **kwargs):
        if self.on and name.startswith(("/jax/core/compile/",
                                        "/jax/compilation_cache/")):
            self.count += 1


def window(loop, seconds: float):
    """Call ``loop`` until ``seconds`` have passed; returns the answers
    (None for a call that raised), their counts and the wall time."""
    from jax.profiler import TraceAnnotation
    answers, counts, ends = [], [], []
    with TraceAnnotation(trace.WINDOW):
        start = time.perf_counter()
        while True:
            with TraceAnnotation("bench.call"):
                try:
                    answer, count = loop.call()
                except Exception:       # counted as failed; the loop goes on
                    traceback.print_exc()
                    answer, count = None, {}
            with TraceAnnotation("bench.between"):
                answers.append(answer)
                counts.append(count)
                ends.append(time.perf_counter() - start)
                if ends[-1] >= seconds:
                    break
        calls = np.diff([0.0] + ends)
        print(f"bench: call seconds min {calls.min():.4f} median "
              f"{np.median(calls):.4f} max {calls.max():.4f}",
              file=sys.stderr)
        return answers, counts, time.perf_counter() - start


def check(entry, answers, graph, transpose) -> dict:
    """The worst of each compared number over the distinct answers."""
    ref = entry.reference_answer(graph, transpose)
    worst = dict.fromkeys(entry.LIMITS, 0)
    distinct = []
    for a in answers:
        if a is not None and not any(np.array_equal(a, d) for d in distinct):
            distinct.append(a)
    for a in distinct:
        for name, value in entry.compare(a, ref).items():
            worst[name] = max(worst[name], value)
    return worst


def run_cell(cell, devices, seed: int, seconds: float, traced: bool,
             peak: dict) -> dict:
    import jax
    t0 = time.perf_counter()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    g, gt = gen.build(cell.generator, cell.config, seed)
    jax.block_until_ready((g, gt))
    gen_s = time.perf_counter() - t0
    loop = cell.entry.Loop(g, gt, cell.mix, seed)
    loop.call()                     # compiles, or loads from the cache
    setup_s = time.perf_counter() - t0

    compiles = CompileEvents()
    summary = None
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        if traced:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(log_dir, profiler_options=options)
        compiles.on = True
        answers, counts, window_s = window(loop, seconds)
        compiles.on = False
        if traced:
            jax.profiler.stop_trace()
            summary = trace.summarize(trace.find_xplane(log_dir))
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    memory_peak = max(d.memory_stats()["peak_bytes_in_use"] for d in devices)
    probe = loop.probe() if traced and hasattr(loop, "probe") else None
    del loop
    graph, transpose = gen.to_host(g), gen.to_host(gt)
    del g, gt

    attempted = len(answers)
    failed = sum(a is None for a in answers)
    t_ref = time.perf_counter()
    worst = check(cell.entry, answers, graph, transpose)
    ref_s = time.perf_counter() - t_ref
    limits = {k: cell.entry.LIMITS[k] for k in worst}
    correct = (failed == 0 and attempted > 0
               and all(worst[k] <= limits[k] for k in worst))
    n, m = len(graph[0]) - 1, len(graph[1])
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    ctx = SimpleNamespace(setup_s=setup_s, window_s=window_s,
                          calls=attempted - failed,
                          counts=[c for c in counts if c], probe=probe,
                          trace=summary, n=n, m=m, peak=peak)
    metrics = {}
    for metric in cell.per_layer if traced else cell.end_to_end:
        value = cell.readers[metric["name"]](ctx)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
        elif not traced:
            raise SystemExit(f"bench: {cell.name} read no {metric['name']}")
    if traced:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    print(f"bench: {cell.name} seed {seed}: n={n} m={m}, {attempted} calls "
          f"({failed} failed) in {window_s:.3f} s; set-up {setup_s:.3f} s "
          f"(graph {gen_s:.3f} s); reference and comparison {ref_s:.3f} s; "
          f"compile events in the window: {compiles.count}", file=sys.stderr)
    for name in worst:
        print(f"check {name}: {worst[name]} (limit {limits[name]})",
              file=sys.stderr)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {k: {"value": worst[k], "limit": limits[k]}
                        for k in worst}
    return result


def main(argv=None, root: Path = ROOT):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(root, args.workload)
    devices = accelerator(cell.chips)
    peak = peak_of(root, devices[0].device_kind)
    result = run_cell(cell, devices, args.seed, args.seconds,
                      bool(args.trace), peak)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
