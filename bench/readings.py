#!/usr/bin/env python3
"""The readings behind the limits of ``correct``: for each seed, at the
cell's own size and in one process, the numbers that ``run.py`` compares,
once for the program's answer through the cell's own entry and once for
the control's (``bench/reference.py``), with the call's wall time and
counts.  Run on the chip:

    python bench/readings.py --workload kron-s22.trim --seeds 1,2,3

One JSON line per seed; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import gen, run  # noqa: E402


def main(argv=None, root: Path = ROOT):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    cell = run.load_cell(root, args.workload)
    run.accelerator(cell.chips)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    entry = cell.entry
    for seed in (int(s) for s in args.seeds.split(",")):
        g, gt = gen.build(cell.generator, cell.config, seed)
        loop = entry.Loop(g, gt, cell.mix, seed)
        t0 = time.perf_counter()
        answer, counts = loop.call()
        call_s = time.perf_counter() - t0
        del loop
        graph, transpose = gen.to_host(g), gen.to_host(gt)
        del g, gt
        ref = entry.reference_answer(graph, transpose)
        print(json.dumps({
            "seed": seed, "call_s": call_s, "counts": counts,
            "program": entry.compare(answer, ref),
            "control": entry.compare(entry.control(graph, transpose), ref)}),
            flush=True)


if __name__ == "__main__":
    main()
