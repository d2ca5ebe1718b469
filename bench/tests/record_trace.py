"""Records ``data/small.xplane.pb``, the chip trace that ``test_trace.py``
reduces: three calls of one jitted elementwise pass over a 4 MiB array,
each inside ``bench.call`` with its host pull in ``bench.pull``, and 20 ms
of host sleep in ``bench.between`` after each, all inside one
``bench.window`` span.  Run on a TPU:

    python bench/tests/record_trace.py OUT_DIR

It copies the trace to ``OUT_DIR/small.xplane.pb`` and prints every
device operation and ``bench.*`` span with its start and end (ns).
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import ProfileData, TraceAnnotation


def main(out_dir):
    f = jax.jit(lambda x: jnp.sin(x) * 2.0 + 1.0)
    x = jnp.ones((1024, 1024), jnp.float32)
    np.asarray(f(x))
    log_dir = tempfile.mkdtemp(prefix="record-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=options)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.call"):
                y = f(x)
                with TraceAnnotation("bench.pull"):
                    np.asarray(y)
            with TraceAnnotation("bench.between"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(log_dir)
    path = os.path.join(out_dir, "small.xplane.pb")
    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  line", repr(line.name), len(events))
            for ev in events:
                if plane.name.startswith("/device:") or \
                        ev.name.startswith("bench."):
                    print("    ", repr(ev.name), int(ev.start_ns),
                          int(ev.start_ns + ev.duration_ns))


if __name__ == "__main__":
    main(sys.argv[1])
