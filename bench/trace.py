"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

The window is the host span ``bench.window`` that the harness opens
around its measured loop.  Within it:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device (events of the ``XLA Ops`` line of each ``/device:`` plane),
  averaged over the devices;
* ``device_ops``: device seconds per operation, summed over the devices
  and divided by their number, the ten largest; only operations that hold
  no other count, so a loop's time is that of the operations in its body;
* ``idle_gaps``: the ten longest gaps between busy intervals on the first
  device, each named by the innermost ``bench.*`` host span that
  holds the gap's midpoint (``bench.call``, ``bench.pull``,
  ``bench.between``), or ``host`` where none does.

The device's timestamps are taken as the trace gives them.  On a v5e they
lead the host's by about 0.8 ms (``tests/data/small.xplane.pb``: each
operation starts before the host span that launched it); over a window of
seconds that moves ``busy_s`` by about a millisecond at most.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW = "bench.window"
DEVICE_PLANE = "/device:"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
TOP = 10                # device operations and idle gaps kept


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    device_ops: list        # [[name, seconds], ...], the TOP largest
    idle_gaps: list         # [[span, seconds], ...], the TOP longest

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops, "idle_gaps": self.idle_gaps}


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` file the profiler wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def union(intervals) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def short_name(hlo: str) -> str:
    """``%fusion.5 = s32[4194304]{0:T(1024)} fusion(...)`` becomes
    ``%fusion.5 s32[4194304]``; a tuple-valued op keeps its name alone."""
    name, _, rest = hlo.partition(" = ")
    shape = rest.split(" ", 1)[0].split("{", 1)[0]
    return name if not shape or shape.startswith("(") else f"{name} {shape}"


def summarize(path: str) -> Summary:
    """Read one ``.xplane.pb`` file and reduce it."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for pl in planes if not pl.name.startswith(DEVICE_PLANE)
             for line in pl.lines for ev in line.events
             if ev.name.startswith(SPAN_PREFIX)]
    devices = [[(short_name(ev.name), ev.start_ns,
                 ev.start_ns + ev.duration_ns)
                for line in pl.lines if line.name == OP_LINE
                for ev in line.events]
               for pl in planes if pl.name.startswith(DEVICE_PLANE)]
    return reduce(spans, [ops for ops in devices if ops])


def leaves(ops) -> list:
    """The operations that hold no other: a ``while`` or ``cond`` event
    spans the operations of its body, which the trace lists too."""
    ops = sorted(ops, key=lambda op: (op[1], -op[2]))
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= op[2] or nxt[2] > op[2]]


def reduce(spans, devices) -> Summary:
    """``spans``: host (name, start, end) of the ``bench.*`` spans;
    ``devices``: per device, its operations as (name, start, end), in ns."""
    windows = [(s, e) for name, s, e in spans if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    if not devices:
        raise ValueError("no device operations in the trace")
    lo, hi = windows[0]
    busy, by_name = [], {}
    for ops in devices:
        busy.append(union(clip([(s, e) for _, s, e in ops], lo, hi)))
        for name, s, e in leaves(ops):
            for cs, ce in clip([(s, e)], lo, hi):
                by_name[name] = by_name.get(name, 0.0) + (ce - cs)
    k = len(devices)
    busy_s = sum(e - s for b in busy for s, e in b) / k * 1e-9
    device_ops = sorted(([name, t / k * 1e-9] for name, t in by_name.items()),
                        key=lambda x: -x[1])
    edges = [lo] + [t for s, e in busy[0] for t in (s, e)] + [hi]
    gaps = sorted(((s, e) for s, e in zip(edges[::2], edges[1::2])
                   if e > s), key=lambda g: g[0] - g[1])[:TOP]
    inner = [(name, s, e) for name, s, e in spans if name != WINDOW]
    idle = []
    for s, e in gaps:
        mid = (s + e) / 2
        holding = [sp for sp in inner if sp[1] <= mid <= sp[2]]
        label = (min(holding, key=lambda sp: sp[2] - sp[1])[0]
                 if holding else "host")
        idle.append([label, (e - s) * 1e-9])
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_s, devices=k,
                   device_ops=device_ops[:TOP], idle_gaps=idle)
