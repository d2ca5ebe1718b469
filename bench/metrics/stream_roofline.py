"""Share of the memory roofline that one apply reaches (%): the least
bytes an apply must move, over the peak HBM bandwidth, over the
device-busy seconds per call in the traced window.

The least bytes read the batch once, a source and a target at 4 bytes
each an arc, and write one status byte per vertex.  They leave out the
cascade, a handful of vertices a batch, and are the same whatever
implements the apply."""


def least_bytes(n: int, arcs: int) -> int:
    return 8 * arcs + n


def read(ctx):
    arcs = [c["arcs"] for c in ctx.counts if c.get("arcs") is not None]
    if ctx.trace is None or not arcs or ctx.trace.busy_s <= 0:
        return None
    least_s = (least_bytes(ctx.n, sum(arcs) / len(arcs))
               / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (ctx.trace.busy_s / ctx.calls)
