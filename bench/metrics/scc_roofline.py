"""Share of the memory roofline that one SCC decomposition reaches (%): the
least bytes a decomposition must move, over the peak HBM bandwidth, over
the device-busy seconds per call in the traced window.

The least bytes read G's CSR and Gᵀ's CSR once each, indptr and indices
at 4 bytes an entry, and write a 4-byte label per vertex; they are the
same whatever implements the decomposition."""


def least_bytes(n: int, m: int) -> int:
    return 2 * (4 * (n + 1) + 4 * m) + 4 * n


def read(ctx):
    if ctx.trace is None or ctx.calls == 0 or ctx.trace.busy_s <= 0:
        return None
    least_s = least_bytes(ctx.n, ctx.m) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (ctx.trace.busy_s / ctx.calls)
