"""Seconds per decomposition in the host transpose, the counting sort of
Gᵀ and its upload (``stats["transpose_s"]``, the ``scc.transpose`` span)."""


def read(ctx):
    seconds = [c["transpose_s"] for c in ctx.counts
               if c.get("transpose_s") is not None]
    return sum(seconds) / len(seconds) if seconds else None
