"""Adjacency entries traversed per trim call, the paper's traversed-edges
count (``TrimResult.edges_traversed``)."""


def read(ctx):
    edges = [c["edges"] for c in ctx.counts if c.get("edges") is not None]
    return sum(edges) / len(edges) if edges else None
