"""Seconds per decomposition spent planning its four engines
(``stats["plan_s"]``, the ``scc.plan`` span)."""


def read(ctx):
    seconds = [c["plan_s"] for c in ctx.counts
               if c.get("plan_s") is not None]
    return sum(seconds) / len(seconds) if seconds else None
