"""Fixpoint rounds per trim call (``TrimResult.rounds``)."""


def read(ctx):
    rounds = [c["rounds"] for c in ctx.counts if c.get("rounds") is not None]
    return sum(rounds) / len(rounds) if rounds else None
