"""Reach rounds per decomposition, forward and backward summed
(``stats["reach_rounds"]`` of the one ``instrument=True`` call that the
entry's ``probe()`` makes after the traced window)."""


def read(ctx):
    if ctx.probe is None:
        return None
    return ctx.probe.get("reach_rounds")
