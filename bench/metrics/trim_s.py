"""Seconds per trim call: the window's wall time over the calls completed
in it (a call in flight at the window's end finishes and counts)."""


def read(ctx):
    return ctx.window_s / max(ctx.calls, 1)
