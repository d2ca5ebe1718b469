"""Seconds of set-up: the graph made on the device, the entry built and
one call to compile and warm it, all before the window opens."""


def read(ctx):
    return ctx.setup_s
