"""Seconds per apply in the stream's host path before the dispatch:
validation, the batch resolved against the overlay's host index, the
padded upload (``StreamResult.resolve_s``, the ``stream.resolve`` span)."""


def read(ctx):
    seconds = [c["resolve_s"] for c in ctx.counts
               if c.get("resolve_s") is not None]
    return sum(seconds) / len(seconds) if seconds else None
