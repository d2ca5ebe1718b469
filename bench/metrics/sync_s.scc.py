"""Seconds per decomposition blocked in device-to-host reads
(``stats["sync_s"]``, the ``scc.sync`` spans)."""


def read(ctx):
    seconds = [c["sync_s"] for c in ctx.counts
               if c.get("sync_s") is not None]
    return sum(seconds) / len(seconds) if seconds else None
