"""Entry ``stream``: one closed-loop client of incremental trimming under an
arc-deletion stream.  ``plan_stream(G, **mix["plan"])`` is planned once in
set-up; call k applies batch k of the stream (``bench/stream_reference.py``:
slice k of ``mix["batch"]`` arcs of one permutation of G's arc ids drawn
from the run's seed) with ``engine.apply(deletions=...)``.

Call k's status is the fixpoint after k batches, so the answer carries k:
``HEADER`` bytes of little-endian uint64 (the seed, the batch size, k)
followed by the status pulled to the host, a byte a vertex.  The counts
are ``rounds`` (``StreamResult.rounds``), ``resolve_s``
(``StreamResult.resolve_s``, the ``stream.resolve`` span), read with
``getattr`` so that a program without them still runs the cell, and
``arcs``, the batch's length.

The reference regenerates the stream from the seed an answer carries and
advances its own fixpoint to each answer's k.  ``run.py`` and
``readings.py`` hand the reference and the control only the graph, so the
clients record what they were made with (``CLIENTS``): the control follows
the stream of the latest client, and the reference checks the last batch
a client applied against ``host_trim`` from scratch as well.
"""
import numpy as np
from jax.profiler import TraceAnnotation

from bench import reference, stream_reference

#: every number compared, with its limit: the comparison is exact
LIMITS = {"status_mismatch": 0}
HEADER = 24
#: seed -> (batch size, batches applied) of the clients made in this
#: process, the latest last
CLIENTS = {}


def header(seed: int, batch: int, k: int) -> np.ndarray:
    return np.array([seed, batch, k], "<u8").view(np.uint8)


def parse(answer) -> tuple:
    """(seed, batch, k, status bytes) of an answer."""
    fields = np.ascontiguousarray(answer[:HEADER]).view("<u8")
    return (*(int(x) for x in fields), answer[HEADER:])


class Loop:
    def __init__(self, g, gt, mix: dict, seed: int):
        from repro.core import plan_stream
        self.engine = plan_stream(g, **mix.get("plan", {}))
        self.seed, self.k, self.batch = seed, 0, int(mix["batch"])
        self.indptr, self.indices = np.asarray(g.indptr), np.asarray(
            g.indices)
        self.order = stream_reference.arc_order(seed, len(self.indices))
        CLIENTS.pop(seed, None)
        CLIENTS[seed] = (self.batch, 0)

    def call(self):
        k = self.k + 1
        src, dst = stream_reference.batch_arcs(self.order, self.indptr,
                                               self.indices, k, self.batch)
        res = self.engine.apply(deletions=(src, dst))
        self.k = k
        CLIENTS[self.seed] = (self.batch, k)
        with TraceAnnotation("bench.pull"):
            status = np.asarray(res.status)
            answer = np.empty(HEADER + status.size, np.uint8)
            answer[:HEADER] = header(self.seed, self.batch, k)
            answer[HEADER:] = status
            return answer, {"rounds": getattr(res, "rounds", None),
                            "resolve_s": getattr(res, "resolve_s", None),
                            "arcs": int(src.size)}


class Reference:
    """The reference fixpoints of every stream an answer names, made on
    first use."""

    def __init__(self, graph, transpose):
        self.graph, self.transpose = graph, transpose
        self.fixpoints = {}

    def live(self, seed: int, batch: int, k: int) -> np.ndarray:
        key = (seed, batch)
        if key not in self.fixpoints:
            self.fixpoints[key] = stream_reference.Fixpoints(
                self.graph, self.transpose, *key)
        fix = self.fixpoints[key]
        live = fix.live(k)
        if CLIENTS.get(seed) == (batch, k):     # the last batch applied
            fix.verify()
        return live


def reference_answer(graph, transpose):
    return Reference(graph, transpose)


def control(graph, transpose):
    if not CLIENTS:
        raise ValueError("the control follows the stream of a client made "
                         "in this process; none was")
    seed = next(reversed(CLIENTS))
    batch, _ = CLIENTS[seed]
    k, live = stream_reference.control(graph, transpose, seed, batch)
    return np.concatenate([header(seed, batch, k), live.astype(np.uint8)])


def compare(answer, ref) -> dict:
    *stream, status = parse(answer)
    return {"status_mismatch": reference.status_mismatch(
        status, ref.live(*stream))}
