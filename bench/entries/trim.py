"""Entry ``trim``: one closed-loop client of the trimming fixpoint,
``plan(G, transpose=Gᵀ, **mix["plan"]).run()``, planned once in set-up.
The answer is the status, pulled to the host; the counts are ``rounds``
and ``edges`` (``TrimResult.rounds``, ``TrimResult.edges_traversed``).
"""
import numpy as np
from jax.profiler import TraceAnnotation

from bench import reference

#: every number compared, with its limit: the comparison is exact
LIMITS = {"status_mismatch": 0}


class Loop:
    def __init__(self, g, gt, mix: dict, seed: int):
        from repro.core import plan
        self.engine = plan(g, transpose=gt, **mix.get("plan", {}))

    def call(self):
        res = self.engine.run()
        with TraceAnnotation("bench.pull"):
            return np.asarray(res.status), {"rounds": res.rounds,
                                            "edges": res.edges_traversed}


def reference_answer(graph, transpose):
    return reference.host_trim(*graph, *transpose)[0]


def control(graph, transpose):
    return reference.trim_control(*graph, *transpose)


def compare(answer, ref) -> dict:
    return {"status_mismatch": reference.status_mismatch(answer, ref)}
