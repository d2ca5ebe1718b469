"""Entry ``scc``: one closed-loop client of ``scc_decompose(G,
**mix["call"])``.  The answer is the labels, on the host when the call
returns; the counts are the scalars of its ``stats``.

``probe()`` makes one more call with ``mix["probe"]`` added
(``instrument=True``, for ``stats["reach_rounds"]``).  That plans other
engines and syncs the host each sweep, so the harness makes it only after
a traced window has closed: the window times and traces the plain call.
"""
from bench import reference

#: every number compared, with its limit: the comparisons are exact
LIMITS = {"partition_mismatch": 0, "unlabelled": 0}


class Loop:
    def __init__(self, g, gt, mix: dict, seed: int):
        from repro.core import scc
        self.scc = scc
        self.graph = g
        self.kwargs = dict(mix.get("call", {}))
        self.probe_kwargs = {**self.kwargs, **mix.get("probe", {})}

    def call(self):
        labels, stats = self.scc.scc_decompose(self.graph, **self.kwargs)
        return labels, _scalars(stats)

    def probe(self) -> dict:
        return _scalars(self.scc.scc_decompose(self.graph,
                                               **self.probe_kwargs)[1])


def _scalars(stats: dict) -> dict:
    return {k: v for k, v in stats.items() if isinstance(v, (int, float))}


def reference_answer(graph, transpose):
    return reference.host_scc(*graph)


def control(graph, transpose):
    return reference.scc_control(*graph, *transpose)


def compare(answer, ref) -> dict:
    return {"partition_mismatch": reference.partition_mismatch(answer, ref),
            "unlabelled": reference.unlabelled(answer)}
