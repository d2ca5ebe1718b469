from .generators import (BENCHMARK_GRAPHS, barabasi_albert, chain, cycle,
                         erdos_renyi, layered_dag, make, rmat, sink_heavy)

__all__ = [
    "BENCHMARK_GRAPHS", "make", "erdos_renyi", "barabasi_albert", "rmat",
    "chain", "cycle", "layered_dag", "sink_heavy",
]
