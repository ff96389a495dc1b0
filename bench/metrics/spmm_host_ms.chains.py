"""Mean time of the SpMM launch on the host (stage kernel.spmm: the COO
sort, its upload and the dispatch) per traced call of chains."""
from bench.stages import mean_ms


def read(run):
    if run.stream("chains") is None:
        return None
    return mean_ms(run, "kernel.spmm")
