"""Mean time of a PageRank job's device phase (stage
analytics.pagerank.device: the COO upload, the power iteration, the ranks
back on the host) over the jobs run in the traced window."""
from bench.stages import mean_ms


def read(run):
    if run.stream("jobs") is None:
        return None
    return mean_ms(run, "analytics.pagerank.device")
