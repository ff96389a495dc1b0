"""Mean time of a PageRank job's host adjacency build (stage
analytics.pagerank.adjacency: the bands' sum, adjacency, square) over
the jobs run in the traced window."""
from bench.stages import mean_ms


def read(run):
    if run.stream("jobs") is None:
        return None
    return mean_ms(run, "analytics.pagerank.adjacency")
