"""Mean time of a CDLP job's host graph build (stage
analytics.cdlp.adjacency: graph.undirected_bands, the square adjacency
of the two bands made undirected and simple) over the jobs run in the
traced window."""
from bench.stages import mean_ms


def read(run):
    if run.stream("jobs") is None:
        return None
    return mean_ms(run, "analytics.cdlp.adjacency")
