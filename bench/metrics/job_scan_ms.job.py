"""Mean time of a PageRank job's band reads (stage
analytics.pagerank.scan) over the jobs run in the traced window."""
from bench.stages import mean_ms


def read(run):
    if run.stream("jobs") is None:
        return None
    return mean_ms(run, "analytics.pagerank.scan")
