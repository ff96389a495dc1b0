"""Mean time of a CDLP job's device phase (stage analytics.cdlp.device:
the pairs split by vertex and uploaded, the label-propagation program,
the labels back on the host) over the jobs run in the traced window."""
from bench.stages import mean_ms


def read(run):
    if run.stream("jobs") is None:
        return None
    return mean_ms(run, "analytics.cdlp.device")
