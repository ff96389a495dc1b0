"""Share of the traced window's device-idle time in which no program
stage was open on any host thread (bench/stages.py)."""
from bench.stages import idle_unattributed_pct


def read(run):
    if run.stream("jobs") is None:
        return None
    return idle_unattributed_pct(run)
