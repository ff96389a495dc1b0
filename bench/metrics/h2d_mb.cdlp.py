"""Megabytes (10^6 B) handed to the device per CDLP job:
repro_h2d_bytes_total at site cdlp over the jobs whose device phase ran
in the window."""
from bench.stages import has


def read(run):
    if run.stream("jobs") is None or not has(run, "repro_h2d_bytes_total"):
        return None
    n = run.delta("repro_stage_seconds_count", stage="analytics.cdlp.device")
    if n <= 0:
        return None
    return run.delta("repro_h2d_bytes_total", site="cdlp") / n / 1e6
