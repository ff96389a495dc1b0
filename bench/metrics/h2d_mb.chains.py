"""Megabytes (10^6 B) handed to the device per call of chains:
repro_h2d_bytes_total at sites coo (the SpMM's COO) and dense (the
stacked x vectors and their gather index), over the calls completed."""
from bench.stages import has


def read(run):
    s = run.stream("chains")
    if s is None or not s.n_calls or not has(run, "repro_h2d_bytes_total"):
        return None
    b = (run.delta("repro_h2d_bytes_total", site="coo")
         + run.delta("repro_h2d_bytes_total", site="dense"))
    return b / s.n_calls / 1e6
