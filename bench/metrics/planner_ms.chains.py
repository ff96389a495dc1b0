"""Self time of planner.eval_batch per traced call of chains: the host
planning, key remapping and COO rebuild around the device launch."""
from bench.spans import mean_ms, self_seconds


def read(run):
    s = run.stream("chains")
    if s is None:
        return None
    return mean_ms(s.spans, lambda sp: self_seconds(sp, "planner.eval"))
