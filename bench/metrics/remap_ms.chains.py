"""Mean time of the planner's key remap (stage
planner.eval_batch.remap: intersect the factor's columns with the
vector's keys, _onto, searchsorted) per traced call of chains."""
from bench.stages import mean_ms


def read(run):
    if run.stream("chains") is None:
        return None
    return mean_ms(run, "planner.eval_batch.remap")
