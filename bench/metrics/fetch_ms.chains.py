"""Mean time of the planner's fetch (stage planner.eval_batch.fetch:
the wait for the device, then the copy of the result to the host) per
traced call of chains."""
from bench.stages import mean_ms


def read(run):
    if run.stream("chains") is None:
        return None
    return mean_ms(run, "planner.eval_batch.fetch")
