"""Mean time of the planner's compaction (stage
planner.eval_batch.compact: the result columns made Assoc) per traced
call of chains."""
from bench.stages import mean_ms


def read(run):
    if run.stream("chains") is None:
        return None
    return mean_ms(run, "planner.eval_batch.compact")
