"""XLA programs the program built inside the window, by its own counter
(repro_xla_compiles_total: compiles and persistent-cache loads)."""
from bench.stages import has


def read(run):
    if run.stream("jobs") is None or not has(run,
                                             "repro_xla_compiles_total"):
        return None
    return run.delta("repro_xla_compiles_total")
