"""The PageRank iterations' least time on the roofline (bench/work.py:
one sparse matvec per iteration over the stored graph's adjacency,
whatever kernel ran it) over the device busy time of the traced
window, for the jobs the window completed."""
from bench import work
from bench.peaks import peaks


def read(run):
    s = run.stream("jobs")
    if run.trace is None or s is None or not s.done:
        return None
    if run.trace["busy_s"] <= 0:
        return None
    ref = run.dep.ref
    nnz = ref.n_edges_distinct
    iters = int(s.spec["params"]["num_iters"])
    least = work.least_seconds(*work.pagerank_iteration(nnz, ref.n),
                               peaks(run.device["kind"]))
    return 100.0 * least * iters * len(s.done) / run.trace["busy_s"]
