"""The CDLP iterations' least time on the roofline (bench/cdlp_work.py:
each pair's neighbour index and label read, each vertex's row pointer
read and label written, whatever groups the pairs) over the device busy
time of the traced window.  Launches come from
repro_kernel_launches_total{kernel="cdlp"}, one per job's program."""
from bench import cdlp_work
from bench.peaks import peaks


def read(run):
    s = run.stream("jobs")
    ref = getattr(run.dep, "cdlp_ref", None)
    if run.trace is None or s is None or ref is None:
        return None
    launches = run.delta("repro_kernel_launches_total", kernel="cdlp")
    if launches <= 0 or run.trace["busy_s"] <= 0:
        return None
    iters = int(s.spec["params"]["num_iters"])
    least = cdlp_work.least_iteration_seconds(ref.n_pairs, ref.n,
                                              peaks(run.device["kind"]))
    return 100.0 * least * iters * launches / run.trace["busy_s"]
