"""The SpMM launches' least time on the roofline (bench/work.py: work
from nnz, rows, columns and b, whatever kernel ran it) over the device
busy time of the traced window.  Launches come from
repro_kernel_launches_total{kernel="spmm"}; each counts the mean work
of the calls the window completed."""
from bench import work
from bench.peaks import peaks


def read(run):
    s = run.stream("chains")
    if run.trace is None or s is None or not s.done:
        return None
    launches = run.delta("repro_kernel_launches_total", kernel="spmm")
    if launches <= 0 or run.trace["busy_s"] <= 0:
        return None
    pk = peaks(run.device["kind"])
    calls = s.work()
    least = sum(work.least_seconds(*work.spmm(*w), pk) for w in calls)
    return 100.0 * least / len(calls) * launches / run.trace["busy_s"]
