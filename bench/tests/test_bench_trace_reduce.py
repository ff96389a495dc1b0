"""The reduction from a profiler trace to busy time, idle gaps and the
device-operation breakdown."""
import pytest

from bench import trace_reduce as R

MS = 1e6


def _planes():
    host = ("/host:CPU", [("python3", [
        ("bench.window", 0.0, 100 * MS),
        ("bench.chains_call", 5 * MS, 60 * MS),
        ("planner", 6 * MS, 30 * MS)])])
    dev = ("/device:TPU:0", [
        ("XLA Ops", [("gather", 40 * MS, 10 * MS),
                     ("scatter-add", 45 * MS, 15 * MS),
                     ("gather", 90 * MS, 20 * MS)]),
        ("XLA Modules", [("jit_spmm", 40 * MS, 70 * MS)])])
    return [host, dev, ("/device:TPU:0 SparseCore", [
        ("XLA Ops", [("x", 0.0, 100 * MS)])])]


def test_busy_is_the_union_clipped_to_the_window():
    r = R.reduce(_planes())
    assert r["window_s"] == pytest.approx(0.1)
    # [40, 60] and [90, 100] inside [0, 100]
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["n_devices"] == 1


def test_breakdown_names_ops_and_gaps():
    r = R.reduce(_planes())
    ops = dict(r["device_ops"])
    assert ops["gather"] == pytest.approx(0.020)
    assert ops["scatter-add"] == pytest.approx(0.015)
    gaps = r["idle_gaps"]
    # [0, 40]: the innermost host event at its midpoint is the planner;
    # [60, 90]: nothing on the host covers 75
    assert gaps[0] == ["planner", pytest.approx(0.040)]
    assert gaps[1] == ["host idle", pytest.approx(0.030)]


def test_missing_window_raises():
    with pytest.raises(ValueError):
        R.reduce([("/host:CPU", [("t", [("other", 0.0, 1.0)])])])


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5e (chains cell, 5 s window), cut to
    the device's XLA Ops line and the harness's host annotations."""
    import gzip
    import json
    from bench.manifest import BENCH
    with gzip.open(BENCH / "tests" / "data" / "chains_trace_v5e.json.gz",
                   "rt") as f:
        rec = json.load(f)
    r = R.reduce(rec["planes"])
    assert r["busy_s"] == pytest.approx(rec["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(rec["window_s"], rel=1e-9)
    assert r["n_devices"] == 1 and 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"][0][0] == "%fusion"
    assert all(name == "bench.chains_call" for name, _ in r["idle_gaps"])
