"""The readers of the program's stages and transfer counters: the
device-idle attribution on small plane tuples, a CPU-size traced run of
each cell reporting every one of them, and the host-to-device byte
count of one batched call against its formula."""
import numpy as np
import pytest

from bench import manifest as M
from bench import stages as ST

from _bench_small import CHAINS, PAGERANK, run

MS = 1e6
NEW = {
    CHAINS: ["remap_ms.chains", "spmm_host_ms.chains", "fetch_ms.chains",
             "compact_ms.chains", "h2d_mb.chains",
             "idle_unattributed_pct.chains"],
    PAGERANK: ["job_scan_ms.job", "job_adjacency_ms.job", "job_device_ms.job",
               "h2d_mb.job", "compiles_in_window.job",
               "idle_unattributed_pct.job"],
}


def _planes():
    """Window [0, 100] ms; the device runs [10, 20] and [70, 80], so it
    idles over [0, 10] (covered by a stage), [20, 70] (its second half
    covered, with a nested stage on another thread) and [80, 100]
    (covered by a host event that is no stage)."""
    host = ("/host:CPU", [
        ("main", [("bench.window", 0.0, 100 * MS),
                  ("job.pagerank", 0.0, 10 * MS),
                  ("planner.eval_batch", 45 * MS, 25 * MS),
                  ("expr.py:800 numpy work", 80 * MS, 20 * MS)]),
        ("worker", [("planner.eval_batch.fetch", 60 * MS, 5 * MS)])])
    dev = ("/device:TPU:0", [
        ("XLA Ops", [("fusion", 10 * MS, 10 * MS),
                     ("copy", 70 * MS, 10 * MS)])])
    return [host, dev]


def test_idle_by_stage_covered_half_and_uncovered():
    names = {"job.pagerank", "planner.eval_batch",
             "planner.eval_batch.fetch"}
    idle = ST.idle_by_stage(_planes(), names)
    assert idle["job.pagerank"] == pytest.approx(0.010)
    # [45, 70] under planner.eval_batch, less its nested fetch [60, 65]
    assert idle["planner.eval_batch"] == pytest.approx(0.020)
    assert idle["planner.eval_batch.fetch"] == pytest.approx(0.005)
    # [20, 45] and [80, 100]: no stage open
    assert idle[ST.NONE] == pytest.approx(0.045)
    assert sum(idle.values()) == pytest.approx(0.080)


def test_idle_by_stage_takes_a_predicate_and_needs_the_window():
    idle = ST.idle_by_stage(_planes(), ST.PROGRAM_STAGE.match)
    assert idle[ST.NONE] == pytest.approx(0.045)
    with pytest.raises(ValueError):
        ST.idle_by_stage([("/host:CPU", [("t", [("x", 0.0, 1.0)])])], ())


def test_untraced_runs_report_none_of_the_new_metrics():
    man = M.load()
    for cell, names in NEW.items():
        c = M.Cell(man, cell)
        assert {m["name"] for m in c.per_layer} >= set(names)
        assert not {m["name"] for m in c.metrics(False)} & set(names)


@pytest.mark.parametrize("cell", [CHAINS, PAGERANK])
def test_traced_cpu_run_reports_every_new_metric(cell):
    out = run(cell, seconds=1.0, trace=True)
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    missing = [n for n in NEW[cell] if m.get(n) is None]
    assert not missing, (missing, sorted(m))
    assert 0.0 <= m[NEW[cell][-1]] <= 100.0
    if cell == CHAINS:
        # the stages are children of eval_batch, so they sum into the
        # planner's self time and never past it
        staged = m["remap_ms.chains"] + m["fetch_ms.chains"] \
            + m["compact_ms.chains"]
        assert staged <= m["planner_ms.chains"] * (1 + 1e-9)
        assert m["h2d_mb.chains"] > 0
    else:
        staged = m["job_scan_ms.job"] + m["job_adjacency_ms.job"] \
            + m["job_device_ms.job"]
        took = out["info"]["job_seconds"]
        assert staged <= 1e3 * sum(took) / len(took)
        assert m["compiles_in_window.job"] == 0
        assert m["h2d_mb.job"] > 0


def test_one_batched_call_hands_over_the_bytes_of_its_formula(monkeypatch):
    """12 B per COO entry (int32 row and column, float32 value), the
    stacked float32 X and its int32 gather index."""
    from repro.core import Assoc, eval_batch, lazy
    from repro.core import expr as X
    from repro.db import DB, put
    from repro.obs.metrics import REGISTRY
    monkeypatch.setattr(X, "DEVICE_NNZ_THRESHOLD", 1)
    rng = np.random.default_rng(3)
    n, nnz = 300, 4000
    rows = np.asarray([f"p{i:05d}" for i in rng.integers(0, 3000, nnz)])
    cols = np.asarray([f"ip.dst|h{i:03d}" for i in rng.integers(0, n, nnz)])
    T = DB("Tedge", "TedgeT")
    put(T, Assoc(rows, cols, np.ones(nnz)))
    F = T[:, "ip.dst|*,"].eval()
    xs = []
    for j in range(8):
        keys = np.sort(rng.choice(F.col, 120, replace=False))
        xs.append(Assoc(keys, np.full(keys.shape[0], f"q{j}"),
                        rng.uniform(0.5, 2.0, keys.shape[0])))
    y_keys = np.unique(np.concatenate([x.row for x in xs]))
    inner = np.intersect1d(F.col, y_keys)
    entries = F.sm[:, np.searchsorted(F.col, inner)].nnz
    want = 12 * entries + 4 * y_keys.shape[0] * 8 + 4 * inner.shape[0]

    def sent():
        d = REGISTRY.as_dict()
        return sum(d.get(("repro_h2d_bytes_total", (("site", s),)), 0)
                   for s in ("coo", "dense"))
    b0 = sent()
    eval_batch([T[:, "ip.dst|*,"] * lazy(x) for x in xs])
    assert sent() - b0 == want
