"""The CDLP cell at a CPU size: a sound run is correct and checks every
job it completed; the bench's vectorised reference equals the plain
dict reference; the control (ties to the largest label), one vertex's
label altered after the job and the program run one iteration short
each make ``correct`` false."""
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import manifest as M

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "tests"))
from _graphalytics_ref import cdlp, neighbours  # noqa: E402

CELL = "graphalytics_kron16.cdlp"
# at scale 11 this seed's labels still change in the 10th iteration, so
# a run one iteration short gives other labels
SCALE, SEED = 11, 2 ** 31 + 77


def run(control=False):
    from bench.run import run_cell
    out = run_cell(CELL, SEED, 0.3, False, require_chip=False,
                   overrides={"scale": SCALE}, control=control)
    assert out["info"]["jobs_checked"] >= 1
    return out


def test_sound_cdlp_run_is_correct():
    out = run()
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"jobs_failed", "labels_bad",
                                  "communities_missed"}
    assert out["checks"]["labels_bad"]["value"] == 0
    assert out["checks"]["communities_missed"]["value"] == 0
    assert set(out["metrics"]) == {"job_s", "setup_s"}


def test_cdlp_control_fails():
    out = run(control=True)
    assert not out["correct"]
    assert out["checks"]["labels_bad"]["value"] > 0


def test_cdlp_altered_label_fails(monkeypatch):
    from repro.analytics import distributed
    real = distributed.cdlp_table

    def altered(*a, **kw):
        keys, labels = real(*a, **kw)
        labels = labels.copy()
        labels[0] = (labels[0] + 1) % len(labels)
        return keys, labels
    monkeypatch.setattr(distributed, "cdlp_table", altered)
    out = run()
    assert not out["correct"]
    assert out["checks"]["labels_bad"]["value"] == \
        out["info"]["jobs_checked"]


def test_cdlp_one_iteration_short_fails(monkeypatch):
    from repro.analytics import distributed
    real = distributed.cdlp_table

    def short(*a, **kw):
        return real(*a, **dict(kw, num_iters=kw["num_iters"] - 1))
    monkeypatch.setattr(distributed, "cdlp_table", short)
    ref = _reference(SEED, SCALE)
    assert not np.array_equal(ref.labels(9), ref.labels(10))
    out = run()
    assert not out["correct"]
    assert out["checks"]["labels_bad"]["value"] == \
        out["info"]["jobs_checked"]


def _reference(seed, scale):
    from bench.data import graphalytics, kronecker
    kr = kronecker.Reference(*kronecker.edges(seed, scale))
    return graphalytics.Reference(kr.nodes, kr.s, kr.d)


@pytest.mark.parametrize("num_iters", [0, 1, 2, 10])
def test_bench_reference_equals_the_dict_reference(num_iters):
    from bench.data import kronecker
    u, v = kronecker.edges(5300000001, 9)
    kr = kronecker.Reference(u, v)
    ref = _reference(5300000001, 9)
    nb = neighbours(range(ref.n), zip(kr.s.tolist(), kr.d.tolist()))
    want = cdlp(nb, num_iters)
    assert ref.labels(num_iters).tolist() == [want[i] for i in range(ref.n)]


def test_control_breaks_ties_the_other_way():
    ref = _reference(5300000001, 9)
    assert not np.array_equal(ref.labels(1), ref.labels(1, "largest"))
    assert np.array_equal(ref.labels(0), ref.labels(0, "largest"))


def test_cell_work_at_scale_16():
    """Bytes of one iteration at scale 16: pairs x 8 B + vertices x 8 B."""
    from bench import cdlp_work
    ops, nbytes = cdlp_work.iteration(1_819_362, 46_682)
    assert ops == 1_819_362 and nbytes == 14_928_352
    assert M.Cell(M.load(), CELL).config["scale"] == 16


def _total(name: str, **labels) -> float:
    from repro.obs.metrics import REGISTRY
    return sum(v for (n, lab), v in REGISTRY.as_dict().items()
               if n == name and all(dict(lab).get(k) == x
                                    for k, x in labels.items()))


@pytest.mark.parametrize("ttl, seconds, jobs", [(60.0, 1.0, 1),
                                                (2.0, 5.0, 2)])
def test_warm_up_leaves_scans_that_outlive_the_window(ttl, seconds, jobs):
    """One warm-up job when its scans' cache entries outlive the window;
    else a second, on fresh scans, once the first ones have expired."""
    from bench.load import make_streams
    cell = M.Cell(M.load(), CELL)
    dep = cell.data_module().deployment(cell.config, SEED, {"scale": 9})
    try:
        dep.table.cache_ttl = ttl
        (s,) = make_streams(cell.traffic, dep, SEED, False, seconds)
        scan_seconds = s.warm.__globals__["_scan_seconds"]
        j0 = _total("repro_stage_seconds_count", stage="job.cdlp")
        m0 = _total("repro_cache_misses_total")
        t0, s0 = _total("repro_stage_seconds_sum",
                        stage="analytics.cdlp.scan"), scan_seconds()
        s.warm()
        assert _total("repro_stage_seconds_count",
                      stage="job.cdlp") - j0 == jobs
        # the warm-up dates the scans by the scan stage's own seconds
        scanned = scan_seconds() - s0
        assert scanned > 0
        assert scanned == _total("repro_stage_seconds_sum",
                                 stage="analytics.cdlp.scan") - t0
        # each job's first reads of the two bands go to the tablets
        assert _total("repro_cache_misses_total") - m0 == 2 * jobs
    finally:
        dep.close()
