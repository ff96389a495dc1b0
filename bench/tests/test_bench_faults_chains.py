"""The chains and pagerank cells: a sound run is correct; the control
(the reference in bfloat16 in the program's place) and each fault
planted in the timed path come out not correct."""
import numpy as np
import pytest

from _bench_small import CHAINS, PAGERANK, run


def test_sound_chains_run_is_correct():
    out = run(CHAINS)
    assert out["correct"], out["checks"]
    assert out["checks"]["chain_rows_bad"]["value"] == 0
    assert out["info"]["chains_checked"] > 0


def test_chains_control_fails():
    out = run(CHAINS, control=True)
    assert not out["correct"]
    assert out["checks"]["chain_rel_err"]["value"] > \
        out["checks"]["chain_rel_err"]["limit"]


def _altered(results):
    from repro.core import Assoc
    a = results[0]
    sm = a.sm.copy()
    sm.data = sm.data.copy()
    sm.data[0] *= 1.01
    return [Assoc._from_parts(a.row, a.col, a.val, sm)] + results[1:]


def _half(results):
    from repro.core import Assoc
    k = len(results) // 2
    return results[:k] + [Assoc() for _ in results[k:]]


@pytest.mark.parametrize("fault", [_altered, _half])
def test_chains_fault_fails(monkeypatch, fault):
    from repro.core import expr
    real = expr.eval_batch
    monkeypatch.setattr(expr, "eval_batch", lambda e: fault(real(e)))
    out = run(CHAINS)
    assert not out["correct"]


def test_sound_pagerank_run_is_correct():
    out = run(PAGERANK, seconds=1.0)
    assert out["correct"], out["checks"]
    assert out["checks"]["rank_rel_err"]["value"] < 1e-5


def test_pagerank_control_fails():
    out = run(PAGERANK, seconds=1.0, control=True)
    assert not out["correct"]


def test_pagerank_altered_ranks_fail(monkeypatch):
    from repro.analytics import distributed
    real = distributed.pagerank_table

    def altered(*a, **kw):
        keys, ranks = real(*a, **kw)
        return keys, np.asarray(ranks) * np.float32(1.001)
    monkeypatch.setattr(distributed, "pagerank_table", altered)
    out = run(PAGERANK, seconds=1.0)
    assert not out["correct"]


def test_chains_kernel_fault_fails(monkeypatch):
    """An answer altered where the device produces it: one column of
    the fused SpMM off by 0.1%."""
    from repro.core import expr
    real = expr._device_spmm_dev

    def off(asm, X):
        Y = real(asm, X)
        return Y.at[:, 0].multiply(1.001)
    monkeypatch.setattr(expr, "_device_spmm_dev", off)
    out = run(CHAINS)
    assert not out["correct"]
    assert out["checks"]["chain_rel_err"]["value"] > \
        out["checks"]["chain_rel_err"]["limit"]


def test_pagerank_unchanged_state_fails(monkeypatch):
    """A power iteration that returns its state unchanged: the ranks
    stay at the uniform start."""
    from repro.analytics import distributed
    real = distributed.pagerank_table

    def stuck(*a, **kw):
        return real(*a, **dict(kw, num_iters=0))
    monkeypatch.setattr(distributed, "pagerank_table", stuck)
    out = run(PAGERANK, seconds=1.0)
    assert not out["correct"]
